"""Paper Table III: ET-operation latency/energy, iMARS (cost model) vs the
paper's measured GPU baselines, plus the NNS comparison of Sec. IV-C2."""
from repro.core import cost_model as cm
from repro.launch.runtime import enable_compile_cache


def rows():
    out = []
    t3 = cm.table3_model()
    for stage, r in t3.items():
        out.append((
            f"table3/{stage}/imars",
            r["model_latency_us"],
            f"energy={r['model_energy_uj']:.4f}uJ;"
            f"paper={r['paper_latency_us']}us/{r['paper_energy_uj']}uJ;"
            f"lat_err={r['latency_rel_err']*100:+.1f}%;"
            f"en_err={r['energy_rel_err']*100:+.1f}%",
        ))
        out.append((
            f"table3/{stage}/speedup",
            0.0,
            f"latency_x={r['speedup_vs_gpu']:.2f};"
            f"energy_x={r['energy_reduction_vs_gpu']:.1f}",
        ))
    nns = cm.ml_nns_model()
    out.append((
        "table3/nns/imars",
        nns["model_latency_us"],
        f"energy={nns['model_energy_uj']*1e3:.3f}nJ;"
        f"latency_x={nns['latency_speedup']:.0f}(paper {cm.PAPER_END_TO_END['nns_latency_speedup']:.0f});"
        f"energy_x={nns['energy_reduction']:.0f}(paper {cm.PAPER_END_TO_END['nns_energy_reduction']:.0f})",
    ))
    return out


def main():
    enable_compile_cache()
    for name, us, derived in rows():
        print(f"{name},{us:.6f},{derived}")


if __name__ == "__main__":
    main()
