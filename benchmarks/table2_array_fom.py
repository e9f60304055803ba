"""Paper Table II: array-level figures of merit (the cost model's inputs).

These are the published HSPICE/Neurosim/45nm-synthesis numbers the paper
measured; we print them alongside the derived per-op quantities the model
composes from them.
"""
from repro.core.cost_model import ARRAY_FOM, CAL, e_shot
from repro.launch.runtime import enable_compile_cache


def rows():
    out = []
    for op, (e_pj, t_ns) in ARRAY_FOM.items():
        out.append((f"table2/{op}", t_ns / 1e3, f"{e_pj}pJ"))
    out.append(("table2/rsc_transfer(cal)", CAL.t_rsc_ns / 1e3,
                f"{e_shot(7):.0f}pJ@7banks"))
    return out


def main():
    enable_compile_cache()
    for name, us, derived in rows():
        print(f"{name},{us:.6f},{derived}")


if __name__ == "__main__":
    main()
