"""Roofline analysis from the compiled dry-run artifacts.

Per (arch x shape x mesh) cell, using the trip-count-corrected HLO stats in
experiments/dryrun/*.json (all per-device — the compiled module IS the
per-device SPMD program):

  compute term    = flops_per_device / 197 TFLOP/s          (bf16 v5e)
  memory term     = hbm_bytes_per_device / 819 GB/s
  collective term = collective_operand_bytes_per_device / 50 GB/s (ICI)

MODEL_FLOPS uses 6*N*D (train, dense), 6*N_active*D (MoE), 2*N*D (prefill),
2*N_active*B (decode: one token per sequence). The reported
`useful_fraction` = (MODEL_FLOPS time at peak) / (dominant term) — the
roofline fraction the hillclimb drives up.
"""
from __future__ import annotations

import json
import pathlib
import sys

from repro.configs.base import SHAPES, active_param_count, param_count_dense
from repro.configs.registry import ARCH_IDS, get_arch
from repro.launch.runtime import enable_compile_cache

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s/link / chip

DRYRUN_DIR = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def model_flops(arch: str, shape_name: str) -> float:
    bundle = get_arch(arch)
    cfg = bundle.model
    shape = SHAPES[shape_name]
    n = param_count_dense(cfg)
    n_act = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    # decode: one token per sequence
    return 2.0 * n_act * shape.global_batch


def _cell_file(arch, shape, mesh, tag=""):
    safe = arch.replace(".", "_").replace("/", "_")
    suffix = f"__{tag}" if tag else ""
    return DRYRUN_DIR / f"{safe}__{shape}__{mesh}{suffix}.json"


def analyze_cell(arch: str, shape: str, mesh: str = "single", tag: str = ""
                 ) -> dict | None:
    path = _cell_file(arch, shape, mesh, tag)
    if not path.exists():
        return None
    d = json.loads(path.read_text())
    if d["status"] == "skipped":
        return {"arch": arch, "shape": shape, "mesh": mesh,
                "status": "skipped", "reason": d["reason"]}
    if d["status"] != "ok":
        return {"arch": arch, "shape": shape, "mesh": mesh,
                "status": d["status"], "error": d.get("error", "")[:200]}
    chips = d["n_devices"]
    flops_dev = d["hlo"]["flops"]
    hbm_dev = d["hlo"]["hbm_bytes"]
    coll_dev = d["hlo"]["collective_bytes"]
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = hbm_dev / HBM_BW
    t_coll = coll_dev / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(arch, shape)
    t_useful = mf / (chips * PEAK_FLOPS)
    bound = max(terms.values())
    frac = t_useful / bound if bound > 0 else 0.0
    hlo_global = flops_dev * chips
    suggestions = {
        "compute": "reduce recompute (remat policy) / shrink redundant "
                   "per-shard math so HLO_FLOPs approaches MODEL_FLOPS",
        "memory": "fuse or shrink HBM round-trips (bigger blocks, int8 "
                  "tables/caches, fewer saved activations)",
        "collective": "reshard to cut the dominant collective (kv-repeat "
                      "layout, SP boundaries, expert placement) or overlap "
                      "it under compute",
    }
    return {
        "arch": arch, "shape": shape, "mesh": mesh, "status": "ok",
        "chips": chips,
        "compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "useful_fraction": frac,
        "memory_per_device_gib": (
            d["memory"]["argument_bytes"] + d["memory"]["temp_bytes"]
        ) / 2**30,
        "what_would_help": suggestions[dominant],
        "per_collective": d["hlo"].get("per_collective", {}),
        "tag": tag,
    }


def full_table(mesh: str = "single", tag: str = "") -> list[dict]:
    rows = []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            r = analyze_cell(arch, shape, mesh, tag)
            if r is not None:
                rows.append(r)
    return rows


def format_markdown(rows: list[dict]) -> str:
    out = [
        "| arch | shape | compute s | memory s | collective s | dominant | "
        "MODEL/HLO | roofline frac | per-dev GiB |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] == "skipped":
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                       f"skipped | — | — | — |")
            continue
        if r["status"] != "ok":
            out.append(f"| {r['arch']} | {r['shape']} | ERROR | | | | | | |")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3f} | "
            f"{r['memory_s']:.3f} | {r['collective_s']:.3f} | "
            f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
            f"{r['useful_fraction']:.2f} | "
            f"{r['memory_per_device_gib']:.1f} |")
    return "\n".join(out)


def main():
    enable_compile_cache()
    mesh = sys.argv[1] if len(sys.argv) > 1 else "single"
    rows = full_table(mesh)
    print(format_markdown(rows))
    print()
    ok = [r for r in rows if r["status"] == "ok"]
    if ok:
        worst = min(ok, key=lambda r: r["useful_fraction"])
        coll = max(ok, key=lambda r: r["collective_s"]
                   / max(r["compute_s"], 1e-12))
        print(f"worst roofline fraction: {worst['arch']} x {worst['shape']}"
              f" ({worst['useful_fraction']:.3f}, {worst['dominant']}-bound)")
        print(f"most collective-bound:  {coll['arch']} x {coll['shape']} "
              f"(coll/compute = "
              f"{coll['collective_s']/max(coll['compute_s'],1e-12):.2f})")


if __name__ == "__main__":
    main()
