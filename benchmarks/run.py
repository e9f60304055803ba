"""Benchmark harness entry point — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV. The roofline table (dry-run
derived) is appended when experiments/dryrun/ artifacts exist.
"""
import argparse
import sys

from repro.launch.runtime import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size accuracy run (slower)")
    ap.add_argument("--skip-accuracy", action="store_true")
    args = ap.parse_args()

    from benchmarks import (
        accuracy_hr,
        end_to_end,
        kernel_bench,
        table2_array_fom,
        table3_et_ops,
    )
    from repro.core import mapping

    print("name,us_per_call,derived")

    ml, cr = mapping.movielens_mapping(), mapping.criteo_mapping()
    print(f"table1/movielens,0.0,banks={ml.banks};mats={ml.mats};"
          f"cmas={ml.cmas};paper=7/8/54")
    print(f"table1/criteo,0.0,banks={cr.banks};mats={cr.mats};"
          f"cmas={cr.cmas};paper=26/104/2860")

    for mod in (table2_array_fom, table3_et_ops):
        for name, us, derived in mod.rows():
            print(f"{name},{us:.6f},{derived}")

    for name, us, derived in end_to_end.rows():
        print(f"{name},{us:.6f},{derived}")

    if not args.skip_accuracy:
        for name, us, derived in accuracy_hr.rows(quick=not args.full):
            print(f"{name},{us:.6f},{derived}")

    for name, us, derived in kernel_bench.rows():
        print(f"{name},{us:.3f},{derived}")

    # roofline summary (if the dry-run has produced artifacts)
    try:
        from benchmarks import roofline

        rows = roofline.full_table("single")
        ok = [r for r in rows if r.get("status") == "ok"]
        for r in ok:
            print(
                f"roofline/{r['arch']}/{r['shape']},0.0,"
                f"compute={r['compute_s']:.4f}s;memory={r['memory_s']:.4f}s;"
                f"collective={r['collective_s']:.4f}s;dom={r['dominant']};"
                f"frac={r['useful_fraction']:.3f}")
    except Exception as e:  # dry-run not yet produced
        print(f"roofline/unavailable,0.0,{type(e).__name__}")


if __name__ == "__main__":
    main()
