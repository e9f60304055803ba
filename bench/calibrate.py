#!/usr/bin/env python3
"""Read the compared numbers of the program and of its control, on many
seeds in one process, to set the limits in a configuration's ``checks``.

    python3 bench/calibrate.py --workload <name> --seconds <s> \\
        --seeds <n>,<n>,...

Each seed is a whole run at the cell's own load (`run.run`), after which
the control, the plain reference computed at the precision below the
configuration's, is put in the program's place for the same sampled
requests. Prints one JSON line a seed, then the largest reading of each
number over the program's runs and the smallest over the control's.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    prog, ctrl = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run(args.workload, seed, args.seconds, False, control=True,
                      t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "metrics": out["metrics"],
                          "numbers": out["numbers"],
                          "control": out["control"]}), flush=True)
        for k, v in out["numbers"].items():
            prog[k] = max(prog.get(k, v), v)
        for k, v in out["control"].items():
            ctrl[k] = min(ctrl.get(k, v), v)
    print(json.dumps({"program_max": prog, "control_min": ctrl}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
