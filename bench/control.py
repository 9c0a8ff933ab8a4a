#!/usr/bin/env python3
"""Readings of a cell's lower-precision control, on chosen seeds.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --calls <n>

For each seed, generates the cell's requests as a run does and puts the
plain reference in the program's place, computed at the precision one
step below what the configuration states (its ``control`` entry, e.g.
bfloat16 state where it states float32).  The answers of ``--calls``
closed-loop calls (segments 0, 1, ... in turn) are compared with the
float32 reference exactly as a run compares the program's, and one JSON
line per seed gives each compared number and whether the run would have
been ``correct``.  It needs no chip: the limits in ``bench/traffic`` sit
between these readings and those of sound runs of the program.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)


def readings(cell, seed: int, calls: int, workers=None) -> dict:
    from bench import drivers
    from bench.run import compare, reference_answers
    drv = drivers.build(cell.config, cell.traffic, seed)
    drv.generate()
    segments = sorted({k % drv.n_segments for k in range(calls)})
    low = reference_answers(drv, segments, cell.config["control"], workers)
    ref = reference_answers(drv, segments, None, workers)
    answers = [(k, {f: [low[(k % drv.n_segments, lane)][f]
                        for lane in range(len(drv.lanes))]
                    for f in drv.fields}) for k in range(calls)]
    return compare(drv, answers, ref, cell.traffic["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--calls", type=int, required=True,
                    help="closed-loop calls a run makes in its window")
    args = ap.parse_args(argv)
    from bench.cell import load_cell
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        v = readings(cell, seed, args.calls)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": v["correct"], "checks": v["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
