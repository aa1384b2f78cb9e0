#!/usr/bin/env python3
"""Read the control of a cell: the plain reference with one of the
configuration's guarantees broken, put in the program's place, and judged
by the same comparison as a run.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --requests 200

For each seed it prints the numbers a run compares, with the control's
answers for the first ``--requests`` requests of the window; each must
fail its limit (see PERF.md).  It runs at the cell's own sizes.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_window(wl, n: int):
    """A window of ``n`` requests answered, on time, by the control."""
    from bench.drive import Req, Window
    w = Window(t0=0.0, seconds=0.0)
    for i in range(n):
        r = Req(i, "control", due=0.0, status="ok", execution="resident")
        r.answer = wl.control(i)
        w.requests.append(r)
    return w


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import Cell, check
    cell = Cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        wl = cell.load(seed)
        checks, failed = check(control_window(wl, args.requests), wl)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "requests": args.requests, "failed": failed,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
