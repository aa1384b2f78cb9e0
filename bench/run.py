#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero, without a result, when
JAX finds no TPU or fewer chips than the cell asks for, or when a file the
cell names is missing.  The last line of standard output is the result: a
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with the reference beside its limit.
The same numbers close standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", metavar="FILE",
                    help="with --trace 1, keep the traced events here "
                         "(gzipped JSON)")
    args = ap.parse_args(argv)
    # JAX's persistent compilation cache lives at a fixed path inside the
    # checkout, whatever the environment names, so that only the first run
    # of a cell in a checkout compiles and two checkouts share nothing.
    # Set before JAX is imported; the program's use_compile_cache() takes
    # it from here.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT))
    from bench.harness import BenchError, run_cell
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START,
                       dump_trace=args.dump_trace)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
