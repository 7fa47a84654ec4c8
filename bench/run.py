#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

(``python3 -m bench.run`` from the root of the checkout works the same.)
The cell's files are found by name (``bench/harness.py``).  The run sets
up, measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON line last on standard output, with
each compared number beside its limit last on standard error.  It exits
non-zero, printing no result, when JAX finds no TPU, fewer chips than the
cell asks for, or a device kind missing from ``bench/peaks.json``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be non-negative and --seconds positive")

    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import harness

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", harness.compile_cache_dir(ROOT))
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"bench: the program is not here ({e}); nothing was run", file=sys.stderr)
        return 2
    try:
        harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START)
    except harness.BenchError as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
