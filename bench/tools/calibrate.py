"""Read the numbers a cell's check compares, for the program and for the
cell's control, on several seeds in one process (on the chip).

    python bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20

The control is the plain reference put in the program's place one precision
below what the configuration states (the cell file's ``control``):

* ``fp8``: float8 e4m3 weights and matrix-product inputs, for bf16 serving;
* ``int4``: weights in 4-bit groups of 128, for the int8 checkpoint;
* ``float32``: the fleet reference in float32, for float64 energies.

Both go through the cell's own comparison (the runner's ``check``, with
the control in the program's place), so each line says whether the run and
the control come out correct.  For a served cell the control reads, at each
served position of the same prompts and tokens, the gap of the token the
control puts first.  Each seed prints one JSON line.  The
benchmark's own runs never run this; its readings set the limits in the
cell files (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def one_seed(cell, seed: int, seconds: float, devices) -> dict:
    from bench import harness

    path = harness.load_module(harness.path_file(cell), f"bench_path_cal_{seed}")
    runner = path.Runner(cell, seed, seconds, devices)
    runner.setup()
    runner.window(seconds)
    runner.release()
    program = runner.check()
    control = runner.check(cell.workload["control"])
    return {
        "seed": seed,
        "program": {k: v["value"] for k, v in program.items()},
        "program_correct": harness.is_correct(program),
        "control": {k: v["value"] for k, v in control.items()},
        "control_correct": harness.is_correct(control),
        "limits": {k: v["limit"] for k, v in program.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from bench import harness

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", harness.compile_cache_dir(ROOT))
    cell = harness.load_cell(args.workload)
    devices = harness.devices_for(cell)
    harness.enable_compile_cache(harness.compile_cache_dir(ROOT))
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload, **one_seed(cell, seed, args.seconds, devices)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
