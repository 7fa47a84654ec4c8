"""Find the highest request rate a serving cell sustains, on the chip.

    python bench/tools/sweep_rate.py --workload qwen3-1.7b.idle_waiting \
        --seed 5 --seconds 20 --fractions 0.6,0.8,0.9,1.0,1.1

First a closed loop (one client, next request when the last is done) over
the cell's mix measures the mean service time S; the capacity is 1/S.  Then
the cell's open-loop schedule runs at each fraction of that capacity, and
each line reports the time to first token (median, p95), the queue wait, and
how late the last tenth of the requests started (a backlog that grows
through the window shows there).  One JSON line per run.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def run_once(cell, seed, seconds, devices):
    import numpy as np

    from bench import harness

    runner = harness.load_module(harness.path_file(cell), "bench_path_sweep").Runner(
        cell, seed, seconds, devices)
    runner.setup()
    runner.window(seconds)
    runner.release()
    recs = [r for r in runner.records if "prefill_s" in r]
    wait = np.array([r["t_infer"] - r["due"] for r in recs])
    ttft = wait + np.array([r["prefill_s"] for r in recs])
    service = np.array([r["t_done"] - r["t_infer"] for r in recs])
    tail = wait[-max(1, len(wait) // 10):]
    return {
        "requests": len(recs), "service_s_mean": float(service.mean()),
        "ttft_ms_p50": 1000 * float(np.percentile(ttft, 50)),
        "ttft_ms_p95": 1000 * float(np.percentile(ttft, 95)),
        "queue_wait_ms_mean": 1000 * float(wait.mean()),
        "last_tenth_wait_ms_mean": 1000 * float(tail.mean()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fractions", default="0.6,0.8,0.9,1.0,1.1")
    args = ap.parse_args(argv)

    from bench import harness

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", harness.compile_cache_dir(ROOT))
    cell = harness.load_cell(args.workload)
    devices = harness.devices_for(cell)
    harness.enable_compile_cache(harness.compile_cache_dir(ROOT))

    closed = copy.deepcopy(cell)
    closed.workload["traffic"]["arrivals"] = "closed_loop"
    base = run_once(closed, args.seed, args.seconds, devices)
    capacity = 1.0 / base["service_s_mean"]
    print(json.dumps({"closed_loop": base, "capacity_per_s": capacity}), flush=True)
    for frac in (float(x) for x in args.fractions.split(",")):
        open_ = copy.deepcopy(cell)
        open_.workload["traffic"]["rate_per_s"] = frac * capacity
        out = run_once(open_, args.seed, args.seconds, devices)
        print(json.dumps({"fraction": frac, "rate_per_s": frac * capacity, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
