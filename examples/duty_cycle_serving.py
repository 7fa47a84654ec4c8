"""End-to-end driver (deliverable b): serve a small model with batched
requests under the paper's duty-cycle strategies — LIVE, on this machine.

bring_up  = restore zstd+int8-compressed checkpoint + jit warm-up
            (the 'configuration phase')
infer     = prefill + 8-token batched generation (the 'workload item')
release   = drop all device buffers (the 'power-off')

The controller measures each phase, computes the analytical cross point
from its OWN measurements, and the 'auto' strategy becomes the paper's
configuration-aware policy.  Energy ratios between strategies are
wall-clock-based and power-model independent.

Run:  PYTHONPATH=src python examples/duty_cycle_serving.py
"""
import time

from repro.launch.serve import build_demo
from repro.serving.scheduler import run_schedule

ARCH = "qwen3-1.7b"
N_REQ = 8


def run(strategy: str, period_s: float):
    controller, make_request = build_demo(ARCH, reduced=True, strategy=strategy)
    res = run_schedule(
        controller, (make_request() for _ in range(N_REQ)), period_s=period_s
    )
    print(
        f"  {strategy:12s}: {res.n_requests} requests, "
        f"{res.n_configurations} configurations, energy {res.energy_mj:9.1f} mJ"
        + (f", measured crossover {res.crossover_ms:.0f} ms" if res.crossover_ms else "")
    )
    return res


if __name__ == "__main__":
    # a fast request period (below the crossover): Idle-Waiting should win
    print(f"== duty-cycle serving of {ARCH} (reduced), period = 0.5 s ==")
    oo = run("on_off", 0.5)
    iw = run("idle_waiting", 0.5)
    auto = run("auto", 0.5)
    print(f"  energy ratio On-Off / Idle-Waiting: {oo.energy_mj / iw.energy_mj:.2f}×")
    assert iw.energy_mj < oo.energy_mj, "Idle-Waiting must win at short periods"
    # 'auto' should have converged to idle-waiting (few configurations)
    assert auto.n_configurations <= 2
    print("  ✓ live measurements agree with the paper's strategy ordering")
