#!/usr/bin/env python3
"""Smoke test of the repo's main paths on a TPU chip, in one process.

    python chip_smoke.py               # one chip: serving, then the fleet simulator
    python chip_smoke.py --four-chips  # the sharded fleet scan on four chips, alone

Phases run in order; each prints its wall time and key numbers on one line.

(a) serving: full-width qwen3-1.7b with weights drawn from a seed, saved as a
    ``zstd+int8`` checkpoint.  Requests are served under ``on_off`` (every
    request brings the engine up from the checkpoint and releases it: the
    paper's configuration phase on a TPU), then under ``idle_waiting`` (one
    bring-up, the engine stays resident).  The two strategies must produce
    the same greedy tokens, the compiled prefill must contain the Pallas
    flash-attention kernel, and its logits must agree with the XLA reference
    attention.
(b) fleet simulator: the fleet CLI at its defaults (4096 devices, 10 s,
    ``round_robin``, with its scalar-oracle self-check), then
    ``run_periodic`` at 10^6 devices over a fixed horizon, its admitted
    counts compared exactly with the scalar oracle and its energy ledger
    conserving within 1e-9.
(c) ``--four-chips``: ``run_periodic_sharded`` on a 4x1 ``("fleet", "seed")``
    mesh against ``run_periodic`` on one chip, bit for bit.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU
the script exits non-zero before any phase: it falls back to nothing.  No
phase's failure is caught.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.launch._cli import device_info, enable_compile_cache  # noqa: E402

#: Largest ‖kernel − reference‖₂ / ‖reference‖₂ of the prefill logits (bf16
#: activations through every layer; the two differ only in attention).  A
#: wrong kernel is off by O(1).
PREFILL_REL_TOL = 5e-2

#: The served model, at its published widths unless ``reduced``.
ARCH = "qwen3-1.7b"

#: Energy budget of each simulated device: On-Off devices exhaust it inside
#: the 1024-step horizon, Idle-Waiting ones outlive it.
BUDGET_J = 2.0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _check(ok, what) -> None:
    if not ok:
        raise SmokeFailure(what)


def _emit(name: str, elapsed_s: float, numbers: dict) -> None:
    print(f"[{name}] wall_s={elapsed_s:.6f} " + json.dumps(numbers, default=str), flush=True)


def _timed(name: str, fn, *args, **kwargs) -> dict:
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    _emit(name, time.perf_counter() - t0, out)
    return out


# ---------------------------------------------------------------------------
# (a) serving
# ---------------------------------------------------------------------------
def _serve(controller, requests) -> tuple[list, dict]:
    """Submit ``requests`` back to back; return the generated tokens and
    the controller's phase times."""
    import numpy as np

    from repro.core.phases import CONFIGURATION, INFERENCE

    tokens = []
    for request in requests:
        result = controller.submit(request)
        tokens.append(np.asarray(result.tokens))
    summary = controller.summary()
    times = {
        "configurations": summary["configurations"],
        "bring_up_s": [r.wall_s for r in controller.records if r.name == CONFIGURATION],
        "infer_s": [r.wall_s for r in controller.records if r.name == INFERENCE],
    }
    return tokens, times


def serving_phase(reduced: bool = False, n_requests: int = 3) -> dict:
    import jax
    import numpy as np
    from functools import partial

    from repro.configs.perf import PerfConfig
    from repro.launch.serve import build_demo
    from repro.models import model_zoo as zoo

    out: dict = {"arch": ARCH, "reduced": reduced}
    with tempfile.TemporaryDirectory(prefix="chip-smoke-ckpt-") as ckpt:
        t0 = time.perf_counter()
        on_off, make_request = build_demo(
            ARCH, reduced=reduced, ckpt_dir=ckpt, strategy="on_off"
        )
        out["init_and_save_checkpoint_s"] = time.perf_counter() - t0
        out["checkpoint_bytes"] = sum(
            os.path.getsize(os.path.join(ckpt, f)) for f in os.listdir(ckpt)
        )
        # one request list for both strategies (each bring-up's warm-up
        # batch draws from make_request's stream too)
        requests = [make_request() for _ in range(n_requests)]
        tok_off, out["on_off"] = _serve(on_off, requests)
        _check(out["on_off"]["configurations"] == n_requests, out["on_off"])
        _check(on_off.handle is None, "on_off left the engine resident")

        idle, _ = build_demo(
            ARCH, reduced=reduced, ckpt_dir=ckpt, strategy="idle_waiting"
        )
        tok_idle, out["idle_waiting"] = _serve(idle, requests)
        _check(out["idle_waiting"]["configurations"] == 1, out["idle_waiting"])

    engine = idle.handle
    cfg = engine.cfg
    out["resident_bytes"] = engine.param_bytes()
    for a, b in zip(tok_off, tok_idle):
        _check(a.shape == tok_off[0].shape and a.dtype == np.int32, a)
        _check(((a >= 0) & (a < cfg.vocab_size)).all(), a)
        np.testing.assert_array_equal(a, b)
    out["tokens"] = [t.tolist() for t in tok_idle]

    request = requests[0]
    out["prefill_has_pallas_kernel"] = "tpu_custom_call" in (
        engine._prefill.lower(engine.params, request).compile().as_text()
    )
    reference = jax.jit(partial(
        zoo.prefill_fn, cfg=cfg, max_len=engine.max_len,
        perf=PerfConfig(attention_impl="xla"),
    ))
    got = np.asarray(engine._prefill(engine.params, request)[0], np.float32)
    want = np.asarray(reference(engine.params, request)[0], np.float32)
    _check(np.isfinite(got).all() and got.shape == want.shape, got.shape)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    out["prefill_vs_reference_rel_err"] = rel
    out["prefill_vs_reference_max_abs_err"] = float(np.max(np.abs(got - want)))
    out["prefill_argmax_agreement"] = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    _check(rel <= PREFILL_REL_TOL, f"prefill logits depart from the reference: {rel}")
    engine.release()
    return out


# ---------------------------------------------------------------------------
# (b) fleet simulator
# ---------------------------------------------------------------------------
def fleet_cli_phase(devices: int = 4096, horizon_s: float = 10.0) -> dict:
    """The fleet CLI's own path (its defaults, round-robin routing); the CLI
    refuses to emit when its sharded and unsharded scans disagree."""
    from repro.launch import fleet

    with tempfile.TemporaryDirectory(prefix="chip-smoke-fleet-") as tmp:
        path = os.path.join(tmp, "fleet.json")
        rc = fleet.main([
            "--devices", str(devices), "--horizon", str(horizon_s),
            "--router", "round_robin", "--out", path,
        ])
        _check(rc == 0, f"fleet CLI exited {rc}")
        with open(path) as f:
            payload = json.load(f)
    check = payload["oracle_self_check"]
    for strat, c in check.items():
        _check(c["n_fleet"] == c["n_oracle"], (strat, c))
    tp = payload["throughput"]
    summary = payload["summary"]
    return {
        "device": payload["manifest"]["device"],
        "n_steps": payload["n_steps"],
        "requests_served": summary["requests"]["served"],
        "total_energy_mj": summary["total_energy_mj"],
        "sharded_bit_identical": tp["sharded"]["bit_identical_to_unsharded"],
        "oracle_counts": {s: (c["n_fleet"], c["n_oracle"]) for s, c in check.items()},
        "oracle_energy_abs_diff_mj": {s: c["energy_abs_diff_mj"] for s, c in check.items()},
        "oracle_energy_bit_equal": all(c["agrees"] for c in check.values()),
    }


def _periodic_fleet(n_devices: int):
    """On-Off and Idle-Waiting devices alternating, the paper's LSTM item at
    a 40 ms period, with each strategy's scalar ``simulate()`` oracle."""
    from repro.core import energy_model as em
    from repro.core.phases import paper_lstm_item
    from repro.core.simulator import simulate
    from repro.core.strategies import IdlePowerMethod
    from repro.core.workload import ExperimentSpec, WorkloadSpec
    from repro.fleet import DeviceSpec, FleetParams

    specs = [
        ExperimentSpec(
            workload=WorkloadSpec(BUDGET_J, 40.0), item=paper_lstm_item(),
            strategy_kind=strategy, method=IdlePowerMethod.METHOD1_2,
            powerup_overhead_mj=em.CALIBRATED_POWERUP_OVERHEAD_MJ,
        )
        for strategy in ("on_off", "idle_waiting")
    ]
    params = FleetParams.from_specs(
        [DeviceSpec.from_experiment(s) for s in specs]
    ).tile(n_devices)
    return params, [simulate(s) for s in specs]


def _check_periodic(result, oracles, n_steps: int) -> dict:
    """Counts exactly equal to the oracle's (capped by the horizon), ledger
    axes summing to the energy within 1e-9; the energy's departure from the
    oracle is reported, not asserted."""
    import numpy as np

    from repro.obs.ledger import AXES

    k = len(oracles)
    n = result.n_items.shape[0]
    kind = np.arange(n) % k
    want_n = np.minimum([o.n_items for o in oracles], n_steps)[kind]
    count_mismatch = int(np.sum(result.n_items != want_n))
    _check(count_mismatch == 0, f"{count_mismatch} devices' counts differ from the oracle")

    led = result.ledger()
    totals = sum(np.asarray(getattr(led, f"{ax}_mj")) for ax in AXES)
    denom = np.maximum(np.abs(result.energy_mj), 1e-300)
    ledger_err = float(np.max(np.abs(totals - result.energy_mj) / denom))
    _check(ledger_err <= 1e-9, f"ledger conservation error {ledger_err}")

    dead = result.n_items < n_steps
    want_e = np.asarray([o.energy_used_mj for o in oracles])[kind]
    e_diff = np.abs(result.energy_mj - want_e)[dead]
    return {
        "devices": n,
        "n_steps": n_steps,
        "total_items": int(result.n_items.sum()),
        "devices_alive": int(result.alive.sum()),
        "count_mismatches": count_mismatch,
        "ledger_max_rel_err": ledger_err,
        "oracle_energy_max_abs_diff_mj": float(e_diff.max()) if e_diff.size else 0.0,
        "oracle_energy_mismatched_devices": int(np.sum(e_diff != 0.0)),
    }


def periodic_phase(n_devices: int = 1_000_000, n_steps: int = 1024) -> dict:
    """``run_periodic`` at fleet scale."""
    from repro.fleet import run_periodic

    params, oracles = _periodic_fleet(n_devices)
    run_periodic(params, n_steps)                      # compile
    t0 = time.perf_counter()
    result = run_periodic(params, n_steps)
    out = {"steady_run_s": time.perf_counter() - t0}
    out.update(_check_periodic(result, oracles, n_steps))
    return out


# ---------------------------------------------------------------------------
# (c) four chips
# ---------------------------------------------------------------------------
def sharded_phase(n_chips: int = 4, n_devices: int = 1_000_000, n_steps: int = 1024) -> dict:
    """``run_periodic_sharded`` over ``n_chips`` on the fleet axis against
    ``run_periodic`` on one chip: every field bit-identical."""
    import numpy as np

    from repro.fleet import fleet_mesh, run_periodic, run_periodic_sharded

    params, oracles = _periodic_fleet(n_devices)
    mesh = fleet_mesh(n_chips, 1)
    single = run_periodic(params, n_steps)
    run_periodic_sharded(params, n_steps, mesh=mesh)   # compile
    t0 = time.perf_counter()
    sharded = run_periodic_sharded(params, n_steps, mesh=mesh)
    out = {"mesh": f"{n_chips}x1", "n_shards": sharded.n_shards,
           "steady_run_s": time.perf_counter() - t0}
    for f in ("n_items", "energy_mj", "lifetime_ms", "alive", "alive_over_time"):
        np.testing.assert_array_equal(
            getattr(sharded, f), getattr(single, f), err_msg=f"sharded {f} differs"
        )
    out["bit_identical_to_one_chip"] = True
    out.update(_check_periodic(single, oracles, n_steps))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet scan on a 4x1 mesh")
    args = ap.parse_args(argv)

    import jax

    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {info}); nothing was run",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()             # before the first compile
    print(f"[setup] device={json.dumps(info)} compile_cache={cache}", flush=True)

    hits = {"hits": 0, "requests": 0}

    def _count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            hits["requests"] += 1

    jax.monitoring.register_event_listener(_count)

    t0 = time.perf_counter()
    if args.four_chips:
        if info["count"] != 4:
            print(f"chip_smoke: --four-chips needs 4 chips, found {info['count']}",
                  file=sys.stderr)
            return 1
        _timed("sharded_4x1", sharded_phase, n_chips=4)
    else:
        serving = _timed("serving", serving_phase)
        _check(serving["prefill_has_pallas_kernel"], "prefill ran without the Pallas kernel")
        fleet_cli = _timed("fleet_cli", fleet_cli_phase)
        if not fleet_cli["oracle_energy_bit_equal"]:
            print("[fleet_cli] emulated f64 departs from the scalar oracle's energy: "
                  + json.dumps(fleet_cli["oracle_energy_abs_diff_mj"]), flush=True)
        periodic = _timed("periodic_1e6", periodic_phase)
        if periodic["oracle_energy_mismatched_devices"]:
            print("[periodic_1e6] emulated f64 departs from the scalar oracle's energy on "
                  f"{periodic['oracle_energy_mismatched_devices']} devices, max "
                  f"{periodic['oracle_energy_max_abs_diff_mj']} mJ", flush=True)
    _emit("total", time.perf_counter() - t0, {"compile_cache": cache, **hits})
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
