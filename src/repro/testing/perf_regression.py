"""Pinned-reference performance regression harness.

Three headline throughputs — periodic-fleet devices/sec, MC ensemble
seeds/sec, and cost-table points/sec — are asserted against references
measured on the CI reference container, with a **machine-scaled** tolerance
band: a pinned jitted ``lax.scan`` microbenchmark (:func:`machine_scale`)
measures how fast *this* machine is relative to the reference box, and every
floor is multiplied by that factor.  A 4× slower laptop gets a 4× lower
floor; a genuine 5× kernel regression still fails everywhere.

Two consumption modes:

* **in-process** — ``measure_*()`` + :func:`check` (the ``slow``-marked
  tests in ``tests/test_perf_regression.py``);
* **artifact** — :func:`check_bench_json` reads a ``BENCH_{fleet,mc,costs}``
  JSON and asserts its recorded throughput fields, so CI enforces the
  artifact trajectories it already uploads::

      PYTHONPATH=src python -m repro.testing.perf_regression BENCH_fleet.json

Floors are deliberately generous (default ``floor_frac`` = 0.15 of the
machine-scaled reference): this harness exists to catch order-of-magnitude
regressions (a lost ``jit``, an accidental Python loop, f64 spilling to
host), not 20% jitter.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Optional

__all__ = [
    "PerfReference",
    "REFERENCES",
    "REFERENCE_SCAN_RATE",
    "machine_scale",
    "measure_scan_rate",
    "measure_periodic_fleet",
    "measure_periodic_fleet_sharded",
    "measure_mc_seeds",
    "measure_batch_sweep",
    "check",
    "check_bench_json",
]


@dataclasses.dataclass(frozen=True)
class PerfReference:
    """One pinned throughput: reference rate + allowed floor fraction."""

    name: str
    reference_per_s: float       # measured on the reference container
    floor_frac: float = 0.15     # pass while measured ≥ frac · scaled ref
    unit: str = "items/s"

    def floor(self, scale: float) -> float:
        return self.reference_per_s * scale * self.floor_frac


#: steps/sec of the pinned calibration scan on the reference container
#: (measured by ``python -m repro.testing.perf_regression --calibrate``).
REFERENCE_SCAN_RATE = 15_600_000.0

#: Reference throughputs, measured on the same container as
#: :data:`REFERENCE_SCAN_RATE` via the ``measure_*`` functions below.
REFERENCES: dict[str, PerfReference] = {
    ref.name: ref
    for ref in (
        # in-process probes (tests/test_perf_regression.py, slow-marked)
        PerfReference("periodic_fleet", 800_000.0, unit="devices/s"),
        # the sharded kernel on a 1x1 mesh must hold the *same* floor as the
        # unsharded scan — shard_map plumbing, padding, and the chunked
        # donated loop are required to cost nothing per device
        PerfReference("periodic_fleet_sharded", 800_000.0, unit="devices/s"),
        PerfReference("mc_seeds", 10_000.0, unit="seeds/s"),
        PerfReference("batch_sweep", 700.0, unit="pts/s"),
        # artifact fields (BENCH_*.json) — the recorded rate varies with run
        # size (smoke vs full), so each reference pins the *highest* observed
        # configuration and the floor fraction is set to clear the lowest
        PerfReference("bench_fleet_devices_per_s", 100_000.0, unit="devices/s"),
        # the CI smoke runs this on a 2x2 fake-device mesh at 256 devices,
        # where per-chunk shard_map dispatch (not the scan) dominates — and
        # dispatch cost doesn't track the scan-rate calibration, so the
        # floor fraction is looser than the unsharded reference's
        PerfReference("bench_fleet_sharded_devices_per_s", 100_000.0,
                      floor_frac=0.1, unit="devices/s"),
        PerfReference("bench_mc_seeds_per_s", 25_000.0, floor_frac=0.1,
                      unit="seeds/s"),
        PerfReference("bench_costs_pts_per_s", 1_000.0, unit="pts/s"),
        # policy rollout: the jitted vmapped trace-simulator scan; the smoke
        # configuration (64 streams x 256 gaps) already clears 1M steps/s on
        # the reference box, so 0.1 of the pinned rate flags a lost jit or a
        # per-gap Python fallback without tripping on batch-size jitter
        PerfReference("bench_policy_steps_per_s", 1_200_000.0, floor_frac=0.1,
                      unit="steps/s"),
        # hierarchical control plane: device-ticks/sec of the epoch loop.
        # Per-epoch Python control (routing, autoscaling, fault machinery)
        # dominates at smoke scale and doesn't track the scan calibration,
        # so the floor fraction is loose — this flags a lost jit in the
        # per-rack routed chunks or an accidental per-tick Python loop
        PerfReference("bench_control_device_ticks_per_s", 40_000.0,
                      floor_frac=0.1, unit="device-ticks/s"),
    )
}


# ---------------------------------------------------------------------------
# Machine calibration
# ---------------------------------------------------------------------------
def measure_scan_rate(n_steps: int = 200_000, reps: int = 3) -> float:
    """Steps/sec of a pinned jitted f64 ``lax.scan`` — the calibration
    primitive.  Deliberately shaped like the simulator's inner loop (a few
    f64 adds/selects per step) so it scales the same way across machines."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64

    with enable_x64():
        def body(carry, x):
            a, b = carry
            a = a + jnp.where(x > 0.5, b, -b)
            b = b * 0.999999 + 1e-6
            return (a, b), ()

        xs = jnp.linspace(0.0, 1.0, n_steps, dtype=jnp.float64)

        @jax.jit
        def run(xs):
            (a, b), _ = jax.lax.scan(body, (jnp.float64(0.0), jnp.float64(1.0)), xs)
            return a + b

        run(xs).block_until_ready()          # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run(xs).block_until_ready()
            best = min(best, time.perf_counter() - t0)
    return n_steps / best


def machine_scale(scan_rate: Optional[float] = None) -> float:
    """This machine's speed relative to the reference container (>1 =
    faster).  Clipped above 1.0 so a faster machine never *raises* floors
    past what the reference box itself could meet."""
    rate = measure_scan_rate() if scan_rate is None else scan_rate
    return min(rate / REFERENCE_SCAN_RATE, 1.0)


# ---------------------------------------------------------------------------
# In-process probes (the three headline throughputs)
# ---------------------------------------------------------------------------
def measure_periodic_fleet(n_devices: int = 1024, n_steps: int = 200) -> float:
    """Devices/sec of the vectorized periodic admission scan."""
    from repro.core.phases import paper_lstm_item
    from repro.fleet import run_periodic, uniform_fleet

    params = uniform_fleet(
        n_devices, item=paper_lstm_item(),
        strategies=("on_off", "idle_waiting", "adaptive"),
        request_period_ms=40.0,
    )
    run_periodic(params, n_steps)            # compile
    t0 = time.perf_counter()
    run_periodic(params, n_steps)
    return n_devices / (time.perf_counter() - t0)


def measure_periodic_fleet_sharded(n_devices: int = 1024, n_steps: int = 200) -> float:
    """Devices/sec of the sharded periodic scan on a 1×1 mesh — held to the
    same floor as :func:`measure_periodic_fleet` (sharding must be free)."""
    from repro.core.phases import paper_lstm_item
    from repro.fleet import fleet_mesh, run_periodic_sharded, uniform_fleet

    params = uniform_fleet(
        n_devices, item=paper_lstm_item(),
        strategies=("on_off", "idle_waiting", "adaptive"),
        request_period_ms=40.0,
    )
    mesh = fleet_mesh(1, 1)
    run_periodic_sharded(params, n_steps, mesh=mesh)    # compile
    t0 = time.perf_counter()
    run_periodic_sharded(params, n_steps, mesh=mesh)
    return n_devices / (time.perf_counter() - t0)


def measure_mc_seeds(n_seeds: int = 256, n_steps: int = 500) -> float:
    """Seeds/sec of the vmapped periodic MC ensemble (3-device mix)."""
    from repro.core.arrivals import JitteredArrivals
    from repro.core.phases import paper_lstm_item
    from repro.fleet import uniform_fleet
    from repro.mc import run_periodic_ensemble

    params = uniform_fleet(
        3, item=paper_lstm_item(),
        strategies=("on_off", "idle_waiting", "adaptive"),
        request_period_ms=40.0,
    )
    process = JitteredArrivals(40.0, 0.1)
    # warm up at the full seed count — a different count is a different
    # vmapped shape, so a smaller warm-up would leave compile in the timing
    run_periodic_ensemble(params, process, n_steps, n_seeds)
    t0 = time.perf_counter()
    run_periodic_ensemble(params, process, n_steps, n_seeds)
    return n_seeds / (time.perf_counter() - t0)


def measure_batch_sweep(batches: tuple[int, ...] = (1, 2, 4, 8)) -> float:
    """Cost-table points/sec: every zoo model × ``batches``, cache-cold."""
    from repro.costs import model_names, model_request_cost
    from repro.costs.zoo import _cached_cost

    _cached_cost.cache_clear()
    models = model_names()
    t0 = time.perf_counter()
    n = 0
    for m in models:
        for b in batches:
            model_request_cost(m, batch=b)
            n += 1
    return n / (time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Checking
# ---------------------------------------------------------------------------
def check(name: str, measured_per_s: float, scale: float) -> dict:
    """One assertion record: measured vs the machine-scaled floor."""
    ref = REFERENCES[name]
    floor = ref.floor(scale)
    return {
        "name": name,
        "unit": ref.unit,
        "measured_per_s": round(measured_per_s, 1),
        "reference_per_s": ref.reference_per_s,
        "machine_scale": round(scale, 4),
        "floor_per_s": round(floor, 1),
        "floor_frac": ref.floor_frac,
        "ok": bool(measured_per_s >= floor),
    }


#: BENCH artifact kind → list of (reference name, path into the payload).
_BENCH_FIELDS: dict[str, list[tuple[str, tuple[str, ...]]]] = {
    "fleet": [
        ("bench_fleet_devices_per_s",
         ("throughput", "periodic", "fleet", "devices_per_s")),
        ("bench_fleet_sharded_devices_per_s",
         ("throughput", "sharded", "fleet", "devices_per_s")),
    ],
    "mc": [
        ("bench_mc_seeds_per_s", ("throughput", "ensemble", "seeds_per_s")),
    ],
    "costs": [
        ("bench_costs_pts_per_s", ("costs", "throughput", "pts_per_s")),
    ],
    "policy": [
        ("bench_policy_steps_per_s", ("throughput", "rollout", "steps_per_s")),
    ],
    # the observability CLI records a ledger/trace-*disabled* periodic run in
    # the fleet layout, so the same floor asserts the plumbing stayed off the
    # hot path
    "obs": [
        ("bench_fleet_devices_per_s",
         ("throughput", "periodic", "fleet", "devices_per_s")),
    ],
    "control": [
        ("bench_control_device_ticks_per_s",
         ("throughput", "hierarchy", "device_ticks_per_s")),
    ],
}


def _dig(d: dict, path: tuple[str, ...]):
    for k in path:
        if not isinstance(d, dict) or k not in d:
            return None
        d = d[k]
    return d


def check_bench_json(
    path_or_payload, scale: Optional[float] = None
) -> list[dict]:
    """Assert the recorded throughput fields of one BENCH artifact.

    Accepts a path or an already-parsed payload dict; the artifact's
    ``kind`` field selects which fields are enforced.  Returns one check
    record per field (missing fields fail explicitly — a silently dropped
    throughput section must not pass)."""
    if isinstance(path_or_payload, dict):
        payload = path_or_payload
    else:
        with open(path_or_payload) as f:
            payload = json.load(f)
    kind = payload.get("kind")
    if kind not in _BENCH_FIELDS:
        raise ValueError(
            f"unknown BENCH kind {kind!r}; expected one of {sorted(_BENCH_FIELDS)}"
        )
    if scale is None:
        scale = machine_scale()
    out = []
    for ref_name, field_path in _BENCH_FIELDS[kind]:
        value = _dig(payload, field_path)
        if value is None:
            out.append({
                "name": ref_name, "ok": False,
                "error": f"missing field {'.'.join(field_path)} in {kind} artifact",
            })
            continue
        out.append(check(ref_name, float(value), scale))
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "--calibrate":
        rate = measure_scan_rate()
        print(f"scan rate: {rate:,.0f} steps/s "
              f"(reference {REFERENCE_SCAN_RATE:,.0f}, "
              f"scale {machine_scale(rate):.3f})")
        return 0
    if not argv:
        print(__doc__)
        return 2
    scale = machine_scale()
    failed = 0
    for path in argv:
        for rec in check_bench_json(path, scale=scale):
            status = "ok  " if rec["ok"] else "FAIL"
            if "error" in rec:
                print(f"[{status}] {path}: {rec['name']}: {rec['error']}")
            else:
                print(
                    f"[{status}] {path}: {rec['name']} "
                    f"{rec['measured_per_s']:,} {rec['unit']} "
                    f"(floor {rec['floor_per_s']:,} @ scale {rec['machine_scale']})"
                )
            failed += 0 if rec["ok"] else 1
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
