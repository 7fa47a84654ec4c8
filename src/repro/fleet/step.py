"""Fleet transition kernels: N devices through one ``jax.lax.scan``.

Two step semantics, sharing :class:`~repro.fleet.state.FleetParams`:

**Periodic** (:func:`run_periodic`) — every device sees its own constant
request period (the paper's duty-cycle mode); one scan step = one request
per device.  Admission recomputes the *closed-form affine* cumulative
energy each step — the same per-item/idle/init costs as
:mod:`repro.core.batch_eval`'s kernels, in the same IEEE-754 association
order as the scalar event loop:

    On-Off       cum(n) = n · E_item^OnOff
    Idle-Waiting cum(n) = E_init + n · E_item^IW + (n−1) · E_idle

admit item ``n`` iff ``cum(n) ≤ budget + FLOOR_EPS · per_period`` — the
scalar ``simulate(mode="step")`` rule, so an N=1 fleet reproduces the scalar
oracle's ``n_items`` exactly and its energy bit-for-bit (final energies are
re-derived *eagerly* from the admitted counts through the identical
expression the oracle uses, outside the jitted scan, so XLA fusion cannot
perturb them).

**Routed** (:func:`run_routed`) — a global clock advances in ``dt_ms``
ticks; a router (:mod:`repro.fleet.router`) splits each tick's global
request count across devices, requests wait in per-device FIFO ring buffers
(arrival timestamps, so latency percentiles are exact), and each device
serves at most one request per tick under ``simulate_trace``'s charging
rules: the idle span since the last completion (capped at the policy's
timeout), a (re)configuration when off or released, then the execution
phases — admitted only if all of it fits the remaining budget, after which
the device is dead.  With N=1, a trivial router, on-grid arrivals, and
periods longer than the service time, the routed kernel agrees with
:func:`repro.core.simulator.simulate_trace` to float-accumulation noise
(≪1e-9 on realistic horizons).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import enable_x64
from jax.profiler import TraceAnnotation

from repro.core import energy_model as em
from repro.fleet.router import ROUTER_CODES, route_counts
from repro.fleet.state import FleetParams, FleetState

__all__ = [
    "INT32_STEP_LIMIT",
    "PeriodicFleetResult",
    "RoutedFleetResult",
    "routed_ledger",
    "run_periodic",
    "run_routed",
]

#: simulate_trace's admission epsilon (relative to max(1, cost)).
_TRACE_EPS = 1e-9

#: Capacity of the int32 per-device step counter the periodic scans carry
#: (see ``repro.fleet.dtypes`` for the full carry-dtype audit).  Guarded
#: explicitly at every entry point rather than silently wrapping.
INT32_STEP_LIMIT = 2**31 - 1


def _check_step_count(n_steps: int, where: str) -> None:
    if n_steps > INT32_STEP_LIMIT:
        raise OverflowError(
            f"{where}: n_steps={n_steps} exceeds the int32 step-counter "
            f"capacity ({INT32_STEP_LIMIT}); the scan carries int32 "
            "admission counters (repro.fleet.dtypes) — split the horizon "
            "or widen the carry deliberately"
        )


# ---------------------------------------------------------------------------
# Periodic kernel
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PeriodicFleetResult:
    """Final fleet state after ``n_steps`` request periods per device."""

    params: FleetParams
    n_steps: int
    n_items: np.ndarray           # i64 (N,) — items admitted within budget
    energy_mj: np.ndarray         # f64 (N,) — cumulative energy (oracle-exact)
    lifetime_ms: np.ndarray       # f64 (N,) — n_items · period
    alive: np.ndarray             # bool (N,) — still admitting at horizon end
    alive_over_time: np.ndarray   # i32 (n_steps,) — devices alive per step

    def ledger(self):
        """Per-device phase-resolved :class:`repro.obs.ledger.EnergyLedger`
        (shape ``(N,)`` per axis), derived from the admitted counts through
        the same closed forms as ``energy_mj`` — axes sum to ``energy_mj``
        within 1e-9 relative (the conservation contract)."""
        from repro.obs.ledger import EnergyLedger

        p = self.params
        nf = self.n_items.astype(np.float64)
        any_items = (self.n_items > 0).astype(np.float64)
        is_onoff = np.asarray(p.is_onoff)
        ovh = np.asarray(p.e_overhead_mj)
        cfg_pure = np.asarray(p.e_config_mj) - ovh
        # On-Off pays configure+overhead per item; Idle-Waiting once (E_init)
        n_cfg = np.where(is_onoff, nf, any_items)
        idle = np.where(
            is_onoff, 0.0, any_items * (nf - 1.0) * np.asarray(p.e_idle_mj)
        )
        return EnergyLedger.from_axes(
            configure=n_cfg * cfg_pure,
            compute=nf * np.asarray(p.e_exec_mj),
            idle=idle,
            off=np.zeros_like(nf),
            overhead=n_cfg * ovh,
        )


def _periodic_limit(params: FleetParams):
    """Per-device admission limit: budget + FLOOR_EPS of one nominal period
    (the scalar ``simulate(mode="step")`` boundary rule)."""
    per_period = params.e_item_mj + params.e_idle_mj   # e_idle = 0 for On-Off
    return params.e_budget_mj + em.FLOOR_EPS * per_period


def _periodic_body(params: FleetParams, limit):
    """The one periodic admission step — shared verbatim by the unsharded
    scan below and every per-shard scan in :mod:`repro.fleet.shard`, so
    sharded results are bit-identical by construction.

    Carry: ``(n int32, alive bool)``; per-step output: the fleet-local
    admitted count as int32 (integer sums are associative, so per-shard
    partial sums + a psum reproduce the global ``jnp.sum`` exactly).
    """

    def body(carry, _):
        n, alive = carry
        nf = (n + 1).astype(jnp.float64)
        cum = jnp.where(
            params.is_onoff,
            nf * params.e_item_mj,
            params.e_init_mj + nf * params.e_item_mj + (nf - 1.0) * params.e_idle_mj,
        )
        admit = alive & params.feasible & (cum <= limit)
        n = jnp.where(admit, n + 1, n)
        return (n, admit), jnp.sum(admit).astype(jnp.int32)

    return body


def _periodic_carry0(params: FleetParams):
    n0 = jnp.zeros(params.period_ms.shape, dtype=jnp.int32)
    alive0 = jnp.ones(params.period_ms.shape, dtype=bool)
    return n0, alive0


def _periodic_final(params: FleetParams, n):
    """Final energies/lifetimes re-derived eagerly from the admitted counts —
    op-for-op the scalar fast path (``onoff_cumulative_energy_mj`` /
    ``idlewait_cumulative_energy_mj``), outside any jitted scan so XLA
    fusion cannot perturb them.  Shared with the sharded runner."""
    nf = n.astype(jnp.float64)
    energy = jnp.where(
        params.is_onoff,
        nf * params.e_item_mj,
        jnp.where(
            n > 0,
            params.e_init_mj + nf * params.e_item_mj + (nf - 1.0) * params.e_idle_mj,
            0.0,
        ),
    )
    lifetime = nf * params.period_ms
    return energy, lifetime


def _periodic_scan(params: FleetParams, n_steps: int):
    body = _periodic_body(params, _periodic_limit(params))
    (n, alive), alive_ts = lax.scan(
        body, _periodic_carry0(params), None, length=n_steps
    )
    return n, alive, alive_ts


_periodic_scan_jit = jax.jit(_periodic_scan, static_argnums=(1,))


def run_periodic(params: FleetParams, n_steps: int, jit: bool = True) -> PeriodicFleetResult:
    """Advance every device through ``n_steps`` of its own request period.

    ``n_items`` is capped by the horizon: a device that would outlive
    ``n_steps`` requests reports ``n_items == n_steps`` with ``alive`` still
    True.  Choose ``n_steps ≥ n_max`` (e.g. from
    :func:`repro.core.batch_eval.evaluate_idlewait_batch`) for full-lifetime
    questions.
    """
    if n_steps < 0:
        raise ValueError(f"n_steps must be non-negative, got {n_steps}")
    _check_step_count(n_steps, "run_periodic")
    with enable_x64():
        fn = _periodic_scan_jit if jit else _periodic_scan
        n, alive, alive_ts = fn(params, n_steps)
        energy, lifetime = _periodic_final(params, n)
    # the first conversion waits for the scan
    with TraceAnnotation("fleet/to_host"):
        return PeriodicFleetResult(
            params=params,
            n_steps=n_steps,
            n_items=np.asarray(n).astype(np.int64),
            energy_mj=np.asarray(energy),
            lifetime_ms=np.asarray(lifetime),
            alive=np.asarray(alive),
            alive_over_time=np.asarray(alive_ts),
        )


# ---------------------------------------------------------------------------
# Routed kernel
# ---------------------------------------------------------------------------
def routed_ledger(params: FleetParams, state: FleetState):
    """Per-device phase-resolved :class:`repro.obs.ledger.EnergyLedger`
    (shape ``(N,)`` per axis) for any routed-kernel :class:`FleetState`:
    configurations split into the pure configure energy and the power-up
    overhead, idle energy from the scan's own accumulator — axes sum to
    ``state.energy_mj`` within 1e-9 relative.  Shared by
    :meth:`RoutedFleetResult.ledger` and the hierarchical control plane
    (:mod:`repro.control`), which builds rack ledgers from carried states.
    """
    from repro.obs.ledger import EnergyLedger

    n_cfg = np.asarray(state.n_configs).astype(np.float64)
    served = np.asarray(state.n_served).astype(np.float64)
    ovh = np.asarray(params.e_overhead_mj)
    cfg_pure = np.asarray(params.e_config_mj) - ovh
    return EnergyLedger.from_axes(
        configure=n_cfg * cfg_pure,
        compute=served * np.asarray(params.e_exec_mj),
        idle=np.asarray(state.idle_energy_mj),
        off=np.zeros_like(served),
        overhead=n_cfg * ovh,
    )


@dataclasses.dataclass(frozen=True)
class RoutedFleetResult:
    """Final state + per-step trajectories of a routed-traffic run."""

    params: FleetParams
    state: FleetState             # final carry (arrays still jnp, f64)
    dt_ms: float
    n_steps: int
    router: Optional[str]         # None = per-device streams ("direct")
    alive_over_time: np.ndarray   # i32 (K,)
    served_over_time: np.ndarray  # i32 (K,)
    queued_over_time: np.ndarray  # i32 (K,)
    latency_ms: Optional[np.ndarray]   # f32 (K, N) — served-request latency
    served_mask: Optional[np.ndarray]  # bool (K, N)
    # state-transition event masks, populated with collect_events=True
    reconfig_mask: Optional[np.ndarray] = None   # bool (K, N) — serve paid a config
    released_mask: Optional[np.ndarray] = None   # bool (K, N) — timeout release
    queue_depth: Optional[np.ndarray] = None     # i32 (K, N) — post-tick backlog
    dropped_per_tick: Optional[np.ndarray] = None  # i32 (K, N) — overflow drops
    start_tick: int = 0           # global tick of this chunk's first step

    @property
    def n_served(self) -> np.ndarray:
        return np.asarray(self.state.n_served)

    @property
    def energy_mj(self) -> np.ndarray:
        return np.asarray(self.state.energy_mj)

    def ledger(self):
        """Per-device phase-resolved :class:`repro.obs.ledger.EnergyLedger`
        (shape ``(N,)`` per axis) — see :func:`routed_ledger`."""
        return routed_ledger(self.params, self.state)

    def final_modes(self) -> np.ndarray:
        """Per-device mode codes at horizon end (state.MODE_*): DEAD if the
        budget is exhausted, BUSY if still mid-service, IDLE if resident
        within its timeout, OFF otherwise (never configured or released)."""
        from repro.fleet.state import MODE_BUSY, MODE_DEAD, MODE_IDLE, MODE_OFF

        end_ms = self.dt_ms * (self.start_tick + self.n_steps)
        alive = np.asarray(self.state.alive)
        resident = np.asarray(self.state.resident)
        completion = np.asarray(self.state.completion_ms)
        served = np.asarray(self.state.n_served) > 0
        timed_out = np.asarray(self.params.timeout_ms) < (end_ms - completion)
        return np.where(
            ~alive,
            MODE_DEAD,
            np.where(
                served & (completion > end_ms),
                MODE_BUSY,
                np.where(resident & served & ~timed_out, MODE_IDLE, MODE_OFF),
            ),
        )


def _routed_body(params: FleetParams, dt_ms, router_code: Optional[int],
                 collect_latency: bool, capacity: int,
                 collect_events: bool = False):
    """Build the scan body; ``router_code`` None means per-device counts.

    ``collect_events=True`` appends per-tick state-transition outputs
    (reconfigure / release masks, queue depth, drops) after the latency
    outputs — the raw material :func:`repro.obs.trace.routed_timeline`
    rebuilds a Chrome-trace timeline from.  Existing ``ys`` indices are
    unchanged, so callers that ignore events are unaffected."""

    def body(state: FleetState, x):
        k, arr = x
        now = k.astype(jnp.float64) * dt_ms
        n_dev = params.period_ms.shape[0]

        if router_code is None:
            counts = arr.astype(jnp.int32)
            rr_next = state.rr_ptr
            unrouted = jnp.zeros((), dtype=jnp.int64)
        else:
            counts, rr_next = route_counts(
                arr, router_code, state.alive, state.q_len,
                state.energy_mj, params.e_budget_mj, state.rr_ptr,
            )
            # requests no alive device could take (counts sums to the global
            # stream otherwise); queue overflow is tracked per device below
            unrouted = arr.astype(jnp.int64) - jnp.sum(counts.astype(jnp.int64))

        # ---- enqueue: masked ring-buffer fill (all arrivals stamp `now`) ----
        space = capacity - state.q_len
        acc = jnp.minimum(counts, space)
        slots = jnp.arange(capacity, dtype=jnp.int32)[None, :]
        rel = (slots - (state.q_head + state.q_len)[:, None]) % capacity
        queue_ms = jnp.where(rel < acc[:, None], now, state.queue_ms)
        q_len = state.q_len + acc

        # ---- serve at most one queued request per device this tick ---------
        free = state.alive & (q_len > 0) & (now >= state.completion_ms)
        head_ts = queue_ms[jnp.arange(n_dev), state.q_head]
        # The *policy-managed* idle span is the time the device sat with an
        # empty queue: from its last completion until the head request
        # *arrived* (simulate_trace's start = max(a, completion)) — only
        # that span is subject to the timeout/release decision, so a
        # backlogged request (arrived before the completion) cannot trigger
        # a phantom release + reconfiguration.  A device that did NOT
        # release stays resident through the remaining hold until this
        # service tick and is charged idle power for all of it.
        head_ready = jnp.maximum(head_ts, state.completion_ms)
        gap_policy = head_ready - state.completion_ms
        managed = (state.n_served > 0) & state.resident
        released = managed & (params.timeout_ms < gap_policy)
        # the remaining *hold* until this service tick (a tick-quantization
        # window the continuous oracle doesn't have) is charged at idle
        # power only for policies that keep the device resident at all
        hold = jnp.where(params.timeout_ms > 0, now - head_ready, 0.0)
        idle_t = jnp.where(
            managed,
            jnp.where(released, params.timeout_ms, gap_policy + hold),
            0.0,
        )
        idle_e = params.p_idle_mw * idle_t / 1000.0
        reconfig = (~state.resident) | released
        cost = idle_e + jnp.where(reconfig, params.e_config_mj, 0.0) + params.e_exec_mj
        fits = state.energy_mj + cost <= params.e_budget_mj + _TRACE_EPS * jnp.maximum(1.0, cost)
        serve = free & fits
        # a device whose next admission no longer fits is exhausted for good
        alive = state.alive & ~(free & ~fits)

        inline_cfg = serve & reconfig & (state.n_configs > 0)
        start = now + jnp.where(inline_cfg, params.t_config_ms, 0.0)
        completion = jnp.where(serve, start + params.t_exec_ms, state.completion_ms)
        energy = state.energy_mj + jnp.where(serve, cost, 0.0)
        latency = jnp.where(serve, completion - head_ts, 0.0)

        new_state = FleetState(
            energy_mj=energy,
            # the idle-waiting share of the same accumulation (ledger axis)
            idle_energy_mj=state.idle_energy_mj + jnp.where(serve, idle_e, 0.0),
            n_served=state.n_served + serve.astype(jnp.int64),
            n_configs=state.n_configs + (serve & reconfig).astype(jnp.int64),
            n_released=state.n_released + (serve & released).astype(jnp.int64),
            n_dropped=state.n_dropped + (counts - acc).astype(jnp.int64),
            resident=jnp.where(serve, True, state.resident),
            alive=alive,
            completion_ms=completion,
            queue_ms=queue_ms,
            q_head=jnp.where(serve, (state.q_head + 1) % capacity, state.q_head),
            q_len=q_len - serve.astype(jnp.int32),
            rr_ptr=rr_next,
        )
        ys = (
            jnp.sum(alive).astype(jnp.int32),
            jnp.sum(serve).astype(jnp.int32),
            jnp.sum(new_state.q_len).astype(jnp.int32),
            unrouted,
        )
        if collect_latency:
            ys = ys + (latency.astype(jnp.float32), serve)
        if collect_events:
            ys = ys + (
                serve & reconfig,
                serve & released,
                new_state.q_len,
                (counts - acc).astype(jnp.int32),
            )
        return new_state, ys

    return body


@functools.lru_cache(maxsize=None)
def _routed_scan_fn(router_code: Optional[int], collect_latency: bool,
                    capacity: int, collect_events: bool = False):
    def scan_fn(params, state0, steps, arrivals, dt_ms):
        body = _routed_body(params, dt_ms, router_code, collect_latency,
                            capacity, collect_events)
        return lax.scan(body, state0, (steps, arrivals))

    return jax.jit(scan_fn)


def run_routed(
    params: FleetParams,
    arrivals,
    dt_ms: float,
    router: Optional[str] = "round_robin",
    queue_capacity: int = 16,
    collect_latency: bool = True,
    collect_events: bool = False,
    jit: bool = True,
    state0: Optional[FleetState] = None,
    start_tick: int = 0,
) -> RoutedFleetResult:
    """Simulate routed traffic over ``K = len(arrivals)`` ticks of ``dt_ms``.

    ``arrivals`` is either a ``(K,)`` int array — the *global* per-tick
    request counts a router distributes — or a ``(K, N)`` int array of
    per-device counts (``router=None``/"direct", e.g. from
    :func:`repro.core.arrivals.bin_arrival_counts`).  Service rate is capped
    at one request per device per tick, so pick ``dt_ms`` at or below the
    per-device inter-arrival scale.

    **Chunked continuation.** Passing ``state0`` (a previous run's
    ``result.state``) and ``start_tick`` (previous ``start_tick + n_steps``)
    resumes the global clock mid-stream: the scan's ``now = k * dt_ms``
    values are the same ones a single full-length run would compute, and the
    carry is handed over unchanged, so a chain of chunked calls is
    *bit-identical* to one call over the concatenated arrivals (per-chunk
    global-drop roll-ups onto device 0 are integer sums, hence exact).  This
    is the differential spine the hierarchical control plane
    (:mod:`repro.control`) collapses onto.  When ``state0`` is given the
    queue capacity is taken from it and ``queue_capacity`` is ignored.
    """
    if dt_ms <= 0:
        raise ValueError(f"dt_ms must be positive, got {dt_ms}")
    if start_tick < 0:
        raise ValueError(f"start_tick must be non-negative, got {start_tick}")
    with enable_x64():
        arrivals = jnp.asarray(arrivals)
        if arrivals.ndim == 1:
            if router is None or router == "direct":
                raise ValueError("1-D arrivals (a global stream) need a router policy")
            code: Optional[int] = ROUTER_CODES[router]
        elif arrivals.ndim == 2:
            if arrivals.shape[1] != params.n_devices:
                raise ValueError(
                    f"per-device arrivals have {arrivals.shape[1]} columns for "
                    f"{params.n_devices} devices"
                )
            if router not in (None, "direct"):
                raise ValueError("per-device (K, N) arrivals are already routed; use router=None")
            code = None
            router = None
        else:
            raise ValueError(f"arrivals must be (K,) or (K, N), got shape {arrivals.shape}")
        n_steps = int(arrivals.shape[0])
        _check_step_count(start_tick + n_steps, "run_routed")
        arrivals = arrivals.astype(jnp.int32)
        steps = jnp.arange(start_tick, start_tick + n_steps, dtype=jnp.int64)
        if state0 is None:
            state0 = FleetState.init(params.n_devices, queue_capacity)
        else:
            if int(state0.energy_mj.shape[0]) != params.n_devices:
                raise ValueError(
                    f"state0 carries {int(state0.energy_mj.shape[0])} devices "
                    f"for {params.n_devices}-device params"
                )
            queue_capacity = state0.queue_capacity
        dt = jnp.asarray(dt_ms, dtype=jnp.float64)
        if jit:
            fn = _routed_scan_fn(code, collect_latency, queue_capacity,
                                 collect_events)
            state, ys = fn(params, state0, steps, arrivals, dt)
        else:
            body = _routed_body(params, dt, code, collect_latency,
                                queue_capacity, collect_events)
            state, ys = lax.scan(body, state0, (steps, arrivals))
        # global drops (dead fleet / unroutable) land on device 0's ledger so
        # totals stay conserved
        global_drops = jnp.sum(ys[3])
        if code is not None:
            state = dataclasses.replace(
                state, n_dropped=state.n_dropped.at[0].add(global_drops)
            )
    return RoutedFleetResult(
        params=params,
        state=state,
        dt_ms=float(dt_ms),
        n_steps=n_steps,
        router=router,
        alive_over_time=np.asarray(ys[0]),
        served_over_time=np.asarray(ys[1]),
        queued_over_time=np.asarray(ys[2]),
        latency_ms=np.asarray(ys[4]) if collect_latency else None,
        served_mask=np.asarray(ys[5]) if collect_latency else None,
        reconfig_mask=np.asarray(ys[-4]) if collect_events else None,
        released_mask=np.asarray(ys[-3]) if collect_events else None,
        queue_depth=np.asarray(ys[-2]) if collect_events else None,
        dropped_per_tick=np.asarray(ys[-1]) if collect_events else None,
        start_tick=start_tick,
    )
