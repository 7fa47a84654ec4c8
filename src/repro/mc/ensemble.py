"""Seed-vmapped stochastic fleet replications with streaming moments.

Every headline number in this repo — 499.06 ms crossover, 12.39× lifetime,
energy-per-request, p99 latency — is a *point estimate* under perfectly
periodic requests.  This module turns each of them into a distribution: it
replicates a whole fleet across S independent random seeds and runs all
S × N trajectories through **one** ``jax.vmap``-ped ``lax.scan`` — no
Python loop over seeds — reusing the fleet substrate
(:class:`repro.fleet.state.FleetParams`, the routed step body from
:mod:`repro.fleet.step`) and the batched samplers of
:mod:`repro.core.arrivals`.

Two replication kernels:

* :func:`run_periodic_ensemble` — the paper's duty-cycle mode under
  stochastic inter-arrival gaps.  One scan step = one request per device
  per seed; request *k* is charged its execution energy plus the idle
  energy of the *realized* preceding gap (Idle-Waiting) or its full
  reconfigure-and-run energy (On-Off), admitted while the accumulated
  energy fits the budget — the gap-driven generalization of
  :func:`repro.fleet.step.run_periodic`.  With zero-jitter gaps (e.g.
  :class:`~repro.core.arrivals.JitteredArrivals` at ``jitter=0``) every
  seed collapses onto the deterministic closed forms: same admitted counts
  as the scalar oracle, same Eq.-4 lifetime.
* :func:`routed_ensemble` / :func:`run_routed_ensemble` — the routed
  tick-clock kernel (queues, exact latency timestamps) replicated across
  seeds by ``jax.vmap`` of the *identical* step body ``run_routed`` uses,
  for CI bands on p50/p99 latency.

Memory: per-seed *fleet aggregates* are O(S) scalars and always kept (the
bootstrap needs them); per-device moments across seeds are accumulated by
:class:`Welford` (Chan's parallel merge) over seed *chunks*, so S = 10k
replications of an N-device fleet run in memory constant in S — set
``seed_chunk`` to bound the live (chunk × steps × N) gap buffer.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import enable_x64

from repro.core import energy_model as em
from repro.core.arrivals import ArrivalProcess, bin_arrival_counts
from repro.fleet.state import FleetParams, FleetState
from repro.fleet.step import _routed_body

__all__ = [
    "Welford",
    "PeriodicEnsembleResult",
    "RoutedEnsembleResult",
    "periodic_ensemble",
    "run_periodic_ensemble",
    "routed_ensemble",
    "run_routed_ensemble",
]


# ---------------------------------------------------------------------------
# Streaming moments
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Welford:
    """Streaming mean/variance over an ensemble axis (Welford / Chan merge).

    ``update`` consumes one *batch* of replications at a time (shape
    ``(chunk, ...)``), merging the batch's moments into the running state
    with Chan's parallel-update formula — numerically stable and O(element)
    memory, so 10k-seed ensembles never materialize a (S, N) array.

    >>> import numpy as np
    >>> w = Welford()
    >>> x = np.arange(12.0).reshape(4, 3)
    >>> _ = w.update(x[:2]); _ = w.update(x[2:])
    >>> bool(np.allclose(w.mean, x.mean(axis=0)))
    True
    >>> bool(np.allclose(w.variance, x.var(axis=0, ddof=1)))
    True
    """

    count: int = 0
    mean: Optional[np.ndarray] = None
    m2: Optional[np.ndarray] = None

    def update(self, batch) -> "Welford":
        b = np.asarray(batch, dtype=np.float64)
        if b.ndim == 0:
            b = b.reshape(1)
        nb = b.shape[0]
        if nb == 0:
            return self
        bm = b.mean(axis=0)
        bm2 = ((b - bm) ** 2).sum(axis=0)
        if self.count == 0:
            self.count, self.mean, self.m2 = nb, bm, bm2
            return self
        n = self.count + nb
        delta = bm - self.mean
        self.mean = self.mean + delta * (nb / n)
        self.m2 = self.m2 + bm2 + delta * delta * (self.count * nb / n)
        self.count = n
        return self

    @property
    def variance(self) -> np.ndarray:
        """Unbiased (ddof=1) variance; 0 until two replications are seen."""
        if self.count < 2:
            return np.zeros_like(np.asarray(self.mean, dtype=np.float64))
        return self.m2 / (self.count - 1)

    @property
    def std(self) -> np.ndarray:
        return np.sqrt(self.variance)

    @property
    def sem(self) -> np.ndarray:
        """Standard error of the mean over the ensemble axis."""
        if self.count < 1:
            raise ValueError("Welford has seen no replications")
        return self.std / math.sqrt(self.count)


# ---------------------------------------------------------------------------
# Periodic (gap-driven) ensemble
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PeriodicEnsembleResult:
    """S fleet replications of the duty-cycle mode under stochastic gaps.

    Per-seed fleet aggregates are 1-D ``(S,)`` arrays (bootstrap inputs);
    per-device cross-seed moments live in the :class:`Welford` fields.  The
    ``(S, N)`` per-device samples are kept only when the run was launched
    with ``keep_device_samples=True``.
    """

    params: FleetParams
    process: str
    n_seeds: int
    n_steps: int
    # per-seed fleet aggregates, shape (S,)
    lifetime_ms: np.ndarray            # device-mean Eq.-4 lifetime
    total_items: np.ndarray            # requests admitted fleet-wide
    total_energy_mj: np.ndarray
    energy_per_request_mj: np.ndarray
    # per-device moments across seeds (arrays of shape (N,))
    device_lifetime_ms: Welford
    device_energy_mj: Welford
    device_items: Welford
    # per-seed fleet-aggregate phase ledger (each axis shape (S,)); axes sum
    # to total_energy_mj within 1e-9 relative (the conservation contract)
    ledger: Optional[object] = None
    # optional full per-device samples, shape (S, N)
    per_device_items: Optional[np.ndarray] = None
    per_device_energy_mj: Optional[np.ndarray] = None
    per_device_lifetime_ms: Optional[np.ndarray] = None

    @property
    def n_devices(self) -> int:
        return self.params.n_devices


def _ens_body(params: FleetParams, limit):
    """The one gap-driven admission step — shared by the unsharded vmapped
    scan and the per-shard scans :mod:`repro.fleet.shard` runs, so sharded
    ensembles are bit-identical by construction.  Carry:
    ``(n int32, alive bool, cum f64, life f64, idle f64)`` — the audited
    dtype contract of :mod:`repro.fleet.dtypes`."""

    def body(carry, g):
        gp, gn = g
        n, alive, cum, life, idle_acc = carry
        idle_t = jnp.maximum(gp - params.t_exec_ms, 0.0)
        idle_e = params.p_idle_mw * idle_t / 1000.0
        cost = jnp.where(
            params.is_onoff, params.e_item_mj, params.e_item_mj + idle_e
        )
        admit = alive & (cum + cost <= limit)
        cum = jnp.where(admit, cum + cost, cum)
        # the idle-waiting share of the same accumulation (ledger axis)
        idle_acc = jnp.where(
            admit & ~params.is_onoff, idle_acc + idle_e, idle_acc
        )
        n = n + admit.astype(jnp.int32)
        life = jnp.where(admit, life + gn, life)
        return (n, admit, cum, life, idle_acc), None

    return body


def _ens_carry0(params: FleetParams):
    shape = params.period_ms.shape
    return (
        jnp.zeros(shape, dtype=jnp.int32),
        # an infeasible device (period below the strategy's latency) never
        # admits — the same static gate run_periodic applies every step
        jnp.broadcast_to(params.feasible, shape),
        # Idle-Waiting owes its one-time bring-up before the first item
        jnp.where(params.is_onoff, 0.0, params.e_init_mj),
        jnp.zeros(shape, dtype=jnp.float64),
        jnp.zeros(shape, dtype=jnp.float64),
    )


def _periodic_ens_scan(params: FleetParams, limit, gaps_prev, gaps_next):
    """One seed's fleet through the gap-driven admission scan.

    ``gaps_prev[k]`` is the realized gap *preceding* request k+1 (0 for the
    first request, which arrives at t = 0: ``max(0 − t_exec, 0)`` charges it
    no idle, and the E_init it owes is pre-loaded into the energy carry);
    ``gaps_next[k]`` is the gap *following* it — the period the request
    occupies, so Eq. 4's ``lifetime = Σ gaps of admitted requests`` reduces
    to ``n · T_req`` exactly in the deterministic limit.

    Returned energies include the pre-loaded E_init even for devices that
    admitted nothing; :func:`periodic_ensemble` zeroes those (the oracle's
    ``n = 0 → energy 0`` convention).
    """
    (n, alive, cum, life, idle_acc), _ = lax.scan(
        _ens_body(params, limit), _ens_carry0(params), (gaps_prev, gaps_next)
    )
    return n, alive, cum, life, idle_acc


def _periodic_ens_vmapped(params, limit, gaps_prev, gaps_next):
    """The whole seed chunk in one vmapped scan: gaps are (S, T, N)."""
    return jax.vmap(_periodic_ens_scan, in_axes=(None, None, 0, 0))(
        params, limit, gaps_prev, gaps_next
    )


_periodic_ens_jit = jax.jit(_periodic_ens_vmapped)


def periodic_ensemble(
    params: FleetParams,
    gaps,
    jit: bool = True,
    keep_device_samples: bool = False,
    mesh=None,
) -> PeriodicEnsembleResult:
    """Run S duty-cycle replications from pre-sampled inter-arrival gaps.

    ``gaps`` is ``(S, n_steps, N)`` float — ``gaps[s, k, d]`` is the gap
    *following* request k+1 on device d in replication s (e.g. from
    :meth:`~repro.core.arrivals.ArrivalProcess.sample_gaps`, reshaped).  All
    S × N trajectories advance through one vmapped ``lax.scan``; this is
    the timed engine of the ``launch.mc`` throughput row (stream sampling
    excluded on both sides, the same convention ``launch.fleet`` uses for
    its looped baseline).

    With ``mesh`` (a ``("fleet", "seed")`` mesh from
    :func:`repro.fleet.shard.fleet_mesh`) the seed and device axes are
    partitioned over the mesh via ``shard_map`` — every trajectory still
    runs the identical scan body, so results are bit-identical to the
    unsharded path; all host-side aggregation below is shared verbatim.
    """
    from repro.fleet.step import _check_step_count

    with enable_x64():
        gaps = jnp.asarray(gaps, dtype=jnp.float64)
        if gaps.ndim != 3 or gaps.shape[2] != params.n_devices:
            raise ValueError(
                f"gaps must be (n_seeds, n_steps, {params.n_devices}), "
                f"got shape {gaps.shape}"
            )
        n_seeds, n_steps = int(gaps.shape[0]), int(gaps.shape[1])
        _check_step_count(n_steps, "periodic_ensemble")
        # the same admission slack run_periodic grants (FLOOR_EPS of one
        # nominal period), so the deterministic limit shares its boundary rule
        limit = params.e_budget_mj + em.FLOOR_EPS * (params.e_item_mj + params.e_idle_mj)
        gaps_prev = jnp.concatenate(
            [jnp.zeros((n_seeds, 1, params.n_devices), dtype=jnp.float64),
             gaps[:, :-1, :]],
            axis=1,
        )
        if mesh is not None:
            from repro.fleet.shard import sharded_periodic_ens_scan

            n, alive, cum, life, idle_acc = sharded_periodic_ens_scan(
                params, limit, gaps_prev, gaps, mesh
            )
        else:
            fn = _periodic_ens_jit if jit else _periodic_ens_vmapped
            n, alive, cum, life, idle_acc = fn(params, limit, gaps_prev, gaps)
    n = np.asarray(n)
    # the scan pre-loads E_init into the energy carry; a device that admitted
    # nothing spent nothing (the oracle's n = 0 convention)
    cum = np.where(n > 0, np.asarray(cum), 0.0)
    life = np.asarray(life)
    total_items = n.sum(axis=1)
    total_energy = cum.sum(axis=1)
    with np.errstate(invalid="ignore", divide="ignore"):
        epr = np.where(total_items > 0, total_energy / np.maximum(total_items, 1), np.nan)
    ledger = _periodic_ledger(params, n, np.asarray(idle_acc))
    return PeriodicEnsembleResult(
        params=params,
        process="direct",
        n_seeds=n_seeds,
        n_steps=n_steps,
        lifetime_ms=life.mean(axis=1),
        total_items=total_items,
        total_energy_mj=total_energy,
        energy_per_request_mj=epr,
        device_lifetime_ms=Welford().update(life),
        device_energy_mj=Welford().update(cum),
        device_items=Welford().update(n.astype(np.float64)),
        ledger=ledger,
        per_device_items=n if keep_device_samples else None,
        per_device_energy_mj=cum if keep_device_samples else None,
        per_device_lifetime_ms=life if keep_device_samples else None,
    )


def _periodic_ledger(params: FleetParams, n: np.ndarray, idle: np.ndarray):
    """Per-seed fleet-aggregate :class:`repro.obs.ledger.EnergyLedger`
    (each axis ``(S,)``) from the ``(S, N)`` admitted counts and the scan's
    idle-energy accumulator, through the same per-item constants the
    admission costs used."""
    from repro.obs.ledger import EnergyLedger

    is_onoff = np.asarray(params.is_onoff)
    ovh = np.asarray(params.e_overhead_mj)
    cfg_pure = np.asarray(params.e_config_mj) - ovh
    e_exec = np.asarray(params.e_exec_mj)
    nf = n.astype(np.float64)                          # (S, N)
    # On-Off pays configure+overhead per item; Idle-Waiting once (E_init)
    n_cfg = np.where(is_onoff, nf, (n > 0).astype(np.float64))
    return EnergyLedger.from_axes(
        configure=(n_cfg * cfg_pure).sum(axis=1),
        compute=(nf * e_exec).sum(axis=1),
        idle=idle.sum(axis=1),
        off=np.zeros(n.shape[0], dtype=np.float64),
        overhead=(n_cfg * ovh).sum(axis=1),
    )


def _merge_ledgers(ledgers):
    """Concatenate per-seed ledgers along the seed axis (None passes through)."""
    from repro.obs.ledger import AXES, EnergyLedger

    if any(led is None for led in ledgers):
        return None
    return EnergyLedger(
        **{
            f"{a}_mj": np.concatenate(
                [np.atleast_1d(np.asarray(getattr(led, f"{a}_mj"))) for led in ledgers]
            )
            for a in AXES
        }
    )


def _merge_periodic(parts: list[PeriodicEnsembleResult]) -> PeriodicEnsembleResult:
    first = parts[0]
    if len(parts) == 1:
        return first
    w_life, w_energy, w_items = (
        first.device_lifetime_ms, first.device_energy_mj, first.device_items
    )
    for p in parts[1:]:
        w_life = _merge_welford(w_life, p.device_lifetime_ms)
        w_energy = _merge_welford(w_energy, p.device_energy_mj)
        w_items = _merge_welford(w_items, p.device_items)
    cat = np.concatenate
    keep = first.per_device_items is not None
    return dataclasses.replace(
        first,
        n_seeds=sum(p.n_seeds for p in parts),
        lifetime_ms=cat([p.lifetime_ms for p in parts]),
        total_items=cat([p.total_items for p in parts]),
        total_energy_mj=cat([p.total_energy_mj for p in parts]),
        energy_per_request_mj=cat([p.energy_per_request_mj for p in parts]),
        device_lifetime_ms=w_life,
        device_energy_mj=w_energy,
        device_items=w_items,
        ledger=_merge_ledgers([p.ledger for p in parts]),
        per_device_items=cat([p.per_device_items for p in parts]) if keep else None,
        per_device_energy_mj=cat([p.per_device_energy_mj for p in parts]) if keep else None,
        per_device_lifetime_ms=cat([p.per_device_lifetime_ms for p in parts]) if keep else None,
    )


def run_periodic_ensemble(
    params: FleetParams,
    process: ArrivalProcess,
    n_steps: int,
    n_seeds: int,
    seed: int = 0,
    seed_chunk: Optional[int] = None,
    keep_device_samples: bool = False,
    jit: bool = True,
    scale_to_device_periods: bool = False,
    mesh=None,
) -> PeriodicEnsembleResult:
    """Replicate an N-device duty-cycle fleet over ``n_seeds`` independent
    request streams drawn from ``process``.

    ``mesh`` (optional, from :func:`repro.fleet.shard.fleet_mesh`) shards
    every chunk's seed/device axes over a JAX device mesh; gap sampling,
    chunking, and all host-side merging are identical, so sharded results
    are bit-identical to the unsharded run for the same ``(seed,
    seed_chunk)``.

    Heterogeneous fleets: with ``scale_to_device_periods=True`` every
    device's sampled gaps are rescaled by ``params.period_ms[d] /
    process.mean_period_ms()``, so a fleet mixing models with different
    request periods (e.g. :func:`repro.costs.model_mix_fleet`) sees each
    device's own traffic rate while sharing the process's *shape*
    (burstiness, jitter).  The zero-variance limit is preserved: a
    deterministic process rescales to exactly each device's period, so the
    ensemble still collapses onto :func:`repro.fleet.step.run_periodic`.

    Each chunk of seeds samples its gaps in one batched ``jax.random`` call
    (:meth:`~repro.core.arrivals.ArrivalProcess.sample_gaps`) and advances
    all chunk × N trajectories through :func:`periodic_ensemble`'s vmapped
    scan; chunk results merge via Chan's parallel Welford update, so memory
    is bounded by the ``seed_chunk × n_steps × N`` gap buffer regardless of
    ``n_seeds``.

    Deterministic limit: with a zero-variance process every seed's admitted
    counts equal :func:`repro.fleet.step.run_periodic`'s (and hence the
    scalar Eq.-3 oracle's) and every CI degenerates to the point estimate.

    Reproducibility: results are a deterministic function of ``(seed,
    seed_chunk)`` — each chunk's streams derive from ``fold_in(key,
    chunk_index)``, so changing the chunk size repartitions the randomness
    (it never changes the *distribution*).
    """
    if n_seeds <= 0:
        raise ValueError(f"n_seeds must be positive, got {n_seeds}")
    if n_steps <= 0:
        raise ValueError(f"n_steps must be positive, got {n_steps}")
    from repro.fleet.step import _check_step_count

    _check_step_count(n_steps, "run_periodic_ensemble")
    if seed_chunk is None:
        # default: bound the live gap buffer near 16M float64 entries
        seed_chunk = max(1, min(n_seeds, 16_000_000 // max(1, n_steps * params.n_devices)))
    if seed_chunk <= 0:
        raise ValueError(f"seed_chunk must be positive, got {seed_chunk}")

    n_dev = params.n_devices
    period_scale = None
    if scale_to_device_periods:
        mean = process.mean_period_ms()
        if not (mean > 0):
            raise ValueError(
                f"process {process.name!r} has non-positive mean period {mean}"
            )
        with enable_x64():
            period_scale = params.period_ms / mean      # (N,)
    base_key = jax.random.PRNGKey(seed)
    parts: list[PeriodicEnsembleResult] = []
    done, chunk_idx = 0, 0
    while done < n_seeds:
        chunk = min(seed_chunk, n_seeds - done)
        key = jax.random.fold_in(base_key, chunk_idx)
        with enable_x64():
            gaps = process.sample_gaps(key, chunk * n_dev, n_steps)
            gaps = gaps.reshape(chunk, n_dev, n_steps).transpose(0, 2, 1)
            if period_scale is not None:
                gaps = gaps * period_scale[None, None, :]
        parts.append(
            periodic_ensemble(
                params, gaps, jit=jit,
                keep_device_samples=keep_device_samples, mesh=mesh,
            )
        )
        done += chunk
        chunk_idx += 1
    merged = _merge_periodic(parts)
    return dataclasses.replace(merged, process=process.name)


# ---------------------------------------------------------------------------
# Routed (tick-clock) ensemble — vmap of the fleet step body
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RoutedEnsembleResult:
    """S replications of the routed kernel; per-seed latency percentiles.

    Latency percentiles are computed per seed over every served request in
    that replication (NaN for a seed that served nothing — filter before
    interval construction).
    """

    params: FleetParams
    process: str
    n_seeds: int
    n_steps: int
    dt_ms: float
    # per-seed fleet aggregates, shape (S,)
    served: np.ndarray
    total_energy_mj: np.ndarray
    energy_per_request_mj: np.ndarray
    p50_latency_ms: np.ndarray
    p99_latency_ms: np.ndarray
    devices_alive: np.ndarray
    # per-device moments across seeds (arrays of shape (N,))
    device_served: Welford
    device_energy_mj: Welford
    # per-seed fleet-aggregate phase ledger (each axis shape (S,)); axes sum
    # to total_energy_mj within 1e-9 relative (the conservation contract)
    ledger: Optional[object] = None
    # optional full per-device samples, shape (S, N)
    per_device_served: Optional[np.ndarray] = None
    per_device_energy_mj: Optional[np.ndarray] = None

    @property
    def n_devices(self) -> int:
        return self.params.n_devices


@functools.lru_cache(maxsize=None)
def _routed_ens_fn(capacity: int):
    """Jitted vmap of the routed scan — the *same* step body
    :func:`repro.fleet.step.run_routed` builds, batched over seeds."""

    def fn(params, state0, steps, counts, dt):
        body = _routed_body(params, dt, None, True, capacity)

        def one(c):
            return lax.scan(body, state0, (steps, c))

        return jax.vmap(one)(counts)

    return jax.jit(fn)


def routed_ensemble(
    params: FleetParams,
    counts,
    dt_ms: float,
    queue_capacity: int = 16,
    keep_device_samples: bool = False,
) -> RoutedEnsembleResult:
    """Run S routed replications from pre-binned per-device arrival counts.

    ``counts`` is ``(S, K, N)`` int — one ``(K, N)`` direct arrival grid per
    seed (e.g. from :func:`repro.core.arrivals.bin_arrival_counts`).  All S
    replications advance through one vmapped ``lax.scan`` of the routed
    step body; the per-request latency timestamps come back per seed for
    exact p50/p99 distributions.
    """
    if dt_ms <= 0:
        raise ValueError(f"dt_ms must be positive, got {dt_ms}")
    with enable_x64():
        counts = jnp.asarray(counts)
        if counts.ndim != 3 or counts.shape[2] != params.n_devices:
            raise ValueError(
                f"counts must be (n_seeds, n_steps, {params.n_devices}), "
                f"got shape {counts.shape}"
            )
        n_seeds, n_steps = int(counts.shape[0]), int(counts.shape[1])
        steps = jnp.arange(n_steps, dtype=jnp.int64)
        state0 = FleetState.init(params.n_devices, queue_capacity)
        dt = jnp.asarray(dt_ms, dtype=jnp.float64)
        state, ys = _routed_ens_fn(queue_capacity)(
            params, state0, steps, counts.astype(jnp.int32), dt
        )
    served_dev = np.asarray(state.n_served)          # (S, N)
    energy_dev = np.asarray(state.energy_mj)         # (S, N)
    alive_dev = np.asarray(state.alive)              # (S, N)
    latency = np.asarray(ys[4])                      # (S, K, N) f32
    served_mask = np.asarray(ys[5])                  # (S, K, N) bool

    lat = np.where(served_mask, latency.astype(np.float64), np.nan)
    with np.errstate(invalid="ignore"), np.testing.suppress_warnings() as sup:
        sup.filter(RuntimeWarning)                   # all-NaN seeds → NaN out
        p50 = np.nanpercentile(lat, 50.0, axis=(1, 2))
        p99 = np.nanpercentile(lat, 99.0, axis=(1, 2))

    served = served_dev.sum(axis=1)
    energy = energy_dev.sum(axis=1)
    with np.errstate(invalid="ignore", divide="ignore"):
        epr = np.where(served > 0, energy / np.maximum(served, 1), np.nan)
    ledger = _routed_ledger(params, state)
    return RoutedEnsembleResult(
        params=params,
        process="direct",
        n_seeds=n_seeds,
        n_steps=n_steps,
        dt_ms=float(dt_ms),
        served=served,
        total_energy_mj=energy,
        energy_per_request_mj=epr,
        p50_latency_ms=p50,
        p99_latency_ms=p99,
        devices_alive=alive_dev.sum(axis=1),
        device_served=Welford().update(served_dev.astype(np.float64)),
        device_energy_mj=Welford().update(energy_dev),
        ledger=ledger,
        per_device_served=served_dev if keep_device_samples else None,
        per_device_energy_mj=energy_dev if keep_device_samples else None,
    )


def _routed_ledger(params: FleetParams, state: FleetState):
    """Per-seed fleet-aggregate ledger of a routed ensemble from the final
    carry: configuration counts split into pure configure + overhead, idle
    energy from the scan's own accumulator."""
    from repro.obs.ledger import EnergyLedger

    n_cfg = np.asarray(state.n_configs).astype(np.float64)    # (S, N)
    served = np.asarray(state.n_served).astype(np.float64)
    ovh = np.asarray(params.e_overhead_mj)
    cfg_pure = np.asarray(params.e_config_mj) - ovh
    return EnergyLedger.from_axes(
        configure=(n_cfg * cfg_pure).sum(axis=1),
        compute=(served * np.asarray(params.e_exec_mj)).sum(axis=1),
        idle=np.asarray(state.idle_energy_mj).sum(axis=1),
        off=np.zeros(n_cfg.shape[0], dtype=np.float64),
        overhead=(n_cfg * ovh).sum(axis=1),
    )


def _merge_routed(parts: list[RoutedEnsembleResult]) -> RoutedEnsembleResult:
    first = parts[0]
    if len(parts) == 1:
        return first
    w_served, w_energy = first.device_served, first.device_energy_mj
    for p in parts[1:]:
        w_served = _merge_welford(w_served, p.device_served)
        w_energy = _merge_welford(w_energy, p.device_energy_mj)
    cat = np.concatenate
    keep = first.per_device_served is not None
    return dataclasses.replace(
        first,
        n_seeds=sum(p.n_seeds for p in parts),
        served=cat([p.served for p in parts]),
        total_energy_mj=cat([p.total_energy_mj for p in parts]),
        energy_per_request_mj=cat([p.energy_per_request_mj for p in parts]),
        p50_latency_ms=cat([p.p50_latency_ms for p in parts]),
        p99_latency_ms=cat([p.p99_latency_ms for p in parts]),
        devices_alive=cat([p.devices_alive for p in parts]),
        device_served=w_served,
        device_energy_mj=w_energy,
        ledger=_merge_ledgers([p.ledger for p in parts]),
        per_device_served=cat([p.per_device_served for p in parts]) if keep else None,
        per_device_energy_mj=cat([p.per_device_energy_mj for p in parts]) if keep else None,
    )


def _merge_welford(a: Welford, b: Welford) -> Welford:
    """Chan's pairwise merge of two streaming-moment states."""
    if a.count == 0:
        return b
    if b.count == 0:
        return a
    n = a.count + b.count
    delta = b.mean - a.mean
    return Welford(
        count=n,
        mean=a.mean + delta * (b.count / n),
        m2=a.m2 + b.m2 + delta * delta * (a.count * b.count / n),
    )


def run_routed_ensemble(
    params: FleetParams,
    process: ArrivalProcess,
    horizon_ms: float,
    dt_ms: float,
    n_seeds: int,
    seed: int = 0,
    seed_chunk: Optional[int] = None,
    queue_capacity: int = 16,
    max_arrivals: Optional[int] = None,
    keep_device_samples: bool = False,
) -> RoutedEnsembleResult:
    """Sample per-device streams from ``process`` for every seed and run the
    routed ensemble — chunked over seeds for constant memory (the
    ``chunk × K × N`` latency trajectory is the live buffer).  Deterministic
    in ``(seed, seed_chunk)`` — see :func:`run_periodic_ensemble`."""
    if n_seeds <= 0:
        raise ValueError(f"n_seeds must be positive, got {n_seeds}")
    n_dev = params.n_devices
    n_steps = int(math.ceil(horizon_ms / dt_ms))
    if seed_chunk is None:
        seed_chunk = max(1, min(n_seeds, 8_000_000 // max(1, n_steps * n_dev)))
    base_key = jax.random.PRNGKey(seed)
    parts: list[RoutedEnsembleResult] = []
    done, chunk_idx = 0, 0
    while done < n_seeds:
        chunk = min(seed_chunk, n_seeds - done)
        key = jax.random.fold_in(base_key, chunk_idx)
        times = process.sample_batch(
            key, chunk * n_dev, horizon_ms, max_arrivals=max_arrivals
        )
        counts = np.asarray(bin_arrival_counts(times, horizon_ms, dt_ms))
        counts = counts.reshape(n_steps, chunk, n_dev).transpose(1, 0, 2)
        parts.append(
            routed_ensemble(
                params, counts, dt_ms,
                queue_capacity=queue_capacity,
                keep_device_samples=keep_device_samples,
            )
        )
        done += chunk
        chunk_idx += 1
    merged = _merge_routed(parts)
    return dataclasses.replace(merged, process=process.name)
