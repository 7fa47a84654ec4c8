"""Sharded fleet kernels: million-device scans over a JAX device mesh.

:func:`run_periodic_sharded` partitions the device axis of
:func:`repro.fleet.step.run_periodic` over a 2-D ``("fleet", "seed")``
mesh via :func:`jax.shard_map` + the logical-axis rules of
:mod:`repro.distributed.sharding`; :func:`run_periodic_ensemble_sharded`
does the same for the Monte Carlo ensemble, sharding devices over the
``fleet`` axis and seeds over the ``seed`` axis.

The correctness contract is **bit-identity**, not approximation:

* every shard runs the *same* scan body the unsharded kernels use
  (:func:`repro.fleet.step._periodic_body`,
  :func:`repro.mc.ensemble._ens_body`) — per-device trajectories are
  embarrassingly parallel, so partitioning cannot reassociate any float;
* the only cross-shard reduction is the per-step alive count — an
  **int32 sum**, which is associative and exact, so per-shard partial
  sums + ``lax.psum`` reproduce the unsharded ``jnp.sum`` bit-for-bit;
* fleets that don't divide the shard count are padded with *inert*
  devices (``feasible=False``, zero budget) that can never admit — they
  contribute exactly 0 to every total and are stripped before results
  are returned (:func:`pad_fleet`);
* a 1×1 mesh collapses to today's single-device path.

The hot loop is chunked and donated: each ``step_chunk``-long jitted
``shard_map`` scan donates its ``(n, alive)`` carries, so carry buffers
are reused allocation-free across chunks, and admission monotonicity
(once a device stops admitting it never resumes) lets the runner stop
early — with zeros filled in for the remaining steps, still bit-exact —
the moment a chunk ends with zero admissions fleet-wide.  That is how a
10^6-device *full-budget* lifetime scan terminates as soon as the last
device exhausts its budget instead of running out a worst-case horizon.

The 1×1-mesh-equals-unsharded claim, as a doctest (this module is in the
CI docs job's ``--doctest-modules`` list):

>>> import numpy as np
>>> from repro.fleet import run_periodic, uniform_fleet
>>> from repro.fleet.shard import fleet_mesh, run_periodic_sharded
>>> params = uniform_fleet(3, strategies=("on_off", "idle_waiting"),
...                        e_budget_mj=100.0)
>>> a = run_periodic(params, 40)
>>> b = run_periodic_sharded(params, 40, mesh=fleet_mesh(1, 1))
>>> bool(np.array_equal(a.n_items, b.n_items)
...      and np.array_equal(a.energy_mj, b.energy_mj)
...      and np.array_equal(a.lifetime_ms, b.lifetime_ms)
...      and np.array_equal(a.alive, b.alive)
...      and np.array_equal(a.alive_over_time, b.alive_over_time))
True

On a multi-device host (CPU CI fakes one with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) the same call
with ``fleet_mesh(2, 2)`` returns the same bits — the differential suite
``tests/test_fleet_sharded.py`` sweeps mesh shapes {1,2,4}×{1,2}.
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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.fleet.state import FleetParams
from repro.fleet.step import (
    PeriodicFleetResult,
    _check_step_count,
    _periodic_body,
    _periodic_carry0,
    _periodic_final,
    _periodic_limit,
)

__all__ = [
    "FLEET_RULES",
    "MESH_AXES",
    "ShardedPeriodicResult",
    "fleet_mesh",
    "pad_fleet",
    "parse_mesh_spec",
    "run_periodic_sharded",
    "run_periodic_ensemble_sharded",
    "shard_slices",
]

#: Physical mesh axes every fleet mesh carries, in order.
MESH_AXES = ("fleet", "seed")

#: Logical-axis rules (extends the shared DEFAULT_RULES table):
#: the periodic kernel shards its device axis over the *whole* mesh (no
#: replication anywhere); the ensemble splits devices over ``fleet`` and
#: seeds over ``seed``.
FLEET_RULES: shd.Rules = dict(
    shd.DEFAULT_RULES,
    fleet_device=MESH_AXES,
    ens_device="fleet",
    mc_seed="seed",
)


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------
def parse_mesh_spec(spec: str) -> tuple[int, int]:
    """CLI mesh spec → ``(fleet, seed)`` axis sizes.

    ``"4"`` → (4, 1); ``"2x2"`` → (2, 2); ``"auto"`` → all local devices
    on the fleet axis.
    """
    s = str(spec).strip().lower()
    if s == "auto":
        return (len(jax.devices()), 1)
    parts = s.split("x")
    try:
        if len(parts) == 1:
            return (int(parts[0]), 1)
        if len(parts) == 2:
            return (int(parts[0]), int(parts[1]))
    except ValueError:
        pass
    raise ValueError(
        f"bad mesh spec {spec!r}: expected 'F', 'FxS', or 'auto' "
        "(e.g. '4' or '2x2')"
    )


def fleet_mesh(
    fleet: Optional[int] = None, seed: int = 1, *, devices=None
) -> Mesh:
    """A ``("fleet", "seed")`` mesh over the first ``fleet × seed`` local
    devices (default: all of them on the fleet axis)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if seed < 1:
        raise ValueError(f"seed axis size must be >= 1, got {seed}")
    if fleet is None:
        fleet = max(1, len(devices) // seed)
    if fleet < 1:
        raise ValueError(f"fleet axis size must be >= 1, got {fleet}")
    need = fleet * seed
    if need > len(devices):
        raise ValueError(
            f"mesh {fleet}x{seed} needs {need} devices but only "
            f"{len(devices)} are visible — on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}"
        )
    arr = np.asarray(devices[:need]).reshape(fleet, seed)
    return Mesh(arr, MESH_AXES)


def shard_slices(n_devices: int, n_shards: int) -> list[slice]:
    """Device-index slices each shard owns after :func:`pad_fleet` —
    contiguous blocks of the padded axis, clipped to the real fleet (the
    last shards may own only padding and get empty slices)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    per = (n_devices + (-n_devices) % n_shards) // n_shards
    return [
        slice(min(i * per, n_devices), min((i + 1) * per, n_devices))
        for i in range(n_shards)
    ]


# ---------------------------------------------------------------------------
# Pad-and-mask
# ---------------------------------------------------------------------------
#: Column values of an inert padding device: infeasible (never admits a
#: single request), zero budget, On-Off accounting (final energy
#: ``n · e_item`` is exactly 0 at n = 0) — it contributes 0 to every sum.
_PAD_COLUMNS = {
    "strategy": 0,
    "is_onoff": True,
    "feasible": False,
    "period_ms": 1.0,
    "e_budget_mj": 0.0,
    "e_item_mj": 0.0,
    "e_init_mj": 0.0,
    "e_idle_mj": 0.0,
    "e_exec_mj": 0.0,
    "t_exec_ms": 1.0,
    "e_config_mj": 0.0,
    "t_config_ms": 0.0,
    "p_idle_mw": 0.0,
    "timeout_ms": 0.0,
    "e_overhead_mj": 0.0,
}


def pad_fleet(params: FleetParams, multiple: int) -> tuple[FleetParams, int]:
    """Pad the device axis up to a multiple of ``multiple`` with inert
    devices; returns ``(padded_params, n_padding)``.

    Inert means *provably* zero-contribution: ``feasible=False`` blocks
    every admission, so the padded devices report ``n_items = 0``, energy
    0, and add 0 to each ``alive_over_time`` count — padding is masked
    out of the totals by construction, not by post-hoc subtraction.
    """
    if multiple < 1:
        raise ValueError(f"pad multiple must be >= 1, got {multiple}")
    pad = (-params.n_devices) % multiple
    if pad == 0:
        return params, 0
    with enable_x64():
        cols = {}
        for f in dataclasses.fields(params):
            a = getattr(params, f.name)
            tail = jnp.full((pad,), _PAD_COLUMNS[f.name], dtype=a.dtype)
            cols[f.name] = jnp.concatenate([a, tail])
    return FleetParams(**cols), pad


# ---------------------------------------------------------------------------
# Periodic kernel, sharded
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardedPeriodicResult(PeriodicFleetResult):
    """A :class:`PeriodicFleetResult` (same arrays, same ``ledger()`` /
    metrics integration, padding already stripped) plus the shard
    geometry and how far the chunked scan actually ran before the
    early-exit (``steps_executed < n_steps`` means the whole fleet was
    dead and the remaining ``alive_over_time`` entries are exact zeros).
    """

    mesh_shape: tuple = (1, 1)
    n_shards: int = 1
    n_padding: int = 0
    steps_executed: int = 0


def _device_pspec(mesh: Mesh) -> P:
    return shd.logical_to_pspec(("fleet_device",), FLEET_RULES, mesh)


@functools.lru_cache(maxsize=None)
def _sharded_chunk_fn(mesh: Mesh, n_chunk: int):
    """Jitted shard_map'd chunk: ``(params, n, alive) -> (n, alive, ts)``
    with the carries donated, so chunk k+1 reuses chunk k's buffers."""
    pspec = _device_pspec(mesh)

    def local(p, n_loc, alive_loc):
        body = _periodic_body(p, _periodic_limit(p))
        (n2, a2), ts = lax.scan(
            body, (n_loc, alive_loc), None, length=n_chunk
        )
        # int32 partial sums + psum == the unsharded global sum, exactly
        return n2, a2, lax.psum(ts, MESH_AXES)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(pspec, pspec, pspec),
        out_specs=(pspec, pspec, P()),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(1, 2))


def _eager_chunk_fn(mesh: Mesh, n_chunk: int):
    """Un-jitted variant (jit=False paths of the determinism tests)."""
    pspec = _device_pspec(mesh)

    def local(p, n_loc, alive_loc):
        body = _periodic_body(p, _periodic_limit(p))
        (n2, a2), ts = lax.scan(
            body, (n_loc, alive_loc), None, length=n_chunk
        )
        return n2, a2, lax.psum(ts, MESH_AXES)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(pspec, pspec, pspec),
        out_specs=(pspec, pspec, P()),
        check_vma=False,
    )


def run_periodic_sharded(
    params: FleetParams,
    n_steps: int,
    mesh: Optional[Mesh] = None,
    *,
    step_chunk: Optional[int] = None,
    jit: bool = True,
) -> ShardedPeriodicResult:
    """:func:`repro.fleet.step.run_periodic` with the device axis sharded
    over ``mesh`` — bit-identical results for any mesh shape.

    ``mesh`` defaults to all visible devices on the fleet axis
    (:func:`fleet_mesh`); a 1×1 mesh is today's single-device path.
    ``step_chunk`` bounds each jitted scan (default: whole horizon up to
    4096 steps per chunk) — chunk boundaries cannot perturb results (the
    carry is exact), they only set the early-exit granularity and keep
    compilations horizon-independent.
    """
    if n_steps < 0:
        raise ValueError(f"n_steps must be non-negative, got {n_steps}")
    _check_step_count(n_steps, "run_periodic_sharded")
    if mesh is None:
        mesh = fleet_mesh()
    with shd.use_sharding(mesh, FLEET_RULES):
        n_shards = shd.axis_size("fleet_device")
    if step_chunk is None:
        step_chunk = max(1, min(n_steps, 4096))
    if step_chunk < 1:
        raise ValueError(f"step_chunk must be >= 1, got {step_chunk}")

    n_real = params.n_devices
    padded, n_pad = pad_fleet(params, n_shards)
    with enable_x64():
        sharding = NamedSharding(mesh, _device_pspec(mesh))
        padded = jax.device_put(padded, sharding)
        n_c, alive_c = _periodic_carry0(padded)
        n_c = jax.device_put(n_c, sharding)
        alive_c = jax.device_put(alive_c, sharding)

        ts_parts: list[np.ndarray] = []
        done = 0
        while done < n_steps:
            c = min(step_chunk, n_steps - done)
            fn = _sharded_chunk_fn(mesh, c) if jit else _eager_chunk_fn(mesh, c)
            n_c, alive_c, ts = fn(padded, n_c, alive_c)
            ts_parts.append(np.asarray(ts))
            done += c
            if done < n_steps and ts_parts[-1][-1] == 0:
                # admission is monotone per device, so a step with zero
                # admissions fleet-wide freezes every carry: the remaining
                # alive_over_time entries are exact zeros
                ts_parts.append(np.zeros(n_steps - done, dtype=np.int32))
                break
        alive_ts = (
            np.concatenate(ts_parts) if ts_parts
            else np.zeros(0, dtype=np.int32)
        )
        n_host = np.asarray(n_c)[:n_real]
        alive_host = np.asarray(alive_c)[:n_real]
        # final energies through the identical eager expression run_periodic
        # uses, on the original (unpadded) params
        energy, lifetime = _periodic_final(params, jnp.asarray(n_host))
    return ShardedPeriodicResult(
        params=params,
        n_steps=n_steps,
        n_items=n_host.astype(np.int64),
        energy_mj=np.asarray(energy),
        lifetime_ms=np.asarray(lifetime),
        alive=alive_host,
        alive_over_time=alive_ts,
        mesh_shape=tuple(int(mesh.shape[a]) for a in MESH_AXES),
        n_shards=n_shards,
        n_padding=n_pad,
        steps_executed=done,
    )


# ---------------------------------------------------------------------------
# Ensemble kernel, sharded
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _sharded_ens_fn(mesh: Mesh):
    """Jitted shard_map of the vmapped ensemble scan: seeds over the
    ``seed`` axis, devices over ``fleet``."""
    dev = shd.logical_to_pspec(("ens_device",), FLEET_RULES, mesh)
    gap = shd.logical_to_pspec(("mc_seed", None, "ens_device"), FLEET_RULES, mesh)
    out = shd.logical_to_pspec(("mc_seed", "ens_device"), FLEET_RULES, mesh)

    def local(p, lim, gp, gn):
        from repro.mc.ensemble import _periodic_ens_vmapped

        return _periodic_ens_vmapped(p, lim, gp, gn)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(dev, dev, gap, gap),
        out_specs=(out,) * 5,
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_periodic_ens_scan(params, limit, gaps_prev, gaps_next, mesh):
    """Drop-in sharded replacement for the unsharded
    ``_periodic_ens_vmapped`` call inside
    :func:`repro.mc.ensemble.periodic_ensemble`: same ``(n, alive, cum,
    life, idle)`` tuple of ``(S, N)`` arrays, bit-identical values —
    every host-side aggregation (Welford, ledger, CI) downstream is
    therefore shared, not reimplemented.
    """
    with shd.use_sharding(mesh, FLEET_RULES):
        n_dev_shards = shd.axis_size("ens_device")
        n_seed_shards = shd.axis_size("mc_seed")
    S, T, N = (int(d) for d in gaps_next.shape)
    padded, _ = pad_fleet(params, n_dev_shards)
    n_pad_dev = padded.n_devices - N
    s_pad = (-S) % n_seed_shards
    with enable_x64():
        lim = jnp.asarray(limit, dtype=jnp.float64)
        lim = jnp.broadcast_to(lim, (N,)) if lim.ndim == 0 else lim
        # padded devices are infeasible (alive0 = feasible = False), so
        # their gap values — zeros here — are never consulted
        lim_p = jnp.concatenate([lim, jnp.zeros((n_pad_dev,), jnp.float64)])
        gp = jnp.pad(gaps_prev, ((0, s_pad), (0, 0), (0, n_pad_dev)))
        gn = jnp.pad(gaps_next, ((0, s_pad), (0, 0), (0, n_pad_dev)))
        outs = _sharded_ens_fn(mesh)(padded, lim_p, gp, gn)
    return tuple(o[:S, :N] for o in outs)


def run_periodic_ensemble_sharded(
    params: FleetParams,
    process,
    n_steps: int,
    n_seeds: int,
    mesh: Optional[Mesh] = None,
    **kwargs,
):
    """:func:`repro.mc.ensemble.run_periodic_ensemble` over a device mesh.

    A thin wrapper: gap sampling, seed chunking (``fold_in(key, chunk)``
    determinism), Welford merging, and the EnergyLedger conservation
    contract all run through the existing unsharded code path — only the
    inner scan is shard_map'd — so sharded ensembles are bit-identical
    to unsharded ones for the same ``(seed, seed_chunk)``.
    """
    from repro.mc.ensemble import run_periodic_ensemble

    if mesh is None:
        mesh = fleet_mesh()
    return run_periodic_ensemble(
        params, process, n_steps, n_seeds, mesh=mesh, **kwargs
    )
