"""Pareto-frontier extraction and crossover surfaces over batch-sweep grids.

The paper picks *one* optimum per experiment (minimum-energy configuration,
best idle method, the 499.06 ms crossover).  Once the design space is a
dense grid (:mod:`repro.core.batch_eval`), the interesting objects are
*sets* and *surfaces*:

* the **Pareto frontier** of (config energy, config time) over the
  Table-1 parameter space — which settings are worth considering at all;
* the **strategy frontier** of (energy/item, latency, −lifetime) across
  request periods and idle methods;
* the **crossover surface** T_cross(device, buswidth, clock, compression,
  P_idle) — how the Idle-Waiting/On-Off switching point moves as the
  configuration phase is optimized (the paper's 89.21 → 499.06 ms shift,
  as a function rather than two endpoints).

Dominance is computed with a ``vmap``-over-candidates kernel in chunks, so
frontier extraction over 10⁵+ points stays array-shaped end to end.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64

from repro.core.batch_eval import GridResult, config_phase_grid
from repro.core.config_phase import FpgaDevice
from repro.core.phases import WorkloadItem

__all__ = [
    "pareto_mask",
    "pareto_mask_jnp",
    "soft_pareto_weight",
    "pareto_points",
    "config_pareto",
    "strategy_pareto",
    "crossover_surface",
]

_CHUNK = 2048


def pareto_mask_jnp(costs: jnp.ndarray) -> jnp.ndarray:
    """Non-dominated mask over a ``(N, K)`` jnp cost array, minimizing every
    column — the jit/vmap-composable core of :func:`pareto_mask`, usable
    inside transformed code (e.g. :mod:`repro.optimize` filtering candidate
    configurations on device, without a host round trip).

    Point *i* is dominated iff some *j* is ≤ in every objective and < in at
    least one.  O(N²) pairwise dominance as one vmap; for very large N
    prefer :func:`pareto_mask`, which chunks the candidate axis.
    """

    def dominated(x):
        le = jnp.all(costs <= x, axis=1)
        lt = jnp.any(costs < x, axis=1)
        return jnp.any(le & lt)

    return ~jax.vmap(dominated)(costs)


def pareto_mask(costs, chunk: int = _CHUNK) -> np.ndarray:
    """Non-dominated mask over ``costs`` of shape (N, K), minimizing every
    column (see :func:`pareto_mask_jnp` for the dominance rule).  Evaluated
    as a vmap over candidate points in chunks of ``chunk`` to bound the
    (chunk × N) intermediate.
    """
    c = np.asarray(costs, dtype=np.float64)
    if c.ndim != 2:
        raise ValueError(f"costs must be (N, K), got shape {c.shape}")
    n = c.shape[0]
    if n == 0:
        return np.zeros(0, dtype=bool)

    with enable_x64():
        all_pts = jnp.asarray(c)

        def dominated(x):
            le = jnp.all(all_pts <= x, axis=1)
            lt = jnp.any(all_pts < x, axis=1)
            return jnp.any(le & lt)

        dominated_chunk = jax.vmap(dominated)
        out = [
            np.asarray(dominated_chunk(all_pts[i : i + chunk]))
            for i in range(0, n, chunk)
        ]
    return ~np.concatenate(out)


def soft_pareto_weight(costs: jnp.ndarray, temperature: float = 1.0) -> jnp.ndarray:
    """Differentiable relaxation of Pareto-frontier membership, shape (N,).

    For each ordered pair (i, j), ``m_ij = max_k (c_jk − c_ik)`` is the
    margin by which *j* fails to dominate *i* (j dominates i iff it is no
    worse in every objective, i.e. ``m_ij ≤ 0`` with some strict
    improvement).  The weight

        w_i = Π_{j≠i} σ(m_ij / τ)

    is 1 when no point comes close to dominating *i* and → 0 as some *j*
    dominates it; as ``τ → 0`` it approaches the hard
    :func:`pareto_mask_jnp` (up to ties).  ``jax.grad`` flows through the
    costs, so an optimizer can *pull a design toward the frontier* by
    maximizing its weight — the frontier as a loss term rather than a
    post-hoc filter.
    """
    c = jnp.asarray(costs)
    if c.ndim != 2:
        raise ValueError(f"costs must be (N, K), got shape {c.shape}")
    margins = jnp.max(c[None, :, :] - c[:, None, :], axis=-1)   # (N, N): m_ij
    s = jax.nn.sigmoid(margins / temperature)
    # a point never dominates itself: force the diagonal factor to 1
    s = jnp.where(jnp.eye(c.shape[0], dtype=bool), 1.0, s)
    return jnp.prod(s, axis=1)


def pareto_points(
    records: Sequence[dict],
    objectives: Sequence[str],
    maximize: Sequence[str] = (),
) -> list[dict]:
    """Filter a record list (e.g. :meth:`GridResult.to_records`) to its
    Pareto-optimal subset.  ``objectives`` are minimized except those also
    named in ``maximize``."""
    if not objectives:
        raise ValueError("need at least one objective")
    cols = []
    for key in objectives:
        sign = -1.0 if key in maximize else 1.0
        cols.append([sign * float(r[key]) for r in records])
    mask = pareto_mask(np.asarray(cols).T)
    return [r for r, keep in zip(records, mask) if keep]


# ---------------------------------------------------------------------------
# Frontiers of the paper's two design spaces
# ---------------------------------------------------------------------------
def config_pareto(
    devices: Sequence[FpgaDevice] | FpgaDevice,
    **grid_kwargs,
) -> list[dict]:
    """(config energy, config time) Pareto frontier of the Table-1 space.

    Returns records with the axis labels plus both objectives, sorted by
    energy.  The paper's best setting (quad/66 MHz/compressed) is always a
    member — it minimizes both objectives at once on the calibrated model.
    """
    if isinstance(devices, FpgaDevice):
        devices = (devices,)
    g = config_phase_grid(devices, **grid_kwargs)
    shape = g["config_energy_mj"].shape
    from repro.core.config_phase import SPI_BUSWIDTHS, SPI_CLOCKS_MHZ, COMPRESSION_OPTIONS

    axes = {
        "device": [d.name for d in devices],
        "buswidth": list(grid_kwargs.get("buswidths", SPI_BUSWIDTHS)),
        "clock_mhz": list(grid_kwargs.get("clocks_mhz", SPI_CLOCKS_MHZ)),
        "compression": [bool(c) for c in grid_kwargs.get("compression", COMPRESSION_OPTIONS)],
    }
    idx = np.indices(shape).reshape(len(shape), -1).T
    records = []
    for ix in map(tuple, idx):
        rec = {name: vals[ix[i]] for i, (name, vals) in enumerate(axes.items())}
        rec["config_energy_mj"] = float(g["config_energy_mj"][ix])
        rec["config_time_ms"] = float(g["config_time_ms"][ix])
        records.append(rec)
    front = pareto_points(records, ("config_energy_mj", "config_time_ms"))
    return sorted(front, key=lambda r: r["config_energy_mj"])


def strategy_pareto(result: GridResult, strategy: str = "iw") -> list[dict]:
    """(energy/item ↓, request period ↓, lifetime ↑) frontier of a sweep.

    ``strategy`` ∈ {'iw', 'onoff', 'adaptive'}.  Only feasible grid points
    compete.  Exposes the paper's Fig. 8/9 trade-off as a set: shorter
    periods cost more idle-free energy but serve more items.
    """
    if strategy not in ("iw", "onoff", "adaptive"):
        raise ValueError(f"unknown strategy {strategy!r}; use 'iw', 'onoff' or 'adaptive'")

    def arm(record: dict) -> str:
        # adaptive inherits the winning static arm's quantities per point
        if strategy == "adaptive":
            return "iw" if record["adaptive_picks_iw"] else "onoff"
        return strategy

    records = []
    for r in result.to_records():
        a = arm(r)
        if not r[f"{a}_feasible"]:
            continue
        r["energy_per_item_mj"] = r[f"{a}_energy_per_item_mj"]
        r["lifetime_ms"] = r[f"{a}_lifetime_ms"]
        r["n_max"] = r[f"{a}_n_max"]
        records.append(r)
    if not records:
        return []
    front = pareto_points(
        records,
        ("energy_per_item_mj", "request_period_ms", "lifetime_ms"),
        maximize=("lifetime_ms",),
    )
    return sorted(front, key=lambda r: r["request_period_ms"])


def crossover_surface(
    item: WorkloadItem,
    devices: Sequence[FpgaDevice] | FpgaDevice,
    idle_powers_mw: Sequence[float],
    buswidths=None,
    clocks_mhz=None,
    compression=None,
    powerup_overhead_mj: float = 0.0,
) -> dict:
    """T_cross as a function of (device, buswidth, clock, compression,
    idle power): shape ``(D, W, F, C, P)``.

    The configuration phase of ``item`` is replaced per grid point by the
    device model (same derivation — average-power round trip, left-fold
    phase sums — as :func:`~repro.core.batch_eval.sweep_batch`, so the
    values are bit-identical to that engine's ``crossover_ms``); execution
    phases are held fixed.  This is the surface the paper samples at two
    points: 89.21 ms (baseline idle power) and 499.06 ms (methods 1+2).
    """
    from repro.core.batch_eval import _arr, _crossover
    from repro.core.config_phase import SPI_BUSWIDTHS, SPI_CLOCKS_MHZ, COMPRESSION_OPTIONS
    from repro.core.phases import CONFIGURATION

    if isinstance(devices, FpgaDevice):
        devices = (devices,)
    buswidths = SPI_BUSWIDTHS if buswidths is None else tuple(buswidths)
    clocks_mhz = SPI_CLOCKS_MHZ if clocks_mhz is None else tuple(clocks_mhz)
    compression = COMPRESSION_OPTIONS if compression is None else tuple(compression)
    if len(idle_powers_mw) == 0:
        raise ValueError(
            "crossover_surface(): idle_powers_mw is empty — pass at least one "
            "idle power (e.g. the Table-3 methods 134.3/34.2/24.0 mW)"
        )

    # T_cross depends only on the per-point On-Off item energy and the idle
    # power — the config grid plus one broadcast axis, no strategy sweep.
    g = config_phase_grid(devices, buswidths, clocks_mhz, compression)
    with enable_x64():
        t_config = _arr(g["config_time_ms"])                         # (D,W,F,C)
        e_config = _arr(g["config_power_mw"]) * t_config / 1000.0    # phase round trip
        e_total = 0.0 + e_config
        for ph in item.phases:
            if ph.name != CONFIGURATION:
                e_total = e_total + _arr(ph.energy_mj)
        e_onoff = e_total + _arr(powerup_overhead_mj)
        p_idle = _arr([float(p) for p in idle_powers_mw])            # (P,)
        cross = _crossover(
            e_onoff[..., None],
            _arr(item.execution_energy_mj),
            _arr(item.execution_time_ms),
            p_idle,
        )
        surface = np.asarray(
            jnp.broadcast_to(cross, e_onoff.shape + (len(p_idle),))
        )
    return {
        "axes": {
            "device": [d.name for d in devices],
            "buswidth": list(buswidths),
            "clock_mhz": list(clocks_mhz),
            "compression": [bool(c) for c in compression],
            "idle_power_mw": [float(p) for p in idle_powers_mw],
        },
        "crossover_ms": surface,
    }
