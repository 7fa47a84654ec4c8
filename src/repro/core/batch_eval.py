"""Vectorized batch evaluation engine (jax.numpy) for the paper's sweeps.

Every headline result of the paper is a *sweep*: 66 configuration-parameter
combinations (Exp. 1), request-period sweeps locating the Idle-Waiting/On-Off
crossover (Exp. 2), and lifetime curves under the 4147 J budget (Exp. 3).
The scalar path (:mod:`repro.core.energy_model`, :mod:`repro.core.
config_phase`) evaluates one point per Python call; this module expresses the
same closed forms as pure array-programs over ``jax.numpy`` so a single jitted
call evaluates an entire grid — millions of points per second instead of
thousands.

Axis layout
-----------
The full design-space grid is a dense 7-axis broadcast; every array a
:class:`GridResult` carries has this shape (axes of size 1 broadcast):

    ==== ======================= =================================
    axis meaning                 source
    ==== ======================= =================================
    0    device                  :class:`~repro.core.config_phase.FpgaDevice`
    1    SPI buswidth            Table 1
    2    SPI clock (MHz)         Table 1
    3    bitstream compression   Table 1
    4    request period (ms)     Exp. 2 x-axis
    5    idle-power method       Table 3
    6    energy budget (mJ)      Eq. 3
    ==== ======================= =================================

Sparse broadcasting (each 1-D axis reshaped onto its own dimension, as
``jnp.meshgrid(..., sparse=True)`` would) keeps memory at O(Σ axis) until the
final element-wise ops, so a 10M-point grid costs one output-sized buffer per
quantity, not seven.

Bit-agreement contract
----------------------
The scalar path is the *reference oracle*: every quantity here is computed
with the identical sequence of IEEE-754 double ops as its scalar counterpart
(same association order, same :data:`~repro.core.energy_model.FLOOR_EPS`
floor convention), under ``jax.enable_x64``.  By default the
kernels run **eagerly** — op-by-op, each primitive correctly rounded — so
``n_max`` matches the scalar path *exactly* (integer equality) and
energies/lifetimes match bit-for-bit.  Pass ``jit=True`` for XLA fusion
(~4× more throughput on multi-million-point grids): XLA's CPU fast-math
contracts ``a·b + c`` into FMA and folds constant divisors into reciprocal
multiplies, so jitted results can drift by one ulp (≲1e-15 relative) and
``n_max`` is only guaranteed up to budgets landing exactly on a floor
boundary.  ``tests/test_batch_eval.py`` enforces the eager contract on
randomized inputs and ``tests/test_paper_numbers.py`` pins every headline
constant through both paths.

Examples
--------
Experiment 1 in one call — the whole (device × buswidth × clock ×
compression) grid, whose worst/best ratio is the paper's ≈**40.13×**
configuration-energy reduction (calibrated model: 40.12×, within 0.5%)
down to the 11.85 mJ optimum:

>>> from repro.core.batch_eval import config_phase_grid
>>> from repro.core.config_phase import SPARTAN7_XC7S15
>>> g = config_phase_grid(SPARTAN7_XC7S15)
>>> g["config_energy_mj"].shape          # (device, buswidth, clock, compression)
(1, 3, 11, 2)
>>> e = g["config_energy_mj"]
>>> round(float(e.min()), 2)
11.85
>>> round(float(e.max() / e.min()), 2)
40.12
>>> abs(float(e.max() / e.min()) - 40.13) / 40.13 < 0.005
True

Strategy evaluation broadcasts over request periods / budgets / idle
powers; ``n_max`` is integer-exact vs the scalar oracle:

>>> import numpy as np
>>> from repro.core import energy_model as em
>>> from repro.core.batch_eval import evaluate_idlewait_batch
>>> from repro.core.phases import paper_lstm_item
>>> item = paper_lstm_item()
>>> r = evaluate_idlewait_batch(item, np.array([40.0, 80.0]),
...                             idle_powers_mw=24.0,
...                             powerup_overhead_mj=em.CALIBRATED_POWERUP_OVERHEAD_MJ)
>>> r.n_max
array([4295042, 2153688])
>>> int(r.n_max[0]) == em.idlewait_n_max(item, 40.0, idle_power_mw=24.0,
...     powerup_overhead_mj=em.CALIBRATED_POWERUP_OVERHEAD_MJ)
True
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64

from repro.core import energy_model as em
from repro.core.config_phase import (
    COMPRESSION_OPTIONS,
    SPI_BUSWIDTHS,
    SPI_CLOCKS_MHZ,
    FpgaDevice,
    SPARTAN7_XC7S15,
)
from repro.core.phases import CONFIGURATION, WorkloadItem, paper_lstm_item
from repro.core.strategies import IDLE_POWER_MW, IdlePowerMethod

__all__ = [
    "DeviceArrays",
    "ItemArrays",
    "BatchStrategyResult",
    "GridResult",
    "SweepGrid",
    "grid_axes",
    "config_phase_grid",
    "evaluate_onoff_batch",
    "evaluate_idlewait_batch",
    "evaluate_adaptive_batch",
    "crossover_batch",
    "sweep_batch",
    # differentiable primitives (repro.optimize builds on these)
    "config_phase_kernel",
    "crossover_kernel",
    "idle_energy_kernel",
    "onoff_n_smooth",
    "idlewait_n_smooth",
]

_F64 = jnp.float64
_I64 = jnp.int64


def _arr(x) -> jnp.ndarray:
    """To a float64 jnp array (must be called inside ``enable_x64``)."""
    return jnp.asarray(x, dtype=_F64)


def grid_axes(*axes: Sequence[float]) -> tuple[jnp.ndarray, ...]:
    """Reshape 1-D axes for sparse broadcasting: axis i becomes shape
    ``(1,)*i + (len,) + (1,)*(n-1-i)`` — the vmap-equivalent outer product
    without materializing the dense mesh."""
    n = len(axes)
    out = []
    with enable_x64():
        for i, ax in enumerate(axes):
            a = _arr(np.atleast_1d(np.asarray(ax, dtype=np.float64)))
            shape = [1] * n
            shape[i] = a.shape[0]
            out.append(a.reshape(shape))
    return tuple(out)


# ---------------------------------------------------------------------------
# Structure-of-arrays views of the scalar dataclasses
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DeviceArrays:
    """Structure-of-arrays view of one or more :class:`FpgaDevice`, shape (D,)."""

    names: tuple[str, ...]
    bitstream_bits: jnp.ndarray
    compression_ratio: jnp.ndarray
    setup_time_ms: jnp.ndarray
    setup_power_mw: jnp.ndarray
    p_static_load_mw: jnp.ndarray
    k_io_mw_per_lane_mhz: jnp.ndarray
    k_comp_mw_per_lane_mhz: jnp.ndarray

    @staticmethod
    def from_devices(devices: Sequence[FpgaDevice]) -> "DeviceArrays":
        if not devices:
            raise ValueError("DeviceArrays needs at least one device")
        cols = {
            f.name: _arr([getattr(d, f.name) for d in devices])
            for f in dataclasses.fields(FpgaDevice)
            if f.name != "name"
        }
        return DeviceArrays(names=tuple(d.name for d in devices), **cols)

    def reshape(self, shape: Sequence[int]) -> "DeviceArrays":
        """Place the device axis into a broadcast layout (e.g. axis 0 of 7)."""
        return dataclasses.replace(
            self,
            **{
                f.name: getattr(self, f.name).reshape(shape)
                for f in dataclasses.fields(self)
                if f.name != "names"
            },
        )

    def cols(self) -> dict[str, jnp.ndarray]:
        """Field arrays as a plain dict (a pytree the jitted kernels accept)."""
        return {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
            if f.name != "names"
        }


@dataclasses.dataclass(frozen=True)
class ItemArrays:
    """Per-item scalar quantities of a :class:`WorkloadItem` as 0-d arrays.

    The values are computed by the item's own Python properties (the exact
    scalar code path, including its left-to-right ``sum()`` association
    order), then wrapped — so the batched closed forms start from
    bit-identical inputs.
    """

    e_exec_mj: jnp.ndarray     # execution energy per item (E_item^IW)
    t_exec_ms: jnp.ndarray     # execution latency (T_latency^IW)
    e_config_mj: jnp.ndarray   # configuration energy
    t_config_ms: jnp.ndarray   # configuration time
    e_total_mj: jnp.ndarray    # all phases (On-Off per-item energy, pre-powerup)
    t_total_ms: jnp.ndarray    # all phases (On-Off latency)
    idle_power_mw: jnp.ndarray

    @staticmethod
    def from_item(item: WorkloadItem) -> "ItemArrays":
        return ItemArrays(
            e_exec_mj=_arr(item.execution_energy_mj),
            t_exec_ms=_arr(item.execution_time_ms),
            e_config_mj=_arr(item.config_energy_mj),
            t_config_ms=_arr(item.config_time_ms),
            e_total_mj=_arr(item.total_energy_mj),
            t_total_ms=_arr(item.total_time_ms),
            idle_power_mw=_arr(item.idle_power_mw),
        )


# ---------------------------------------------------------------------------
# Array kernels: the closed forms of energy_model.py / config_phase.py,
# op-for-op.  All run element-wise over broadcastable float64 arrays.
# ---------------------------------------------------------------------------
def _floor_n(x):
    return jnp.floor(x + em.FLOOR_EPS).astype(_I64)


def _onoff_n_max(e_item, budget):
    return _floor_n(budget / e_item)


def _idle_energy(p_idle, t_req, t_exec):
    # idle_energy_mj: p_idle * (t_req - t_exec) / 1000.0
    return p_idle * (t_req - t_exec) / 1000.0


def _idlewait_n_max(e_init, e_exec, e_idle, budget):
    # idlewait_n_max: floor((B - E_init + e_idle) / (e_item + e_idle)), ≥ 0
    per_period = e_exec + e_idle
    return jnp.maximum(_floor_n((budget - e_init + e_idle) / per_period), 0)


def _crossover(e_onoff, e_exec, t_exec, p_idle):
    # crossover_period_ms: (E_onoff - E_iw) / (P_idle/1000) + T_lat^IW ; inf at P_idle ≤ 0
    safe = jnp.where(p_idle > 0, p_idle, 1.0)
    t = (e_onoff - e_exec) / (safe / 1000.0) + t_exec
    return jnp.where(p_idle > 0, t, jnp.inf)


def _config_grid_kernel(dev: Mapping[str, jnp.ndarray], w, f, c):
    """config_phase.FpgaDevice stage models over broadcast arrays.

    ``dev`` is a :meth:`DeviceArrays.cols` dict (a pytree, so this kernel is
    jittable as-is).
    """
    lanes = w * f                                   # ConfigParams.lanes_mhz
    load_bits = dev["bitstream_bits"] * jnp.where(c, dev["compression_ratio"], 1.0)
    load_time = load_bits / lanes / 1000.0          # load_time_ms
    k = dev["k_io_mw_per_lane_mhz"] + jnp.where(c, dev["k_comp_mw_per_lane_mhz"], 0.0)
    load_power = dev["p_static_load_mw"] + k * lanes   # load_power_mw
    load_energy = load_power * load_time / 1000.0   # energy_mj(P, T)
    setup_energy = dev["setup_power_mw"] * dev["setup_time_ms"] / 1000.0
    config_time = dev["setup_time_ms"] + load_time
    config_energy = setup_energy + load_energy
    config_power = 1000.0 * config_energy / config_time
    return {
        "load_time_ms": load_time,
        "load_power_mw": load_power,
        "load_energy_mj": load_energy,
        "config_time_ms": config_time,
        "config_power_mw": config_power,
        "config_energy_mj": config_energy,
    }


# ---------------------------------------------------------------------------
# Differentiable primitives
# ---------------------------------------------------------------------------
# The closed forms above are pure jnp array programs, so they are also the
# *differentiable* substrate :mod:`repro.optimize` runs gradient descent on.
# The public aliases below are that contract: ``config_phase_kernel`` accepts
# arbitrary continuous buswidth/clock values (the model is defined on the
# continuum; Table 1 is just where the hardware was measured) and a *fractional*
# compression in [0, 1] (interpolating the compressed-bits/extra-switching
# terms linearly — exact at the {0, 1} endpoints); ``onoff_n_smooth`` /
# ``idlewait_n_smooth`` are the pre-floor real-valued item counts (the floor
# in Eq. 3 is the only non-differentiable op in the whole model, so the
# relaxation simply omits it and re-validates through the exact kernels after
# rounding).  All have well-defined ``jax.grad`` everywhere the paper's grid
# lives.

#: Configuration-phase stage models over broadcast arrays (see
#: :func:`config_phase_grid` for the dict-of-arrays layout).  Differentiable
#: in ``w`` (buswidth), ``f`` (clock MHz) and ``c`` (compression fraction —
#: pass booleans for the exact Table-1 behaviour, floats in [0, 1] for the
#: relaxed model).  Exactness note: the fractional form recovers the exact
#: kernel's values at ``c ∈ {0, 1}`` bit-for-bit because ``1 + (r − 1) == r``
#: exactly for ``compression_ratio ∈ [0.5, 2]`` (Sterbenz); real 7-series
#: compression ratios live in (0.5, 1), but a hypothetical device outside
#: that range would drift by one ulp at the compressed corner.
def config_phase_kernel(dev: Mapping[str, jnp.ndarray], w, f, c) -> dict[str, jnp.ndarray]:
    lanes = jnp.multiply(w, f)   # jnp.ndarray even for Python-scalar w/f
    c = jnp.asarray(c)
    cf = c.astype(lanes.dtype) if c.dtype == bool else c
    load_bits = dev["bitstream_bits"] * (1.0 + cf * (dev["compression_ratio"] - 1.0))
    load_time = load_bits / lanes / 1000.0
    k = dev["k_io_mw_per_lane_mhz"] + cf * dev["k_comp_mw_per_lane_mhz"]
    load_power = dev["p_static_load_mw"] + k * lanes
    load_energy = load_power * load_time / 1000.0
    setup_energy = dev["setup_power_mw"] * dev["setup_time_ms"] / 1000.0
    config_time = dev["setup_time_ms"] + load_time
    config_energy = setup_energy + load_energy
    config_power = 1000.0 * config_energy / config_time
    return {
        "load_time_ms": load_time,
        "load_power_mw": load_power,
        "load_energy_mj": load_energy,
        "config_time_ms": config_time,
        "config_power_mw": config_power,
        "config_energy_mj": config_energy,
    }


def onoff_n_smooth(e_item, budget):
    """Real-valued Eq.-3 count for On-Off: ``budget / e_item`` (no floor)."""
    return budget / e_item


def idlewait_n_smooth(e_init, e_exec, e_idle, budget):
    """Real-valued Eq.-3 count for Idle-Waiting (no floor), clamped at 0."""
    return jnp.maximum((budget - e_init + e_idle) / (e_exec + e_idle), 0.0)


#: :func:`repro.core.energy_model.idle_energy_mj` as an array program:
#: ``p_idle · (t_req − t_exec) / 1000``.
idle_energy_kernel = _idle_energy

#: :func:`repro.core.energy_model.crossover_period_ms` as an array program
#: (∞ where ``p_idle ≤ 0``); differentiable in every argument elsewhere.
crossover_kernel = _crossover


# ---------------------------------------------------------------------------
# Public batch API: strategy evaluation over broadcastable arrays
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BatchStrategyResult:
    """Array counterpart of :class:`~repro.core.energy_model.StrategyResult`.

    All fields broadcast to one common shape; ``n_max`` is int64 and exactly
    equal to the scalar path's, ``feasible`` is bool.
    """

    strategy: str
    request_period_ms: np.ndarray
    n_max: np.ndarray
    lifetime_ms: np.ndarray
    energy_per_item_mj: np.ndarray
    feasible: np.ndarray

    @property
    def lifetime_hours(self) -> np.ndarray:
        return self.lifetime_ms / 3_600_000.0


def _to_np(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


@functools.lru_cache(maxsize=None)
def _jitted(fn):
    return jax.jit(fn)


def _run(fn, jit: bool, *args):
    """Dispatch a kernel eagerly (bit-exact, the default) or jitted (fused,
    ~4× faster on huge grids, last-ulp drift — see module docstring)."""
    return (_jitted(fn) if jit else fn)(*args)


def _onoff_kernel(e_total, t_total, t_req, budget, powerup):
    e_item = e_total + powerup      # onoff_item_energy_mj
    feasible = t_req >= t_total
    n = jnp.where(feasible, _onoff_n_max(e_item, budget), 0)
    t_req_b, n = jnp.broadcast_arrays(t_req + 0.0 * budget, n)
    return {
        "n_max": n,
        "lifetime_ms": n * t_req_b,
        "energy_per_item_mj": jnp.broadcast_to(e_item, n.shape),
        "feasible": jnp.broadcast_to(feasible, n.shape),
        "request_period_ms": t_req_b,
    }


def _idlewait_kernel(e_config, e_exec, t_exec, t_req, budget, p_idle, powerup):
    feasible = t_req >= t_exec
    # guard the infeasible lanes: scalar path never evaluates idle energy there
    t_safe = jnp.where(feasible, t_req, t_exec)
    e_idle = _idle_energy(p_idle, t_safe, t_exec)
    e_init = e_config + powerup                     # idlewait_init_energy_mj
    n = jnp.where(feasible, _idlewait_n_max(e_init, e_exec, e_idle, budget), 0)
    marginal = e_exec + jnp.where(feasible, e_idle, 0.0)
    t_req_b, n, marginal = jnp.broadcast_arrays(t_req + 0.0 * budget + 0.0 * p_idle, n, marginal)
    return {
        "n_max": n,
        "lifetime_ms": n * t_req_b,
        "energy_per_item_mj": marginal,
        "feasible": jnp.broadcast_to(feasible, n.shape),
        "request_period_ms": t_req_b,
    }


def evaluate_onoff_batch(
    item: WorkloadItem,
    request_periods_ms,
    e_budgets_mj=em.PAPER_ENERGY_BUDGET_MJ,
    powerup_overhead_mj: float = 0.0,
    jit: bool = False,
) -> BatchStrategyResult:
    """Vectorized :func:`repro.core.energy_model.evaluate_onoff`.

    ``request_periods_ms`` and ``e_budgets_mj`` are broadcast together (pass
    pre-shaped arrays, e.g. from :func:`grid_axes`, for outer products).
    """
    with enable_x64():
        it = ItemArrays.from_item(item)
        out = _run(
            _onoff_kernel,
            jit,
            it.e_total_mj,
            it.t_total_ms,
            _arr(request_periods_ms),
            _arr(e_budgets_mj),
            _arr(powerup_overhead_mj),
        )
    out = _to_np(out)
    return BatchStrategyResult(strategy="on_off", **out)


def evaluate_idlewait_batch(
    item: WorkloadItem,
    request_periods_ms,
    e_budgets_mj=em.PAPER_ENERGY_BUDGET_MJ,
    idle_powers_mw=None,
    powerup_overhead_mj: float = 0.0,
    jit: bool = False,
) -> BatchStrategyResult:
    """Vectorized :func:`repro.core.energy_model.evaluate_idlewait`."""
    with enable_x64():
        it = ItemArrays.from_item(item)
        p_idle = it.idle_power_mw if idle_powers_mw is None else _arr(idle_powers_mw)
        out = _run(
            _idlewait_kernel,
            jit,
            it.e_config_mj,
            it.e_exec_mj,
            it.t_exec_ms,
            _arr(request_periods_ms),
            _arr(e_budgets_mj),
            p_idle,
            _arr(powerup_overhead_mj),
        )
    out = _to_np(out)
    return BatchStrategyResult(strategy="idle_waiting", **out)


def crossover_batch(
    item: WorkloadItem,
    idle_powers_mw=None,
    powerup_overhead_mj: float = 0.0,
) -> np.ndarray:
    """Vectorized :func:`repro.core.energy_model.crossover_period_ms` over an
    array of idle powers."""
    with enable_x64():
        it = ItemArrays.from_item(item)
        p_idle = it.idle_power_mw if idle_powers_mw is None else _arr(idle_powers_mw)
        e_onoff = it.e_total_mj + _arr(powerup_overhead_mj)
        out = _crossover(e_onoff, it.e_exec_mj, it.t_exec_ms, p_idle)
    return np.asarray(out)


def evaluate_adaptive_batch(
    item: WorkloadItem,
    request_periods_ms,
    e_budgets_mj=em.PAPER_ENERGY_BUDGET_MJ,
    idle_powers_mw=None,
    powerup_overhead_mj: float = 0.0,
    jit: bool = False,
) -> BatchStrategyResult:
    """Vectorized :meth:`repro.core.adaptive.AdaptiveStrategy.evaluate`: the
    pure-threshold rule ``T_req ≤ T_cross → Idle-Waiting else On-Off``,
    selecting the winning static's arrays element-wise."""
    oo = evaluate_onoff_batch(item, request_periods_ms, e_budgets_mj, powerup_overhead_mj, jit=jit)
    iw = evaluate_idlewait_batch(
        item, request_periods_ms, e_budgets_mj, idle_powers_mw, powerup_overhead_mj, jit=jit
    )
    cross = crossover_batch(item, idle_powers_mw, powerup_overhead_mj)
    pick_iw = np.broadcast_arrays(np.asarray(iw.request_period_ms) <= cross, iw.n_max)[0]
    sel = lambda a, b: np.where(pick_iw, a, b)  # noqa: E731
    return BatchStrategyResult(
        strategy="adaptive",
        request_period_ms=iw.request_period_ms,
        n_max=sel(iw.n_max, oo.n_max),
        lifetime_ms=sel(iw.lifetime_ms, oo.lifetime_ms),
        energy_per_item_mj=sel(iw.energy_per_item_mj, oo.energy_per_item_mj),
        feasible=sel(iw.feasible, oo.feasible),
    )


# ---------------------------------------------------------------------------
# Configuration-phase grid (Exp. 1, vectorized)
# ---------------------------------------------------------------------------
def config_phase_grid(
    devices: Sequence[FpgaDevice] | FpgaDevice,
    buswidths: Sequence[int] = SPI_BUSWIDTHS,
    clocks_mhz: Sequence[float] = SPI_CLOCKS_MHZ,
    compression: Sequence[bool] = COMPRESSION_OPTIONS,
    jit: bool = False,
) -> dict[str, np.ndarray]:
    """Vectorized :func:`repro.core.config_phase.sweep_config_space`.

    Returns a dict of arrays with shape ``(D, W, F, C)`` — device, buswidth,
    clock, compression — matching every :class:`SweepPoint` field.  Unlike
    the scalar path, arbitrary (off-Table-1) clock/buswidth values are
    accepted: the closed-form model is defined on the continuum.
    """
    if isinstance(devices, FpgaDevice):
        devices = (devices,)
    from repro.core.config_phase import _validate_grid_axis

    _validate_grid_axis("buswidths", buswidths, caller="config_phase_grid")
    _validate_grid_axis("clocks_mhz", clocks_mhz, caller="config_phase_grid")
    _validate_grid_axis("compression", compression, caller="config_phase_grid")
    with enable_x64():
        dev = DeviceArrays.from_devices(devices).reshape((len(devices), 1, 1, 1))
        w, f, c = grid_axes(buswidths, clocks_mhz, [1.0 * bool(x) for x in compression])
        w, f, c = w[None], f[None], c[None].astype(bool)  # prepend device axis
        out = _run(_config_grid_kernel, jit, dev.cols(), w, f, c)
        shape = jnp.broadcast_shapes(*(a.shape for a in out.values()))
        out = {k: jnp.broadcast_to(v, shape) for k, v in out.items()}
    return _to_np(out)


# ---------------------------------------------------------------------------
# The full 7-axis design-space sweep
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SweepGrid:
    """Declarative description of a design-space grid (see module docstring
    for the axis layout).  ``base_item`` supplies the execution phases and
    the baseline idle power; the configuration phase is *derived* per grid
    point from the device model, exactly as the paper derives Table 2's
    configuration row from Experiment 1's optimum."""

    devices: tuple[FpgaDevice, ...] = (SPARTAN7_XC7S15,)
    buswidths: tuple[int, ...] = SPI_BUSWIDTHS
    clocks_mhz: tuple[float, ...] = SPI_CLOCKS_MHZ
    compression: tuple[bool, ...] = COMPRESSION_OPTIONS
    request_periods_ms: tuple[float, ...] = (40.0,)
    idle_methods: tuple[IdlePowerMethod, ...] = (IdlePowerMethod.BASELINE,)
    e_budgets_mj: tuple[float, ...] = (em.PAPER_ENERGY_BUDGET_MJ,)
    base_item: WorkloadItem | None = None
    powerup_overhead_mj: float = 0.0

    def __post_init__(self) -> None:
        # same contract as the scalar sweeps (Strategy.sweep /
        # sweep_config_space), via the shared validator: no silent empty
        # grids, no shuffled axes — GridResult.to_records maps flat indices
        # back by axis order.
        from repro.core.config_phase import _validate_grid_axis

        for name, vals in (
            ("buswidths", self.buswidths),
            ("clocks_mhz", self.clocks_mhz),
            ("request_periods_ms", self.request_periods_ms),
            ("e_budgets_mj", self.e_budgets_mj),
        ):
            _validate_grid_axis(name, vals, caller="SweepGrid")
        for name, vals in (
            ("devices", self.devices),
            ("compression", self.compression),
            ("idle_methods", self.idle_methods),
        ):
            _validate_grid_axis(name, vals, sorted_required=False, caller="SweepGrid")

    @property
    def shape(self) -> tuple[int, ...]:
        return (
            len(self.devices),
            len(self.buswidths),
            len(self.clocks_mhz),
            len(self.compression),
            len(self.request_periods_ms),
            len(self.idle_methods),
            len(self.e_budgets_mj),
        )

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))

    def item(self) -> WorkloadItem:
        return self.base_item if self.base_item is not None else paper_lstm_item()

    def idle_powers_mw(self) -> list[float]:
        item = self.item()
        return [
            item.idle_power_mw if m is IdlePowerMethod.BASELINE else IDLE_POWER_MW[m]
            for m in self.idle_methods
        ]

    def axis_labels(self) -> dict[str, list]:
        return {
            "device": [d.name for d in self.devices],
            "buswidth": list(self.buswidths),
            "clock_mhz": list(self.clocks_mhz),
            "compression": [bool(c) for c in self.compression],
            "request_period_ms": list(self.request_periods_ms),
            "idle_method": [m.value for m in self.idle_methods],
            "e_budget_mj": list(self.e_budgets_mj),
        }


#: Names of the quantity arrays a full sweep produces.
GRID_QUANTITIES = (
    "config_time_ms",
    "config_energy_mj",
    "onoff_n_max",
    "onoff_lifetime_ms",
    "onoff_energy_per_item_mj",
    "onoff_feasible",
    "iw_n_max",
    "iw_lifetime_ms",
    "iw_energy_per_item_mj",
    "iw_feasible",
    "crossover_ms",
    "adaptive_n_max",
    "adaptive_lifetime_ms",
    "adaptive_picks_iw",
)


@dataclasses.dataclass(frozen=True)
class GridResult:
    """Dense result arrays (each of ``grid.shape``) plus the axes that index
    them.  ``arrays`` keys are :data:`GRID_QUANTITIES`."""

    grid: SweepGrid
    arrays: Mapping[str, np.ndarray]

    def __getitem__(self, key: str) -> np.ndarray:
        return self.arrays[key]

    def to_records(self, limit: int | None = None) -> list[dict]:
        """Flatten to one dict per grid point (C-order over the 7 axes).
        ``limit`` caps the record count for JSON emission."""
        labels = self.grid.axis_labels()
        names = list(labels)
        idx = np.indices(self.grid.shape).reshape(len(names), -1).T
        n = len(idx) if limit is None else min(limit, len(idx))
        out = []
        flat = {k: np.broadcast_to(v, self.grid.shape).reshape(-1) for k, v in self.arrays.items()}
        for j in range(n):
            rec = {name: labels[name][idx[j][i]] for i, name in enumerate(names)}
            for k, v in flat.items():
                x = v[j]
                rec[k] = x.item() if hasattr(x, "item") else x
            out.append(rec)
        return out

    def to_json_dict(self, limit: int | None = None) -> dict:
        return {
            "shape": list(self.grid.shape),
            "size": self.grid.size,
            "axes": self.grid.axis_labels(),
            "powerup_overhead_mj": self.grid.powerup_overhead_mj,
            "item": self.grid.item().to_dict(),
            "records": self.to_records(limit),
        }


def _sweep_kernel(dev_cols, w, f, c, t_req, p_idle, budget,
                  exec_energies, exec_times, e_exec, t_exec, powerup):
    cfg = _config_grid_kernel(dev_cols, w, f, c)
    t_config = cfg["config_time_ms"]

    # The scalar pipeline derives the per-item configuration phase with
    # FpgaDevice.config_phase(): energy round-trips through the phase's
    # *average power* (E → P=1000·E/T → P·T/1000), and item totals are
    # left-to-right sums over phases.  Reproduce both so grid points are
    # bit-identical to scalar evaluation of the constructed WorkloadItem.
    e_config = cfg["config_power_mw"] * t_config / 1000.0
    e_total = 0.0 + e_config
    t_total = 0.0 + t_config
    for e_p, t_p in zip(exec_energies, exec_times):
        e_total = e_total + e_p
        t_total = t_total + t_p

    e_onoff = e_total + powerup
    oo_feasible = t_req >= t_total
    oo_n = jnp.where(oo_feasible, _onoff_n_max(e_onoff, budget), 0)

    iw_feasible = t_req >= t_exec
    t_safe = jnp.where(iw_feasible, t_req, t_exec)
    e_idle = _idle_energy(p_idle, t_safe, t_exec)
    e_init = e_config + powerup
    iw_n = jnp.where(iw_feasible, _idlewait_n_max(e_init, e_exec, e_idle, budget), 0)

    cross = _crossover(e_onoff, e_exec, t_exec, p_idle)
    pick_iw = t_req <= cross

    out = {
        "config_time_ms": t_config,
        "config_energy_mj": cfg["config_energy_mj"],
        "onoff_n_max": oo_n,
        "onoff_lifetime_ms": oo_n * t_req,
        "onoff_energy_per_item_mj": e_onoff,
        "onoff_feasible": oo_feasible,
        "iw_n_max": iw_n,
        "iw_lifetime_ms": iw_n * t_req,
        "iw_energy_per_item_mj": e_exec + jnp.where(iw_feasible, e_idle, 0.0),
        "iw_feasible": iw_feasible,
        "crossover_ms": cross,
        "adaptive_n_max": jnp.where(pick_iw, iw_n, oo_n),
        "adaptive_lifetime_ms": jnp.where(pick_iw, iw_n, oo_n) * t_req,
        "adaptive_picks_iw": pick_iw,
    }
    shape = jnp.broadcast_shapes(*(a.shape for a in out.values()))
    return {k: jnp.broadcast_to(v, shape) for k, v in out.items()}


def sweep_batch(grid: SweepGrid, jit: bool = False) -> GridResult:
    """Evaluate every quantity of :data:`GRID_QUANTITIES` over the full grid
    in one vectorized x64 call (``jit=True`` for XLA fusion — see module
    docstring for the exactness trade-off).

    Scalar-oracle equivalence: grid point ``(d, w, f, c, t, m, b)`` equals
    building the workload item whose configuration phase is
    ``devices[d].config_phase(ConfigParams(w, f, c))`` and evaluating the
    scalar strategies at period ``t``, idle method ``m``, budget ``b``.
    """
    item = grid.item()
    if not item.has_phase(CONFIGURATION):
        raise ValueError(
            "sweep_batch derives the configuration phase from the device model; "
            f"base_item {item.name!r} must carry a configuration phase to replace"
        )
    with enable_x64():
        nd = len(grid.shape)
        dev = DeviceArrays.from_devices(grid.devices).reshape((len(grid.devices),) + (1,) * (nd - 1))
        axes = grid_axes(
            [0.0] * len(grid.devices),          # placeholder: device handled above
            grid.buswidths,
            grid.clocks_mhz,
            [1.0 * bool(x) for x in grid.compression],
            grid.request_periods_ms,
            grid.idle_powers_mw(),
            grid.e_budgets_mj,
        )
        _, w, f, c, t_req, p_idle, budget = axes
        it = ItemArrays.from_item(item)
        exec_phases = [p for p in item.phases if p.name != CONFIGURATION]
        out = _run(
            _sweep_kernel,
            jit,
            dev.cols(), w, f, c.astype(bool), t_req, p_idle, budget,
            tuple(_arr(p.energy_mj) for p in exec_phases),
            tuple(_arr(p.time_ms) for p in exec_phases),
            it.e_exec_mj, it.t_exec_ms, _arr(grid.powerup_overhead_mj),
        )
    return GridResult(grid=grid, arrays=_to_np(out))
