"""Vmapped multi-start gradient descent over the relaxed design space.

One jitted ``lax.scan`` advances S independent Adam chains
(:func:`repro.optim.adamw.adamw` — the repo's own optimizer, vmapped over
the start axis) on a relaxed objective from :mod:`repro.optimize.relax`.
After descent every chain is **rounded to the legal grid** (nearest clock —
plus its grid neighbours, so a chain that converged between two legal
clocks nominates both — argmax choices) and every rounded candidate is
**re-validated through the exact oracle** (:mod:`repro.core.batch_eval`'s
eager kernels, bit-identical to the scalar closed forms).  The returned
optimum is therefore always an *exact* grid value; the relaxation only
steers the search.

Why descend at all when the paper's grid has 66 points?  Because the grid
is a *measurement artifact*, not the design space: the closed-form model is
defined on the clock continuum, and once the grid is densified (finer clock
steps, more devices, more periods) exhaustive sweeping scales linearly
while descent's cost is constant in grid density —
``python -m repro.launch.optimize`` reports the crossover empirically.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import enable_x64

from repro.core import energy_model as em
from repro.core.batch_eval import SweepGrid, config_phase_grid, sweep_batch
from repro.core.config_phase import FpgaDevice, SPI_BUSWIDTHS, SPI_CLOCKS_MHZ
from repro.core.pareto import pareto_mask_jnp, soft_pareto_weight
from repro.core.phases import WorkloadItem
from repro.core.strategies import IDLE_POWER_MW, IdlePowerMethod
from repro.optim.adamw import adamw
from repro.optimize import relax

__all__ = [
    "DescentSettings",
    "OptimizeResult",
    "descend",
    "optimize_config",
    "optimize_lifetime",
    "trace_config_frontier",
]


@dataclasses.dataclass(frozen=True)
class DescentSettings:
    """Knobs of the multi-start Adam loop."""

    n_starts: int = 16
    steps: int = 250
    lr: float = 0.5    # Adam-normalized steps are ~lr in clock-MHz/logit
                       # units; 250 × 0.5 comfortably spans the 3–66 MHz axis
    seed: int = 0
    jit: bool = True

    def __post_init__(self) -> None:
        if self.n_starts < 1:
            raise ValueError(f"n_starts must be ≥ 1, got {self.n_starts}")
        if self.steps < 1:
            raise ValueError(f"steps must be ≥ 1, got {self.steps}")
        if not (self.lr > 0):
            raise ValueError(f"lr must be positive, got {self.lr}")


@dataclasses.dataclass(frozen=True)
class OptimizeResult:
    """Outcome of one descent + exact re-validation pass.

    ``best`` holds the winning legal configuration and its **exact** oracle
    objective value; ``candidates`` every distinct rounded candidate the
    starts nominated (with exact values — the re-validation audit trail);
    ``loss_curve`` the per-step minimum relaxed loss across starts.
    """

    objective: str
    best: dict
    candidates: list[dict]
    loss_curve: np.ndarray
    settings: DescentSettings
    grid_points_considered: int

    def to_json_dict(self) -> dict:
        return {
            "objective": self.objective,
            "best": self.best,
            "candidates": self.candidates,
            "final_relaxed_loss": float(self.loss_curve[-1]),
            "n_starts": self.settings.n_starts,
            "steps": self.settings.steps,
            "grid_points_considered": self.grid_points_considered,
        }


_OPT = adamw(weight_decay=0.0, clip_norm=None, moment_dtype=jnp.float64)


def _make_run(core, n_w: int, steps: int):
    """THE multi-start Adam loop — the single definition every path uses
    (jitted-and-cached, eager, and the custom-loss :func:`descend`).

    ``run(params, state, lv, lr, lam)`` advances every start through
    ``steps`` vmapped value-and-grad/Adam updates of
    ``core(params, lv, n_w, lam)`` in one ``lax.scan``, returning the final
    (params, state) carry and the per-step min-loss curve.
    """

    def run(params, state, lv, lr, lam):
        value_grad = jax.value_and_grad(lambda p: core(p, lv, n_w, lam))

        def step(carry, _):
            p, s = carry
            loss, grads = jax.vmap(value_grad)(p)
            p, s, _ = jax.vmap(_OPT.update, in_axes=(0, 0, 0, None))(grads, s, p, lr)
            return (p, s), jnp.min(loss)

        return lax.scan(step, (params, state), None, steps)

    return run


@functools.lru_cache(maxsize=None)
def _compiled_loop(core_name: str, n_w: int, steps: int):
    """One jitted multi-start Adam loop per (objective, |buswidths|, steps).

    Everything else — device constants, operating point, clock bounds, λ,
    lr, the start states — flows in as arrays, so re-targeting the
    optimizer (new device, denser grid, different period/budget) reuses the
    compiled loop: descent cost is amortized-constant in grid density.
    """
    return jax.jit(_make_run(relax.LOSS_CORES[core_name], n_w, steps))


def _descend_core(
    core_name: str,
    problem: relax.RelaxedProblem,
    settings: DescentSettings,
    lam: float = 0.0,
) -> tuple[dict, np.ndarray]:
    with enable_x64():
        key = jax.random.PRNGKey(settings.seed)
        params = relax.init_params(key, problem, settings.n_starts)
        state = jax.vmap(_OPT.init)(params)
        n_w = len(problem.buswidths)
        if settings.jit:
            fn = _compiled_loop(core_name, n_w, settings.steps)
        else:
            fn = _make_run(relax.LOSS_CORES[core_name], n_w, settings.steps)
        (params, _), curve = fn(
            params, state, relax.leaves(problem),
            jnp.float64(settings.lr), jnp.float64(lam),
        )
    return params, np.asarray(curve)


def descend(
    loss_fn: Callable[[dict], jnp.ndarray],
    problem: relax.RelaxedProblem,
    settings: DescentSettings = DescentSettings(),
) -> tuple[dict, np.ndarray]:
    """Run S Adam chains on an arbitrary ``loss_fn(params) → ()`` (vmapped
    over starts).

    Returns (final params pytree with leading axis S, per-step min-loss
    curve).  Runs under x64 — the closed forms are calibrated in double
    precision and the optimizer states follow suit.  The named objectives
    (:func:`optimize_config` / :func:`optimize_lifetime` /
    :func:`trace_config_frontier`) go through a compile-once cached loop
    instead; use this entry point for custom losses.
    """
    with enable_x64():
        key = jax.random.PRNGKey(settings.seed)
        params = relax.init_params(key, problem, settings.n_starts)
        state = jax.vmap(_OPT.init)(params)
        run = _make_run(lambda p, lv, n_w, lam: loss_fn(p), 0, settings.steps)
        if settings.jit:
            run = jax.jit(run)
        (params, _), curve = run(
            params, state, {}, jnp.float64(settings.lr), jnp.float64(0.0)
        )
    return params, np.asarray(curve)


# ---------------------------------------------------------------------------
# Rounding + exact re-validation
# ---------------------------------------------------------------------------
def _candidate_set(
    params: dict, problem: relax.RelaxedProblem, neighbours: int = 1
) -> list[tuple[int, float, bool]]:
    """Distinct legal (buswidth, clock, compression) candidates from the
    final starts: each start nominates its snapped point plus ``neighbours``
    grid clocks on each side (a chain that converged between two legal
    clocks is agnostic between them — let the exact oracle decide)."""
    snapped = relax.snap(params, problem)
    clocks = np.asarray(problem.clocks_mhz)
    idx = relax.nearest_clock_index(
        np.atleast_1d(snapped["clock_mhz"]).astype(np.float64), clocks
    )
    out: dict[tuple[int, float, bool], None] = {}
    for s in range(len(np.atleast_1d(snapped["clock_mhz"]))):
        w = int(np.atleast_1d(snapped["buswidth"])[s])
        c = bool(np.atleast_1d(snapped["compression"])[s])
        fi = int(idx[s])
        for j in range(max(0, fi - neighbours), min(clocks.size, fi + neighbours + 1)):
            out[(w, float(clocks[j]), c)] = None
    return list(out)


def _exact_config_energy(
    device: FpgaDevice, candidates: Sequence[tuple[int, float, bool]]
) -> list[float]:
    """Exact oracle values for config-energy candidates (eager kernels)."""
    vals = []
    for w, f, c in candidates:
        g = config_phase_grid(device, (w,), (f,), (c,))
        vals.append(float(g["config_energy_mj"].reshape(())))
    return vals


def _exact_adaptive_lifetime(
    device: FpgaDevice,
    item: WorkloadItem,
    candidates: Sequence[tuple[int, float, bool]],
    request_period_ms: float,
    e_budget_mj: float,
    method: IdlePowerMethod,
    powerup_overhead_mj: float,
) -> list[float]:
    """Exact adaptive lifetimes via :func:`sweep_batch` one point at a time
    (the eager kernels — bit-identical to the scalar oracle)."""
    vals = []
    for w, f, c in candidates:
        grid = SweepGrid(
            devices=(device,),
            buswidths=(w,),
            clocks_mhz=(f,),
            compression=(c,),
            request_periods_ms=(request_period_ms,),
            idle_methods=(method,),
            e_budgets_mj=(e_budget_mj,),
            base_item=item,
            powerup_overhead_mj=powerup_overhead_mj,
        )
        vals.append(float(sweep_batch(grid)["adaptive_lifetime_ms"].reshape(())))
    return vals


def _pick(
    objective: str,
    candidates: list[tuple[int, float, bool]],
    exact_vals: list[float],
    maximize: bool,
    curve: np.ndarray,
    settings: DescentSettings,
    value_key: str,
) -> OptimizeResult:
    order = np.argsort(exact_vals)
    best_i = int(order[-1] if maximize else order[0])
    recs = [
        {
            "buswidth": w,
            "clock_mhz": f,
            "compression": c,
            value_key: v,
        }
        for (w, f, c), v in zip(candidates, exact_vals)
    ]
    return OptimizeResult(
        objective=objective,
        best=recs[best_i],
        candidates=sorted(recs, key=lambda r: r[value_key], reverse=maximize),
        loss_curve=curve,
        settings=settings,
        grid_points_considered=len(candidates),
    )


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
def optimize_config(
    device: FpgaDevice,
    buswidths: Sequence[int] = SPI_BUSWIDTHS,
    clocks_mhz: Sequence[float] = SPI_CLOCKS_MHZ,
    settings: DescentSettings = DescentSettings(),
) -> OptimizeResult:
    """Find the minimum-configuration-energy legal setting by descent
    (Experiment 1's argmin, without sweeping the grid).

    The result's ``best`` is exact-oracle-valued; on the paper's Table-1
    grid it recovers the 11.85 mJ (quad, 66 MHz, compressed) optimum — the
    40.13× reduction — exactly.
    """
    problem = relax.RelaxedProblem.from_device(
        device, buswidths=buswidths, clocks_mhz=clocks_mhz
    )
    params, curve = _descend_core("config_energy", problem, settings)
    cands = _candidate_set(params, problem)
    vals = _exact_config_energy(device, cands)
    return _pick("config_energy", cands, vals, False, curve, settings, "config_energy_mj")


def optimize_lifetime(
    device: FpgaDevice,
    item: WorkloadItem | None = None,
    request_period_ms: float = 40.0,
    e_budget_mj: float = em.PAPER_ENERGY_BUDGET_MJ,
    method: IdlePowerMethod = IdlePowerMethod.METHOD1_2,
    powerup_overhead_mj: float = 0.0,
    buswidths: Sequence[int] = SPI_BUSWIDTHS,
    clocks_mhz: Sequence[float] = SPI_CLOCKS_MHZ,
    settings: DescentSettings = DescentSettings(),
) -> OptimizeResult:
    """Find the configuration maximizing the *adaptive* lifetime (Eqs. 3–4
    with the crossover rule choosing the strategy arm) at one workload
    point — the per-workload tuning loop the application-specific-knowledge
    line of work argues for, closed through gradients."""
    from repro.core.phases import paper_lstm_item

    item = item if item is not None else paper_lstm_item()
    problem = relax.RelaxedProblem.from_device(
        device,
        item=item,
        buswidths=buswidths,
        clocks_mhz=clocks_mhz,
        request_period_ms=request_period_ms,
        e_budget_mj=e_budget_mj,
        idle_power_mw=(
            item.idle_power_mw
            if method is IdlePowerMethod.BASELINE
            else IDLE_POWER_MW[method]
        ),
        powerup_overhead_mj=powerup_overhead_mj,
    )
    params, curve = _descend_core("adaptive_lifetime", problem, settings)
    cands = _candidate_set(params, problem)
    vals = _exact_adaptive_lifetime(
        device, item, cands, request_period_ms, e_budget_mj, method, powerup_overhead_mj
    )
    return _pick("adaptive_lifetime", cands, vals, True, curve, settings, "lifetime_ms")


def trace_config_frontier(
    device: FpgaDevice,
    lambdas: Sequence[float] = tuple(np.linspace(0.02, 0.98, 13)),
    buswidths: Sequence[int] = SPI_BUSWIDTHS,
    clocks_mhz: Sequence[float] = SPI_CLOCKS_MHZ,
    settings: DescentSettings = DescentSettings(n_starts=4),
    temperature: float = 1e-3,
) -> dict:
    """Trace the (config energy, config time) Pareto frontier by descending
    λ-scalarizations — one multi-start chain per λ — then keep the exact
    non-dominated subset (:func:`repro.core.pareto.pareto_mask_jnp`).

    Returns ``{"points": [...], "lambdas": [...]}`` where each point also
    carries its differentiable frontier weight
    (:func:`repro.core.pareto.soft_pareto_weight` at ``temperature``) — 1.0
    means no other traced point comes close to dominating it.
    """
    lams = [float(x) for x in lambdas]
    if not lams:
        raise ValueError("need at least one λ to trace a frontier")
    problem = relax.RelaxedProblem.from_device(
        device, buswidths=buswidths, clocks_mhz=clocks_mhz
    )
    seen: dict[tuple[int, float, bool], None] = {}
    for k, lam in enumerate(lams):
        params, _ = _descend_core(
            "config_scalarized",
            problem,
            dataclasses.replace(settings, seed=settings.seed + k),
            lam=lam,
        )
        for cand in _candidate_set(params, problem):
            seen[cand] = None
    cands = list(seen)
    points = []
    for w, f, c in cands:
        g = config_phase_grid(device, (w,), (f,), (c,))
        points.append(
            {
                "buswidth": w,
                "clock_mhz": f,
                "compression": c,
                "config_energy_mj": float(g["config_energy_mj"].reshape(())),
                "config_time_ms": float(g["config_time_ms"].reshape(())),
            }
        )
    with enable_x64():
        costs = jnp.asarray(
            [[p["config_energy_mj"], p["config_time_ms"]] for p in points],
            dtype=jnp.float64,
        )
        mask = np.asarray(pareto_mask_jnp(costs))
        weight = np.asarray(soft_pareto_weight(costs, temperature))
    front = [
        {**p, "soft_weight": float(weight[i])}
        for i, p in enumerate(points)
        if mask[i]
    ]
    return {
        "lambdas": lams,
        "traced_points": len(points),
        "points": sorted(front, key=lambda r: r["config_energy_mj"]),
    }
