"""Vectorized policy rollouts: the trace simulator as one jitted ``lax.scan``.

``rollout`` replays the *exact* discrete-event semantics of
:func:`repro.core.simulator.simulate_trace` — queueing at the previous
completion, strict ``timeout < gap`` release, inline reconfiguration delay,
pre-staged initial configuration, and the budget admission epsilon — for a
whole batch of arrival streams at once, with the idle timeout chosen per
gap by the policy network over the online features.  N-streams-of-T-gaps
run as a single ``vmap``-ped ``lax.scan``; ``tests/test_policy.py`` pins
bit-agreement (item counts exact, energies within 1e-9) against the scalar
simulator.

The same scan carries a *smooth* energy accumulator (``smooth=True``): the
hard ``min(gap, timeout)`` idle term and the 0/1 release indicator are
replaced by :func:`repro.optimize.relax.smooth_min` and
:func:`repro.optimize.relax.sigmoid_gate` at sharpness ``smooth_ms``, so
the accumulated energy is differentiable in the network parameters while
the *dynamics* (queueing, admission) stay hard.  Backprop trains on the
smooth total; antithetic ES (:mod:`repro.policy.train`) trains on the hard
one, closing the relaxation bias on the routed path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import enable_x64

from repro.core import energy_model as em
from repro.core.adaptive import break_even_timeout_ms
from repro.core.phases import WorkloadItem
from repro.core.strategies import IDLE_POWER_MW, IdlePowerMethod
from repro.optimize.relax import sigmoid_gate, smooth_min
from repro.policy import features as F
from repro.policy import net as N

#: Default sharpness (ms) of the smooth release gate / idle kink, as a
#: fraction of T*_be — wide enough that gradients reach the network from a
#: decision boundary half a break-even time away.
DEFAULT_SMOOTH_FRAC = 0.1

_ADMIT_EPS = 1e-9  # simulate_trace's budget admission epsilon


def idle_power_for(item: WorkloadItem, method: IdlePowerMethod) -> float:
    """The idle-power convention of PolicyController.idle_power_mw."""
    if method is IdlePowerMethod.BASELINE:
        return item.idle_power_mw
    return IDLE_POWER_MW[method]


def make_consts(
    item: WorkloadItem,
    method: IdlePowerMethod = IdlePowerMethod.BASELINE,
    powerup_overhead_mj: float = 0.0,
    budget_mj: float = math.inf,
    smooth_ms: float | None = None,
) -> dict:
    """Scalar physics constants of one workload item as a float pytree.

    Passed to :func:`rollout` as dynamic data (one jit specialisation per
    *shape*, not per item).  ``budget_mj=inf`` is the training setting —
    admission never trips and the objective is pure energy rate.
    """
    p_idle = idle_power_for(item, method)
    t_be = break_even_timeout_ms(item, p_idle, powerup_overhead_mj)
    if not (math.isfinite(t_be) and t_be > 0):
        raise ValueError(
            f"degenerate break-even timeout {t_be!r} ms for item "
            f"{item.name!r}: the learned policy needs a finite, positive "
            "ski-rental scale to normalise against"
        )
    return {
        "e_exec": float(item.execution_energy_mj),
        "t_exec": float(item.execution_time_ms),
        "e_config": float(item.config_energy_mj + powerup_overhead_mj),
        # overhead share of e_config, so the energy ledger can report the
        # power-up ramp separately from the configure phase
        "e_overhead": float(powerup_overhead_mj),
        "t_config": float(item.config_time_ms),
        "p_idle": float(p_idle),
        "t_be": float(t_be),
        "budget": float(budget_mj),
        "smooth_ms": float(
            smooth_ms if smooth_ms is not None else DEFAULT_SMOOTH_FRAC * t_be
        ),
    }


def _rollout_stream(params, gaps, consts, smooth: bool):
    """One stream of gaps through the trace-simulator semantics."""
    c = consts
    e_init = c["e_config"] + c["e_exec"]
    admit0 = e_init <= c["budget"] + _ADMIT_EPS * jnp.maximum(1.0, e_init)

    fs0 = F.init_state_jnp()
    tau0 = N.timeout_ms(params, F.feature_vector(fs0, c["t_be"]), c["t_be"])

    carry0 = dict(
        fs=fs0,
        tau=tau0,
        completion=jnp.where(admit0, c["t_exec"], 0.0),
        alive=admit0,
        energy=jnp.where(admit0, e_init, 0.0),
        energy_smooth=e_init + 0.0 * tau0,
        n=admit0.astype(jnp.float64),
        releases=jnp.float64(0.0),
        configs=admit0.astype(jnp.float64),
        idle_mj=jnp.float64(0.0),
        lifetime=jnp.where(admit0, c["t_exec"], 0.0),
        arrival=jnp.float64(0.0),
    )

    def body(carry, g):
        c_ = consts
        a_new = carry["arrival"] + g
        start = jnp.maximum(a_new, carry["completion"])
        gap_m = start - carry["completion"]
        tau = carry["tau"]

        idle_t = jnp.minimum(gap_m, tau)
        released = tau < gap_m
        idle_e = c_["p_idle"] * idle_t / 1000.0
        cost = idle_e + jnp.where(released, c_["e_config"], 0.0) + c_["e_exec"]
        admit = carry["alive"] & (
            carry["energy"] + cost
            <= c_["budget"] + _ADMIT_EPS * jnp.maximum(1.0, cost)
        )
        energy = carry["energy"] + jnp.where(admit, cost, 0.0)
        start2 = start + jnp.where(released, c_["t_config"], 0.0)
        completion = jnp.where(admit, start2 + c_["t_exec"], carry["completion"])

        if smooth:
            s = c_["smooth_ms"]
            rel_g = sigmoid_gate(gap_m - tau, s)
            cost_s = (
                c_["p_idle"] * smooth_min(gap_m, tau, s) / 1000.0
                + rel_g * c_["e_config"]
                + c_["e_exec"]
            )
            energy_smooth = carry["energy_smooth"] + cost_s
        else:
            energy_smooth = carry["energy_smooth"]

        # Observe the *arrival* gap (a_new - a_prev == g), then choose the
        # timeout that will manage the NEXT idle span — the simulator's
        # decide-after-observe ordering.
        fs = F.update_state(carry["fs"], g, c_["t_be"])
        tau_next = N.timeout_ms(params, F.feature_vector(fs, c_["t_be"]), c_["t_be"])

        new = dict(
            fs=fs,
            tau=tau_next,
            completion=completion,
            alive=admit,
            energy=energy,
            energy_smooth=energy_smooth,
            n=carry["n"] + admit.astype(jnp.float64),
            releases=carry["releases"] + (admit & released).astype(jnp.float64),
            configs=carry["configs"] + (admit & released).astype(jnp.float64),
            # the idle-waiting share of the same accumulation (ledger axis)
            idle_mj=carry["idle_mj"] + jnp.where(admit, idle_e, 0.0),
            lifetime=jnp.where(admit, completion, carry["lifetime"]),
            arrival=a_new,
        )
        return new, ()

    final, _ = jax.lax.scan(body, carry0, gaps)
    return {
        "energy_mj": final["energy"],
        "energy_smooth_mj": final["energy_smooth"],
        "n_items": final["n"],
        "releases": final["releases"],
        "configurations": final["configs"],
        "idle_energy_mj": final["idle_mj"],
        "lifetime_ms": final["lifetime"],
    }


def _rollout_batch(params, gaps, consts, smooth: bool):
    consts = {k: jnp.asarray(v, dtype=jnp.float64) for k, v in consts.items()}
    return jax.vmap(lambda g: _rollout_stream(params, g, consts, smooth))(gaps)


_rollout_jit = jax.jit(_rollout_batch, static_argnums=(3,))


def rollout(params, gaps, consts: dict, smooth: bool = False, jit: bool = True) -> dict:
    """Batched policy rollout.

    ``params`` — network pytree (:func:`repro.policy.net.init_mlp`);
    ``gaps`` — ``(n_streams, n_gaps)`` inter-arrival gaps (ms), e.g. from
    :meth:`repro.core.arrivals.ArrivalProcess.sample_gaps`;
    ``consts`` — :func:`make_consts` output.  Returns per-stream arrays:
    ``energy_mj``, ``energy_smooth_mj`` (== hard init energy unless
    ``smooth``), ``n_items``, ``releases``, ``configurations``,
    ``idle_energy_mj`` (the idle-waiting share of ``energy_mj`` — feed the
    output to :func:`repro.obs.ledger.ledger_from_rollout` for the full
    phase breakdown), ``lifetime_ms``, each ``(n_streams,)`` float64.
    """
    with enable_x64():
        gaps = jnp.asarray(gaps, dtype=jnp.float64)
        if gaps.ndim != 2:
            raise ValueError(f"gaps must be (n_streams, n_gaps), got {gaps.shape}")
        fn = _rollout_jit if jit else _rollout_batch
        return fn(params, gaps, consts, smooth)


def mean_energy_per_gap(params, gaps, consts, smooth: bool):
    """Training objective: mean accumulated energy per gap, in units of one
    reconfiguration (dimensionless, O(1) across items) — traced, so both
    ``jax.grad`` (smooth path) and ES perturbations run through it."""
    out = _rollout_batch(params, gaps, consts, smooth)
    total = out["energy_smooth_mj"] if smooth else out["energy_mj"]
    n_gaps = gaps.shape[1]
    return jnp.mean(total) / (n_gaps * consts["e_config"])
