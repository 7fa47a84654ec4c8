"""Two-phase training of the timeout policy, each phase one jitted scan.

Phase 1 — **backprop through the smooth relaxation**: ``jax.value_and_grad``
of the smooth rollout energy (:func:`repro.policy.rollout.mean_energy_per_gap`
with ``smooth=True``), stepped by :func:`repro.optim.adamw.adamw` inside a
single cached jitted ``lax.scan`` over optimisation steps (the
``optimize/descent.py`` pattern: compile once per shape, reuse across
restarts/items).

Phase 2 — **antithetic evolution strategies on the hard objective**: the
smooth relaxation is biased near the release boundary, and the *routed*
discrete dynamics (admission, inline reconfig delay) are not differentiable
at all, so the finisher estimates

    ∇f(θ) ≈ 1/(P·σ) · Σ_i (f(θ + σ·ε_i) − f(θ − σ·ε_i))/2 · ε_i

with mirrored Gaussian perturbations over seed-vmapped hard rollouts —
every population member's whole fleet of streams evaluated in one vmap,
every ES step one scan iteration of the same jitted loop.

Both phases start from a zero-output network, i.e. from the ski-rental
hybrid itself: training can only improve on the 2-competitive baseline
(``history["baseline_hard"]`` pins the starting cost for the benchmark).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64
from jax.flatten_util import ravel_pytree

from repro.core.arrivals import (
    ArrivalProcess,
    DeterministicArrivals,
    DiurnalArrivals,
    FlashCrowdArrivals,
    MMPPArrivals,
    PoissonArrivals,
)
from repro.core.phases import WorkloadItem
from repro.core.strategies import IdlePowerMethod
from repro.optim.adamw import adamw
from repro.policy import net as N
from repro.policy.rollout import make_consts, mean_energy_per_gap

# Plain AdamW on raw float64 parameters: no weight decay (the zero-init
# output layer IS the ski-rental prior — decay would drag the policy back
# to it), full-precision moments, norm clip for the occasional cliff the
# hard objective's admission boundary produces under ES noise.
_OPT = adamw(weight_decay=0.0, clip_norm=10.0, moment_dtype=jnp.float64)


def _bp_run(params, gaps, consts, lr, steps: int):
    opt_state = _OPT.init(params)

    def body(carry, _):
        p, s = carry
        loss, g = jax.value_and_grad(
            lambda q: mean_energy_per_gap(q, gaps, consts, True)
        )(p)
        p2, s2, _ = _OPT.update(g, s, p, lr)
        return (p2, s2), loss

    (pf, _), losses = jax.lax.scan(body, (params, opt_state), None, length=steps)
    return pf, losses


_bp_jit = jax.jit(_bp_run, static_argnums=(4,))


def _es_run(params, gaps, consts, key, lr, sigma, steps: int, half_pop: int):
    opt_state = _OPT.init(params)
    flat0, unravel = ravel_pytree(params)

    def obj(flat):
        return mean_energy_per_gap(unravel(flat), gaps, consts, False)

    def body(carry, k):
        flat, s = carry
        eps = jax.random.normal(k, (half_pop, flat.shape[0]), dtype=flat.dtype)
        f_plus = jax.vmap(lambda e: obj(flat + sigma * e))(eps)
        f_minus = jax.vmap(lambda e: obj(flat - sigma * e))(eps)
        gflat = jnp.mean((f_plus - f_minus)[:, None] * eps, axis=0) / (2.0 * sigma)
        p2, s2, _ = _OPT.update(unravel(gflat), s, unravel(flat), lr)
        flat2, _ = ravel_pytree(p2)
        return (flat2, s2), 0.5 * (jnp.mean(f_plus) + jnp.mean(f_minus))

    keys = jax.random.split(key, steps)
    (flatf, _), losses = jax.lax.scan(body, (flat0, opt_state), keys)
    return unravel(flatf), losses


_es_jit = jax.jit(_es_run, static_argnums=(6, 7))


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    """Knobs of one training run (defaults sized for CPU minutes)."""

    hidden: tuple = (24, 24)
    n_streams: int = 24          # training streams (mixture, round-robin)
    n_gaps: int = 384            # gaps per stream
    bp_steps: int = 300          # phase-1 optimisation steps
    bp_lr: float = 0.02
    es_steps: int = 120          # phase-2 optimisation steps
    es_lr: float = 0.01
    es_pop: int = 16             # perturbation pairs = es_pop // 2
    es_sigma: float = 0.05
    seed: int = 0

    @staticmethod
    def smoke() -> "TrainSettings":
        """CI-sized run: seconds on CPU, still clearly beats the hybrid."""
        return TrainSettings(
            hidden=(16, 16), n_streams=16, n_gaps=256,
            bp_steps=150, es_steps=40, es_pop=8,
        )


def training_processes(t_be_ms: float) -> list:
    """The regime mixture the policy trains on, scaled by the item's T*_be.

    Covers both statics' home turf (deterministic / Poisson well below and
    above the crossover — where the trained policy must not regress) and
    the three regime-switching shapes where the hybrid is beatable.
    """
    t = t_be_ms
    return [
        DeterministicArrivals(0.08 * t),
        DeterministicArrivals(0.6 * t),
        DeterministicArrivals(3.0 * t),
        PoissonArrivals(0.25 * t),
        PoissonArrivals(6.0 * t),
        MMPPArrivals(
            burst_ms=0.04 * t, quiet_ms=8.0 * t,
            mean_burst_len=12.0, mean_quiet_len=3.0,
        ),
        FlashCrowdArrivals(
            quiet_ms=6.0 * t, flash_gap_ms=0.02 * t,
            flash_len=32, flash_every=4.0,
        ),
        DiurnalArrivals(
            mean_ms=2.0 * t, day_ms=400.0 * t, amplitude=0.75,
            burst_ms=0.04 * t, mean_burst_len=10.0, mean_quiet_len=6.0,
        ),
    ]


def sample_training_gaps(
    processes: Sequence[ArrivalProcess],
    n_streams: int,
    n_gaps: int,
    seed: int,
) -> jnp.ndarray:
    """``(n_streams, n_gaps)`` float64 gaps, processes round-robined across
    rows so every compile of the training loop sees the full mixture."""
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, len(processes))
    per = int(math.ceil(n_streams / len(processes)))
    with enable_x64():
        blocks = [
            p.sample_gaps(k, per, n_gaps) for p, k in zip(processes, keys)
        ]
        # interleave: row i is process (i mod P), stream (i div P)
        stacked = jnp.stack(blocks, axis=1).reshape(-1, n_gaps)
        return stacked[:n_streams]


@dataclasses.dataclass
class TrainedPolicy:
    """A trained timeout policy: parameters + the physics it was trained for.

    ``params`` is float64 numpy (JSON-serialisable via :meth:`to_json_dict`);
    ``consts`` the :func:`repro.policy.rollout.make_consts` dict (with the
    training budget, normally ``inf``); ``history`` the loss curves and the
    ski-rental baseline cost; ``meta`` the settings/method provenance.
    """

    params: list
    consts: dict
    history: dict
    meta: dict

    @property
    def t_be_ms(self) -> float:
        return float(self.consts["t_be"])

    def to_json_dict(self) -> dict:
        return {
            "params": [
                {"w": layer["w"].tolist(), "b": layer["b"].tolist()}
                for layer in self.params
            ],
            "consts": {
                k: (None if math.isinf(v) else float(v))
                for k, v in self.consts.items()
            },
            "history": {
                k: (list(map(float, v)) if isinstance(v, (list, np.ndarray)) else float(v))
                for k, v in self.history.items()
            },
            "meta": self.meta,
        }

    @staticmethod
    def from_json_dict(d: dict) -> "TrainedPolicy":
        params = [
            {"w": np.asarray(layer["w"], dtype=np.float64),
             "b": np.asarray(layer["b"], dtype=np.float64)}
            for layer in d["params"]
        ]
        consts = {
            k: (math.inf if v is None else float(v))
            for k, v in d["consts"].items()
        }
        return TrainedPolicy(
            params=params, consts=consts,
            history=dict(d.get("history", {})), meta=dict(d.get("meta", {})),
        )


def untrained_policy(
    item: WorkloadItem,
    method: IdlePowerMethod = IdlePowerMethod.BASELINE,
    powerup_overhead_mj: float = 0.0,
    hidden: tuple = (8,),
) -> TrainedPolicy:
    """The zero-output network: exactly the ski-rental hybrid (timeout
    T*_be for every feature vector).  No training, no RNG — the documented
    stationary-limit anchor and the cheapest drop-in for tests."""
    consts = make_consts(item, method, powerup_overhead_mj)
    with enable_x64():
        params = N.init_mlp(jax.random.PRNGKey(0), hidden=hidden)
        # zero the hidden layers too: the output is zero either way (the
        # last layer is zero-init), this just makes the anchor exact-by-
        # construction rather than exact-by-initialisation-convention
        params = jax.tree.map(lambda a: jnp.zeros_like(a), params)
    return TrainedPolicy(
        params=N.params_to_numpy(params),
        consts=consts,
        history={"baseline_hard": float("nan"), "final_hard": float("nan")},
        meta={
            "trained": False, "hidden": list(hidden),
            "method": method.name, "powerup_overhead_mj": powerup_overhead_mj,
        },
    )


def train_policy(
    item: WorkloadItem,
    method: IdlePowerMethod = IdlePowerMethod.BASELINE,
    powerup_overhead_mj: float = 0.0,
    settings: Optional[TrainSettings] = None,
    processes: Optional[Sequence[ArrivalProcess]] = None,
) -> TrainedPolicy:
    """Run both phases and return the trained policy.

    Deterministic in ``settings.seed``; ``processes`` overrides the default
    :func:`training_processes` mixture (e.g. to specialise on a tenant's
    recorded traces).
    """
    st = settings or TrainSettings()
    consts = make_consts(item, method, powerup_overhead_mj)
    procs = list(processes) if processes is not None else training_processes(consts["t_be"])

    with enable_x64():
        gaps = sample_training_gaps(procs, st.n_streams, st.n_gaps, st.seed)
        cj = {k: jnp.asarray(v, dtype=jnp.float64) for k, v in consts.items()}
        params = N.init_mlp(jax.random.PRNGKey(st.seed), hidden=st.hidden)

        baseline_hard = float(mean_energy_per_gap(params, gaps, cj, False))

        bp_losses = jnp.zeros((0,))
        if st.bp_steps > 0:
            params, bp_losses = _bp_jit(
                params, gaps, cj, jnp.float64(st.bp_lr), st.bp_steps
            )
        es_losses = jnp.zeros((0,))
        if st.es_steps > 0:
            params, es_losses = _es_jit(
                params, gaps, cj,
                jax.random.PRNGKey(st.seed + 1),
                jnp.float64(st.es_lr), jnp.float64(st.es_sigma),
                st.es_steps, max(st.es_pop // 2, 1),
            )
        final_hard = float(mean_energy_per_gap(params, gaps, cj, False))

    return TrainedPolicy(
        params=N.params_to_numpy(params),
        consts=consts,
        history={
            "bp_loss": np.asarray(bp_losses, dtype=np.float64),
            "es_loss": np.asarray(es_losses, dtype=np.float64),
            "baseline_hard": baseline_hard,
            "final_hard": final_hard,
        },
        meta={
            "trained": True,
            "hidden": list(st.hidden),
            "method": method.name,
            "powerup_overhead_mj": powerup_overhead_mj,
            "n_streams": st.n_streams, "n_gaps": st.n_gaps,
            "bp_steps": st.bp_steps, "es_steps": st.es_steps,
            "es_pop": st.es_pop, "es_sigma": st.es_sigma,
            "seed": st.seed,
            "processes": [p.name for p in procs],
        },
    )
