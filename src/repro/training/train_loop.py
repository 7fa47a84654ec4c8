"""Train-step builder: microbatched grad accumulation, AdamW, optional
int8 cross-pod gradient compression, donation-friendly TrainState.

The returned ``train_step(state, batch, lr)`` is pure and pjit-compatible;
``launch/train.py`` wires it to the mesh/shardings and the data pipeline,
``launch/dryrun.py`` lowers it abstractly for every (arch × shape) cell.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.configs.perf import BASELINE, PerfConfig
from repro.models import model_zoo as zoo
from repro.optim.adamw import AdamW, AdamWState, adamw
from repro.optim import grad_compress


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    compress_err: Optional[grad_compress.CompressState]


@dataclasses.dataclass(frozen=True)
class TrainStepFns:
    init_state: Callable[[Any], TrainState]
    train_step: Callable  # (state, batch, lr) -> (state, metrics)


def _microbatch_grads(loss_fn, params, batch, num_micro: int):
    """Grad accumulation over microbatches via lax.scan (fp32 accumulators).

    Splitting is along the leading (batch) axis of every batch leaf."""
    if num_micro <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, jax.tree.map(lambda g: g.astype(jnp.float32), grads)

    def resplit(x):
        b = x.shape[0]
        assert b % num_micro == 0, (b, num_micro)
        return x.reshape(num_micro, b // num_micro, *x.shape[1:])

    mb = jax.tree.map(resplit, batch)

    def body(carry, micro):
        loss_acc, g_acc = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, micro)
        g_acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), g_acc, grads
        )
        return (loss_acc + loss, g_acc), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, g_sum), _ = jax.lax.scan(body, (jnp.zeros(()), g0), mb)
    inv = 1.0 / num_micro
    return loss_sum * inv, jax.tree.map(lambda g: g * inv, g_sum)


def make_train_step(
    cfg: ArchConfig,
    perf: PerfConfig = BASELINE,
    optimizer: AdamW | None = None,
    mesh=None,
) -> TrainStepFns:
    moment_dtype = (
        jnp.bfloat16 if perf.optimizer_moment_dtype == "bfloat16" else jnp.float32
    )
    opt = optimizer or adamw(moment_dtype=moment_dtype)
    loss_fn = lambda p, b: zoo.loss_fn(p, b, cfg, perf)
    use_compress = (
        perf.grad_compress_pod
        and mesh is not None
        and "pod" in getattr(mesh, "axis_names", ())
    )

    def init_state(params) -> TrainState:
        err = None
        if use_compress:
            err = grad_compress.init_error(params)
        return TrainState(params=params, opt=opt.init(params), compress_err=err)

    # gather-weights-once: re-constrain params to drop the FSDP (pod/data)
    # axes BEFORE the microbatch loop, so XLA all-gathers each weight one
    # time per step instead of once per microbatch (and per remat replay);
    # the constraint's transpose makes the gradient arrive as a single
    # reduce per step.  Trades HBM (params live gathered over the fsdp
    # axes) for ICI — only sensible when params/model_shard fits.
    gather_shardings = None
    if perf.gather_weights_once and mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def _strip(p):
            out = []
            for ax in p:
                if ax is None:
                    out.append(None)
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                keep = tuple(a for a in axes if a not in ("pod", "data"))
                out.append(keep[0] if len(keep) == 1 else (keep or None))
            return P(*out)

        pspecs = zoo.param_pspecs(cfg, mesh)
        gather_shardings = jax.tree.map(
            lambda p: NamedSharding(mesh, _strip(p)),
            pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def _staged(params):
        if gather_shardings is None:
            return params
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            params,
            gather_shardings,
        )

    if not use_compress:

        def train_step(state: TrainState, batch, lr):
            loss, grads = _microbatch_grads(
                loss_fn, _staged(state.params), batch, perf.num_microbatches
            )
            new_p, new_opt, gnorm = opt.update(grads, state.opt, state.params, lr)
            metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
            return TrainState(new_p, new_opt, None), metrics

        return TrainStepFns(init_state=init_state, train_step=train_step)

    # ---- compressed cross-pod path ------------------------------------
    # Hierarchical ZeRO: params replicated across pods (sharded over
    # data×model within each pod — rules drop "pod" from the FSDP axes),
    # batch split over pods; per-pod grads are int8-compressed with error
    # feedback and mean-reduced over the pod axis (optim/grad_compress.py).
    def pod_body(params, opt_state, err, batch, lr):
        loss, grads = _microbatch_grads(loss_fn, params, batch, perf.num_microbatches)
        grads, new_err = grad_compress.compress_psum(grads, err, "pod")
        loss = jax.lax.pmean(loss, "pod")
        new_p, new_opt, gnorm = opt.update(grads, opt_state, params, lr)
        return new_p, new_opt, new_err, {"loss": loss, "grad_norm": gnorm, "lr": lr}

    from jax.sharding import PartitionSpec as P

    def train_step(state: TrainState, batch, lr):
        rep = jax.tree.map(lambda _: P(), state.params)
        rep_opt = jax.tree.map(lambda _: P(), state.opt)
        rep_err = jax.tree.map(lambda _: P(), state.compress_err)
        batch_spec = jax.tree.map(lambda _: P("pod"), batch)
        new_p, new_opt, new_err, metrics = jax.shard_map(
            partial(pod_body),
            mesh=mesh,
            in_specs=(rep, rep_opt, rep_err, batch_spec, P()),
            out_specs=(rep, rep_opt, rep_err, jax.tree.map(lambda _: P(), {
                "loss": 0, "grad_norm": 0, "lr": 0,
            })),
            axis_names=frozenset({"pod"}),
            check_vma=False,
        )(state.params, state.opt, state.compress_err, batch, lr)
        return TrainState(new_p, new_opt, new_err), metrics

    return TrainStepFns(init_state=init_state, train_step=train_step)
