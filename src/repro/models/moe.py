"""Mixture-of-Experts FFN with expert parallelism.

Three execution paths, one semantics (top-k routing, renormalized weights,
per-expert capacity with token dropping):

* **reference** (no mesh): dropless dense — every expert runs on every
  token, combined by routing weights.  Oracle for tests.
* **EP path** (E % tp == 0): shard_map dispatch.  Tokens sharded over
  (pod, data) × model; per-device capacity buffers; `all_to_all` over the
  model axis routes slots to expert owners; expert weights FSDP-gathered
  over (pod, data); `all_to_all` back; local combine.  This is the
  TPU-native expert-parallel pattern (GShard/MaxText lineage) — the
  collective cost is 2 × k·cf·T·d bytes of all-to-all per layer.
* **f-TP path** (E < tp, e.g. mixtral's 8 experts on a 16-wide model axis):
  experts replicated across the model axis, d_ff sharded; partial products
  `psum` over model.  No all-to-all; tokens stay sharded over (pod, data).

Routing ties between the paths are broken identically (stable argsort), so
with a non-dropping capacity factor the paths agree exactly.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed import sharding as shd
from repro.models.common import Spec


#: model-axis width of the production meshes (16×16 and 2×16×16); experts
#: shard over the model axis (EP) when divisible, else d_ff shards (f-TP).
EP_MODEL_AXIS = 16


def uses_ep(cfg: ArchConfig) -> bool:
    return cfg.num_experts % EP_MODEL_AXIS == 0


def moe_specs(cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    if uses_ep(cfg):
        # expert-parallel storage: E over model, d over (pod, data)
        return {
            "router": Spec((d, e), ("embed", None), scale=0.02),
            "w_gate": Spec((e, d, f), ("expert", "expert_in", None)),
            "w_up": Spec((e, d, f), ("expert", "expert_in", None)),
            "w_down": Spec((e, f, d), ("expert", None, "expert_in")),
        }
    # f-TP storage (e.g. mixtral's 8 experts < 16-wide model axis):
    # experts replicated over model, d_ff sharded over model
    return {
        "router": Spec((d, e), ("embed", None), scale=0.02),
        "w_gate": Spec((e, d, f), (None, "expert_in", "mlp")),
        "w_up": Spec((e, d, f), (None, "expert_in", "mlp")),
        "w_down": Spec((e, f, d), (None, "mlp", "expert_in")),
    }


def route(
    xt: jax.Array, router: jax.Array, k: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing.  xt (T, d) → weights (T, k) fp32 (renormalized),
    ids (T, k) int32, plus the aux load-balance loss."""
    logits = (xt.astype(jnp.float32)) @ router.astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # Switch-style aux loss: E · Σ_e f_e · p_e
    e = router.shape[-1]
    me = jnp.mean(probs, axis=0)                                     # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=1), axis=0
    )
    aux = e * jnp.sum(me * ce)
    return w, ids, aux


def _capacity(tokens: int, num_experts: int, k: int, cf: float) -> int:
    c = int(math.ceil(tokens * k * cf / num_experts))
    return max(8, ((c + 7) // 8) * 8)   # pad to 8 for TPU-friendly tiling


def _dispatch_indices(ids: jax.Array, num_experts: int, capacity: int):
    """Per-slot expert rank with capacity dropping.

    ids (T, k) → flat expert ids (T·k,), ranks (T·k,) where rank ≥ capacity
    means dropped.  Stable argsort ⇒ earlier tokens win slots (GShard
    semantics)."""
    tk = ids.size
    flat = ids.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(num_experts))       # (E,)
    rank_sorted = jnp.arange(tk) - start[sorted_e]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    return flat, rank


def _expert_ffn(xe: jax.Array, wg, wu, wd) -> jax.Array:
    """(E, C, d) × (E, d, f) → (E, C, d), SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum(
        "ecd,edf->ecf", xe, wu
    )
    return jnp.einsum("ecf,efd->ecd", h, wd)


# ---------------------------------------------------------------------------
# reference (dropless dense) — oracle & single-device path
# ---------------------------------------------------------------------------
def moe_reference(params: dict, x: jax.Array, cfg: ArchConfig) -> tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    k, e = cfg.experts_per_token, cfg.num_experts
    xt = x.reshape(-1, d)
    w, ids, aux = route(xt, params["router"], k)
    # all experts on all tokens (fine at test scale)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xt, params["w_gate"])) * jnp.einsum(
        "td,edf->etf", xt, params["w_up"]
    )
    ye = jnp.einsum("etf,efd->etd", h, params["w_down"])              # (E, T, d)
    sel = jnp.take_along_axis(
        jnp.moveaxis(ye, 0, 1), ids[..., None], axis=1
    )                                                                 # (T, k, d)
    y = jnp.einsum("tk,tkd->td", w, sel.astype(jnp.float32))
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# sharded paths
# ---------------------------------------------------------------------------
def _dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _tp_axis(mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None


def moe_block(
    params: dict,
    x: jax.Array,
    cfg: ArchConfig,
    capacity_factor: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Dispatching MoE FFN.  x (B, S, d) → (y, aux_loss)."""
    mesh = shd.current_mesh()
    cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor
    if mesh is None or math.prod(mesh.shape.values()) == 1:
        return moe_reference(params, x, cfg)

    b, s, d = x.shape
    dp = _dp_axes(mesh)
    tp = _tp_axis(mesh)
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1
    tp_size = mesh.shape[tp] if tp else 1

    e = cfg.num_experts
    ep = bool(tp) and uses_ep(cfg) and e % tp_size == 0
    batch_shard = dp if (dp and b % dp_size == 0) else ()
    # EP: tokens also shard over model (each column dispatches its slice).
    # f-TP: tokens replicate over model (each column holds an f-slice of
    # every expert and needs every local token; partials psum over model).
    seq_shard = tp if (ep and s % tp_size == 0) else None
    x_spec = P(batch_shard if batch_shard else None, seq_shard, None)

    if ep:
        impl = partial(_moe_ep_body, cfg=cfg, cf=cf, dp=dp, tp=tp)
        w_spec = P(tp, dp if dp else None, None)
        wd_spec = P(tp, None, dp if dp else None)
    elif tp and cfg.d_ff % tp_size == 0:
        impl = partial(_moe_ftp_body, cfg=cfg, cf=cf, dp=dp, tp=tp)
        w_spec = P(None, dp if dp else None, tp)
        wd_spec = P(None, tp, dp if dp else None)
    else:
        raise ValueError(
            f"{cfg.name}: no MoE sharding for E={e} on model={tp_size}"
        )

    out = jax.shard_map(
        impl,
        mesh=mesh,
        in_specs=(x_spec, P(), w_spec, w_spec, wd_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return out


def _gather_fsdp(w, dp, axis):
    for ax_name in dp[::-1]:
        w = jax.lax.all_gather(w, ax_name, axis=axis, tiled=True)
    return w


def _moe_ep_body(x, router, wg, wu, wd, *, cfg, cf, dp, tp):
    """Expert-parallel body (E % tp == 0).  Local shapes:
    x (B_l, S_l, d); wg/wu (E_l, d_l, f); wd (E_l, f, d_l)."""
    bl, sl, d = x.shape
    k, e = cfg.experts_per_token, cfg.num_experts
    tp_size = jax.lax.psum(1, tp)
    t = bl * sl

    xt = x.reshape(t, d)
    w, ids, aux = route(xt, router, k)
    cap = _capacity(t, e, k, cf)

    flat, rank = _dispatch_indices(ids, e, cap)
    x_rep = jnp.repeat(xt, k, axis=0)                                  # (T·k, d)
    rank_c = jnp.where(rank < cap, rank, cap)                          # cap ⇒ drop
    xbuf = jnp.zeros((e, cap, d), x.dtype).at[flat, rank_c].set(
        x_rep, mode="drop"
    )

    # route slots to expert owners over the model axis: split the expert dim
    # (tp blocks of E_l), receive tp slot-blocks concatenated on the slot dim
    xe = jax.lax.all_to_all(
        xbuf, tp, split_axis=0, concat_axis=1, tiled=True
    )                                                                  # (E_l, tp·cap, d)

    wg_f = _gather_fsdp(wg, dp, axis=1)
    wu_f = _gather_fsdp(wu, dp, axis=1)
    wd_f = _gather_fsdp(wd, dp, axis=2)
    ye = _expert_ffn(xe, wg_f, wu_f, wd_f)                             # (E_l, tp·cap, d)

    # return slots to their source columns (inverse exchange)
    yb = jax.lax.all_to_all(
        ye, tp, split_axis=1, concat_axis=0, tiled=True
    )                                                                  # (E, cap, d)

    got = yb[flat, rank_c % cap]                                       # (T·k, d)
    got = jnp.where((rank < cap)[:, None], got, 0)
    y = jnp.einsum(
        "tk,tkd->td", w, got.reshape(t, k, d).astype(jnp.float32)
    ).astype(x.dtype)
    aux = jax.lax.pmean(aux, tp)
    if dp:
        for a in dp:
            aux = jax.lax.pmean(aux, a)
    return y.reshape(bl, sl, d), aux


def _moe_ftp_body(x, router, wg, wu, wd, *, cfg, cf, dp, tp):
    """f-sharded tensor-parallel body (E < tp; experts replicated on model,
    d_ff sharded, psum over model).  Local: x (B_l, S, d) — tokens are NOT
    sharded over model here; wg/wu (E, d_l, f_l); wd (E, f_l, d_l)."""
    bl, sl, d = x.shape
    k, e = cfg.experts_per_token, cfg.num_experts
    t = bl * sl

    xt = x.reshape(t, d)
    w, ids, aux = route(xt, router, k)
    cap = _capacity(t, e, k, cf)

    flat, rank = _dispatch_indices(ids, e, cap)
    x_rep = jnp.repeat(xt, k, axis=0)
    rank_c = jnp.where(rank < cap, rank, cap)
    xbuf = jnp.zeros((e, cap, d), x.dtype).at[flat, rank_c].set(x_rep, mode="drop")

    wg_f = _gather_fsdp(wg, dp, axis=1)
    wu_f = _gather_fsdp(wu, dp, axis=1)
    wd_f = _gather_fsdp(wd, dp, axis=2)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xbuf, wg_f)) * jnp.einsum(
        "ecd,edf->ecf", xbuf, wu_f
    )
    ye = jnp.einsum("ecf,efd->ecd", h, wd_f)                           # partial over f
    ye = jax.lax.psum(ye, tp)

    got = ye[flat, rank_c % cap]
    got = jnp.where((rank < cap)[:, None], got, 0)
    y = jnp.einsum(
        "tk,tkd->td", w, got.reshape(t, k, d).astype(jnp.float32)
    ).astype(x.dtype)
    if dp:
        for a in dp:
            aux = jax.lax.pmean(aux, a)
    return y.reshape(bl, sl, d), aux
