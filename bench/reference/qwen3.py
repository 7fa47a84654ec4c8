"""Plain reference of a Qwen3 dense decoder, and the weights both sides use.

Follows the published Qwen3 architecture (``Qwen3ForCausalLM`` in Hugging
Face ``transformers``): RMSNorm before attention and MLP, RMSNorm of each
query and key head (``q_norm``/``k_norm``) before rotary embedding
(rotate-half form, ``rope_theta``), grouped-query attention with scale
``head_dim ** -0.5`` and a causal mask, a SiLU-gated MLP, a final RMSNorm and
the output head tied to the embedding.  Everything is float32 with
``Precision.HIGHEST`` matrix products, one layer at a time, with no cache:
the full forward pass over prompt and served tokens.

It imports nothing of the program.  The weights are made here, from the
seed, in the layout the served engine takes them in (one stack per layer
kind, ``periods/pos0/...``), and handed to both sides.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def shapes(c: dict) -> dict:
    """Leaf shapes of the weights, from the configuration's published keys."""
    L, d, f, V = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    hd, H, KV = c["head_dim"], c["num_attention_heads"], c["num_key_value_heads"]
    return {
        "embed": (V, d),
        "final_norm": (d,),
        "periods": {"pos0": {
            "ln1": (L, d),
            "ln2": (L, d),
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, KV * hd), "wv": (L, d, KV * hd),
                     "wo": (L, H * hd, d), "q_norm": (L, hd), "k_norm": (L, hd)},
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)},
        }},
    }


@partial(jax.jit, static_argnums=(0,))
def _init(spec: tuple, key):
    names, shape_list = spec
    keys = jax.random.split(key, len(shape_list))
    out = []
    for name, shape, k in zip(names, shape_list, keys):
        z = jax.random.normal(k, shape, jnp.float32)
        if name.endswith(("norm", "ln1", "ln2")):
            w = 1.0 + 0.1 * z
        elif name == "embed":
            w = 0.02 * z
        else:
            w = z * (1.0 / np.sqrt(shape[-2]))
        out.append(w.astype(jnp.bfloat16))
    return out


def init_weights(c: dict, seed31: int, device=None) -> dict:
    """Random bf16 weights, made on the device in one jitted call."""
    tree = shapes(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, int) for i in x))
    names = tuple(str(getattr(p[-1], "key", p[-1])) for p, _ in flat)
    spec = (names, tuple(s for _, s in flat))
    key = jax.random.key(seed31)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.tree_util.tree_unflatten(treedef, _init(spec, key))


# ---------------------------------------------------------------------------
# Stated storage precisions (the checkpoint) and the control's lower ones
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnums=(1, 2))
def group_roundtrip(w, levels: int, group: int):
    """Quantize each row's ``group``-column groups of the last axis to
    ``-levels..levels`` with scale ``max(absmax, 1e-12) / levels`` (rounded
    half to even), and back to the leaf's dtype."""
    shape = w.shape
    g = w.astype(jnp.float32).reshape(-1, shape[-1] // group, group)
    scale = jnp.maximum(jnp.max(jnp.abs(g), axis=-1, keepdims=True), 1e-12) / levels
    q = jnp.clip(jnp.round(g / scale), -levels, levels)
    return (q * scale).reshape(shape).astype(w.dtype)


def quantized(weights: dict, levels: int, group: int = 128) -> dict:
    """The weights as a group-quantized checkpoint stores them: every matrix
    (two or more axes, last axis a multiple of ``group``, at least 2^16
    elements); vectors stay exact."""
    def one(w):
        if w.ndim >= 2 and w.shape[-1] % group == 0 and w.size >= 1 << 16:
            return group_roundtrip(w, levels, group)
        return w
    return jax.tree.map(one, weights)


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    scale = amax / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(x, w, fp8: bool):
    if fp8:
        x = _fp8(x, -1)
    return jnp.matmul(x, w, precision=HI)


@partial(jax.jit, static_argnums=(3, 4))
def _layer(h, stack, i, consts: tuple, fp8: bool):
    H, KV, hd, eps, theta = consts
    p = {k: v[i].astype(jnp.float32) for k, v in stack.items()}
    if fp8:
        p = {**p, **{k: _fp8(p[k], -2) for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}}
    B, S, _ = h.shape
    pos = jnp.arange(S)
    x = _rms(h, p["ln1"], eps)
    q = _mm(x, p["wq"], fp8).reshape(B, S, H, hd)
    k = _mm(x, p["wk"], fp8).reshape(B, S, KV, hd)
    v = _mm(x, p["wv"], fp8).reshape(B, S, KV, hd)
    q = _rope(_rms(q, p["q_norm"], eps), pos, theta)
    k = _rope(_rms(k, p["k_norm"], eps), pos, theta)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * (hd ** -0.5)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    h = h + _mm(a.reshape(B, S, H * hd), p["wo"], fp8)
    x = _rms(h, p["ln2"], eps)
    g = jax.nn.silu(_mm(x, p["w_gate"], fp8)) * _mm(x, p["w_up"], fp8)
    return h + _mm(g, p["w_down"], fp8)


@partial(jax.jit, static_argnums=(4, 5))
def _head(h, final_norm, embed, at, eps: float, fp8: bool):
    x = _rms(h[:, at], final_norm.astype(jnp.float32), eps)        # (B, T, d)
    e = embed.astype(jnp.float32)
    if fp8:
        return jnp.einsum("btd,vd->btv", _fp8(x, -1), _fp8(e, -1), precision=HI)
    return jnp.einsum("btd,vd->btv", x, e, precision=HI)


def logits(weights: dict, c: dict, tokens, at, fp8: bool = False):
    """Float32 logits ``(B, len(at), V)`` at positions ``at`` of ``tokens``."""
    consts = (c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
              float(c["rms_norm_eps"]), float(c["rope_theta"]))
    h = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    stack = weights["periods"]["pos0"]
    flat = {"ln1": stack["ln1"], "ln2": stack["ln2"], **stack["attn"], **stack["mlp"]}
    for i in range(c["num_hidden_layers"]):
        h = _layer(h, flat, i, consts, fp8)
    return _head(h, weights["final_norm"], weights["embed"], jnp.asarray(at),
                 float(c["rms_norm_eps"]), fp8)


def served_gaps(weights: dict, c: dict, prompt: np.ndarray, served: np.ndarray,
                control: str | None = None) -> dict:
    """How far each served greedy token's reference logit lies below the
    reference's best at its position.

    ``prompt`` is ``(B, L)`` and ``served`` ``(B, T)``: token ``t`` of
    ``served`` was produced at position ``L - 1 + t``.  Returns the widest
    gap, and with ``control`` (``"fp8"``: float8 weights and activations, or
    ``"int4"``: weights quantized to 4 bits in groups of 128) the widest gap
    of the token the control puts first at each of those positions.
    """
    prompt = np.asarray(prompt)
    served = np.asarray(served)
    B, L = prompt.shape
    T = served.shape[1]
    V = c["vocab_size"]
    tokens = jnp.asarray(np.concatenate([prompt, served[:, : T - 1]], axis=1))
    at = np.arange(L - 1, L - 1 + T)
    ref = logits(weights, c, tokens, at)
    best = jnp.max(ref, axis=-1)
    valid = (served >= 0) & (served < V)
    got = jnp.take_along_axis(ref, jnp.asarray(np.clip(served, 0, V - 1))[..., None], -1)[..., 0]
    gap = np.where(valid, np.asarray(best - got), np.inf)
    out = {"max_gap": float(np.max(gap)), "n_tokens": int(gap.size)}
    if control is not None:
        if control == "fp8":
            low = logits(weights, c, tokens, at, fp8=True)
        elif control == "int4":
            low = logits(quantized(weights, 7), c, tokens, at)
        else:
            raise ValueError(control)
        top = jnp.argmax(low, axis=-1)
        cgap = best - jnp.take_along_axis(ref, top[..., None], -1)[..., 0]
        out["control_max_gap"] = float(jnp.max(cgap))
    return out
