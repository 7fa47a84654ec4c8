"""Pallas TPU kernel: blocked int8 → bf16 dequantize (weight-load path).

Bring-up ("configuration phase") reads int8 weights + scales from HBM and
writes bf16 — the kernel tiles (Br × C) row blocks through VMEM so the
dequant runs at HBM streaming bandwidth; column groups of ``group`` share
one fp32 scale.

A block spans whole rows when 32 of them fit the block budget: the scales
block is then ``(Br, C/group)``, the full scale width, which the TPU
lowering accepts for any group count.  Wider leaves (an untied
``(d_model, vocab)`` head) are also tiled over columns, ``128 * group`` at a
time, so the scales block is a whole number of 128-lane tiles (a partial
lane block must be a multiple of 128).  Every group is scaled by a static,
group-aligned lane slice times one broadcast scale column, so no lane
repeat is needed.  The grid is ``cdiv(R, Br) x cdiv(C, Bc)``: a ragged last
block (e.g. the 151,936-row qwen3 embedding) is padded on read and masked
on write.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Elements of q per row block — int8 in, bf16/f32 out, double-buffered,
#: plus the f32 product stays well inside v5e's 16 MiB scoped VMEM.
_BLOCK_ELEMS = 1 << 19


def _dequant_kernel(q_ref, s_ref, o_ref, *, group: int):
    for k in range(q_ref.shape[1] // group):
        cols = slice(k * group, (k + 1) * group)
        q = q_ref[:, cols].astype(jnp.float32)            # (Br, group)
        o_ref[:, cols] = (q * s_ref[:, k : k + 1]).astype(o_ref.dtype)


def _block_shape(
    r: int, c: int, group: int, block_r: int | None, block_c: int | None
) -> tuple[int, int]:
    lanes = 128 * group         # columns whose scales fill one 128-lane tile
    if block_c is None:
        fits = 32 * c <= _BLOCK_ELEMS
        block_c = c if fits else max(lanes, _BLOCK_ELEMS // 32 // lanes * lanes)
    assert block_c >= c or block_c % lanes == 0, (
        f"a column block narrower than the leaf must be a multiple of "
        f"128 * group = {lanes} columns, got {block_c}"
    )
    block_c = min(block_c, c)
    if block_r is None:
        block_r = max(32, (_BLOCK_ELEMS // block_c) // 32 * 32)
    return min(block_r, r), block_c


def dequantize_blocked(
    q: jax.Array,          # int8 (R, C)
    scales: jax.Array,     # fp32 (R, C/group)
    *,
    group: int = 128,
    block_r: int | None = None,
    block_c: int | None = None,
    dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    r, c = q.shape
    assert c % group == 0 and scales.shape == (r, c // group), (q.shape, scales.shape, group)
    br, bc = _block_shape(r, c, group, block_r, block_c)

    kernel = functools.partial(_dequant_kernel, group=group)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc // group), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), dtype),
        interpret=interpret,
    )(q, scales)
