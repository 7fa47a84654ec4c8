"""Pallas TPU kernel for the chunked SSD scan (Mamba-2).

TPU-native design (DESIGN.md §7): the sequential selective scan of Mamba-1
does not map to the MXU; SSD's chunked dual form does.  Per grid step
(b, h, c) the kernel computes, entirely in VMEM with (Q×Q) and (Q×N)/(Q×P)
MXU matmuls (Q = chunk = 128 aligned):

    intra-chunk:  Y_d = ((C·Bᵀ) ⊙ L) · X̄           (Q,Q)·(Q,P)
    chunk state:  S_c = Bᵀ · (decay_to_end ⊙ X̄)     (N,Q)·(Q,P)
    inter-chunk:  Y_o = (C · H) ⊙ exp(cs)           (Q,N)·(N,P)
    recurrence:   H  ← exp(total) · H + S_c         (fp32 scratch, carried
                                                     across the c grid dim)

The head axis is embarrassingly parallel (B/C shared per group via the
index map), matching the model-axis sharding of heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
    y_ref, hout_ref,
    state_ref,                        # scratch (P, N) fp32
    *,
    n_chunks: int,
    q: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = h0_ref[...].astype(jnp.float32)

    x = x_ref[...].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[...].astype(jnp.float32)         # (Q, 1)
    a = a_ref[...].astype(jnp.float32)           # (1, 1) scalar decay rate
    bm = b_ref[...].astype(jnp.float32)          # (Q, N)
    cm = c_ref[...].astype(jnp.float32)          # (Q, N)
    dsk = d_ref[...].astype(jnp.float32)         # (1, 1) scalar skip

    xbar = x * dt                                # dt-scaled input
    # within-chunk prefix sums without cumsum (the TPU lowering has none):
    # a masked column sum — the product with a lower-triangular ones
    # matrix — gives cs as a row, and a diagonal pick turns it into a column
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = rows >= cols
    la = jnp.broadcast_to(a * dt, (q, q))        # la[t, j] = log-decay of step t
    cs_row = jnp.sum(jnp.where(rows <= cols, la, 0.0), axis=0, keepdims=True)  # (1, Q)
    cs = jnp.sum(
        jnp.where(rows == cols, jnp.broadcast_to(cs_row, (q, q)), 0.0),
        axis=1, keepdims=True,
    )                                            # (Q, 1), the same values
    total = cs_row[:, q - 1 :]                   # (1, 1)

    # intra-chunk: L[i,j] = exp(cs_i − cs_j) for i ≥ j
    lmat = jnp.where(tril, jnp.exp(cs - cs_row), 0.0)
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                            # (Q, Q)
    y = jax.lax.dot_general(
        scores * lmat, xbar, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (Q, P)

    # inter-chunk: contribution of the entering state
    h = state_ref[...]                           # (P, N)
    y += jnp.exp(cs) * jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    # state update: H ← exp(total)·H + Σ_j exp(total − cs_j)·x̄_j ⊗ B_j
    decay_to_end = jnp.exp(total - cs)           # (Q, 1)
    s_c = jax.lax.dot_general(
        xbar * decay_to_end, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (P, N)
    state_ref[...] = jnp.exp(total) * h + s_c

    y_ref[...] = (y + dsk * x).astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        hout_ref[...] = state_ref[...]


def ssd_pallas(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)
    a: jax.Array,      # (H,)
    b_mat: jax.Array,  # (B, S, G, N)
    c_mat: jax.Array,  # (B, S, G, N)
    d_vec: jax.Array,  # (H,)
    *,
    chunk: int = 128,
    init_state: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    hpg = h // g

    if init_state is None:
        init_state = jnp.zeros((bsz, h, p, n), jnp.float32)

    # layouts: (B, H, NC, Q, ·)
    xt = jnp.moveaxis(x, 2, 1).reshape(bsz, h, nc, chunk, p)
    dtt = jnp.moveaxis(dt, 2, 1).reshape(bsz, h, nc, chunk, 1)
    bt = jnp.moveaxis(b_mat, 2, 1).reshape(bsz, g, nc, chunk, n)
    ct = jnp.moveaxis(c_mat, 2, 1).reshape(bsz, g, nc, chunk, n)
    a2 = a.reshape(h, 1, 1)
    d2 = d_vec.reshape(h, 1, 1)

    kernel = functools.partial(_ssd_kernel, n_chunks=nc, q=chunk)
    y, hout = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((None, None, None, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((None, None, None, chunk, 1), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda ib, ih, ic: (ih, 0, 0)),
            pl.BlockSpec(
                (None, None, None, chunk, n), lambda ib, ih, ic, _hpg=hpg: (ib, ih // _hpg, ic, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, None, chunk, n), lambda ib, ih, ic, _hpg=hpg: (ib, ih // _hpg, ic, 0, 0)
            ),
            pl.BlockSpec((None, 1, 1), lambda ib, ih, ic: (ih, 0, 0)),
            pl.BlockSpec((None, None, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((None, None, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, nc, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, a2, bt, ct, d2, init_state)

    y = jnp.moveaxis(y.reshape(bsz, h, s, p), 1, 2)
    return y, hout
