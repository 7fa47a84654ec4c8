"""Dequant Pallas kernel vs oracle + quantization round-trip properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.dequant.kernel import dequantize_blocked
from repro.kernels.dequant.ref import (
    dequantize_blocked_reference,
    quantize_blocked,
)


@pytest.mark.parametrize(
    "r,c,group",
    [(256, 1024, 128), (128, 512, 128), (64, 256, 64), (100, 384, 128)],  # last: ragged rows
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_reference(r, c, group, dtype):
    w = jax.random.normal(jax.random.PRNGKey(0), (r, c))
    q, s = quantize_blocked(w, group=group)
    ref = dequantize_blocked_reference(q, s, group=group, dtype=dtype)
    out = dequantize_blocked(
        q, s, group=group, dtype=dtype, interpret=True, block_r=64
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("c", [2048, 2560])  # last: a ragged column block
def test_kernel_column_blocks_match_reference(c):
    """Leaves too wide for whole-row blocks are tiled over columns too, in
    multiples of 128 * group (here group 8, so 1024 columns)."""
    w = jax.random.normal(jax.random.PRNGKey(1), (100, c))  # ragged rows too
    q, s = quantize_blocked(w, group=8)
    ref = dequantize_blocked_reference(q, s, group=8, dtype=jnp.bfloat16)
    out = dequantize_blocked(q, s, group=8, interpret=True, block_r=32, block_c=1024)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_block_shape_tiles_columns_only_when_rows_do_not_fit():
    from repro.kernels.dequant.kernel import _block_shape

    assert _block_shape(151936, 2048, 128, None, None) == (256, 2048)
    assert _block_shape(5120, 151936, 128, None, None) == (32, 16384)
    with pytest.raises(AssertionError, match="multiple of 128"):
        _block_shape(64, 4096, 8, None, 1000)


@settings(max_examples=15, deadline=None)
@given(
    r=st.sampled_from([32, 64]),
    groups=st.integers(1, 4),
    scale=st.floats(1e-3, 1e3),
    seed=st.integers(0, 2**31 - 1),
)
def test_round_trip_error_bound(r, groups, scale, seed):
    """|w − dequant(quant(w))| ≤ scale_per_group / 2 element-wise (half-ULP
    of the int8 grid) — the compression is lossy but bounded."""
    group = 128
    w = jax.random.normal(jax.random.PRNGKey(seed), (r, groups * group)) * scale
    q, s = quantize_blocked(w, group=group)
    back = dequantize_blocked_reference(q, s, group=group, dtype=jnp.float32)
    err = jnp.abs(w - back)
    # half-ULP of the int8 grid, with fp32 division-rounding allowance
    bound = jnp.repeat(s, group, axis=1) * 0.5 * (1 + 1e-4) + 1e-9
    assert bool(jnp.all(err <= bound))


def test_quantize_preserves_zero_and_extremes():
    w = jnp.array([[0.0] * 64 + [1.0] * 32 + [-1.0] * 32], jnp.float32)
    q, s = quantize_blocked(w, group=128)
    back = dequantize_blocked_reference(q, s, group=128, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(back[0, :64]), 0.0)
    np.testing.assert_allclose(np.asarray(back[0, 64:]), np.asarray(w[0, 64:]), rtol=1e-2)
