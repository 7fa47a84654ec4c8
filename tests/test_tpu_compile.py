"""Ahead-of-time compiles for a described TPU v5e chip (no chip attached).

Each test lowers and compiles one main-path kernel at its real widths for
one chip of a ``v5e:2x2`` topology, so what the TPU compiler refuses
(unaligned blocks, unsupported ops, VMEM overruns) fails here instead of on
the chip.  Nothing runs: these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for the described chip must not be cached: it cannot be
    # read back without the chip
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("s", [32, 2048])
def test_flash_attention_qwen3_widths(one_chip, s):
    from repro.kernels.flash_attention.kernel import flash_attention

    b, h, kvh, d = 2, 16, 8, 128
    q = _sds((b, s, h, d), jnp.bfloat16, one_chip)
    kv = _sds((b, s, kvh, d), jnp.bfloat16, one_chip)
    compiled = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True), q, kv, kv)
    assert _has_kernel(compiled)


@pytest.mark.parametrize(
    "rows,cols",
    [
        (151936, 2048),      # qwen3-1.7b tied embedding (rows not a block multiple)
        (28 * 2048, 6144),   # stacked w_gate / w_up, reshaped as the serializer does
        (5120, 151936),      # an untied (d_model, vocab) head: column blocks too
    ],
)
def test_dequant_qwen3_leaves(one_chip, rows, cols):
    from repro.kernels.dequant.kernel import dequantize_blocked

    q = _sds((rows, cols), jnp.int8, one_chip)
    s = _sds((rows, cols // 128), jnp.float32, one_chip)
    compiled = _compile(lambda q, s: dequantize_blocked(q, s, group=128), q, s)
    assert _has_kernel(compiled)


def test_lstm_paper_size(one_chip):
    from repro.configs.paper_lstm import full
    from repro.kernels.lstm.kernel import lstm_pallas

    cfg = full()
    b, hdim = 4, cfg.hidden_size
    args = (
        _sds((b, cfg.seq_len, cfg.input_dim), jnp.float32, one_chip),
        _sds((cfg.input_dim, 4 * hdim), jnp.float32, one_chip),
        _sds((hdim, 4 * hdim), jnp.float32, one_chip),
        _sds((4 * hdim,), jnp.float32, one_chip),
    )
    compiled = _compile(lstm_pallas, *args)
    assert _has_kernel(compiled)


def test_ssd_mamba2_370m_widths(one_chip):
    from repro.kernels.ssd.kernel import ssd_pallas

    b, s, h, p, g, n = 1, 512, 32, 64, 1, 128
    args = (
        _sds((b, s, h, p), jnp.bfloat16, one_chip),
        _sds((b, s, h), jnp.float32, one_chip),
        _sds((h,), jnp.float32, one_chip),
        _sds((b, s, g, n), jnp.bfloat16, one_chip),
        _sds((b, s, g, n), jnp.bfloat16, one_chip),
        _sds((h,), jnp.float32, one_chip),
    )
    compiled = _compile(lambda *a: ssd_pallas(*a, chunk=128), *args)
    assert _has_kernel(compiled)


def test_periodic_scan_million_devices(one_chip):
    """The fleet's periodic scan under x64 at 10^6 devices."""
    from repro.core.phases import paper_lstm_item
    from repro.fleet import uniform_fleet
    from repro.fleet.step import _periodic_scan

    with jax.enable_x64():
        params = uniform_fleet(
            1_000_000, item=paper_lstm_item(),
            strategies=("on_off", "idle_waiting", "adaptive"),
        )
        shapes = jax.tree.map(
            lambda x: _sds(np.shape(x), jnp.asarray(x).dtype, one_chip), params
        )
        compiled = jax.jit(_periodic_scan, static_argnums=(1,)).lower(
            shapes, 1000
        ).compile()
    assert compiled.memory_analysis() is not None


def test_routed_scan_fleet_cli_defaults(one_chip):
    """The routed scan at the fleet CLI's defaults: 4096 devices, 250 ticks,
    round-robin routing, latency trajectories on."""
    from repro.core.phases import paper_lstm_item
    from repro.fleet import uniform_fleet
    from repro.fleet.router import ROUTER_CODES
    from repro.fleet.state import FleetState
    from repro.fleet.step import _routed_scan_fn

    n, k = 4096, 250
    with jax.enable_x64():
        params = uniform_fleet(n, item=paper_lstm_item(),
                               strategies=("on_off", "idle_waiting", "adaptive"))
        args = jax.tree.map(
            lambda x: _sds(np.shape(x), jnp.asarray(x).dtype, one_chip),
            (params, FleetState.init(n, 16)),
        )
        fn = _routed_scan_fn(ROUTER_CODES["round_robin"], True, 16)
        compiled = fn.lower(
            *args,
            _sds((k,), jnp.int64, one_chip),
            _sds((k,), jnp.int32, one_chip),
            _sds((), jnp.float64, one_chip),
        ).compile()
    assert compiled.memory_analysis() is not None


def test_arrival_prefix_sum_f64(one_chip):
    """``sample_batch``'s f64 prefix sum over a 4096-arrival stream."""
    from repro.core.arrivals import prefix_sum

    with jax.enable_x64():
        compiled = _compile(prefix_sum, _sds((1, 4096), jnp.float64, one_chip))
    assert compiled.memory_analysis() is not None


def test_sharded_periodic_scan_four_chips(topo, one_chip):
    """The sharded periodic scan over all four chips of the host, the
    ``("fleet", "seed")`` mesh laid out 4x1."""
    from jax.sharding import Mesh, NamedSharding

    from repro.core.phases import paper_lstm_item
    from repro.fleet import uniform_fleet
    from repro.fleet.shard import MESH_AXES, _device_pspec, _sharded_chunk_fn

    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), MESH_AXES)
    with jax.enable_x64():
        params = uniform_fleet(1_000_000, item=paper_lstm_item(),
                               strategies=("on_off", "idle_waiting"))
        sharding = NamedSharding(mesh, _device_pspec(mesh))
        shapes = jax.tree.map(
            lambda x: _sds(np.shape(x), jnp.asarray(x).dtype, sharding), params
        )
        n = _sds((1_000_000,), jnp.int32, sharding)
        alive = _sds((1_000_000,), jnp.bool_, sharding)
        compiled = _sharded_chunk_fn(mesh, 1024).lower(shapes, n, alive).compile()
    assert "all-reduce" in compiled.as_text()
