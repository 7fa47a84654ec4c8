"""Checkpoint system: modes, atomicity, rotation, async, fault tolerance."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import pytest

from repro.checkpoint import (
    AsyncCheckpointer,
    CheckpointManager,
    MODES,
    deserialize,
    serialize,
)
from repro.checkpoint.serializer import _decompressor
from repro.configs import get_config
from repro.kernels.dequant.ref import dequantize_blocked_reference
from repro.models import model_zoo as zoo
from repro.serving.engine import ServingEngine, bring_up_from_checkpoint


@pytest.fixture
def tree():
    key = jax.random.PRNGKey(0)
    return {
        "w": jax.random.normal(key, (256, 256), jnp.bfloat16) * 0.02,
        "b": jnp.zeros((256,), jnp.float32),
        "nested": {"scale": jnp.ones((8,), jnp.float32)},
        "step": jnp.asarray(7, jnp.int32),
    }


class TestSerializer:
    @pytest.mark.parametrize("mode", MODES)
    def test_round_trip_structure(self, tree, mode):
        blob = serialize(tree, mode=mode)
        back = deserialize(blob, tree)
        assert jax.tree.structure(back) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.shape == b.shape and a.dtype == b.dtype

    def test_lossless_modes_exact(self, tree):
        for mode in ("none", "zstd"):
            back = deserialize(serialize(tree, mode=mode), tree)
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_int8_mode_bounded_error(self):
        key = jax.random.PRNGKey(1)
        big = {"w": jax.random.normal(key, (512, 512), jnp.float32)}
        back = deserialize(serialize(big, mode="zstd+int8"), big)
        err = np.abs(np.asarray(big["w"]) - np.asarray(back["w"]))
        assert err.max() < np.abs(np.asarray(big["w"])).max() / 100.0

    def test_zstd_smaller_than_raw(self, tree):
        # structured (normal) bf16 data compresses at least a little
        assert len(serialize(tree, "zstd")) < len(serialize(tree, "none"))

    def test_missing_leaf_raises(self, tree):
        blob = serialize({"w": tree["w"]})
        with pytest.raises(KeyError):
            deserialize(blob, tree)


class TestManager:
    def test_save_restore_latest(self, tree, tmp_path):
        m = CheckpointManager(str(tmp_path))
        m.save(10, tree)
        m.save(20, tree)
        step, back = m.restore_latest(tree)
        assert step == 20
        assert jax.tree.structure(back) == jax.tree.structure(tree)

    def test_rotation_keeps_latest(self, tree, tmp_path):
        m = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            m.save(s, tree)
        assert m.steps() == [3, 4]

    def test_partial_write_ignored(self, tree, tmp_path):
        """Crash-mid-write leaves only a .tmp — restart must see step 5."""
        m = CheckpointManager(str(tmp_path))
        m.save(5, tree)
        with open(os.path.join(str(tmp_path), "step_9.ckpt.tmp"), "wb") as f:
            f.write(b"partial garbage")
        step, _ = m.restore_latest(tree)
        assert step == 5

    def test_empty_dir(self, tmp_path):
        m = CheckpointManager(str(tmp_path))
        step, state = m.restore_latest()
        assert step is None and state is None

    def test_async_checkpointer(self, tree, tmp_path):
        m = CheckpointManager(str(tmp_path))
        a = AsyncCheckpointer(m)
        a.save(1, tree)
        a.save(2, tree)   # implicitly waits for save(1)
        a.wait()
        assert m.steps() == [1, 2]


class TestElasticRestore:
    def test_restore_into_different_dtype_target(self, tree, tmp_path):
        """Elastic/remesh path: restore adapts to the target's dtypes."""
        m = CheckpointManager(str(tmp_path))
        m.save(1, tree)
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), tree
        )
        _, back = m.restore_latest(target)
        for leaf in jax.tree.leaves(back):
            assert leaf.dtype == np.float32


def bits(x) -> np.ndarray:
    """The raw bits of an array, so that equality is bit-equality."""
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.itemsize}"))


class TestDeviceRestore:
    """A ``zstd+int8`` restore leaves each dequantized leaf on the device,
    where the dequant kernel wrote it; the other leaves stay host numpy."""

    @pytest.fixture
    def mixed(self):
        key = jax.random.PRNGKey(2)
        return {
            "embed": jax.random.normal(key, (512, 256), jnp.bfloat16),
            "layers": {"w": jax.random.normal(key, (2, 128, 512), jnp.float32),
                       "norm": jnp.ones((2, 256), jnp.float32)},
            "bias": jnp.full((256,), 0.5, jnp.bfloat16),
            "step": jnp.asarray(3, jnp.int32),
        }

    @staticmethod
    def stored(blob: bytes) -> dict:
        """path -> the kernel's output for that leaf's stored int8 values and
        scales, in the stored dtype and shape (quantized leaves only)."""
        payload = msgpack.unpackb(blob, raw=False)
        dctx = _decompressor(payload["codec"])
        out = {}
        for record in payload["leaves"]:
            if "quant" in record:
                qd, shape = record["quant"], tuple(record["shape"])
                cols = int(np.prod(shape)) // qd["rows"]
                q = np.frombuffer(dctx.decompress(qd["q"]), np.int8).reshape(qd["rows"], cols)
                scales = np.frombuffer(dctx.decompress(qd["scales"]), np.float32)
                out[record["path"]] = dequantize_blocked_reference(
                    jnp.asarray(q), jnp.asarray(scales.reshape(qd["rows"], -1)),
                    group=qd["group"], dtype=jnp.dtype(record["dtype"])).reshape(shape)
        return out

    @pytest.mark.parametrize("dtype", [None, jnp.float32], ids=["stored", "float32"])
    def test_dequantized_leaves_are_device_arrays_bit_equal_to_the_kernel(self, mixed, dtype):
        """With the stored dtypes as target, or a float32 target, every
        quantized leaf is a ``jax.Array`` on the default device in the
        target's dtype, bit-equal to the kernel's output cast to it."""
        blob = serialize(mixed, mode="zstd+int8")
        kernel = self.stored(blob)
        assert sorted(kernel) == ["embed", "layers/w"]
        target = mixed if dtype is None else jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, dtype), mixed)
        back = deserialize(blob, target)
        for path in kernel:
            leaf = back["embed"] if path == "embed" else back["layers"]["w"]
            want = kernel[path].astype(dtype or kernel[path].dtype)
            assert isinstance(leaf, jax.Array)
            assert leaf.devices() == {jax.devices()[0]}
            assert leaf.dtype == want.dtype and leaf.shape == want.shape
            np.testing.assert_array_equal(bits(leaf), bits(want))

    @pytest.mark.parametrize("mode", MODES)
    def test_unquantized_leaves_stay_host_numpy_unchanged(self, mixed, mode):
        back = deserialize(serialize(mixed, mode=mode), mixed)
        kept = [("layers", "norm"), ("bias",), ("step",)]
        if mode != "zstd+int8":
            kept += [("embed",), ("layers", "w")]
        for keys in kept:
            a, b = mixed, back
            for k in keys:
                a, b = a[k], b[k]
            assert type(b) is np.ndarray and b.dtype == a.dtype
            np.testing.assert_array_equal(bits(b), bits(a))

    def test_bring_up_serves_the_tokens_of_host_pulled_weights(self, tmp_path):
        """Every leaf of a brought-up engine is a ``jax.Array``, and its greedy
        tokens equal those of an engine built from the same restore pulled to
        the host and pushed back."""
        # wide enough that the projections and the embedding are int8-quantized
        cfg = dataclasses.replace(get_config("qwen3-1.7b", reduced=True), d_model=256,
                                  d_ff=256, vocab_size=512, num_heads=4, num_kv_heads=2,
                                  head_dim=64)
        m = CheckpointManager(str(tmp_path), mode="zstd+int8")
        m.save(0, zoo.init_params(cfg, jax.random.PRNGKey(0)))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                              cfg.vocab_size, jnp.int32)}
        engine = bring_up_from_checkpoint(cfg, m, max_len=32, warmup_batch=batch)
        assert all(isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(engine.params))
        _, restored = m.restore_latest(zoo.param_shapes(cfg))
        pulled = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), restored)
        host = ServingEngine(cfg, pulled, max_len=32)
        np.testing.assert_array_equal(engine.generate(batch, n_new=8).tokens,
                                      host.generate(batch, n_new=8).tokens)
