"""Data-pipeline determinism/resume + logical-sharding unit tests."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.data.pipeline import SyntheticLMStream, TimeSeriesStream, batch_for_arch
from repro.distributed import sharding as shd


class TestSyntheticStream:
    def test_deterministic_across_instances(self):
        a = SyntheticLMStream(100, 4, 16, seed=7)
        b = SyntheticLMStream(100, 4, 16, seed=7)
        np.testing.assert_array_equal(a.next_batch()["tokens"], b.next_batch()["tokens"])

    def test_resume_exact(self):
        a = SyntheticLMStream(100, 4, 16, seed=7)
        for _ in range(5):
            a.next_batch()
        state = a.state()
        want = a.next_batch()["tokens"]
        b = SyntheticLMStream(100, 4, 16, seed=0)
        b.restore(state)
        np.testing.assert_array_equal(b.next_batch()["tokens"], want)

    def test_distinct_steps_differ(self):
        a = SyntheticLMStream(100, 4, 16)
        assert not np.array_equal(a.next_batch()["tokens"], a.next_batch()["tokens"])

    def test_modality_adapters(self):
        s = SyntheticLMStream(1000, 2, 32)
        vlm = get_config("llava-next-mistral-7b", reduced=True)
        b = batch_for_arch(vlm, s.next_batch())
        assert b["tokens"].shape == (2, 32 - vlm.frontend_tokens)
        assert b["patch_embeds"].shape == (2, vlm.frontend_tokens, vlm.frontend_dim)
        audio = get_config("hubert-xlarge", reduced=True)
        b = batch_for_arch(audio, s.next_batch())
        assert b["features"].shape == (2, 32, audio.frontend_dim)
        assert b["labels"].max() < audio.vocab_size


class TestTimeSeries:
    def test_classes_distinguishable(self):
        s = TimeSeriesStream(batch=64)
        x, y = s.next_batch()
        # per-class mean dominant frequency should be ordered
        import numpy.fft as fft

        dom = np.abs(fft.rfft(x[..., 0], axis=1))[:, 1:].argmax(axis=1)
        means = [dom[y == k].mean() for k in range(5) if (y == k).any()]
        assert all(a < b for a, b in zip(means, means[1:]))


class TestLogicalSharding:
    def setup_method(self):
        # abstract 16×16 production mesh: no devices needed for spec logic
        self.mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))

    def test_divisibility_filtering(self):
        # vocab 504 on a 16-wide model axis must drop to None
        spec = shd.logical_to_pspec(
            ("embed", "vocab"), mesh=self.mesh, shape=(1280, 504)
        )
        assert spec == P("data")

    def test_divisible_dims_keep_axes(self):
        spec = shd.logical_to_pspec(
            ("embed", "vocab"), mesh=self.mesh, shape=(1280, 512)
        )
        assert spec == P("data", "model")

    def test_duplicate_axis_dropped(self):
        spec = shd.logical_to_pspec(
            ("cache_batch", "long_cache_seq"),
            mesh=self.mesh,
            shape=(16, 64),
        )
        # both rules resolve to 'data'; only the first position may keep it
        flat = [x for x in spec if x is not None]
        names = []
        for x in flat:
            names.extend(x) if isinstance(x, tuple) else names.append(x)
        assert len(names) == len(set(names))

    def test_no_mesh_is_identity(self):
        x = jax.numpy.ones((4, 4))
        assert shd.constrain(x, ("batch", None)) is x

    def test_tuple_rule_prefix(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        mesh = jax.make_mesh(
            (2, 2, 1), ("pod", "data", "model"),
            devices=np.array(jax.devices() * 4)[:4].reshape(2, 2, 1),
            axis_types=(jax.sharding.AxisType.Auto,) * 3,
        )


class TestAxisSizeRequiresMesh:
    """Regression: axis_size()/divisible() with no active mesh used to
    silently answer 1 — a forgotten use_sharding block became wrong
    padding far from the root cause.  They now raise, naming the logical
    axis and the fix."""

    def test_axis_size_raises_naming_axis(self):
        with pytest.raises(ValueError, match=r"axis_size\('fleet_device'\)"):
            shd.axis_size("fleet_device")

    def test_axis_size_error_names_the_fix(self):
        with pytest.raises(ValueError, match="use_sharding"):
            shd.axis_size("embed")

    def test_divisible_raises_naming_dim_and_axis(self):
        with pytest.raises(ValueError, match=r"divisible\(dim=12, logical='vocab'\)"):
            shd.divisible(12, "vocab")

    def test_explicit_mesh_still_works(self):
        mesh = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
        assert shd.axis_size("embed", mesh) == 4
        assert shd.divisible(12, "embed", mesh)
        assert not shd.divisible(13, "embed", mesh)

    def test_installed_mesh_still_works(self):
        mesh = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
        with shd.use_sharding(mesh):
            assert shd.axis_size("vocab") == 2
            assert shd.divisible(10, "vocab")

    def test_unmapped_axis_with_mesh_is_one(self):
        # an axis with no rule shards nowhere: size 1, everything divides
        mesh = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
        assert shd.axis_size("no_such_logical_axis", mesh) == 1
        assert shd.divisible(7, "no_such_logical_axis", mesh)
