"""Unit tests for the dry-run sharding builders (no multi-device needed:
AbstractMesh carries shapes/axis names for spec logic)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES_BY_NAME, get_config
from repro.configs.perf import BASELINE, PerfConfig
from repro.launch import dryrun_lib as dl
from repro.launch.roofline import RooflineTerms


@pytest.fixture
def single_mesh():
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


@pytest.fixture
def multi_mesh():
    return jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestBatchPspecs:
    def test_train_batch_sharded_over_dp(self, single_mesh, multi_mesh):
        cfg = get_config("yi-6b")
        sp = dl.batch_pspecs(cfg, SHAPES_BY_NAME["train_4k"], single_mesh, BASELINE)
        assert sp["tokens"] == P(("data",), None)
        sp = dl.batch_pspecs(cfg, SHAPES_BY_NAME["train_4k"], multi_mesh, BASELINE)
        assert sp["tokens"] == P(("pod", "data"), None)

    def test_long_decode_batch1_not_sharded(self, single_mesh):
        cfg = get_config("mamba2-370m")
        sp = dl.batch_pspecs(cfg, SHAPES_BY_NAME["long_500k"], single_mesh, BASELINE)
        assert sp["token"] == P(None)

    def test_decode_cache_seq_lever(self, single_mesh):
        cfg = get_config("qwen3-32b")
        perf = PerfConfig(shard_cache_seq_over_model=True)
        sp = dl.batch_pspecs(cfg, SHAPES_BY_NAME["decode_32k"], single_mesh, perf)
        kv = jax.tree.leaves(
            sp["state"],
            is_leaf=lambda x: isinstance(x, P),
        )
        # some cache leaf must carry 'model' on the seq dim
        assert any(
            isinstance(p, P) and len(p) >= 3 and p[2] == "model" for p in kv
        )

    def test_long_cache_seq_over_data(self, single_mesh):
        cfg = get_config("jamba-1.5-large-398b")
        sp = dl.batch_pspecs(cfg, SHAPES_BY_NAME["long_500k"], single_mesh, BASELINE)
        leaves = jax.tree.leaves(sp["state"], is_leaf=lambda x: isinstance(x, P))
        assert any(isinstance(p, P) and len(p) >= 3 and p[2] == "data" for p in leaves)


class TestPerfRules:
    def test_compress_drops_pod_everywhere(self):
        rules = dl.perf_rules(PerfConfig(grad_compress_pod=True))
        for k, v in rules.items():
            if isinstance(v, tuple):
                assert "pod" not in v, k
            else:
                assert v != "pod", k

    def test_cache_lever_rewrites_rule(self):
        rules = dl.perf_rules(PerfConfig(shard_cache_seq_over_model=True))
        assert rules["cache_seq"] == "model"

    def test_baseline_rules_untouched(self):
        from repro.distributed.sharding import DEFAULT_RULES

        assert dl.perf_rules(BASELINE) == DEFAULT_RULES


class TestRooflineTerms:
    def test_dominant_and_bound(self):
        t = RooflineTerms(
            flops_per_device=197e12,        # 1 s compute
            bytes_per_device=819e9 * 2,     # 2 s memory
            collective_bytes_per_device=50e9 * 0.5,
            chips=256,
            model_flops=197e12 * 256,       # perfect-efficiency model
        )
        assert t.compute_s == pytest.approx(1.0)
        assert t.memory_s == pytest.approx(2.0)
        assert t.collective_s == pytest.approx(0.5)
        assert t.dominant == "memory"
        assert t.step_time_lower_bound_s == pytest.approx(2.0)
        assert t.useful_flops_fraction == pytest.approx(1.0)
        assert t.mfu_bound == pytest.approx(0.5)   # 1 s useful / 2 s bound

    def test_skip_cells_accounted(self):
        """40-cell accounting: every skipped cell has a reason recorded."""
        import json, os

        path = os.path.join(os.path.dirname(__file__), "..", "results",
                            "dryrun_single.json")
        if not os.path.exists(path):
            pytest.skip("dry-run cache not present")
        d = json.load(open(path))
        assert len(d) == 40
        for k, v in d.items():
            assert v["status"] in ("ok", "skipped")
            if v["status"] == "skipped":
                assert v["reason"]
