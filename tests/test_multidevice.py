"""Multi-device integration tests (8 fake CPU devices via subprocess).

The main pytest process must keep seeing 1 device (dry-run rule), so every
multi-device scenario runs in a fresh subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _spawn(code: str, timeout: float) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def run_py(code: str, timeout=560) -> str:
    out = _spawn(code, timeout)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_moe_ep_sharded_matches_reference():
    """shard_map expert-parallel MoE ≡ dense reference (no-drop capacity)."""
    run_py("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.models import moe

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        # 16 experts → the true expert-parallel (all_to_all) path
        cfg = dataclasses.replace(
            get_config("qwen3-moe-235b-a22b", reduced=True),
            num_experts=16, experts_per_token=2,
        )
        assert moe.uses_ep(cfg)
        key = jax.random.PRNGKey(0)
        from repro.models.common import init_from_specs
        params = init_from_specs(moe.moe_specs(cfg), key, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)

        y_ref, aux_ref = moe.moe_reference(params, x, cfg)
        with shd.use_sharding(mesh):
            xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            ps = jax.device_put(params, jax.tree.map(
                lambda _: NamedSharding(mesh, P()), params))
            y_sh, aux_sh = moe.moe_block(ps, xs, cfg, capacity_factor=64.0)
        err = float(jnp.max(jnp.abs(y_ref - y_sh)))
        print("MOE_ERR", err)
        assert err < 2e-5, err
    """)


def test_moe_ftp_sharded_matches_reference():
    """f-TP MoE path (mixtral-style small expert count) ≡ reference."""
    run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.models import moe

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = get_config("mixtral-8x7b", reduced=True)   # 4 experts top-2
        assert not moe.uses_ep(cfg)
        from repro.models.common import init_from_specs
        params = init_from_specs(moe.moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
        y_ref, _ = moe.moe_reference(params, x, cfg)
        with shd.use_sharding(mesh):
            xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            ps = jax.device_put(params, jax.tree.map(
                lambda _: NamedSharding(mesh, P()), params))
            y_sh, _ = moe.moe_block(ps, xs, cfg, capacity_factor=64.0)
        err = float(jnp.max(jnp.abs(y_ref - y_sh)))
        print("MOE_FTP_ERR", err)
        assert err < 2e-5, err
    """)


def test_sharded_train_step_matches_single_device():
    """One train step on a (4,2) mesh ≡ the same step on 1 device."""
    run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.perf import PerfConfig
        from repro.distributed import sharding as shd
        from repro.launch.dryrun_lib import batch_pspecs
        from repro.models import model_zoo as zoo
        from repro.training.train_loop import make_train_step

        cfg = get_config("yi-6b", reduced=True)
        perf = PerfConfig(num_microbatches=2)
        params = zoo.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size),
        }

        def loss_after_step(mesh):
            with shd.use_sharding(mesh):
                fns = make_train_step(cfg, perf, mesh=mesh)
                state = fns.init_state(params)
                state, metrics = jax.jit(fns.train_step)(state, batch, 1e-3)
                return float(metrics["loss"])

        mesh1 = jax.make_mesh((1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
                              devices=jax.devices()[:1])
        mesh8 = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        l1 = loss_after_step(mesh1)
        l8 = loss_after_step(mesh8)
        print("LOSS", l1, l8)
        assert abs(l1 - l8) < 1e-4, (l1, l8)
    """)


def test_checkpoint_elastic_remesh():
    """Save on a (4,2) mesh → restore onto (2,4) → bit-identical params."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.models import model_zoo as zoo

        cfg = get_config("qwen3-1.7b", reduced=True)
        params = zoo.init_params(cfg, jax.random.PRNGKey(0))
        d = tempfile.mkdtemp()
        m = CheckpointManager(d, mode="zstd")

        mesh_a = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with shd.use_sharding(mesh_a):
            ps_a = zoo.param_pspecs(cfg, mesh_a)
            sharded = jax.device_put(params, jax.tree.map(
                lambda p: NamedSharding(mesh_a, p), ps_a,
                is_leaf=lambda x: isinstance(x, P)))
        m.save(1, sharded)

        mesh_b = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with shd.use_sharding(mesh_b):
            _, host = m.restore_latest(zoo.param_shapes(cfg))
            ps_b = zoo.param_pspecs(cfg, mesh_b)
            resharded = jax.device_put(host, jax.tree.map(
                lambda p: NamedSharding(mesh_b, p), ps_b,
                is_leaf=lambda x: isinstance(x, P)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(resharded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("REMESH_OK")
    """)


def test_elastic_training_resume_across_mesh_change():
    """The full elastic story: train on a (4,2) mesh, checkpoint, 'lose
    half the fleet', resume on (2,2) — the loss trajectory must continue
    exactly (mesh-agnostic checkpoints + deterministic data)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.configs import get_config
        from repro.configs.perf import PerfConfig
        from repro.data.pipeline import SyntheticLMStream, batch_for_arch, shard_batch
        from repro.checkpoint import CheckpointManager
        from repro.distributed import sharding as shd
        from repro.distributed.fault_tolerance import plan_elastic_mesh
        from repro.models import model_zoo as zoo
        from repro.training.train_loop import make_train_step

        cfg = get_config("qwen3-1.7b", reduced=True)
        perf = PerfConfig()

        def steps_on(mesh, state, stream, n):
            losses = []
            with shd.use_sharding(mesh):
                fns = make_train_step(cfg, perf, mesh=mesh)
                step = jax.jit(fns.train_step)
                for _ in range(n):
                    b = shard_batch(batch_for_arch(cfg, stream.next_batch()), mesh)
                    state, m = step(state, b, 1e-3)
                    losses.append(float(m["loss"]))
            return state, losses

        params = zoo.init_params(cfg, jax.random.PRNGKey(0))

        # reference: 6 uninterrupted steps on the big mesh
        mesh_a = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with shd.use_sharding(mesh_a):
            fns = make_train_step(cfg, perf, mesh=mesh_a)
            s_ref = fns.init_state(params)
        stream = SyntheticLMStream(cfg.vocab_size, 4, 32, seed=3)
        _, ref_losses = steps_on(mesh_a, s_ref, stream, 6)

        # elastic: 3 steps on (4,2) → checkpoint → resume on survivors (2,2)
        with shd.use_sharding(mesh_a):
            fns = make_train_step(cfg, perf, mesh=mesh_a)
            s1 = fns.init_state(params)
        stream = SyntheticLMStream(cfg.vocab_size, 4, 32, seed=3)
        s1, l1 = steps_on(mesh_a, s1, stream, 3)
        d = tempfile.mkdtemp()
        m = CheckpointManager(d)
        m.save(3, s1)

        plan = plan_elastic_mesh(survivors=4, model_axis=2)
        assert plan.devices == 4
        mesh_b = jax.make_mesh((plan.data, plan.model), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
                               devices=jax.devices()[:4])
        _, host = m.restore_latest(jax.eval_shape(lambda s: s, s1))
        s2 = jax.tree.map(jnp.asarray, host)
        s2, l2 = steps_on(mesh_b, s2, stream, 3)

        print("REF", ref_losses)
        print("ELASTIC", l1 + l2)
        np.testing.assert_allclose(l1 + l2, ref_losses, atol=2e-3)
    """)


def test_grad_compression_close_to_exact():
    """int8 cross-'pod' gradient psum with error feedback ≈ exact mean."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim import grad_compress as gc

        mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        def body(g_loc, e_loc):
            out, new_e = gc.compress_psum({"g": g_loc}, gc.CompressState({"g": e_loc}), "pod")
            return out["g"], new_e.error["g"]

        out, err = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("pod"), P("pod")),
            out_specs=(P("pod"), P("pod")),
            check_vma=False,
        )(g, jnp.zeros_like(g))
        # exact mean over pods of each shard's grads == its own value
        # (each pod holds a different shard half; compare vs exact psum)
        exact = jax.shard_map(
            lambda x: jax.lax.pmean(x, "pod"), mesh=mesh,
            in_specs=P("pod"), out_specs=P("pod"), check_vma=False)(g)
        rel = float(jnp.max(jnp.abs(out - exact)) / jnp.max(jnp.abs(exact)))
        print("COMPRESS_REL_ERR", rel)
        assert rel < 0.02, rel
    """)


def test_compressed_crosspod_train_step():
    """grad_compress_pod=True: hierarchical-ZeRO train step on a
    ('pod','data','model') mesh — loss finite, params move, and the loss
    trajectory stays close to the uncompressed path (error feedback)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.perf import PerfConfig
        from repro.distributed import sharding as shd
        from repro.launch.dryrun_lib import perf_rules
        from repro.models import model_zoo as zoo
        from repro.training.train_loop import make_train_step

        cfg = get_config("yi-6b", reduced=True)
        params = zoo.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size),
        }

        def run(compress):
            mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 3)
            perf = PerfConfig(grad_compress_pod=compress)
            with shd.use_sharding(mesh, perf_rules(perf)):
                fns = make_train_step(cfg, perf, mesh=mesh)
                state = fns.init_state(params)
                losses = []
                step = jax.jit(fns.train_step)
                for _ in range(3):
                    state, m = step(state, batch, 1e-2)
                    losses.append(float(m["loss"]))
                return losses, state

        l_ref, _ = run(False)
        l_c, st = run(True)
        print("LOSSES", l_ref, l_c)
        assert all(np.isfinite(l_c)), l_c
        assert abs(l_c[0] - l_ref[0]) < 1e-3           # same fwd
        assert abs(l_c[-1] - l_ref[-1]) < 0.05         # compressed ≈ exact
        assert st.compress_err is not None
    """)


def test_crosspod_vocab_sharded_logits_abort_the_partitioner():
    """Why ``perf_rules`` leaves ``act_vocab`` unsharded under
    ``grad_compress_pod``: with the loss logits constrained to
    ``(data, None, model)`` inside the chunked-loss scan of the pod-manual
    ``shard_map``, XLA's SPMD partitioner fails a check and aborts the
    process.  Once this test fails, the compile goes through: delete the
    ``act_vocab`` rule in ``perf_rules`` and this test."""
    out = _spawn("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.perf import PerfConfig
        from repro.distributed import sharding as shd
        from repro.launch.dryrun_lib import perf_rules
        from repro.models import model_zoo as zoo
        from repro.training.train_loop import make_train_step

        cfg = get_config("yi-6b", reduced=True)
        params = zoo.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                 "labels": jnp.zeros((8, 32), jnp.int32)}
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        perf = PerfConfig(grad_compress_pod=True)
        rules = perf_rules(perf)
        assert rules["act_vocab"] is None
        rules["act_vocab"] = "model"
        with shd.use_sharding(mesh, rules):
            fns = make_train_step(cfg, perf, mesh=mesh)
            state = fns.init_state(params)
            jax.jit(fns.train_step).lower(state, batch, 1e-2).compile()
        print("COMPILED")
    """, timeout=300)
    assert out.returncode != 0 and "COMPILED" not in out.stdout, (
        "the vocab-sharded logits now compile: drop perf_rules' act_vocab rule"
    )
    assert "spmd_partitioner_util" in out.stderr, out.stderr[-3000:]


def test_roofline_parser_counts_sharded_collectives():
    """Collective bytes parsed from a sharded scan module ≈ analytic value."""
    run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.roofline import parse_hlo_costs

        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        L, B, D, F = 7, 32, 256, 512
        Ws = jax.ShapeDtypeStruct((L, D, F), jnp.float32)
        X = jax.ShapeDtypeStruct((B, D), jnp.float32)

        def f(x, ws):
            def body(x, w):
                return jnp.tanh(x @ w @ w.T), None
            x, _ = jax.lax.scan(body, x, ws)
            return x

        comp = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P(None, "data", "model")),
        )).lower(X, Ws).compile()
        cost = parse_hlo_costs(comp.as_text())
        flops = cost.flops
        expected = 2 * (B//4) * D * (F//2) * 2 * L   # two matmuls per layer
        print("FLOPS", flops, expected, flops/expected)
        assert 0.9 < flops / expected < 1.6, (flops, expected)
        assert cost.collective_bytes > 0
    """)
