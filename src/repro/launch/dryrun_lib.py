"""Dry-run core: lower + compile every (arch × shape × mesh) cell abstractly.

No device arrays are ever allocated: parameters, optimizer state, caches and
batches are ShapeDtypeStructs; ``jit(...).lower(...).compile()`` proves the
sharding config is coherent, ``memory_analysis()`` proves it fits, and
``cost_analysis()`` + the HLO parse feed §Roofline.

This module has NO import-time side effects on jax device state — the
``dryrun.py`` entry point owns the XLA_FLAGS=512-device environment.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES_BY_NAME, get_config
from repro.configs.base import ArchConfig, ShapeSpec
from repro.configs.perf import BASELINE, PerfConfig
from repro.distributed import sharding as shd
from repro.launch import roofline as rf
from repro.launch.mesh import chips, make_production_mesh
from repro.models import decoder, model_zoo as zoo
from repro.models.attention import KVCache
from repro.models.mamba2 import SSMCache
from repro.optim.adamw import AdamWState
from repro.training.train_loop import TrainState, make_train_step


# ---------------------------------------------------------------------------
# Sharding builders
# ---------------------------------------------------------------------------
def _dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _dp_size(mesh) -> int:
    n = 1
    for a in _dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def _batch_dim_spec(b: int, mesh) -> Any:
    dp = _dp_axes(mesh)
    return dp if (dp and b % _dp_size(mesh) == 0) else None


def batch_pspecs(cfg: ArchConfig, shape: ShapeSpec, mesh, perf: PerfConfig) -> Any:
    """PartitionSpecs for the input batch tree of one cell."""
    bspec = _batch_dim_spec(shape.global_batch, mesh)
    long = shape.name.startswith("long")

    def leaf_spec(path: str, sds) -> P:
        if path == "token":
            return P(bspec)
        if sds.ndim >= 2:
            return P(bspec, *([None] * (sds.ndim - 1)))
        return P()

    spec = zoo.batch_spec(cfg, shape)
    out: dict[str, Any] = {}
    for k, v in spec.items():
        if k == "state":
            out[k] = _decode_state_pspecs(cfg, shape, mesh, perf, v)
        else:
            out[k] = leaf_spec(k, v)
    return out


def _decode_state_pspecs(
    cfg: ArchConfig, shape: ShapeSpec, mesh, perf: PerfConfig, state_sds
) -> Any:
    bspec = _batch_dim_spec(shape.global_batch, mesh)
    long = shape.name.startswith("long")
    model_ok = "model" in mesh.axis_names
    tp = mesh.shape["model"] if model_ok else 1

    def cache_spec(c):
        if isinstance(c, KVCache):
            seq_len_c = c.k.shape[2]
            if long and bspec is None:
                seq = "data" if "data" in mesh.axis_names else None
                if cfg.sliding_window and seq_len_c <= cfg.sliding_window:
                    seq = None      # ring buffer: small, replicate
                kv = P(None, None, seq, None, None)
            else:
                seq = (
                    "model"
                    if (
                        perf.shard_cache_seq_over_model
                        and model_ok
                        and seq_len_c % tp == 0
                    )
                    else None
                )
                kv = P(None, bspec, seq, None, None)
            return KVCache(k=kv, v=kv, positions=P(), index=P())
        if isinstance(c, SSMCache):
            h = c.state.shape[2]
            hspec = "model" if (model_ok and h % tp == 0 and bspec is None) else None
            return SSMCache(
                state=P(None, bspec, hspec, None, None),
                conv=P(None, bspec, None, None),
            )
        raise TypeError(type(c))

    return decoder.DecodeState(
        caches=jax.tree.map(
            cache_spec,
            state_sds.caches,
            is_leaf=lambda x: isinstance(x, (KVCache, SSMCache)),
        )
    )


def _named(tree_pspec, mesh):
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        tree_pspec,
        is_leaf=lambda x: isinstance(x, P),
    )


def perf_rules(perf: PerfConfig) -> dict:
    rules = dict(shd.DEFAULT_RULES)
    if perf.grad_compress_pod:
        # hierarchical ZeRO: the pod axis is handled manually by the
        # compressed-reduction shard_map — params replicate across pods and
        # NO logical rule may reference "pod" (Manual/Auto axes cannot mix
        # inside one PartitionSpec tuple)
        for k, v in list(rules.items()):
            if isinstance(v, tuple) and "pod" in v:
                slim = tuple(a for a in v if a != "pod")
                rules[k] = slim if slim else None
        # the loss's per-chunk logits stay replicated over "model": XLA's
        # SPMD partitioner (jax 0.9) aborts on a (data, model) constraint
        # inside the chunked-loss scan within the pod-manual shard_map.
        # test_multidevice::test_crosspod_vocab_sharded_logits_abort_the_partitioner
        # reproduces it and fails once the rule can go.
        rules["act_vocab"] = None
    if perf.shard_long_cache_over_model:
        rules["long_cache_seq"] = "model"
    if perf.shard_cache_seq_over_model:
        rules["cache_seq"] = "model"
    return rules


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    status: str                  # ok | skipped | error
    reason: str = ""
    compile_s: float = 0.0
    memory: Optional[dict] = None
    cost_analysis: Optional[dict] = None
    roofline: Optional[dict] = None
    collectives: Optional[dict] = None

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    perf: PerfConfig = BASELINE,
    compile_only: bool = False,
) -> CellResult:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_name = "multi(2x16x16)" if multi_pod else "single(16x16)"
    ok, reason = cfg.shape_supported(shape)
    if not ok:
        return CellResult(arch, shape_name, mesh_name, "skipped", reason)

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = perf_rules(perf)
    t0 = time.time()
    try:
        with shd.use_sharding(mesh, rules):
            lowered, tokens_per_step, training = _lower(cfg, shape, mesh, perf)
            compiled = lowered.compile()
        compile_s = time.time() - t0
        mem = compiled.memory_analysis()
        memd = {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "per_device_total_gb": (
                mem.argument_size_in_bytes
                + mem.temp_size_in_bytes
                + mem.output_size_in_bytes
                - mem.alias_size_in_bytes
            )
            / 1e9,
        }
        try:
            ca = dict(compiled.cost_analysis())
            ca = {k: float(v) for k, v in ca.items() if isinstance(v, (int, float))}
            ca = {
                "flops_1iter": ca.get("flops", 0.0),
                "bytes_accessed_1iter": ca.get("bytes accessed", 0.0),
            }
        except Exception as e:  # pragma: no cover
            ca = {"error": str(e)}
        cost = rf.parse_hlo_costs(compiled.as_text(), default_trip=decoder.num_periods(cfg))
        model_flops = cfg.model_flops_per_token(training) * tokens_per_step
        terms = rf.RooflineTerms(
            flops_per_device=cost.flops,
            bytes_per_device=cost.hbm_bytes,
            collective_bytes_per_device=cost.collective_bytes,
            chips=chips(mesh),
            model_flops=model_flops,
        )
        return CellResult(
            arch, shape_name, mesh_name, "ok",
            compile_s=compile_s,
            memory=memd,
            cost_analysis=ca,
            roofline=terms.to_dict(),
            collectives={
                "bytes_by_kind": cost.coll_bytes,
                "count_by_kind": {k: float(v) for k, v in cost.coll_count.items()},
            },
        )
    except Exception as e:  # noqa: BLE001 — dry-run failures are data
        return CellResult(
            arch, shape_name, mesh_name, "error",
            reason=f"{type(e).__name__}: {e}", compile_s=time.time() - t0,
        )


def _lower(cfg: ArchConfig, shape: ShapeSpec, mesh, perf: PerfConfig):
    param_sds = zoo.param_shapes(cfg)
    param_ps = zoo.param_pspecs(cfg, mesh)
    bspecs = batch_pspecs(cfg, shape, mesh, perf)
    batch_sds = zoo.batch_spec(cfg, shape)

    if shape.kind == "train":
        fns = make_train_step(cfg, perf, mesh=mesh)
        state_sds = jax.eval_shape(fns.init_state, param_sds)
        from repro.optim.grad_compress import CompressState

        state_ps = TrainState(
            params=param_ps,
            opt=AdamWState(step=P(), m=param_ps, v=param_ps),
            compress_err=(
                None
                if state_sds.compress_err is None
                else CompressState(error=param_ps)
            ),
        )
        metrics_ps = {"loss": P(), "grad_norm": P(), "lr": P()}
        step = jax.jit(
            fns.train_step,
            in_shardings=(_named(state_ps, mesh), _named(bspecs, mesh), None),
            out_shardings=(_named(state_ps, mesh), _named(metrics_ps, mesh)),
            donate_argnums=(0,),
        )
        lowered = step.lower(
            state_sds, batch_sds, jax.ShapeDtypeStruct((), jnp.float32)
        )
        tokens = shape.global_batch * shape.seq_len
        return lowered, tokens, True

    if shape.kind == "prefill":
        if not cfg.decode_supported:
            fn = lambda p, b: zoo.encode_fn(p, b, cfg, perf)
        else:
            fn = lambda p, b: zoo.prefill_fn(
                p, b, cfg, max_len=shape.seq_len, perf=perf
            )
        step = jax.jit(
            fn, in_shardings=(_named(param_ps, mesh), _named(bspecs, mesh))
        )
        lowered = step.lower(param_sds, batch_sds)
        return lowered, shape.global_batch * shape.seq_len, False

    if shape.kind == "decode":
        long = shape.name.startswith("long")
        fn = lambda p, s, t: zoo.decode_fn(p, s, t, cfg, perf, long_context=long)
        step = jax.jit(
            fn,
            in_shardings=(
                _named(param_ps, mesh),
                _named(bspecs["state"], mesh),
                _named(bspecs["token"], mesh),
            ),
            donate_argnums=(1,),
        )
        lowered = step.lower(param_sds, batch_sds["state"], batch_sds["token"])
        return lowered, shape.global_batch, False

    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# Cache-driven runner
# ---------------------------------------------------------------------------
def run_cells(
    cells: list[tuple[str, str, bool]],
    out_path: str,
    perf: PerfConfig = BASELINE,
    tag: str = "baseline",
) -> list[CellResult]:
    import os

    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = {tuple(k.split("|")): v for k, v in json.load(f).items()}
    out = []
    for arch, shape_name, multi in cells:
        key = (arch, shape_name, "multi" if multi else "single", tag)
        if key in results and results[key].get("status") in ("ok", "skipped"):
            out.append(CellResult(**results[key]))
            continue
        res = lower_cell(arch, shape_name, multi_pod=multi, perf=perf)
        results[key] = res.to_json()
        with open(out_path, "w") as f:
            json.dump({"|".join(k): v for k, v in results.items()}, f, indent=1)
        print(
            f"[{res.status:7s}] {arch} × {shape_name} × {res.mesh} "
            f"({res.compile_s:.1f}s) {res.reason[:120]}",
            flush=True,
        )
        out.append(res)
    return out
