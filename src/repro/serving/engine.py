"""Serving engine: prefill + batched decode over any registered arch.

The engine is the ``infer``/``bring_up``/``release`` provider for the
duty-cycle controller: ``bring_up`` loads weights from a (compressed)
checkpoint and re-jits; ``release`` drops every device buffer.  On a real
pod the same object runs under the production mesh; on this container it
runs reduced configs on CPU (examples/duty_cycle_serving.py).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial, update_wrapper
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig
from repro.configs.perf import BASELINE, PerfConfig
from repro.models import decoder, model_zoo as zoo


@dataclasses.dataclass
class GenerationResult:
    tokens: Any                      # (B, n_new) int32
    prefill_s: float
    decode_s: float

    @property
    def total_s(self) -> float:
        return self.prefill_s + self.decode_s


class ServingEngine:
    def __init__(
        self,
        cfg: ArchConfig,
        params: Any,
        max_len: int,
        perf: PerfConfig = BASELINE,
    ):
        if not cfg.decode_supported:
            raise ValueError(f"{cfg.name} is encoder-only")
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.perf = perf
        # wrapped so that the device trace names the programs
        # jit_prefill_fn and jit_decode_fn
        self._prefill = jax.jit(update_wrapper(
            partial(zoo.prefill_fn, cfg=cfg, max_len=max_len, perf=perf), zoo.prefill_fn
        ))
        self._decode = jax.jit(update_wrapper(
            partial(zoo.decode_fn, cfg=cfg, perf=perf), zoo.decode_fn
        ))

    def generate(
        self, batch: dict, n_new: int, greedy: bool = True,
        key: Optional[jax.Array] = None,
    ) -> GenerationResult:
        if self.params is None:
            raise RuntimeError(
                "engine was released (powered off); bring up from a "
                "checkpoint before generating"
            )
        with TraceAnnotation("generate"):
            t0 = time.perf_counter()
            logits, state = self._prefill(self.params, batch)
            logits.block_until_ready()
            t1 = time.perf_counter()
            outs = []
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            for i in range(n_new):
                with TraceAnnotation("generate/decode_step"):
                    outs.append(tok)
                    logits, state = self._decode(self.params, state, tok)
                    if greedy or key is None:
                        tok = jnp.argmax(logits, -1).astype(jnp.int32)
                    else:
                        key, sub = jax.random.split(key)
                        tok = jax.random.categorical(sub, logits).astype(jnp.int32)
            jax.block_until_ready(outs[-1])
            t2 = time.perf_counter()
            return GenerationResult(
                tokens=jnp.stack(outs, axis=1), prefill_s=t1 - t0, decode_s=t2 - t1
            )

    @property
    def resident(self) -> bool:
        """Whether weights are on device (idle-waiting) or dropped (off)."""
        return self.params is not None

    def param_bytes(self) -> int:
        """Resident footprint — feeds multi-tenant HBM budgeting
        (:class:`repro.serving.multi_tenant.Tenant.hbm_gb`)."""
        if self.params is None:
            return 0
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.params))

    def release(self) -> None:
        """Drop device buffers (the On-Off 'power-off')."""
        if self.params is None:
            return
        for leaf in jax.tree.leaves(self.params):
            if hasattr(leaf, "delete"):
                leaf.delete()
        self.params = None


def bring_up_from_checkpoint(
    cfg: ArchConfig,
    manager: CheckpointManager,
    max_len: int,
    perf: PerfConfig = BASELINE,
    warmup_batch: Optional[dict] = None,
) -> ServingEngine:
    """The 'configuration phase': restore (decompress) weights + build the
    engine (+ optional jit warm-up so infer latency excludes compile)."""
    with TraceAnnotation("bring_up"):
        target = zoo.param_shapes(cfg)
        _, params = manager.restore_latest(target)
        if params is None:
            raise FileNotFoundError(f"no checkpoint in {manager.directory}")
        with TraceAnnotation("bring_up/to_device"):
            params = jax.block_until_ready(jax.tree.map(jnp.asarray, params))
        with TraceAnnotation("bring_up/warmup"):
            engine = ServingEngine(cfg, params, max_len, perf)
            if warmup_batch is not None:
                engine.generate(warmup_batch, n_new=1)
        return engine
