"""Serving driver: duty-cycle strategy demo on a live engine.

    python -m repro.launch.serve --arch qwen3-1.7b \
        --period-ms 200 --requests 20 --strategy auto

The model runs at its published widths; ``--reduced`` swaps in the small
CPU-sized variant of the same architecture.
"""
from __future__ import annotations

import argparse
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.duty_cycle import DutyCycleController, PowerModel
from repro.launch._cli import enable_compile_cache
from repro.serving.engine import ServingEngine, bring_up_from_checkpoint
from repro.serving.scheduler import run_schedule
from repro.models import model_zoo as zoo


def build_demo(
    arch: str,
    reduced: bool = False,
    max_len: int = 96,
    prompt_len: int = 32,
    batch: int = 2,
    n_new: int = 8,
    ckpt_dir: str | None = None,
    power: PowerModel | None = None,
    strategy: str = "auto",
):
    cfg = get_config(arch, reduced=reduced)
    ckpt_dir = ckpt_dir or tempfile.mkdtemp(prefix="repro-serve-")
    manager = CheckpointManager(ckpt_dir, mode="zstd+int8")
    if not manager.steps():
        params = zoo.init_params(cfg, jax.random.PRNGKey(0))
        manager.save(0, params)

    rng = np.random.default_rng(0)
    def make_request():
        return {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32
            )
        }

    # conservative single-host power placeholders (mW) — examples report
    # RATIOS between strategies, which are power-model independent
    power = power or PowerModel(
        config_mw=90_000.0, infer_mw=200_000.0, idle_mw=65_000.0
    )

    def bring_up():
        return bring_up_from_checkpoint(
            cfg, manager, max_len, warmup_batch=make_request()
        )

    def infer(engine: ServingEngine, request):
        return engine.generate(request, n_new=n_new)

    def release(engine: ServingEngine):
        engine.release()

    controller = DutyCycleController(bring_up, infer, release, power, strategy)
    return controller, make_request


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true",
                    help="small variant of the architecture (CPU runs)")
    ap.add_argument("--period-ms", type=float, default=300.0)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--strategy", default="auto",
                    choices=["auto", "adaptive", "on_off", "idle_waiting"])
    args = ap.parse_args(argv)

    enable_compile_cache()
    controller, make_request = build_demo(
        args.arch, reduced=args.reduced, strategy=args.strategy
    )
    result = run_schedule(
        controller,
        (make_request() for _ in range(args.requests)),
        period_s=args.period_ms / 1000.0,
    )
    print(f"strategy       : {result.strategy}")
    print(f"requests       : {result.n_requests}")
    print(f"configurations : {result.n_configurations}")
    print(f"energy (mJ)    : {result.energy_mj:.1f}")
    print(f"by phase       : { {k: round(v,1) for k,v in result.energy_by_phase_mj.items()} }")
    print(f"crossover (ms) : {result.crossover_ms and round(result.crossover_ms,1)}")


if __name__ == "__main__":
    main()
