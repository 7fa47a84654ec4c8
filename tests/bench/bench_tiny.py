"""A copy of the benchmark's files at a size the CPU runs in seconds, for the
benchmark's own tests.  Widths and depths shrink; everything else (paths,
references, metrics, limits) is the committed benchmark's."""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")


def benchmark_spec() -> dict:
    """The committed BENCHMARK.json."""
    with open(BENCHMARK) as f:
        return json.load(f)


def write_benchmark(directory: str) -> str:
    """Write :func:`benchmark_spec` as ``<directory>/BENCHMARK.json``."""
    path = os.path.join(str(directory), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(benchmark_spec(), f)
    return path

#: Small enough for the CPU.  The hidden size stays published, so logits
#: (and the gaps the check compares) have the scale they have at full size;
#: every matrix has 2^16 elements or more and a last axis that is a multiple
#: of 128, so the int8 checkpoint quantizes it.
TINY_MODEL = dict(num_hidden_layers=2, hidden_size=2048, intermediate_size=256,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=64, vocab_size=512)
TINY_SERVING = {"prompt_lens": [16, 32], "batch": 2, "new_tokens": 4}


def edit(path: str, **changes) -> None:
    with open(path) as f:
        data = json.load(f)
    for key, value in changes.items():
        if isinstance(value, dict) and isinstance(data.get(key), dict):
            data[key].update(value)
        else:
            data[key] = value
    with open(path, "w") as f:
        json.dump(data, f)


def tiny_bench(tmp_path) -> str:
    """A tiny copy of ``bench/`` under ``tmp_path``, with
    :func:`benchmark_spec` beside it; returns its directory."""
    bench = os.path.join(str(tmp_path), "bench")
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("testdata", "__pycache__"))
    edit(os.path.join(bench, "configs", "qwen3-1.7b.json"), **TINY_MODEL)
    edit(os.path.join(bench, "workloads", "qwen3-1.7b.on_off.json"),
         traffic={**TINY_SERVING, "prompt_lens": [32]})
    edit(os.path.join(bench, "workloads", "qwen3-1.7b.idle_waiting.json"),
         traffic={**TINY_SERVING, "rate_per_s": 4.0})
    edit(os.path.join(bench, "configs", "exp2-fleet.json"), n_devices=4000, horizon_steps=256,
         energy_budget_mj=2000.0)
    write_benchmark(tmp_path)
    return bench


def run_tiny(bench: str, cell: str, seconds: float = 1.5, seed: int = 2**33 + 5, **kw) -> dict:
    """One run of a tiny cell on the CPU, the chip check skipped."""
    import io

    from bench import harness

    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(cell, seed, seconds, False, bench_dir=bench, require_tpu=False,
                              use_compile_cache=False, out=out, err=err, **kw)
    result["stderr"] = err.getvalue()
    result["stdout"] = out.getvalue()
    return result


def tiny_runner(bench: str, cell: str, seconds: float = 1.5, seed: int = 2**33 + 7):
    """A tiny cell's runner after its window (the chip check skipped), for
    reading its comparison with and without the control."""
    import jax

    from bench import harness

    c = harness.load_cell(cell, bench)
    runner = harness.load_module(harness.path_file(c), f"tiny_path_{c.workload['path']}").Runner(
        c, seed, seconds, jax.devices()[:1])
    runner.setup()
    runner.window(seconds)
    runner.release()
    return runner
