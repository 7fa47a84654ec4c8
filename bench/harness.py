"""One run of one benchmark cell: set up, measure, check, print.

Everything that belongs to one cell, configuration or metric is a file of
its own, found by name:

* ``BENCHMARK.json`` (at the root of the checkout) lists the cells and, for
  each metric, its unit and the cells that report it;
* ``bench/workloads/<cell>.json`` names the cell's configuration, the driver
  of the path its window drives, its traffic parameters and the limits of its
  correctness check;
* ``bench/configs/<config>.json`` holds the configuration as it is run, and
  names its plain reference ``bench/reference/<reference>.py``;
* ``bench/paths/<path>.py`` drives the window (a ``Runner`` class);
* ``bench/metrics/<metric>.py`` reads one metric (``read(run)``, a number or
  None when the run holds nothing to read it from).

Nothing in this module names a cell, a configuration or a metric.
"""
from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """The run cannot be measured; it prints no result."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A cell as its files describe it."""

    name: str
    entry: dict          # the cell's entry in BENCHMARK.json's "workloads"
    workload: dict       # bench/workloads/<name>.json
    config: dict         # bench/configs/<config>.json
    metrics: dict        # metric name -> its BENCHMARK.json entry, for this cell
    bench_dir: str

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def end_to_end(self) -> list[str]:
        return [m for m, e in self.metrics.items() if e["kind"] == "end_to_end"]

    def per_layer(self) -> list[str]:
        return [m for m, e in self.metrics.items() if e["kind"] == "per_layer"]


def _cell_metrics(benchmark: dict, name: str) -> dict:
    """The metrics BENCHMARK.json says this cell reports: an end-to-end
    metric without ``workloads`` is reported everywhere; a per-layer metric
    without it wherever the metric it moves is reported."""
    out: dict[str, dict] = {}
    for m in benchmark["end_to_end"]:
        if name in m.get("workloads", [name]):
            out[m["name"]] = {**m, "kind": "end_to_end"}
    for m in benchmark["per_layer"]:
        if name in m.get("workloads", [name] if m["moves"] in out else []):
            out[m["name"]] = {**m, "kind": "per_layer"}
    return out


def load_cell(name: str, bench_dir: str = BENCH_DIR,
              benchmark_file: Optional[str] = None) -> Cell:
    benchmark = load_json(benchmark_file or os.path.join(os.path.dirname(bench_dir),
                                                         "BENCHMARK.json"))
    entries = {w["name"]: w for w in benchmark["workloads"]}
    if name not in entries:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json")
    workload = load_json(os.path.join(bench_dir, "workloads", f"{name}.json"))
    if workload["config"] != entries[name]["config"]:
        raise BenchError(f"{name}: BENCHMARK.json and the cell file name different configurations")
    config = load_json(os.path.join(bench_dir, "configs", f"{workload['config']}.json"))
    cell = Cell(name, entries[name], workload, config, _cell_metrics(benchmark, name), bench_dir)
    for file in [path_file(cell)] + [metric_file(cell, m) for m in cell.metrics]:
        if not os.path.isfile(file):
            raise BenchError(f"{name}: missing {os.path.relpath(file, bench_dir)}")
    return cell


def path_file(cell: Cell) -> str:
    return os.path.join(cell.bench_dir, "paths", f"{cell.workload['path']}.py")


def metric_file(cell: Cell, metric: str) -> str:
    return os.path.join(cell.bench_dir, "metrics", f"{metric}.py")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# Device, peaks, compile cache
# ---------------------------------------------------------------------------
def peaks_for(kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))["kinds"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def devices_for(cell: Cell, require_tpu: bool = True) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(
            f"no TPU: JAX sees {len(devices)} {devices[0].platform} device(s); "
            "the benchmark never measures another platform"
        )
    if len(devices) < cell.chips:
        raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
    return devices[: cell.chips]


def compile_cache_dir(root: str = ROOT) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set, else a fixed directory in the
    checkout (the path is part of the cache key)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".bench_cache", "jax")


def enable_compile_cache(path: str) -> None:
    """Cache every compile, not only those of a second or more."""
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# Compile spans (jax.monitoring), for readers that attribute compile time
# ---------------------------------------------------------------------------
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileSpans:
    """Host-clock (``time.time``) spans of tracing, lowering and compiling
    (a persistent-cache load counts as compiling), and cache hit counts."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self.cache_hits = 0
        self.cache_requests = 0
        self.cache_misses = 0
        self.enabled = True

    def install(self) -> "CompileSpans":
        import jax

        def on_span(event, start, end, **_):
            if self.enabled and event in COMPILE_EVENTS:
                self.spans.append((event, start, end))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/compile_requests_use_cache":
                self.cache_requests += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_time_span_listener(on_span)
        jax.monitoring.register_event_listener(on_event)
        return self


# ---------------------------------------------------------------------------
# The data readers see
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunData:
    """What a metric reader may read.  ``records`` and ``spans`` come from
    the path's runner; ``trace`` is the reduced profiler trace of a
    ``--trace 1`` run (else None)."""

    cell: Cell
    setup_s: float
    window_s: float
    records: list
    spans: dict
    peaks: Optional[dict]
    trace: Any = None


def is_correct(checks: dict) -> bool:
    """A run is correct when every compared number is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def read_metrics(cell: Cell, data: RunData, names: list[str]) -> dict:
    out = {}
    for name in names:
        reader = load_module(metric_file(cell, name), f"bench_metric_{len(out)}_{os.getpid()}")
        value = reader.read(data)
        if value is None:
            continue
        out[name] = {"value": float(value), "unit": cell.metrics[name]["unit"]}
    return out


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
class Profiler:
    """Trace the window with the JAX profiler into a temporary directory
    (under ``TMPDIR``); ``path`` is the written ``.xplane.pb``."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Optional[str] = None
        self.path: Optional[str] = None
        self.stop_s = 0.0

    def __enter__(self) -> "Profiler":
        if self.enabled:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc) -> None:
        if not self.enabled:
            return
        import jax

        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        self.path = found[0] if found else None

    def close(self) -> None:
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    bench_dir: str = BENCH_DIR,
    benchmark_file: Optional[str] = None,
    require_tpu: bool = True,
    use_compile_cache: bool = True,
    t_start: Optional[float] = None,
    out=None,
    err=None,
) -> dict:
    """Run one cell and return its result dict (also printed)."""
    out = out or sys.stdout
    err = err or sys.stderr
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(name, bench_dir, benchmark_file)

    import jax
    from jax.profiler import TraceAnnotation

    from bench import trace_reduce

    devices = devices_for(cell, require_tpu)
    kind = devices[0].device_kind
    peaks = peaks_for(kind, bench_dir) if (require_tpu or trace) else None
    if use_compile_cache:
        enable_compile_cache(compile_cache_dir())
    compiles = CompileSpans().install()

    path = load_module(path_file(cell), f"bench_path_{cell.workload['path']}")
    runner = path.Runner(cell, seed, seconds, devices)
    split = runner.setup()
    setup_s = time.perf_counter() - t_start
    print("[setup] " + json.dumps({"setup_s": setup_s, **split,
                                   "cache_hits": compiles.cache_hits,
                                   "cache_requests": compiles.cache_requests}),
          file=out, flush=True)

    misses_before = compiles.cache_misses
    with Profiler(trace) as traced:
        with TraceAnnotation("bench/window"):
            t0 = time.perf_counter()
            runner.window(seconds)
            window_s = time.perf_counter() - t0
    window_misses = compiles.cache_misses - misses_before
    compiles.enabled = False

    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak_bytes(devices),
    }
    runner.release()
    checks = runner.check()
    correct = is_correct(checks)

    data = RunData(cell, setup_s, window_s, runner.records,
                   {**runner.spans, "compile": compiles.spans}, peaks)
    result: dict[str, Any] = {"correct": correct}
    result["attempted"], result["failed"] = runner.attempted_failed()
    breakdown = None
    if trace:
        try:
            if traced.path is None:
                raise BenchError("the profiler wrote no trace")
            t = time.perf_counter()
            data.trace = trace_reduce.reduce_file(traced.path, n_devices=len(devices))
            reduce_s = time.perf_counter() - t
        finally:
            traced.close()
        device["busy_s"] = data.trace.busy_s
        device["window_s"] = data.trace.window_s
        result["metrics"] = read_metrics(cell, data, cell.per_layer())
        t = time.perf_counter()
        breakdown = data.trace.breakdown()
        print("[trace] " + json.dumps({"stop_s": traced.stop_s, "reduce_s": reduce_s,
                                       "breakdown_s": time.perf_counter() - t}),
              file=out, flush=True)
    else:
        result["metrics"] = read_metrics(cell, data, cell.end_to_end())
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compile_cache_misses_in_window"] = window_misses
    result["check"] = checks
    for cname, c in checks.items():
        print(f"[check] {cname} = {c['value']!r} (limit {c['limit']!r})", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result
