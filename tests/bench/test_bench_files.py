"""The benchmark's files: BENCHMARK.json against the contract's shape, every
cell's files found by name, and a new cell added by adding files alone."""
from __future__ import annotations

import json
import os
import re
import shutil

import pytest

from bench_tiny import BENCHMARK, ROOT, benchmark_spec

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def benchmark():
    with open(BENCHMARK) as f:
        return json.load(f)


def test_benchmark_keys_and_names(benchmark):
    assert list(benchmark) == ["command", "paths", "run_seconds", "configs", "workloads",
                               "end_to_end", "per_layer"]
    assert benchmark["paths"] == ["bench", "tests/bench"]
    assert 1 <= benchmark["run_seconds"] <= 51
    names = [x["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for x in benchmark[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in benchmark["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in benchmark["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    for c in benchmark["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_cells_name_listed_configurations_and_metrics(benchmark):
    """Every cell names a listed configuration, every configuration has a
    cell, and a metric's ``workloads`` lists only cells."""
    listed = {c["name"] for c in benchmark["configs"]}
    used = {w["config"] for w in benchmark["workloads"]}
    assert used == listed
    cells = {w["name"] for w in benchmark["workloads"]}
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    assert all(w["chips"] == 1 for w in benchmark["workloads"])


@pytest.mark.parametrize("cell", ["qwen3-1.7b.on_off", "qwen3-1.7b.idle_waiting",
                                  "exp2-fleet.periodic"])
def test_cell_files_found_by_name(cell):
    c = harness.load_cell(cell)
    assert c.workload["config"] == c.config["name"]
    assert os.path.isfile(os.path.join(harness.BENCH_DIR, "reference",
                                       f"{c.config['reference']}.py"))
    e2e = c.end_to_end()
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer()
    for m in c.metrics:
        assert callable(harness.load_module(harness.metric_file(c, m), f"reader_{m}").read)
    # each per-layer metric moves an end-to-end metric this cell reports
    assert all(c.metrics[m]["moves"] in e2e for m in c.per_layer())


def test_config_files_are_listed():
    benchmark = benchmark_spec()
    listed = {c["file"] for c in benchmark["configs"]}
    on_disk = {f"bench/configs/{f}" for f in os.listdir(os.path.join(ROOT, "bench", "configs"))}
    assert listed == on_disk
    for c in benchmark["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert all(k in data for k in c["reduced"])


def test_serving_config_is_the_registered_architecture():
    """The benchmark runs the program's ``ArchConfig`` built from the file's
    published keys; it equals the repo's registered qwen3-1.7b."""
    from bench.paths.serving import arch_config
    from repro.configs import get_config

    c = harness.load_cell("qwen3-1.7b.on_off").config
    got, want = arch_config(c), get_config("qwen3-1.7b")
    for field in ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                  "vocab_size", "qk_norm", "rope_theta", "tie_embeddings", "norm_eps",
                  "mlp_kind", "family"):
        assert getattr(got, field) == getattr(want, field), field


def test_a_new_cell_is_added_by_files_alone(tmp_path):
    """Copy the benchmark, add one workload file and its BENCHMARK.json
    entry, and the harness loads it without any other edit."""
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("testdata", "__pycache__"))
    spec = benchmark_spec()
    with open(bench / "workloads" / "qwen3-1.7b.idle_waiting.json") as f:
        wl = json.load(f)
    wl["traffic"]["prompt_lens"] = [1024]
    (bench / "workloads" / "qwen3-1.7b.long.json").write_text(json.dumps(wl))
    spec["workloads"].append({"name": "qwen3-1.7b.long", "config": "qwen3-1.7b",
                              "traffic": "poisson_8x1024", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "qwen3-1.7b.idle_waiting" in m.get("workloads", []):
            m["workloads"].append("qwen3-1.7b.long")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("qwen3-1.7b.long", str(bench))
    assert cell.workload["traffic"]["prompt_lens"] == [1024]
    assert set(cell.end_to_end()) == {"ttft_ms.p95", "tpot_ms", "setup_s"}
    assert "prefill_mfu" in cell.per_layer()


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.peaks_for("TPU v9 imaginary")
