"""A whole run at a CPU size, the chip check skipped: a sound run is
correct, and each fault the served cells can have, planted under the timed
path, makes ``correct`` false."""
from __future__ import annotations

import pytest

from bench_tiny import run_tiny, tiny_bench, tiny_runner


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", ["qwen3-1.7b.on_off", "qwen3-1.7b.idle_waiting"])
def test_sound_run_is_correct(bench, cell):
    r = run_tiny(bench, cell)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) >= {"setup_s"} and len(r["metrics"]) >= 2
    assert "[check] max_logit_gap" in r["stderr"].splitlines()[-1]
    assert list(r)[-3:] == ["check", "stderr", "stdout"]


@pytest.mark.parametrize("cell", ["qwen3-1.7b.on_off", "qwen3-1.7b.idle_waiting"])
def test_control_fails_through_the_check(bench, cell):
    """The reference one precision below the stated one (int4 for the int8
    checkpoint, fp8 for bf16 serving), put in the program's place and read
    through the cell's own comparison, comes out not correct."""
    from bench import harness

    runner = tiny_runner(bench, cell)
    assert harness.is_correct(runner.check())
    control = runner.check(runner.wl["control"])
    assert not harness.is_correct(control), control


def test_altered_token_is_caught(bench, monkeypatch):
    from repro.serving import engine

    real = engine.ServingEngine.generate

    def altered(self, batch, n_new, **kw):
        out = real(self, batch, n_new, **kw)
        out.tokens = out.tokens.at[0, n_new // 2].set((out.tokens[0, n_new // 2] + 1) % 512)
        return out

    monkeypatch.setattr(engine.ServingEngine, "generate", altered)
    r = run_tiny(bench, "qwen3-1.7b.idle_waiting")
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > r["check"]["max_logit_gap"]["limit"]


def test_decode_state_left_unchanged_is_caught(bench, monkeypatch):
    from repro.models import model_zoo

    real = model_zoo.decode_fn

    def stale(params, state, token, **kw):
        logits, _ = real(params, state, token, **kw)
        return logits, state

    monkeypatch.setattr(model_zoo, "decode_fn", stale)
    r = run_tiny(bench, "qwen3-1.7b.on_off")
    assert not r["correct"], r["check"]
