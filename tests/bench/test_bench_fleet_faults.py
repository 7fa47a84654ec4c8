"""The fleet cell at a CPU size: a sound run is correct against the plain
reference, and an altered answer or a scan that leaves its state unchanged
makes ``correct`` false; the float32 control fails the energy limit."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench_tiny import run_tiny, tiny_bench, tiny_runner

CELL = "exp2-fleet.periodic"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("tiny_fleet"))


def test_sound_run_is_correct_and_devices_die(bench):
    r = run_tiny(bench, CELL)
    assert r["correct"], r["check"]
    assert r["metrics"]["device_steps_per_s"]["value"] > 0


def _patch(monkeypatch, change):
    import repro.fleet as fleet

    real = fleet.run_periodic

    def broken(params, n_steps, **kw):
        return change(real(params, n_steps, **kw))

    monkeypatch.setattr(fleet, "run_periodic", broken)


def test_altered_answer_is_caught(bench, monkeypatch):
    def alter(res):
        n = res.n_items.copy()
        n[7] += 1
        return dataclasses.replace(res, n_items=n)

    _patch(monkeypatch, alter)
    r = run_tiny(bench, CELL)
    assert not r["correct"] and r["check"]["count_mismatches"]["value"] >= 1


def test_state_left_unchanged_is_caught(bench, monkeypatch):
    def unchanged(res):
        return dataclasses.replace(res, n_items=np.zeros_like(res.n_items),
                                   energy_mj=np.zeros_like(res.energy_mj))

    _patch(monkeypatch, unchanged)
    r = run_tiny(bench, CELL)
    assert not r["correct"]


def test_float32_control_fails_the_limits(bench):
    """The reference one precision below the configuration's float64, put
    in the program's place and read through the cell's own comparison, is
    refused by the cell's limits; devices die inside the horizon."""
    from bench import harness

    runner = tiny_runner(bench, CELL)
    assert harness.is_correct(runner.check())
    assert (runner.last.n_items < runner.steps).sum() > 100
    control = runner.check("float32")
    assert not harness.is_correct(control)
    assert control["energy_max_rel_err"]["value"] > control["energy_max_rel_err"]["limit"]
