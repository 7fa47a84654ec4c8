"""``chip_smoke.py``'s phases on the CPU, at the reduced model and a small
fleet.  The TPU requirement lives in its ``main``, tested here to refuse a
CPU-only machine without running anything."""
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Entry points turn on the persistent compile cache; tests do not."""
    from repro.launch import fleet, serve

    monkeypatch.setattr(fleet, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)


def test_serving_phase_reduced(smoke):
    out = smoke.serving_phase(reduced=True, n_requests=3)
    assert out["on_off"]["configurations"] == 3
    assert len(out["on_off"]["bring_up_s"]) == 3
    assert out["idle_waiting"]["configurations"] == 1
    assert out["resident_bytes"] > 0
    assert len(out["tokens"]) == 3
    # the CPU runs the XLA attention, so the "kernel" is the reference itself
    assert not out["prefill_has_pallas_kernel"]
    assert out["prefill_vs_reference_rel_err"] == 0.0


def test_fleet_cli_phase_small(smoke, no_compile_cache):
    out = smoke.fleet_cli_phase(devices=64, horizon_s=1.0)
    assert out["sharded_bit_identical"]
    assert out["oracle_energy_bit_equal"]
    assert out["requests_served"] == 64 * out["n_steps"]
    for n_fleet, n_oracle in out["oracle_counts"].values():
        assert n_fleet == n_oracle


@pytest.mark.parametrize("phase", ["periodic", "sharded"])
def test_periodic_phases_small(smoke, phase):
    if phase == "periodic":
        out = smoke.periodic_phase(n_devices=1000, n_steps=256)
    else:
        out = smoke.sharded_phase(n_chips=1, n_devices=1000, n_steps=256)
        assert out["bit_identical_to_one_chip"]
    assert out["count_mismatches"] == 0
    assert out["ledger_max_rel_err"] <= 1e-9
    # On-Off devices exhaust the 2 J budget inside the horizon; Idle-Waiting
    # devices outlive it
    assert out["devices_alive"] == 500
    assert out["oracle_energy_mismatched_devices"] == 0


def test_main_refuses_a_machine_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_serve_main_passes_reduced_through(monkeypatch, no_compile_cache):
    from repro.launch import serve

    seen = {}

    class Reached(Exception):
        pass

    def fake_build_demo(arch, reduced=False, **kwargs):
        seen.update(arch=arch, reduced=reduced)
        raise Reached

    monkeypatch.setattr(serve, "build_demo", fake_build_demo)
    with pytest.raises(Reached):
        serve.main(["--reduced", "--arch", "qwen3-1.7b", "--requests", "1"])
    assert seen == {"arch": "qwen3-1.7b", "reduced": True}
    with pytest.raises(Reached):
        serve.main([])
    assert seen["reduced"] is False
