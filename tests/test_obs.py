"""Observability layer (ISSUE 8): ledger conservation on every numeric
path, Chrome-trace schema validity, metrics agreement between the host
registry and the in-scan accumulator, run manifests, and the snapshot
comparator.

The central property is **conservation**: on the scalar, fleet (N=1 and
N=4096), Monte Carlo, and policy-rollout paths, the five
:class:`~repro.obs.ledger.EnergyLedger` axes sum to the path's own energy
total within 1e-9 relative — so the observability layer doubles as an
audit of each kernel's internal accounting.
"""
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.core import energy_model as em
from repro.core.adaptive import (
    FixedTimeoutPolicy,
    StaticPolicy,
    break_even_timeout_ms,
)
from repro.core.arrivals import (
    DeterministicArrivals,
    DiurnalArrivals,
    JitteredArrivals,
    MMPPArrivals,
    PoissonArrivals,
)
from repro.core.phases import CONFIGURATION, paper_lstm_item
from repro.core.simulator import simulate, simulate_trace
from repro.core.strategies import IdlePowerMethod
from repro.core.workload import ExperimentSpec, WorkloadSpec
from repro.fleet import run_periodic, run_routed, uniform_fleet
from repro.obs import (
    AXES,
    EnergyLedger,
    Histogram,
    MetricsRegistry,
    TraceRecorder,
    axis_of_phase,
    default_latency_edges_ms,
    fleet_queue_depth_edges,
    ledger_from_rollout,
    render_markdown,
    routed_metrics,
    routed_timeline,
    run_report,
    scan_histogram,
    trace_summary,
    validate_chrome_trace,
)

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import bench_report  # noqa: E402

CAL = em.CALIBRATED_POWERUP_OVERHEAD_MJ
RTOL = 1e-9

PROCESSES = {
    "deterministic": lambda: DeterministicArrivals(40.0),
    "poisson": lambda: PoissonArrivals(40.0),
    "mmpp": lambda: MMPPArrivals(burst_ms=8.0, quiet_ms=200.0),
    "diurnal": lambda: DiurnalArrivals(mean_ms=40.0, day_ms=4000.0),
}


@pytest.fixture(scope="module")
def item():
    return paper_lstm_item()


def _policy(strategy, item):
    if strategy == "adaptive":
        p_idle = item.idle_power_mw
        return FixedTimeoutPolicy(break_even_timeout_ms(item, p_idle, CAL), p_idle)
    return StaticPolicy(strategy, item)


def _axes_close(a: EnergyLedger, b: EnergyLedger, rtol: float = RTOL):
    for axis in AXES:
        x = np.asarray(getattr(a, f"{axis}_mj"), dtype=np.float64)
        y = np.asarray(getattr(b, f"{axis}_mj"), dtype=np.float64)
        err = np.max(np.abs(x - y) / np.maximum(1.0, np.abs(y)), initial=0.0)
        assert err <= rtol, f"axis {axis}: {x} vs {y} ({err:.3e} rel)"


# ---------------------------------------------------------------------------
# EnergyLedger unit behavior
# ---------------------------------------------------------------------------
class TestLedgerUnit:
    def test_axis_mapping(self):
        assert axis_of_phase(CONFIGURATION) == "configure"
        assert axis_of_phase("initial_configuration") == "configure"
        assert axis_of_phase("idle_waiting") == "idle"
        assert axis_of_phase("powerup") == "overhead"
        assert axis_of_phase("initial_powerup") == "overhead"
        assert axis_of_phase("inference") == "compute"
        assert axis_of_phase("anything_else") == "compute"

    def test_from_axes_rejects_unknown(self):
        with pytest.raises(ValueError, match="unknown ledger axes"):
            EnergyLedger.from_axes(configure=1.0, bogus=2.0)

    def test_add_and_aggregate(self):
        a = EnergyLedger.from_axes(configure=np.array([1.0, 2.0]),
                                   compute=np.array([3.0, 4.0]))
        b = EnergyLedger.from_axes(idle=np.array([0.5, 0.5]))
        total = (a + b).aggregate()
        assert total.configure_mj == 3.0
        assert total.idle_mj == 1.0
        assert total.total_mj == 11.0

    def test_add_rejects_shape_mismatch(self):
        # adding a per-device (N,) ledger to a scalar aggregate would
        # broadcast the aggregate onto every row and count it N times
        per_dev = EnergyLedger.from_axes(compute=np.array([1.0, 2.0, 3.0]))
        agg = EnergyLedger.from_axes(compute=10.0)
        with pytest.raises(ValueError, match="aggregate"):
            per_dev + agg
        (per_dev.aggregate() + agg).assert_conserves(16.0)

    def test_conservation_error_normalization(self):
        # sub-unit totals use an absolute denominator of 1 (no false alarms)
        led = EnergyLedger.from_axes(compute=1e-12)
        assert led.conservation_error(0.0) == pytest.approx(1e-12)

    def test_assert_conserves_raises(self):
        led = EnergyLedger.from_axes(compute=100.0)
        with pytest.raises(AssertionError, match="conservation"):
            led.assert_conserves(101.0)

    def test_pytree_roundtrip(self):
        import jax

        led = EnergyLedger.from_axes(configure=1.0, compute=2.0)
        mapped = jax.tree.map(lambda x: x * 2, led)
        assert isinstance(mapped, EnergyLedger)
        assert float(mapped.configure_mj) == 2.0

    def test_fractions_sum_to_one(self):
        led = EnergyLedger.from_axes(configure=2.0, compute=6.0, idle=2.0)
        f = led.fractions()
        assert sum(f.values()) == pytest.approx(1.0)
        assert f["compute"] == pytest.approx(0.6)


# ---------------------------------------------------------------------------
# Conservation: scalar paths
# ---------------------------------------------------------------------------
class TestScalarConservation:
    @pytest.mark.parametrize("process", sorted(PROCESSES), ids=str)
    @pytest.mark.parametrize("strategy", ["on_off", "idle_waiting", "adaptive"])
    def test_trace_ledger_conserves(self, item, strategy, process):
        arrivals = PROCESSES[process]().arrival_times(150, seed=2)
        res = simulate_trace(
            item, arrivals, _policy(strategy, item),
            powerup_overhead_mj=CAL,
        )
        err = res.ledger.assert_conserves(res.energy_used_mj, RTOL)
        assert err <= RTOL

    @pytest.mark.parametrize("budget_mj", [50.0, 2_000.0])
    @pytest.mark.parametrize("strategy", ["on_off", "idle_waiting"])
    def test_trace_ledger_under_budget_exhaustion(self, item, strategy, budget_mj):
        arrivals = DeterministicArrivals(40.0).arrival_times(200, seed=0)
        res = simulate_trace(
            item, arrivals, _policy(strategy, item),
            e_budget_mj=budget_mj, powerup_overhead_mj=CAL,
        )
        res.ledger.assert_conserves(res.energy_used_mj, RTOL)

    @pytest.mark.parametrize("mode", ["fast", "step"])
    @pytest.mark.parametrize("strategy", ["on_off", "idle_waiting"])
    def test_simulate_ledger_conserves(self, item, strategy, mode):
        spec = ExperimentSpec(
            workload=WorkloadSpec(0.1, 40.0),   # 0.1 J: thousands of items
            item=item,
            strategy_kind=strategy,
            method=IdlePowerMethod.METHOD1_2,
            powerup_overhead_mj=CAL,
        )
        res = simulate(spec, mode=mode)
        assert res.n_items > 0
        res.ledger.assert_conserves(res.energy_used_mj, RTOL)


class TestPaperHeadlineViaLedger:
    def test_40x_configuration_energy_reduction_from_configure_rows(self):
        """The paper's ≈40.13× is a ratio of two ledger ``configure`` rows
        (same derivation as the docs/observability.md walkthrough; the
        calibrated model gives 40.12×, within the repo-wide 0.5% bar the
        headline tests in tests/test_system.py use)."""
        from repro.core.config_phase import (
            BEST_PARAMS,
            SPARTAN7_XC7S15,
            WORST_PARAMS,
        )

        def configure_row_mj(params):
            it = paper_lstm_item().with_phase(SPARTAN7_XC7S15.config_phase(params))
            res = simulate_trace(it, [0.0], StaticPolicy("on_off", it))
            return float(res.ledger.configure_mj)

        ratio = configure_row_mj(WORST_PARAMS) / configure_row_mj(BEST_PARAMS)
        assert ratio == pytest.approx(40.13, rel=5e-3)
        assert round(ratio, 2) == 40.12


class TestPowerupSplit:
    """Satellite 1: the calibrated power-up ramp is its own ledger row, not
    folded into the configure phase — on the scalar *and* trace paths."""

    def test_fast_idlewait_reports_initial_powerup(self, item):
        spec = ExperimentSpec(
            workload=WorkloadSpec(0.1, 40.0), item=item,
            strategy_kind="idle_waiting", powerup_overhead_mj=CAL,
        )
        for mode in ("fast", "step"):
            by = simulate(spec, mode=mode).energy_by_phase_mj
            assert by["initial_powerup"] == pytest.approx(CAL)
            # the configure row is the pure bitstream-load energy
            assert by["initial_configuration"] == pytest.approx(
                em.idlewait_init_energy_mj(item, 0.0)
            )

    def test_fast_onoff_reports_powerup_per_item(self, item):
        spec = ExperimentSpec(
            workload=WorkloadSpec(0.1, 40.0), item=item,
            strategy_kind="on_off", powerup_overhead_mj=CAL,
        )
        res = simulate(spec)
        assert res.energy_by_phase_mj["powerup"] == pytest.approx(res.n_items * CAL)

    def test_trace_path_splits_overhead(self, item):
        arrivals = DeterministicArrivals(40.0).arrival_times(5, seed=0)
        res = simulate_trace(
            item, arrivals, StaticPolicy("on_off", item),
            powerup_overhead_mj=CAL,
        )
        by = res.energy_by_phase_mj
        assert by["initial_powerup"] == pytest.approx(CAL)
        assert by["powerup"] == pytest.approx((res.configurations - 1) * CAL)
        led = res.ledger
        assert float(led.overhead_mj) == pytest.approx(res.configurations * CAL)

    def test_no_overhead_rows_without_calibration(self, item):
        arrivals = DeterministicArrivals(40.0).arrival_times(5, seed=0)
        res = simulate_trace(item, arrivals, StaticPolicy("on_off", item))
        assert "powerup" not in res.energy_by_phase_mj
        assert float(res.ledger.overhead_mj) == 0.0


# ---------------------------------------------------------------------------
# Conservation: fleet paths
# ---------------------------------------------------------------------------
class TestFleetConservation:
    @pytest.mark.parametrize("strategy", ["on_off", "idle_waiting"])
    def test_n1_periodic_matches_scalar_ledger(self, item, strategy):
        from repro.fleet import DeviceSpec, FleetParams

        spec = ExperimentSpec(
            workload=WorkloadSpec(41.47, 40.0), item=item,
            strategy_kind=strategy, powerup_overhead_mj=CAL,
        )
        oracle = simulate(spec)
        fleet = run_periodic(
            FleetParams.from_specs([DeviceSpec.from_experiment(spec)]),
            n_steps=oracle.n_items + 10,
        )
        assert int(fleet.n_items[0]) == oracle.n_items
        fled = fleet.ledger()
        fled.assert_conserves(fleet.energy_mj, RTOL)
        _axes_close(fled.aggregate(), oracle.ledger)

    def test_mixed_fleet_n4096_conserves(self):
        params = uniform_fleet(
            4096,
            strategies=("on_off", "idle_waiting", "adaptive"),
            request_period_ms=40.0,
            powerup_overhead_mj=CAL,
        )
        result = run_periodic(params, 200)
        led = result.ledger()
        err = led.assert_conserves(result.energy_mj, RTOL)
        assert err <= RTOL
        # per-device ledger, not a pre-aggregated scalar
        assert np.asarray(led.compute_mj).shape == (4096,)

    def test_routed_fleet_conserves(self):
        params = uniform_fleet(
            12,
            strategies=("on_off", "idle_waiting", "adaptive"),
            request_period_ms=40.0,
            powerup_overhead_mj=CAL,
        )
        counts = np.full(50, 12, dtype=np.int32)
        res = run_routed(params, counts, 40.0, router="round_robin")
        res.ledger().assert_conserves(np.asarray(res.state.energy_mj), RTOL)

    def test_collect_events_does_not_change_physics(self):
        params = uniform_fleet(8, strategies=("on_off", "idle_waiting"),
                               request_period_ms=40.0,
                               powerup_overhead_mj=CAL)
        counts = np.full(40, 8, dtype=np.int32)
        plain = run_routed(params, counts, 40.0, router="round_robin")
        events = run_routed(params, counts, 40.0, router="round_robin",
                            collect_events=True)
        np.testing.assert_array_equal(
            np.asarray(plain.state.energy_mj), np.asarray(events.state.energy_mj)
        )
        np.testing.assert_array_equal(
            np.asarray(plain.state.n_served), np.asarray(events.state.n_served)
        )
        assert plain.reconfig_mask is None
        assert events.reconfig_mask is not None
        assert events.reconfig_mask.shape == (40, 8)
        assert events.queue_depth.shape == (40, 8)


# ---------------------------------------------------------------------------
# Conservation: Monte Carlo + policy rollout paths
# ---------------------------------------------------------------------------
class TestEnsembleConservation:
    def test_periodic_ensemble_zero_jitter(self):
        from repro.mc import run_periodic_ensemble

        params = uniform_fleet(
            3, strategies=("on_off", "idle_waiting", "adaptive"),
            request_period_ms=40.0, powerup_overhead_mj=CAL,
        )
        ens = run_periodic_ensemble(
            params, JitteredArrivals(40.0, 0.0), 300, n_seeds=4, seed=0
        )
        assert ens.ledger is not None
        err = ens.ledger.assert_conserves(ens.total_energy_mj, RTOL)
        assert err <= RTOL
        assert np.asarray(ens.ledger.compute_mj).shape == (4,)

    def test_periodic_ensemble_chunked_merge(self):
        """_merge_ledgers keeps per-seed rows aligned with per-seed totals.

        (Chunked results are NOT expected to equal the unchunked run —
        ensemble randomness is a function of ``(seed, seed_chunk)`` by
        contract — but every merged seed row must still conserve against
        that seed's own total, and the merge must be a pure concatenation
        of the chunk ledgers.)"""
        import jax

        from repro.mc import run_periodic_ensemble

        params = uniform_fleet(3, strategies=("idle_waiting",),
                               request_period_ms=40.0,
                               powerup_overhead_mj=CAL)
        process = PoissonArrivals(40.0)
        chunked = run_periodic_ensemble(params, process, 200, n_seeds=4,
                                        seed=7, seed_chunk=2)
        assert np.asarray(chunked.ledger.idle_mj).shape == (4,)
        chunked.ledger.assert_conserves(chunked.total_energy_mj, RTOL)
        # the merged rows are exactly the two chunks' rows, in order
        first = run_periodic_ensemble(params, process, 200, n_seeds=2,
                                      seed=7, seed_chunk=2)
        _axes_close(
            first.ledger,
            jax.tree.map(lambda x: np.asarray(x)[:2], chunked.ledger),
            rtol=0.0,
        )

    def test_routed_ensemble_conserves(self):
        from repro.mc import routed_ensemble

        params = uniform_fleet(4, strategies=("on_off", "idle_waiting"),
                               request_period_ms=40.0,
                               powerup_overhead_mj=CAL)
        counts = np.ones((2, 50, 4), dtype=np.int32)
        ens = routed_ensemble(params, counts, 40.0)
        assert ens.ledger is not None
        ens.ledger.assert_conserves(ens.total_energy_mj, RTOL)


class TestRolloutConservation:
    def test_rollout_ledger_conserves(self, item):
        import jax

        from repro.policy import net as N
        from repro.policy.rollout import make_consts, rollout

        consts = make_consts(item, powerup_overhead_mj=CAL)
        params = N.init_mlp(jax.random.PRNGKey(1))
        gaps = PoissonArrivals(40.0).sample_gaps(jax.random.PRNGKey(0), 4, 128)
        out = rollout(params, gaps, consts)
        led = ledger_from_rollout(out, consts)
        err = led.assert_conserves(out["energy_mj"], RTOL)
        assert err <= RTOL
        # idle + configure + overhead + compute, nothing lands on "off"
        assert float(np.max(np.asarray(led.off_mj))) == 0.0


# ---------------------------------------------------------------------------
# Traces
# ---------------------------------------------------------------------------
class TestTraces:
    def test_scalar_trace_schema(self, item):
        rec = TraceRecorder()
        p_idle = item.idle_power_mw
        policy = FixedTimeoutPolicy(
            break_even_timeout_ms(item, p_idle, CAL), p_idle
        )
        arrivals = [0.0, 10.0, 700.0, 710.0, 2500.0]
        res = simulate_trace(item, arrivals, policy,
                             powerup_overhead_mj=CAL, recorder=rec)
        assert res.n_items == 5
        payload = rec.to_chrome()
        assert validate_chrome_trace(payload) == []
        names = {e["name"] for e in payload["traceEvents"] if e["ph"] != "M"}
        assert {"arrival", "serve", "initial_configuration"} <= names
        # the long gaps exceeded the break-even timeout → releases happened
        assert res.releases >= 1
        assert "timeout_release" in names

    def test_routed_timeline_schema(self, tmp_path):
        params = uniform_fleet(6, strategies=("on_off", "idle_waiting", "adaptive"),
                               request_period_ms=40.0,
                               powerup_overhead_mj=CAL)
        counts = np.full(30, 6, dtype=np.int32)
        res = run_routed(params, counts, 40.0, router="round_robin",
                         collect_latency=True, collect_events=True)
        rec = routed_timeline(res)
        payload = rec.to_chrome()
        assert validate_chrome_trace(payload) == []
        out = tmp_path / "trace.json"
        rec.write(str(out))
        loaded = json.loads(out.read_text())
        assert validate_chrome_trace(loaded) == []
        names = {e["name"] for e in loaded["traceEvents"]}
        assert "serve" in names
        assert "devices_alive" in names          # counter track
        summ = trace_summary(loaded)
        assert summ["n_events"] > 0
        assert summ["span_ms"] > 0

    def test_routed_timeline_requires_event_arrays(self):
        params = uniform_fleet(2, strategies=("idle_waiting",),
                               request_period_ms=40.0)
        counts = np.full(10, 2, dtype=np.int32)
        res = run_routed(params, counts, 40.0, router="round_robin")
        with pytest.raises(ValueError, match="collect_events"):
            routed_timeline(res)

    def test_validator_flags_problems(self):
        bad = {"traceEvents": [
            {"name": "x", "ph": "B", "ts": 0, "pid": 1, "tid": 1},
            {"name": "y", "ph": "X", "ts": -5, "dur": 1, "pid": 1, "tid": 1},
        ]}
        errors = validate_chrome_trace(bad)
        assert any("unbalanced" in e or "unclosed" in e for e in errors)
        assert any("ts" in e for e in errors)

    def test_recorder_rejects_nonfinite(self):
        rec = TraceRecorder()
        with pytest.raises(ValueError):
            rec.instant("bad", float("nan"))


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_host_and_scan_histograms_agree(self):
        rng = np.random.default_rng(3)
        values = rng.lognormal(mean=2.0, sigma=1.5, size=(40, 16))
        mask = rng.random((40, 16)) < 0.7
        edges = default_latency_edges_ms()
        host = Histogram("h", edges)
        host.observe_many(values, mask=mask)
        scanned = scan_histogram(values, edges, mask=mask)
        np.testing.assert_array_equal(host.counts, scanned)
        assert host.total == int(mask.sum())

    def test_registry_get_or_create_and_type_safety(self):
        reg = MetricsRegistry()
        c = reg.counter("x")
        assert reg.counter("x") is c
        with pytest.raises(TypeError):
            reg.gauge("x")
        reg.histogram("h", edges=[1.0, 2.0])
        with pytest.raises(ValueError, match="different edges"):
            reg.histogram("h", edges=[1.0, 3.0])

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("c").inc(-1)

    def test_percentiles(self):
        h = Histogram("lat", edges=list(np.linspace(1, 100, 100)))
        h.observe_many(np.arange(1, 101, dtype=np.float64))
        assert h.percentile(50) == pytest.approx(50.0, rel=0.05)
        assert h.percentile(99) == pytest.approx(99.0, rel=0.05)
        assert Histogram("empty", edges=[1.0]).percentile(50) is None

    def test_percentile_open_ended_buckets_report_finite_edge(self):
        # underflow may hold negative observations: report edges[0], never
        # a value interpolated from an invented 0.0 lower bound
        h = Histogram("signed", edges=[-1.0, 1.0])
        h.observe_many([-5.0, -3.0, -2.0])
        assert h.percentile(50) == -1.0
        over = Histogram("over", edges=[1.0])
        over.observe_many([10.0, 20.0])
        assert over.percentile(99) == 1.0

    def test_fleet_queue_depth_edges_helper(self):
        small = fleet_queue_depth_edges(4, 3)  # 12 <= 128: unit-width buckets
        np.testing.assert_array_equal(small, np.arange(13.0))
        big = fleet_queue_depth_edges(16, 256)  # log-spaced past 128
        assert big[0] == 0.0 and big[-1] == 16 * 256
        assert np.all(np.diff(big) > 0)
        with pytest.raises(ValueError):
            fleet_queue_depth_edges(0, 4)

    def test_fleet_queue_depth_spans_fleet_capacity(self):
        # fleet-total backlog across N devices must not saturate at one
        # device's queue capacity
        n_dev, qcap = 12, 4
        params = uniform_fleet(n_dev, strategies=("idle_waiting",),
                               request_period_ms=40.0,
                               powerup_overhead_mj=CAL)
        counts = np.full(10, n_dev, dtype=np.int32)
        res = run_routed(params, counts, 40.0, router="round_robin",
                         queue_capacity=qcap)
        d = routed_metrics(res).to_dict()["fleet_queue_depth"]
        assert d["edges"][-1] == qcap * n_dev
        assert d["total"] == np.asarray(res.queued_over_time).size
        assert d["counts"][-1] == 0  # backlog can never exceed fleet capacity

    def test_routed_metrics_match_state(self):
        params = uniform_fleet(6, strategies=("on_off", "idle_waiting"),
                               request_period_ms=40.0,
                               powerup_overhead_mj=CAL)
        counts = np.full(30, 6, dtype=np.int32)
        res = run_routed(params, counts, 40.0, router="round_robin",
                         collect_latency=True)
        reg = routed_metrics(res)
        d = reg.to_dict()
        s = res.state
        assert d["requests_served"]["value"] == int(np.sum(np.asarray(s.n_served)))
        assert d["configurations"]["value"] == int(np.sum(np.asarray(s.n_configs)))
        assert d["devices_alive"]["value"] == int(np.asarray(s.alive).sum())
        lat = d["request_latency_ms"]
        assert lat["total"] == int(np.asarray(res.served_mask).sum())
        assert lat["p50"] is not None


# ---------------------------------------------------------------------------
# Manifest + report + summaries
# ---------------------------------------------------------------------------
class TestManifestAndReport:
    def test_run_manifest_fields(self):
        from repro.launch._cli import run_manifest

        m = run_manifest(seed=5)
        assert m["seed"] == 5
        assert isinstance(m["git_sha"], str) and len(m["git_sha"]) == 40
        assert m["versions"]["python"]
        assert m["versions"]["jax"]
        assert m["versions"]["numpy"]
        assert m["device"]["platform"] == jax.devices()[0].platform
        assert m["device"]["kind"] == jax.devices()[0].device_kind
        assert m["device"]["count"] == len(jax.devices())
        assert m["unix_time"] > 0
        assert "T" in m["timestamp"]

    def test_emit_stamps_manifest(self, tmp_path):
        from repro.launch._cli import emit

        out = tmp_path / "payload.json"
        emit({"kind": "x", "config": {"seed": 7}}, str(out))
        payload = json.loads(out.read_text())
        assert payload["manifest"]["seed"] == 7
        assert payload["manifest"]["git_sha"]

    def test_emit_respects_existing_manifest(self, tmp_path):
        from repro.launch._cli import emit

        out = tmp_path / "payload.json"
        emit({"kind": "x", "manifest": {"git_sha": "pinned"}}, str(out))
        assert json.loads(out.read_text())["manifest"] == {"git_sha": "pinned"}

    def test_run_report_markdown(self):
        led = EnergyLedger.from_axes(configure=10.0, compute=30.0, idle=5.0,
                                     overhead=1.0)
        reg = MetricsRegistry()
        reg.counter("requests_served").inc(42)
        report = run_report(
            ledger=led, metrics=reg,
            conservation={"fleet_periodic": 1.2e-16},
            config={"seed": 0},
        )
        assert report["kind"] == "obs"
        assert report["ledger"]["total_mj"] == pytest.approx(46.0)
        md = render_markdown(report)
        assert "## Energy ledger" in md
        assert "requests_served" in md
        assert "Conservation" in md

    def test_fleet_summaries_carry_ledger(self):
        from repro.fleet.metrics import periodic_summary, routed_summary

        params = uniform_fleet(4, strategies=("on_off", "idle_waiting"),
                               request_period_ms=40.0,
                               powerup_overhead_mj=CAL)
        psum = periodic_summary(run_periodic(params, 50))
        assert psum["ledger"]["total_mj"] == pytest.approx(
            psum["total_energy_mj"], rel=RTOL
        )
        counts = np.full(20, 4, dtype=np.int32)
        rsum = routed_summary(run_routed(params, counts, 40.0,
                                         router="round_robin"))
        assert rsum["ledger"]["total_mj"] == pytest.approx(
            rsum["total_energy_mj"], rel=RTOL
        )


# ---------------------------------------------------------------------------
# Snapshot comparator (tools/bench_report.py) + obs perf-regression kind
# ---------------------------------------------------------------------------
class TestBenchReport:
    BASE = {
        "kind": "fleet",
        "config": {"devices": 64, "seed": 0},
        "throughput": {"periodic": {"fleet": {
            "devices_per_s": 100_000.0, "elapsed_s": 0.5,
        }}},
        "manifest": {"git_sha": "aaa", "unix_time": 1.0},
    }

    def _current(self, devices_per_s, elapsed_s=0.5):
        cur = json.loads(json.dumps(self.BASE))
        cur["throughput"]["periodic"]["fleet"]["devices_per_s"] = devices_per_s
        cur["throughput"]["periodic"]["fleet"]["elapsed_s"] = elapsed_s
        return cur

    def test_flatten_skips_provenance(self):
        flat = bench_report.flatten(self.BASE)
        assert "throughput.periodic.fleet.devices_per_s" in flat
        assert not any(k.startswith(("manifest", "config")) for k in flat)

    def test_flatten_skips_segments_not_substrings(self):
        flat = bench_report.flatten({
            "config": {"seed": 3},
            "throughput": {"seeded_runs_per_s": 5.0},
            "metrics": {"lat": {"edges": [1.0, 2.0], "counts": [0, 1],
                                "p50": 1.5}},
        })
        assert flat["throughput.seeded_runs_per_s"] == 5.0  # substring "seed"
        assert "config.seed" not in flat
        assert "metrics.lat.p50" in flat
        assert not any(k.endswith((".edges.0", ".counts.0")) for k in flat)

    def test_direction_heuristics(self):
        assert bench_report.direction_of("a.devices_per_s") == 1
        assert bench_report.direction_of("x.speedup_devices_per_s") == 1
        assert bench_report.direction_of("a.elapsed_s") == -1
        assert bench_report.direction_of("metrics.request_latency_ms.p99") == -1
        assert bench_report.direction_of("summary.items_total") == 0

    def test_detects_regression_and_improvement(self):
        recs = bench_report.compare(
            bench_report.flatten(self.BASE),
            bench_report.flatten(self._current(50_000.0, elapsed_s=0.1)),
            threshold=0.10,
        )
        by = {r["metric"]: r for r in recs}
        assert by["throughput.periodic.fleet.devices_per_s"]["status"] == "regression"
        assert by["throughput.periodic.fleet.elapsed_s"]["status"] == "improvement"

    def test_within_threshold_is_ok(self):
        recs = bench_report.compare(
            bench_report.flatten(self.BASE),
            bench_report.flatten(self._current(95_000.0)),
            threshold=0.10,
        )
        by = {r["metric"]: r for r in recs}
        assert by["throughput.periodic.fleet.devices_per_s"]["status"] == "ok"

    def test_main_exit_codes(self, tmp_path, capsys):
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps(self.BASE))
        b.write_text(json.dumps(self._current(50_000.0)))
        out_json = tmp_path / "cmp.json"
        rc = bench_report.main([str(a), str(b), "--json", str(out_json)])
        assert rc == 1
        assert "REGRESSION" in capsys.readouterr().out
        cmp_payload = json.loads(out_json.read_text())
        assert cmp_payload["n_regressions"] == 1

        b.write_text(json.dumps(self._current(101_000.0)))
        assert bench_report.main([str(a), str(b)]) == 0

    def test_obs_kind_enforced_by_perf_regression(self):
        from repro.testing.perf_regression import check_bench_json

        payload = {"kind": "obs", "throughput": {"periodic": {"fleet": {
            "devices_per_s": 1e9,
        }}}}
        recs = check_bench_json(payload, scale=1.0)
        assert [r["ok"] for r in recs] == [True]
        recs = check_bench_json({"kind": "obs"}, scale=1.0)
        assert recs[0]["ok"] is False and "missing field" in recs[0]["error"]


# ---------------------------------------------------------------------------
# End-to-end CLI: combined periodic+routed ledger must conserve
# ---------------------------------------------------------------------------
class TestObsCLI:
    def test_report_combined_ledger_conserves(self, tmp_path):
        from repro.launch import obs

        out = tmp_path / "OBS_report.json"
        trace = tmp_path / "OBS_trace.json"
        rc = obs.main([
            "--devices", "8", "--horizon", "0.4",
            "--out", str(out), "--trace-out", str(trace),
        ])
        assert rc == 0
        report = json.loads(out.read_text())
        # the report's aggregated ledger is the sum of the two paths' totals
        # (an (N,)-per-device + scalar-aggregate mix would count one path's
        # energy N times); the CLI self-check must cover the combined ledger
        expected = (report["summary"]["periodic"]["energy_total_mj"]
                    + report["summary"]["routed"]["energy_total_mj"])
        assert report["ledger"]["total_mj"] == pytest.approx(expected, rel=RTOL)
        assert report["conservation"]["combined"] <= RTOL
        assert validate_chrome_trace(json.loads(trace.read_text())) == []
