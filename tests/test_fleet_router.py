"""Routed fleet kernel: routing policies, queueing, simulate_trace oracle,
metrics, and the multi-tenant fleet backend."""
import numpy as np
import pytest

import jax.numpy as jnp
from jax import enable_x64

from repro.core import energy_model as em
from repro.core.adaptive import FixedTimeoutPolicy, StaticPolicy, break_even_timeout_ms
from repro.core.phases import paper_lstm_item
from repro.core.simulator import simulate_trace
from repro.fleet import (
    ROUTER_CODES,
    DeviceSpec,
    FleetParams,
    fleet_summary,
    route_counts,
    run_routed,
    uniform_fleet,
)


@pytest.fixture(scope="module")
def item():
    return paper_lstm_item()


def _route(r, policy, alive, q_len, energy, budget, rr=0):
    with enable_x64():
        counts, rr_next = route_counts(
            jnp.asarray(r),
            ROUTER_CODES[policy],
            jnp.asarray(alive, dtype=bool),
            jnp.asarray(q_len, dtype=jnp.int32),
            jnp.asarray(energy, dtype=jnp.float64),
            jnp.asarray(budget, dtype=jnp.float64),
            jnp.asarray(rr, dtype=jnp.int32),
        )
    return np.asarray(counts), int(rr_next)


class TestRouteCounts:
    ALIVE = [True] * 4
    ZEROS = [0.0] * 4
    ONES = [1.0] * 4

    def test_round_robin_spreads_and_rotates(self):
        counts, rr = _route(6, "round_robin", self.ALIVE, [0] * 4, self.ZEROS, self.ONES)
        # base 1 each + extras to devices 0, 1 (pointer at 0)
        np.testing.assert_array_equal(counts, [2, 2, 1, 1])
        assert rr == 2                       # advanced by the remainder
        # pointer at 2: extras go to devices 2, 3, then wrap to 0
        counts, rr = _route(3, "round_robin", self.ALIVE, [0] * 4, self.ZEROS, self.ONES, rr=2)
        np.testing.assert_array_equal(counts, [1, 0, 1, 1])
        assert rr == 1

    def test_conservation(self):
        for policy in ROUTER_CODES:
            counts, _ = _route(13, policy, self.ALIVE, [3, 0, 5, 1], [1, 9, 4, 0], self.ONES)
            assert counts.sum() == 13

    def test_dead_devices_get_nothing(self):
        counts, _ = _route(9, "round_robin", [True, False, True, False],
                           [0] * 4, self.ZEROS, self.ONES)
        assert counts[1] == counts[3] == 0
        assert counts.sum() == 9

    def test_all_dead_drops_everything(self):
        counts, _ = _route(5, "least_loaded", [False] * 4, [0] * 4, self.ZEROS, self.ONES)
        assert counts.sum() == 0

    def test_least_loaded_prefers_short_queues(self):
        counts, _ = _route(2, "least_loaded", self.ALIVE, [5, 0, 3, 1], self.ZEROS, self.ONES)
        np.testing.assert_array_equal(counts, [0, 1, 0, 1])

    def test_power_aware_prefers_remaining_budget(self):
        counts, _ = _route(2, "power_aware", self.ALIVE, [0] * 4,
                           [0.9, 0.1, 0.5, 0.2], self.ONES)
        np.testing.assert_array_equal(counts, [0, 1, 0, 1])


class TestTraceOracleAgreementN1:
    """N=1 routed fleet vs simulate_trace on identical on-grid arrivals."""

    PERIOD = 80.0
    DT = 40.0
    N_ARR = 400
    BUDGET = 3000.0

    def _arrivals(self):
        return [i * self.PERIOD for i in range(self.N_ARR)]

    def _counts(self):
        k = int(self.N_ARR * self.PERIOD / self.DT)
        counts = np.zeros(k, np.int32)
        counts[:: int(self.PERIOD / self.DT)] = 1
        return counts

    @pytest.mark.parametrize("kind", ["idle_waiting", "on_off"])
    def test_static_policies(self, item, kind):
        oracle = simulate_trace(item, self._arrivals(), StaticPolicy(kind, item), self.BUDGET)
        params = FleetParams.from_specs(
            [DeviceSpec(item, strategy=kind, request_period_ms=self.PERIOD,
                        e_budget_mj=self.BUDGET)]
        )
        res = run_routed(params, self._counts(), self.DT, router="round_robin")
        s = res.state
        assert int(s.n_served[0]) == oracle.n_items
        assert abs(float(s.energy_mj[0]) - oracle.energy_used_mj) <= 1e-9
        assert int(s.n_configs[0]) == oracle.configurations
        assert int(s.n_released[0]) == oracle.releases
        assert bool(s.alive[0]) != oracle.exhausted

    def test_break_even_timeout_policy(self, item):
        """The fleet's adaptive arm (ski-rental break-even timeout) agrees
        with a fixed-timeout simulate_trace policy."""
        p_idle = item.idle_power_mw
        timeout = break_even_timeout_ms(item, p_idle)
        oracle = simulate_trace(
            item, self._arrivals(), FixedTimeoutPolicy(timeout, p_idle), self.BUDGET
        )
        params = FleetParams.from_specs(
            [DeviceSpec(item, strategy="adaptive", request_period_ms=self.PERIOD,
                        e_budget_mj=self.BUDGET)]
        )
        assert float(params.timeout_ms[0]) == timeout
        res = run_routed(params, self._counts(), self.DT, router="round_robin")
        s = res.state
        assert int(s.n_served[0]) == oracle.n_items
        assert abs(float(s.energy_mj[0]) - oracle.energy_used_mj) <= 1e-9
        assert int(s.n_released[0]) == oracle.releases

    @pytest.mark.parametrize("kind", ["idle_waiting", "on_off"])
    def test_backlogged_arrivals_charge_no_phantom_release(self, item, kind):
        """Simultaneous arrivals queue; a backlogged request must not
        trigger a spurious timeout release + reconfiguration.  on_off
        matches the trace oracle exactly (idle is never charged); for
        idle_waiting the tick-quantized schedule completes the backlog one
        tick later than the oracle's back-to-back service, so energies
        agree within one tick of idle power per backlogged request."""
        n_pairs = 100
        arrivals = sorted([i * self.PERIOD for i in range(n_pairs)] * 2)
        oracle = simulate_trace(item, arrivals, StaticPolicy(kind, item), 1e6)
        k = int(n_pairs * self.PERIOD / self.DT)
        counts = np.zeros(k, np.int32)
        counts[:: int(self.PERIOD / self.DT)] = 2
        params = FleetParams.from_specs(
            [DeviceSpec(item, strategy=kind, request_period_ms=self.PERIOD,
                        e_budget_mj=1e6)]
        )
        res = run_routed(params, counts, self.DT, router="round_robin")
        s = res.state
        assert int(s.n_served[0]) == oracle.n_items
        assert int(s.n_configs[0]) == oracle.configurations
        assert int(s.n_released[0]) == oracle.releases
        if kind == "on_off":
            assert abs(float(s.energy_mj[0]) - oracle.energy_used_mj) <= 1e-9
        else:
            tick_slack = n_pairs * item.idle_power_mw * self.DT / 1000.0
            diff = abs(float(s.energy_mj[0]) - oracle.energy_used_mj)
            assert diff <= tick_slack


class TestRoutedQueueing:
    def test_request_conservation(self, item):
        """served + still-queued + dropped == offered, across routers."""
        params = uniform_fleet(32, item=item, e_budget_mj=1e9)
        rng = np.random.default_rng(0)
        counts = rng.poisson(24.0, 500).astype(np.int32)
        for router in ROUTER_CODES:
            res = run_routed(params, counts, 10.0, router=router, queue_capacity=4)
            s = res.state
            total = int(np.sum(s.n_served)) + int(np.sum(s.q_len)) + int(np.sum(s.n_dropped))
            assert total == int(counts.sum()), router

    def test_overload_drops_at_queue_capacity(self, item):
        # one device, 5 requests per tick, capacity 2 → most arrivals drop
        params = uniform_fleet(1, item=item, e_budget_mj=1e9)
        counts = np.full(50, 5, np.int32)
        res = run_routed(params, counts, 40.0, router="round_robin", queue_capacity=2)
        s = res.state
        assert int(np.sum(s.n_dropped)) > 0
        assert int(np.sum(s.n_served)) + int(np.sum(s.q_len)) + int(np.sum(s.n_dropped)) == 250

    def test_queued_request_waits_and_latency_reports_it(self, item):
        # two same-tick arrivals on one device: the second serves a tick later
        params = uniform_fleet(1, item=item, e_budget_mj=1e9)
        counts = np.zeros(10, np.int32)
        counts[0] = 2
        res = run_routed(params, counts, 40.0, router="round_robin")
        assert int(np.sum(res.state.n_served)) == 2
        lat = res.latency_ms[res.served_mask]
        assert lat.shape == (2,)
        # first served immediately (exec latency only), second waited ≥ one tick
        assert min(lat) < 1.0
        assert max(lat) >= 40.0

    def test_power_aware_outlives_round_robin_under_skew(self, item):
        """power_aware equalizes depletion, so its devices-alive curve
        dominates round-robin's when budgets are heterogeneous."""
        specs = [
            DeviceSpec(item, strategy="on_off", request_period_ms=40.0,
                       e_budget_mj=200.0 if d % 2 else 2000.0)
            for d in range(8)
        ]
        params = FleetParams.from_specs(specs)
        # under-offered load (4 requests, 8 devices) so routing choice
        # matters: power_aware steers work away from the shallow budgets
        counts = np.full(400, 4, np.int32)
        alive_rr = run_routed(params, counts, 40.0, router="round_robin").alive_over_time
        alive_pa = run_routed(params, counts, 40.0, router="power_aware").alive_over_time
        assert np.all(alive_pa >= alive_rr)
        assert int(alive_pa.sum()) > int(alive_rr.sum())

    def test_routed_arg_validation(self, item):
        params = uniform_fleet(2, item=item)
        with pytest.raises(ValueError, match="router"):
            run_routed(params, np.ones(5, np.int32), 10.0, router=None)
        with pytest.raises(ValueError, match="columns"):
            run_routed(params, np.ones((5, 3), np.int32), 10.0, router=None)
        with pytest.raises(ValueError, match="dt_ms"):
            run_routed(params, np.ones(5, np.int32), 0.0)


class TestScaleAndMetrics:
    def test_4096_devices_routed_scan(self, item):
        params = uniform_fleet(
            4096, item=item, strategies=("on_off", "idle_waiting", "adaptive")
        )
        counts = np.full(250, 4096, np.int32)   # 10 s at one tick per period
        res = run_routed(params, counts, 40.0, router="round_robin")
        summ = fleet_summary(res)
        assert summ["n_devices"] == 4096
        assert summ["requests"]["served"] == 250 * 4096
        assert summ["latency_ms"]["p99"] is not None
        assert summ["energy_per_request_mj"] > 0

    def test_summary_shapes(self, item):
        params = uniform_fleet(4, item=item)
        counts = np.full(20, 4, np.int32)
        summ = fleet_summary(run_routed(params, counts, 40.0))
        for key in ("mode", "router", "requests", "configurations",
                    "latency_ms", "devices_alive_over_time", "energy_per_request_mj"):
            assert key in summ
        curve = summ["devices_alive_over_time"]
        assert len(curve["t_ms"]) == len(curve["alive"]) <= 128

    def test_final_modes_partition_the_fleet(self, item):
        specs = (
            [DeviceSpec(item, strategy="idle_waiting", e_budget_mj=1e9)] * 2   # idle
            + [DeviceSpec(item, strategy="on_off", e_budget_mj=1e9)] * 2       # off
            + [DeviceSpec(item, strategy="on_off", e_budget_mj=10.0)] * 2      # dead
        )
        params = FleetParams.from_specs(specs)
        counts = np.full((100, 6), 1, np.int32)
        summ = fleet_summary(run_routed(params, counts, 40.0, router=None))
        modes = summ["final_modes"]
        assert modes == {"off": 2, "idle": 2, "busy": 0, "dead": 2}
        assert sum(modes.values()) == 6

    def test_exhausted_devices_leave_the_alive_curve(self, item):
        params = uniform_fleet(8, item=item, strategies=("on_off",), e_budget_mj=100.0)
        counts = np.full(300, 8, np.int32)
        res = run_routed(params, counts, 40.0, router="round_robin")
        assert res.alive_over_time[-1] == 0
        assert np.all(np.diff(res.alive_over_time.astype(int)) <= 0)
        # energy stays within every budget
        assert np.all(res.energy_mj <= np.asarray(params.e_budget_mj) + 1e-6)


@pytest.mark.slow
class TestFleetStress:
    """Beyond-tier-1 scale: the CI benchmarks job runs these (`-m slow`)."""

    def test_16384_devices_long_horizon(self, item):
        params = uniform_fleet(
            16384, item=item, strategies=("on_off", "idle_waiting", "adaptive"),
            e_budget_mj=5_000.0,
        )
        counts = np.full(750, 16384, np.int32)    # 30 s at one tick per period
        res = run_routed(params, counts, 40.0, router="least_loaded",
                         collect_latency=False)
        s = res.state
        total = int(np.sum(s.n_served)) + int(np.sum(s.q_len)) + int(np.sum(s.n_dropped))
        assert total == int(counts.sum())
        # the 5 J budget exhausts the on_off third of the fleet mid-horizon
        assert res.alive_over_time[-1] < 16384
        assert np.all(res.energy_mj <= np.asarray(params.e_budget_mj) + 1e-6)

    def test_periodic_full_budget_exhaustion_all_methods(self, item):
        """Every (strategy, method) pair runs its entire paper-budget
        lifetime in one scan and matches the closed-form n_max."""
        from repro.core import energy_model as em
        from repro.core.strategies import IdlePowerMethod
        from repro.fleet import run_periodic

        CAL = em.CALIBRATED_POWERUP_OVERHEAD_MJ
        specs = [
            DeviceSpec(item, strategy="idle_waiting", method=m,
                       request_period_ms=40.0,
                       e_budget_mj=em.PAPER_ENERGY_BUDGET_MJ,
                       powerup_overhead_mj=CAL)
            for m in (IdlePowerMethod.BASELINE, IdlePowerMethod.METHOD1,
                      IdlePowerMethod.METHOD1_2)
        ]
        res = run_periodic(FleetParams.from_specs(specs), n_steps=4_400_000)
        expected = [
            em.idlewait_n_max(item, 40.0, powerup_overhead_mj=CAL),
            em.idlewait_n_max(item, 40.0, idle_power_mw=34.2, powerup_overhead_mj=CAL),
            em.idlewait_n_max(item, 40.0, idle_power_mw=24.0, powerup_overhead_mj=CAL),
        ]
        np.testing.assert_array_equal(res.n_items, expected)


class TestFleetBackend:
    def test_two_tenant_backend(self):
        from repro.serving.fleet_backend import FleetBackend, FleetTenantSpec

        tenants = [
            FleetTenantSpec("hot", 300.0, 0.5, 170.0, 0.01, 100.0,
                            policy="idle_waiting", replicas=8, mean_period_ms=200.0,
                            e_budget_mj=1e9),
            FleetTenantSpec("cold", 300.0, 0.5, 170.0, 0.01, 100.0,
                            policy="on_off", replicas=4, mean_period_ms=5000.0,
                            e_budget_mj=1e9),
        ]
        backend = FleetBackend(tenants)
        assert backend.n_devices == 12
        out = backend.run(horizon_ms=60_000.0, dt_ms=100.0, seed=1)
        assert set(out["tenants"]) == {"hot", "cold"}
        hot, cold = out["tenants"]["hot"], out["tenants"]["cold"]
        assert hot["served"] > cold["served"] > 0
        assert hot["replicas_alive"] == 8
        # idle_waiting tenant configures each replica at most once; the
        # on_off tenant reconfigures per request
        assert hot["configurations"] <= 8
        assert cold["configurations"] == cold["served"]
        assert out["fleet"]["requests"]["served"] == hot["served"] + cold["served"]

    def test_backend_validation(self):
        from repro.serving.fleet_backend import FleetBackend, FleetTenantSpec

        with pytest.raises(ValueError, match="at least one tenant"):
            FleetBackend([])
        with pytest.raises(ValueError, match="unknown policy"):
            FleetTenantSpec("x", 1, 1, 1, 1, 1, policy="nope")
        with pytest.raises(ValueError, match="replicas"):
            FleetTenantSpec("x", 1, 1, 1, 1, 1, replicas=0)


class TestPeriodicRoutedConsistency:
    def test_modes_agree_on_uniform_deterministic_load(self, item):
        """One request per device per period: the routed kernel serves the
        same counts as the periodic kernel over the same horizon, and the
        Idle-Waiting energies coincide (no reconfigs, identical gaps)."""
        from repro.fleet import run_periodic

        budget = 50_000.0
        params = FleetParams.from_specs(
            [DeviceSpec(item, strategy="idle_waiting", request_period_ms=40.0,
                        e_budget_mj=budget)] * 4
        )
        n_steps = 500
        per = run_periodic(params, n_steps)
        counts = np.full((n_steps, 4), 1, np.int32)
        rt = run_routed(params, counts, 40.0, router=None)
        np.testing.assert_array_equal(per.n_items, np.asarray(rt.state.n_served))
        # periodic charges E_init at admission of item 1 and the gap before
        # item n at item n's admission — identical totals to the trace rules
        # once the same item count is served (rel tolerance: accumulation
        # order differs)
        np.testing.assert_allclose(
            per.energy_mj, np.asarray(rt.state.energy_mj), rtol=1e-12
        )
