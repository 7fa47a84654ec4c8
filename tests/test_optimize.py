"""Tests for the differentiable configuration optimizer + fleet budget
planner (``repro.optimize``).

Four contracts:

* **gradient correctness** — ``jax.grad`` of the relaxed losses matches
  central finite differences on randomized parameter points;
* **relaxation exactness** — at every one-hot corner the relaxed closed
  forms equal the exact oracle values bit-for-bit;
* **argmin agreement** — multi-start descent recovers the exhaustive
  sweep's argmin/argmax on the paper grid EXACTLY (same configuration,
  same float);
* **planner exactness** — allocated budgets sum to the fleet budget by
  construction, and replaying an allocation through ``run_periodic``
  reproduces the predicted item counts, energies and lifetimes
  bit-for-bit.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import enable_x64

from repro.core import energy_model as em
from repro.core.batch_eval import config_phase_grid
from repro.core.config_phase import (
    SPARTAN7_XC7S15,
    SPARTAN7_XC7S25,
    SPI_BUSWIDTHS,
    SPI_CLOCKS_MHZ,
    optimal_params,
)
from repro.core.phases import paper_lstm_item
from repro.core.strategies import IdlePowerMethod
from repro.fleet import DeviceSpec, FleetParams, run_periodic
from repro.optimize import (
    DescentSettings,
    optimize_config,
    optimize_lifetime,
    plan_budgets,
    relax,
    replay_allocation,
    trace_config_frontier,
)

OVERHEAD = em.CALIBRATED_POWERUP_OVERHEAD_MJ
FAST = DescentSettings(n_starts=6, steps=150)


@pytest.fixture(scope="module")
def problem():
    return relax.RelaxedProblem.from_device(
        SPARTAN7_XC7S15,
        request_period_ms=40.0,
        idle_power_mw=24.0,
        powerup_overhead_mj=OVERHEAD,
    )


def _random_params(seed):
    rng = np.random.default_rng(seed)
    with enable_x64():
        return {
            "f_raw": jnp.float64(rng.uniform(5.0, 60.0)),
            "w_logits": jnp.asarray(rng.normal(0, 1, 3), jnp.float64),
            "c_logits": jnp.asarray(rng.normal(0, 1, 2), jnp.float64),
        }


# ---------------------------------------------------------------------------
# gradient correctness vs central finite differences
# ---------------------------------------------------------------------------
class TestGradients:
    @pytest.mark.parametrize("loss_name", ["config_energy_loss", "lifetime_loss"])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_grad_matches_central_differences(self, problem, loss_name, seed):
        loss = getattr(relax, loss_name)
        params = _random_params(seed)
        with enable_x64():
            grads = jax.grad(loss)(params, problem)
            flat, tree = jax.tree_util.tree_flatten(params)
            gflat = jax.tree_util.tree_leaves(grads)
            h = 1e-4
            for li, leaf in enumerate(flat):
                shape = np.shape(leaf)
                for idx in np.ndindex(shape or (1,)):
                    def perturbed(delta):
                        l2 = list(flat)
                        arr = np.array(leaf, dtype=np.float64)
                        if arr.ndim:
                            arr[idx] += delta
                        else:
                            arr = arr + delta
                        l2[li] = jnp.asarray(arr)
                        return float(loss(jax.tree_util.tree_unflatten(tree, l2), problem))

                    fd = (perturbed(h) - perturbed(-h)) / (2 * h)
                    an = float(np.asarray(gflat[li])[idx] if shape else gflat[li])
                    assert an == pytest.approx(fd, rel=1e-4, abs=1e-7 * max(1.0, abs(fd)))

    def test_soft_pareto_weight_grad_and_limit(self):
        from repro.core.pareto import pareto_mask, soft_pareto_weight

        rng = np.random.default_rng(3)
        costs = rng.random((40, 2))
        with enable_x64():
            c = jnp.asarray(costs)
            g = jax.grad(lambda x: jnp.sum(soft_pareto_weight(x, 0.1)))(c)
            assert np.isfinite(np.asarray(g)).all()
            w = np.asarray(soft_pareto_weight(c, 1e-5))
        # the τ→0 limit is the hard frontier mask
        assert np.array_equal(w > 0.5, pareto_mask(costs))


# ---------------------------------------------------------------------------
# relaxation exactness at one-hot corners
# ---------------------------------------------------------------------------
class TestRelaxationExactness:
    def test_kernel_accepts_scalar_booleans(self):
        """The documented usage — Python scalars + boolean compression —
        must work and agree with the exact oracle (regression: the bool
        branch used to touch ``lanes.dtype`` on a Python float)."""
        from repro.core.batch_eval import DeviceArrays, config_phase_kernel
        from repro.core.config_phase import ConfigParams

        with enable_x64():
            cols = DeviceArrays.from_devices([SPARTAN7_XC7S15]).reshape(()).cols()
            out = config_phase_kernel(cols, 4, 66.0, True)
            assert float(out["config_energy_mj"]) == SPARTAN7_XC7S15.config_energy_mj(
                ConfigParams(4, 66, True)
            )

    @pytest.mark.parametrize("w_i,f,c", [(0, 3.0, False), (2, 66.0, True), (1, 22.0, True)])
    def test_one_hot_corner_is_exact(self, problem, w_i, f, c):
        """At a one-hot choice the expectation collapses to the exact
        oracle value of that grid point — same float, not approximately."""
        with enable_x64():
            w_probs = jnp.zeros(3, jnp.float64).at[w_i].set(1.0)
            e, t = relax.relaxed_config(
                problem, jnp.float64(f), w_probs, jnp.float64(1.0 if c else 0.0)
            )
        g = config_phase_grid(SPARTAN7_XC7S15, (SPI_BUSWIDTHS[w_i],), (f,), (c,))
        assert float(e) == float(g["config_energy_mj"].reshape(()))
        assert float(t) == float(g["config_time_ms"].reshape(()))

    def test_straight_through_round(self):
        with enable_x64():
            grid = jnp.asarray([3.0, 6.0, 9.0])
            x = jnp.float64(7.2)
            y = relax.straight_through_round(x, grid)
            assert float(y) == 6.0
            # ST estimator: forward uses the snapped value, backward is the
            # identity — d/dx ST(x)² = 2·snap(x)·1 = 12, not 2·x
            assert float(jax.grad(lambda v: relax.straight_through_round(v, grid) ** 2)(x)) \
                == pytest.approx(2 * 6.0)

    def test_straight_through_onehot(self):
        with enable_x64():
            logits = jnp.asarray([0.1, 2.0, -1.0], jnp.float64)
            y = relax.straight_through_onehot(logits)
            assert np.array_equal(np.asarray(y), [0.0, 1.0, 0.0])
            g = jax.grad(lambda l: jnp.sum(relax.straight_through_onehot(l) * l))(logits)
            assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# descent argmin == exhaustive argmin (EXACT, on the paper grid)
# ---------------------------------------------------------------------------
class TestDescentArgminAgreement:
    @pytest.mark.parametrize("device", [SPARTAN7_XC7S15, SPARTAN7_XC7S25])
    def test_config_energy_argmin_exact(self, device):
        res = optimize_config(device, settings=FAST)
        oracle = optimal_params(device)
        assert res.best["buswidth"] == oracle.params.buswidth
        assert res.best["clock_mhz"] == oracle.params.clock_mhz
        assert res.best["compression"] == oracle.params.compression
        assert res.best["config_energy_mj"] == oracle.config_energy_mj

    def test_lifetime_argmax_exact_vs_sweep(self):
        from repro.core.batch_eval import SweepGrid, sweep_batch

        grid = SweepGrid(
            devices=(SPARTAN7_XC7S15,),
            request_periods_ms=(40.0,),
            idle_methods=(IdlePowerMethod.METHOD1_2,),
            powerup_overhead_mj=OVERHEAD,
        )
        lt = sweep_batch(grid)["adaptive_lifetime_ms"]
        ix = np.unravel_index(np.argmax(lt), lt.shape)
        res = optimize_lifetime(
            SPARTAN7_XC7S15, powerup_overhead_mj=OVERHEAD, settings=FAST
        )
        assert res.best["buswidth"] == grid.buswidths[ix[1]]
        assert res.best["clock_mhz"] == float(grid.clocks_mhz[ix[2]])
        assert res.best["compression"] == bool(grid.compression[ix[3]])
        assert res.best["lifetime_ms"] == float(lt[ix])

    def test_densified_grid_still_exact(self):
        """On a 10×-denser clock axis (off-Table-1 points) descent still
        lands on the dense grid's exact argmin."""
        clocks = tuple(np.linspace(min(SPI_CLOCKS_MHZ), max(SPI_CLOCKS_MHZ), 111))
        g = config_phase_grid(SPARTAN7_XC7S15, clocks_mhz=clocks)
        e = g["config_energy_mj"]
        ix = np.unravel_index(np.argmin(e), e.shape)
        res = optimize_config(SPARTAN7_XC7S15, clocks_mhz=clocks, settings=FAST)
        assert res.best["clock_mhz"] == float(clocks[ix[2]])
        assert res.best["config_energy_mj"] == float(e[ix])

    def test_frontier_trace_covers_exact_frontier(self):
        from repro.core.pareto import config_pareto

        traced = trace_config_frontier(
            SPARTAN7_XC7S15,
            lambdas=(0.1, 0.5, 0.9),
            settings=DescentSettings(n_starts=3, steps=120),
        )
        exact = {
            (r["buswidth"], r["clock_mhz"], r["compression"])
            for r in config_pareto(SPARTAN7_XC7S15)
        }
        got = {
            (r["buswidth"], r["clock_mhz"], r["compression"])
            for r in traced["points"]
        }
        assert exact <= got


# ---------------------------------------------------------------------------
# fleet budget planner
# ---------------------------------------------------------------------------
def _mixed_fleet(n=12):
    item = paper_lstm_item()
    template = [
        ("idle_waiting", 40.0, IdlePowerMethod.METHOD1_2),
        ("on_off", 80.0, IdlePowerMethod.BASELINE),
        ("adaptive", 120.0, IdlePowerMethod.METHOD1),
        ("idle_waiting", 200.0, IdlePowerMethod.BASELINE),
    ]
    specs = [
        DeviceSpec(
            item=item,
            strategy=s,
            method=m,
            request_period_ms=p,
            powerup_overhead_mj=OVERHEAD,
        )
        for s, p, m in template
    ]
    return FleetParams.from_specs([specs[i % len(specs)] for i in range(n)])


class TestPlanner:
    @pytest.mark.parametrize("objective", ["min_lifetime", "total_requests"])
    def test_conservation_and_exact_replay(self, objective):
        params = _mixed_fleet()
        budget = 12 * em.PAPER_ENERGY_BUDGET_MJ / 40.0
        alloc = plan_budgets(params, budget, n_cap=300_000, objective=objective)
        # conservation: allocated + leftover IS the fleet budget (identity
        # by construction), nothing over-spent, caps respected
        assert float(alloc.budgets_mj.sum()) + alloc.leftover_mj == budget
        assert alloc.leftover_mj >= 0.0
        assert (alloc.n_items <= alloc.n_cap).all()
        # bit-for-bit replay through the vectorized periodic kernel
        rep = replay_allocation(params, alloc)
        assert rep["exact"]
        assert rep["lifetime_max_rel_err"] == 0.0
        assert rep["energy_max_rel_err"] == 0.0
        result = rep["result"]
        assert np.array_equal(result.n_items, alloc.n_items)
        assert np.array_equal(result.lifetime_ms, alloc.predicted_lifetime_ms)
        assert np.array_equal(result.energy_mj, alloc.budgets_mj)

    def test_total_requests_dominates_min_lifetime(self):
        params = _mixed_fleet()
        budget = 12 * em.PAPER_ENERGY_BUDGET_MJ / 40.0
        a = plan_budgets(params, budget, 300_000, objective="total_requests")
        b = plan_budgets(params, budget, 300_000, objective="min_lifetime")
        assert a.total_requests >= b.total_requests
        assert b.min_lifetime_ms >= a.min_lifetime_ms

    def test_min_lifetime_waterfills(self):
        """With ample per-device variety the max-min allocation equalizes
        lifetimes to within one request period."""
        params = _mixed_fleet()
        budget = 12 * em.PAPER_ENERGY_BUDGET_MJ / 40.0
        alloc = plan_budgets(params, budget, 10**7, objective="min_lifetime")
        spread = alloc.predicted_lifetime_ms.max() - alloc.predicted_lifetime_ms.min()
        assert spread <= float(np.asarray(params.period_ms).max())

    def test_zero_budget_and_infeasible_devices(self):
        item = paper_lstm_item()
        specs = [
            DeviceSpec(item=item, strategy="on_off", request_period_ms=1.0),  # infeasible
            DeviceSpec(item=item, strategy="idle_waiting", request_period_ms=40.0),
        ]
        params = FleetParams.from_specs(specs)
        zero = plan_budgets(params, 0.0, 100)
        assert zero.total_requests == 0 and replay_allocation(params, zero)["exact"]
        alloc = plan_budgets(params, 1e5, 1000, objective="total_requests")
        assert alloc.n_items[0] == 0          # infeasible device gets nothing
        assert alloc.n_items[1] == 1000       # cap binds for the feasible one
        assert replay_allocation(params, alloc)["exact"]

    def test_per_device_caps(self):
        params = _mixed_fleet(4)
        caps = np.asarray([1, 2, 3, 4], dtype=np.int64)
        alloc = plan_budgets(params, 1e6, caps, objective="total_requests")
        assert (alloc.n_items == caps).all()   # budget is ample, caps bind
        assert replay_allocation(params, alloc)["exact"]

    def test_rejects_bad_inputs(self):
        params = _mixed_fleet(4)
        with pytest.raises(ValueError, match="objective"):
            plan_budgets(params, 1.0, 10, objective="nope")
        with pytest.raises(ValueError, match="non-negative"):
            plan_budgets(params, -1.0, 10)
        with pytest.raises(ValueError, match="n_cap"):
            plan_budgets(params, 1.0, -3)

    def test_with_budgets_validates_shape(self):
        params = _mixed_fleet(4)
        with pytest.raises(ValueError, match="shape"):
            params.with_budgets(np.ones(3))

    def test_spec_with_budget_matches_column_replacement(self):
        """The spec-level and column-level planner hand-offs agree: specs
        rebuilt via DeviceSpec.with_budget stack to the same fleet as
        FleetParams.with_budgets on the original stack."""
        item = paper_lstm_item()
        specs = [
            DeviceSpec(item=item, strategy=s, request_period_ms=p,
                       powerup_overhead_mj=OVERHEAD)
            for s, p in [("idle_waiting", 40.0), ("on_off", 80.0)]
        ]
        params = FleetParams.from_specs(specs)
        alloc = plan_budgets(params, 1e4, 10_000, objective="total_requests")
        rebuilt = FleetParams.from_specs(
            [s.with_budget(b) for s, b in zip(specs, alloc.budgets_mj)]
        )
        replaced = params.with_budgets(alloc.budgets_mj)
        for field in ("e_budget_mj", "e_item_mj", "e_init_mj", "e_idle_mj"):
            assert np.array_equal(
                np.asarray(getattr(rebuilt, field)),
                np.asarray(getattr(replaced, field)),
            )


class TestBackendPlacement:
    def test_plan_and_replay_through_backend(self):
        from repro.optimize.planner import replay_allocation as replay
        from repro.serving.fleet_backend import FleetBackend, FleetTenantSpec

        tenants = [
            FleetTenantSpec("a", 300.0, 0.04, 180.0, 0.03, 24.0,
                            policy="auto", replicas=3, mean_period_ms=500.0),
            FleetTenantSpec("b", 300.0, 0.04, 160.0, 0.02, 34.2,
                            policy="idle_waiting", replicas=2, mean_period_ms=200.0),
            FleetTenantSpec("c", 300.0, 0.04, 200.0, 0.05, 134.3,
                            policy="on_off", replicas=2, mean_period_ms=2000.0),
        ]
        be = FleetBackend(tenants)
        alloc, per_tenant = be.plan_budgets(2e5, horizon_ms=3_600_000.0)
        # per-tenant aggregation is a partition of the device allocation
        assert sum(t["budget_mj"] for t in per_tenant.values()) == pytest.approx(
            float(alloc.budgets_mj.sum())
        )
        assert sum(t["planned_requests"] for t in per_tenant.values()) \
            == alloc.total_requests
        assert replay(be.params, alloc)["exact"]
        planned = be.with_allocation(alloc)
        assert np.array_equal(
            np.asarray(planned.params.e_budget_mj), alloc.budgets_mj
        )
        # every non-budget column untouched
        assert np.array_equal(
            np.asarray(planned.params.e_item_mj), np.asarray(be.params.e_item_mj)
        )

    def test_periodic_replay_matches_scalar_oracle_budgets(self):
        """A planned single-device budget behaves exactly like the scalar
        closed form at that budget (the planner's budgets are ordinary
        budgets, not a special code path)."""
        item = paper_lstm_item()
        spec = DeviceSpec(
            item=item,
            strategy="idle_waiting",
            method=IdlePowerMethod.METHOD1_2,
            request_period_ms=40.0,
            powerup_overhead_mj=OVERHEAD,
        )
        params = FleetParams.from_specs([spec])
        alloc = plan_budgets(params, 50_000.0, 10**6, objective="total_requests")
        n_scalar = em.idlewait_n_max(
            item, 40.0, float(alloc.budgets_mj[0]), idle_power_mw=24.0,
            powerup_overhead_mj=OVERHEAD,
        )
        assert int(alloc.n_items[0]) == n_scalar
        res = run_periodic(params.with_budgets(alloc.budgets_mj), n_scalar + 1)
        assert int(res.n_items[0]) == n_scalar
