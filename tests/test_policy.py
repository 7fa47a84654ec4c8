"""Learned idle-timeout policy: parity, guard exactness, training, serving.

The load-bearing contracts of ``src/repro/policy/``:

* the jitted batched rollout replays :func:`repro.core.simulator.
  simulate_trace` — item counts EXACT, energies within 1e-9 — so gradients
  and ES perturbations optimise the same physics the benchmarks score;
* the numpy serving path and the jnp training path compute the same
  features and the same network timeout;
* the untrained (zero-output) network IS the ski-rental hybrid, and the
  stationarity guard reproduces :meth:`repro.core.adaptive.
  AdaptiveStrategy.decide` bit-for-bit on stationary streams — the
  stationary-limit acceptance criterion;
* training on the regime mixture strictly improves the hard objective and
  the trained policy beats the analytical hybrid on flash-crowd traffic
  (the nonstationary acceptance criterion, seeded and deterministic);
* :class:`repro.policy.LearnedTimeoutPolicy` drops into
  ``DutyCycleController(policy=...)`` and ``Tenant(controller=...)``.
"""
import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import enable_x64

from repro.core import energy_model as em
from repro.core.adaptive import (
    AdaptiveStrategy,
    FixedTimeoutPolicy,
    PolicyController,
    StaticPolicy,
)
from repro.core.arrivals import (
    DeterministicArrivals,
    FlashCrowdArrivals,
    MMPPArrivals,
    PoissonArrivals,
)
from repro.core.phases import paper_lstm_item
from repro.core.simulator import simulate_trace
from repro.core.strategies import IdlePowerMethod
from repro.policy import (
    LearnedTimeoutPolicy,
    TrainedPolicy,
    TrainSettings,
    train_policy,
    untrained_policy,
)
from repro.policy import features as F
from repro.policy import net as N
from repro.policy.rollout import make_consts, rollout
from repro.policy.train import sample_training_gaps, training_processes

M12 = IdlePowerMethod.METHOD1_2
OVERHEAD = em.CALIBRATED_POWERUP_OVERHEAD_MJ


@pytest.fixture(scope="module")
def item():
    return paper_lstm_item()


@pytest.fixture(scope="module")
def consts(item):
    return make_consts(item, M12, OVERHEAD)


def random_params(seed=7, hidden=(8, 8)):
    """A small *non-zero* network (the zero init is the anchor; parity must
    also hold when the net actually steers the timeout per gap)."""
    with enable_x64():
        params = N.init_mlp(jax.random.PRNGKey(seed), hidden=hidden)
        keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(params))
        params = [
            {
                "w": layer["w"] + 0.3 * jax.random.normal(k, layer["w"].shape, dtype=jnp.float64),
                "b": layer["b"] + 0.1 * jax.random.normal(k, layer["b"].shape, dtype=jnp.float64),
            }
            for layer, k in zip(params, keys)
        ]
    return params


def replica_policy(trained, item_):
    """LearnedTimeoutPolicy configured as a pure network replica: no guard,
    no snapping — the scalar twin of the rollout kernel's timeout path."""
    return LearnedTimeoutPolicy(
        trained, item=item_, guard=False, snap_lo=0.0, snap_hi=math.inf
    )


def trace_from_gaps(gaps_row):
    """Arrival times the rollout semantics assume: item 0 at t=0, then the
    gap sequence."""
    return np.concatenate([[0.0], np.cumsum(np.asarray(gaps_row))])


# ---------------------------------------------------------------------------
# feature extractor: jnp training twin == numpy serving twin
# ---------------------------------------------------------------------------
class TestFeatureParity:
    T_BE = 493.831

    def _gap_seq(self):
        rng = np.random.default_rng(3)
        return np.concatenate([
            rng.exponential(40.0, 50),
            np.full(20, 2000.0),
            rng.exponential(5.0, 30),
        ])

    def test_state_and_features_match(self):
        with enable_x64():
            s_j = F.init_state_jnp()
            s_p = F.init_state()
            for g in self._gap_seq():
                s_j = F.update_state(s_j, jnp.float64(g), jnp.float64(self.T_BE))
                s_p = F.update_state_py(s_p, float(g), self.T_BE)
                f_j = np.asarray(F.feature_vector(s_j, jnp.float64(self.T_BE)))
                f_p = np.asarray(F.feature_vector_py(s_p, self.T_BE))
                np.testing.assert_allclose(f_j, f_p, rtol=0, atol=1e-12)

    def test_feature_vector_is_bounded(self):
        """Every feature stays O(1) — the net never sees raw milliseconds."""
        with enable_x64():
            s = F.init_state()
            for g in [0.0, 1e-3, 40.0, 1e6, 40.0] * 10:
                s = F.update_state_py(s, g, self.T_BE)
                f = np.asarray(F.feature_vector_py(s, self.T_BE))
                assert f.shape == (F.N_FEATURES,)
                assert np.all(np.isfinite(f))
                assert np.all(np.abs(f) < 20.0)


# ---------------------------------------------------------------------------
# network: zero-output anchor + numpy/jnp forward parity
# ---------------------------------------------------------------------------
class TestNetwork:
    def test_untrained_net_is_ski_rental(self, item):
        trained = untrained_policy(item, method=M12, powerup_overhead_mj=OVERHEAD)
        t_be = trained.t_be_ms
        rng = np.random.default_rng(0)
        for _ in range(20):
            feats = rng.normal(size=F.N_FEATURES)
            tau = N.timeout_ms_np(trained.params, feats, t_be)
            assert tau == t_be  # exact: zero raw output, exp(0) == 1

    def test_numpy_forward_matches_jnp(self):
        params = random_params()
        np_params = N.params_to_numpy(params)
        rng = np.random.default_rng(1)
        with enable_x64():
            for _ in range(10):
                feats = rng.normal(size=F.N_FEATURES)
                raw_j = float(N.apply_mlp(params, jnp.asarray(feats, dtype=jnp.float64)))
                raw_n = float(N.apply_mlp_np(np_params, feats))
                assert raw_n == pytest.approx(raw_j, rel=1e-9, abs=1e-12)

    def test_timeout_is_clipped_and_positive(self):
        params = N.params_to_numpy(random_params())
        huge = np.full(F.N_FEATURES, 50.0)
        t_be = 500.0
        tau = N.timeout_ms_np(params, huge, t_be)
        assert 0.0 < tau <= t_be * math.exp(N.LOG_SPAN) * (1 + 1e-12)


# ---------------------------------------------------------------------------
# rollout kernel == simulate_trace (the tentpole parity contract)
# ---------------------------------------------------------------------------
class TestRolloutParity:
    N_STREAMS = 4
    N_GAPS = 300

    def _gaps(self, proc, seed=0):
        with enable_x64():
            return np.asarray(
                proc.sample_gaps(jax.random.PRNGKey(seed), self.N_STREAMS, self.N_GAPS)
            )

    def _check(self, item, trained, policy_factory, proc, budget):
        gaps = self._gaps(proc)
        out = rollout(trained.params, gaps, dict(trained.consts, budget=budget))
        for i in range(self.N_STREAMS):
            res = simulate_trace(
                item, trace_from_gaps(gaps[i]), policy_factory(), budget, OVERHEAD
            )
            assert res.n_items == int(out["n_items"][i])
            assert res.configurations == int(out["configurations"][i])
            assert res.releases == int(out["releases"][i])
            assert res.energy_used_mj == pytest.approx(
                float(out["energy_mj"][i]), rel=1e-9, abs=1e-9
            )
            assert res.lifetime_ms == pytest.approx(
                float(out["lifetime_ms"][i]), rel=1e-12, abs=1e-9
            )

    @pytest.mark.parametrize("budget", [math.inf, 300.0])
    def test_untrained_matches_fixed_break_even(self, item, budget):
        """Zero net ⇒ constant timeout T*_be: the scalar reference is the
        plain FixedTimeoutPolicy ski-rental arm."""
        trained = untrained_policy(item, method=M12, powerup_overhead_mj=OVERHEAD)
        proc = MMPPArrivals(burst_ms=2.0, quiet_ms=4000.0,
                            mean_burst_len=12.0, mean_quiet_len=3.0)
        self._check(
            item, trained,
            lambda: FixedTimeoutPolicy(
                timeout_ms=trained.t_be_ms,
                idle_power_mw=trained.consts["p_idle"],
            ),
            proc, budget,
        )

    @pytest.mark.parametrize("proc_name", ["mmpp", "poisson", "flash"])
    def test_random_net_matches_replica_policy(self, item, proc_name):
        """A non-zero net steers the timeout per gap; the scalar twin is the
        guard-less LearnedTimeoutPolicy on the same stream."""
        consts = make_consts(item, M12, OVERHEAD)
        trained = TrainedPolicy(
            params=N.params_to_numpy(random_params()),
            consts=consts, history={},
            meta={"method": "METHOD1_2", "powerup_overhead_mj": OVERHEAD},
        )
        proc = {
            "mmpp": MMPPArrivals(burst_ms=2.0, quiet_ms=4000.0,
                                 mean_burst_len=12.0, mean_quiet_len=3.0),
            "poisson": PoissonArrivals(600.0),
            "flash": FlashCrowdArrivals(quiet_ms=3000.0, flash_gap_ms=10.0),
        }[proc_name]
        gaps = self._gaps(proc, seed=11)
        out = rollout(trained.params, gaps, dict(consts, budget=400.0))
        for i in range(self.N_STREAMS):
            res = simulate_trace(
                item, trace_from_gaps(gaps[i]), replica_policy(trained, item),
                400.0, OVERHEAD,
            )
            # counts must be exact; energy to 1e-6 rel (libm vs XLA tanh can
            # differ in the last ulp, which perturbs idle spans but must
            # never change a discrete decision on these streams)
            assert res.n_items == int(out["n_items"][i])
            assert res.configurations == int(out["configurations"][i])
            assert res.releases == int(out["releases"][i])
            assert res.energy_used_mj == pytest.approx(
                float(out["energy_mj"][i]), rel=1e-6
            )

    def test_smooth_energy_tracks_hard_energy(self, item, consts):
        """As the relaxation sharpens, the smooth accumulator converges to
        the hard one (same streams, same params)."""
        params = random_params()
        proc = PoissonArrivals(800.0)
        gaps = self._gaps(proc, seed=5)
        errs = []
        for frac in (0.1, 1e-3):
            c = make_consts(item, M12, OVERHEAD,
                            smooth_ms=frac * consts["t_be"])
            out = rollout(params, gaps, c, smooth=True, jit=False)
            hard = np.asarray(out["energy_mj"])
            smooth = np.asarray(out["energy_smooth_mj"])
            errs.append(float(np.max(np.abs(smooth - hard) / hard)))
        assert errs[1] < errs[0]
        assert errs[1] < 1e-3

    def test_smooth_objective_is_differentiable(self, consts):
        from repro.policy.rollout import mean_energy_per_gap

        with enable_x64():
            params = random_params(hidden=(4,))
            gaps = jnp.asarray(self._gaps(PoissonArrivals(600.0), seed=9))
            cj = {k: jnp.float64(v) for k, v in consts.items()}
            g = jax.grad(lambda p: mean_energy_per_gap(p, gaps, cj, True))(params)
            leaves = jax.tree.leaves(g)
            assert all(bool(jnp.all(jnp.isfinite(x))) for x in leaves)
            assert any(float(jnp.max(jnp.abs(x))) > 0 for x in leaves)


# ---------------------------------------------------------------------------
# stationarity guard: bit-for-bit the analytical adaptive decision
# ---------------------------------------------------------------------------
class TestStationaryGuard:
    BUDGET = 2000.0
    N_ARRIVALS = 600

    def _trace(self, period_ms, kind, seed=0):
        if kind == "deterministic":
            gaps = np.full(self.N_ARRIVALS - 1, period_ms)
        else:
            gaps = np.asarray(PoissonArrivals(period_ms).sample_gaps(
                jax.random.PRNGKey(seed), 1, self.N_ARRIVALS - 1
            ))[0]
        return trace_from_gaps(gaps)

    @pytest.mark.parametrize("kind,period", [
        ("deterministic", 40.0), ("deterministic", 2000.0),
        ("poisson", 40.0), ("poisson", 4000.0),
    ])
    def test_matches_adaptive_strategy_exactly(self, item, kind, period):
        """Choice identical AND energy identical to the static strategy the
        analytical rule picks — even with a deliberately non-zero network
        behind the guard."""
        trained = TrainedPolicy(
            params=N.params_to_numpy(random_params()),
            consts=make_consts(item, M12, OVERHEAD), history={},
            meta={"method": "METHOD1_2", "powerup_overhead_mj": OVERHEAD},
        )
        ref = AdaptiveStrategy(item=item, method=M12, powerup_overhead_mj=OVERHEAD)
        choice = ref.decide(period)

        trace = self._trace(period, kind)
        pol = LearnedTimeoutPolicy(trained, item=item, prior_period_ms=period)
        got = simulate_trace(item, trace, pol, self.BUDGET, OVERHEAD)
        want = simulate_trace(
            item, trace,
            StaticPolicy(choice, item, method=M12, powerup_overhead_mj=OVERHEAD),
            self.BUDGET, OVERHEAD,
        )
        assert pol.regime() == choice
        assert got.n_items == want.n_items
        assert abs(got.energy_used_mj - want.energy_used_mj) <= 1e-9
        # the guard never flapped: one initial switch into the regime
        assert pol.regime_switches <= 1

    def test_guard_disengages_on_bursty_traffic(self, item):
        trained = untrained_policy(item, method=M12, powerup_overhead_mj=OVERHEAD)
        pol = LearnedTimeoutPolicy(trained, item=item)
        rng = np.random.default_rng(0)
        # strongly bimodal gaps: CV well above the latch
        for _ in range(200):
            pol.observe_gap(2.0 if rng.random() < 0.8 else 8000.0)
        assert pol.regime() == "learned"
        assert not pol.summary()["guard_engaged"]
        # untrained net behind a disengaged guard == ski-rental timeout
        assert pol.idle_timeout_ms() == pytest.approx(pol.break_even_ms())

    def test_prior_must_be_finite_positive(self, item):
        trained = untrained_policy(item)
        for bad in (0.0, -1.0, math.inf, math.nan):
            with pytest.raises(ValueError):
                LearnedTimeoutPolicy(trained, item=item, prior_period_ms=bad)


# ---------------------------------------------------------------------------
# serialisation
# ---------------------------------------------------------------------------
class TestSerialisation:
    def test_json_round_trip(self, item):
        trained = untrained_policy(item, method=M12, powerup_overhead_mj=OVERHEAD)
        blob = json.dumps(trained.to_json_dict())   # must be JSON-clean
        back = TrainedPolicy.from_json_dict(json.loads(blob))
        assert back.consts == trained.consts        # inf budget survives
        assert back.meta == trained.meta
        for a, b in zip(back.params, trained.params):
            np.testing.assert_array_equal(a["w"], b["w"])
            np.testing.assert_array_equal(a["b"], b["b"])

    def test_round_tripped_policy_same_decisions(self, item):
        trained = TrainedPolicy(
            params=N.params_to_numpy(random_params()),
            consts=make_consts(item, M12, OVERHEAD), history={},
            meta={"method": "METHOD1_2", "powerup_overhead_mj": OVERHEAD},
        )
        back = TrainedPolicy.from_json_dict(json.loads(json.dumps(trained.to_json_dict())))
        a = replica_policy(trained, item)
        b = replica_policy(back, item)
        for g in (40.0, 2000.0, 3.0, 900.0):
            a.observe_gap(g)
            b.observe_gap(g)
            assert a.idle_timeout_ms() == b.idle_timeout_ms()


# ---------------------------------------------------------------------------
# training (slow: two jitted optimisation scans)
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestTraining:
    @pytest.fixture(scope="class")
    def trained(self, item):
        return train_policy(
            item, method=M12, powerup_overhead_mj=OVERHEAD,
            settings=TrainSettings.smoke(),
        )

    def test_training_improves_hard_objective(self, trained):
        h = trained.history
        assert h["final_hard"] < h["baseline_hard"] * 0.95

    def test_training_is_deterministic_in_seed(self, item, trained):
        again = train_policy(
            item, method=M12, powerup_overhead_mj=OVERHEAD,
            settings=TrainSettings.smoke(),
        )
        for a, b in zip(again.params, trained.params):
            np.testing.assert_array_equal(a["w"], b["w"])

    def test_learned_beats_hybrid_on_flash_crowd(self, item, trained):
        """The nonstationary acceptance criterion, as a seeded regression:
        more requests served per budget than the analytical hybrid."""
        t = trained.t_be_ms
        proc = FlashCrowdArrivals(quiet_ms=6.0 * t, flash_gap_ms=0.02 * t,
                                  flash_len=32, flash_every=4.0)
        budget = 1500.0
        learned_n = hybrid_n = 0
        for seed in range(6):
            gaps = np.asarray(
                proc.sample_gaps(jax.random.PRNGKey(seed), 1, 999)
            )[0]
            trace = trace_from_gaps(gaps)
            pol = LearnedTimeoutPolicy(trained, item=item)
            learned_n += simulate_trace(item, trace, pol, budget, OVERHEAD).n_items
            ctrl = PolicyController(item=item, method=M12,
                                    powerup_overhead_mj=OVERHEAD)
            hybrid_n += simulate_trace(item, trace, ctrl, budget, OVERHEAD).n_items
        assert learned_n > hybrid_n * 1.05

    def test_training_gap_mixture_shape(self, item, consts):
        procs = training_processes(consts["t_be"])
        gaps = sample_training_gaps(procs, 16, 64, seed=0)
        assert gaps.shape == (16, 64)
        assert bool(jnp.all(gaps >= 0))
        assert bool(jnp.all(jnp.isfinite(gaps)))


# ---------------------------------------------------------------------------
# serving integration: drop-in for the PolicyController consumers
# ---------------------------------------------------------------------------
class TestServingIntegration:
    def _policy(self, item, prior=None, prior_weight=8.0):
        trained = untrained_policy(item, method=M12, powerup_overhead_mj=OVERHEAD)
        return LearnedTimeoutPolicy(trained, item=item, prior_period_ms=prior,
                                    prior_weight=prior_weight)

    def test_duty_cycle_controller_accepts_learned_policy(self, item):
        from repro.core.duty_cycle import DutyCycleController, PowerModel

        clock = [0.0]
        power = PowerModel(config_mw=300.0, infer_mw=170.0, idle_mw=134.0)

        def bring_up():
            clock[0] += 0.5
            return "h"

        def infer(h, x):
            clock[0] += 0.01
            return x

        # heavy prior: the first observed gap includes the 0.5 s bring-up,
        # and a trusted declared period should absorb that outlier
        c = DutyCycleController(
            bring_up, infer, lambda h: None, power,
            strategy="adaptive", clock=lambda: clock[0],
            policy=self._policy(item, prior=40.0, prior_weight=64.0),
        )
        for x in range(4):
            c.submit(x)
            clock[0] += 0.04          # 40 ms period, below the crossover
        # prior below the crossover ⇒ idle-waiting ⇒ never release
        assert c.timeout_s() is None
        assert c.policy.summary()["regime"] == "idle_waiting"

    def test_tenant_accepts_learned_controller(self, item):
        from repro.serving.multi_tenant import Tenant

        t = Tenant(
            name="m", bring_up=lambda: "h", infer=lambda h, x: x,
            release=lambda h: None, hbm_gb=1.0,
            config_mw=300.0, infer_mw=170.0, idle_mw=134.0,
            policy="adaptive", controller=self._policy(item, prior=5000.0),
        )
        assert isinstance(t.controller, LearnedTimeoutPolicy)
        t.observe_gap(5.0)
        assert t.controller.n_observed == 1
        # prior above the crossover ⇒ on-off ⇒ release immediately
        assert t.controller.idle_timeout_ms() == 0.0

    def test_simulate_trace_accepts_learned_policy(self, item):
        pol = self._policy(item, prior=40.0)
        trace = trace_from_gaps(np.full(50, 40.0))
        res = simulate_trace(item, trace, pol, 100.0, OVERHEAD)
        assert res.policy == "learned"
        assert res.n_items > 0
        assert res.releases == 0     # idle-waiting regime: stays resident
