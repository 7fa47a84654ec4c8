"""Arrival-process unit tests: determinism, statistics, trace round-trip."""
import io
import math

import numpy as np
import pytest

from repro.core.arrivals import (
    DeterministicArrivals,
    JitteredArrivals,
    MMPPArrivals,
    PoissonArrivals,
    TraceArrivals,
    make_process,
)


class TestDeterministic:
    def test_constant_period(self):
        t = DeterministicArrivals(40.0).arrival_times(5)
        np.testing.assert_allclose(t, [0.0, 40.0, 80.0, 120.0, 160.0])

    def test_first_arrival_at_zero(self):
        for proc in (
            DeterministicArrivals(10.0),
            PoissonArrivals(10.0),
            MMPPArrivals(5.0, 100.0),
        ):
            assert proc.arrival_times(3, seed=4)[0] == 0.0

    def test_invalid_period_rejected(self):
        with pytest.raises(ValueError):
            DeterministicArrivals(0.0)


class TestSeededDeterminism:
    @pytest.mark.parametrize(
        "proc",
        [PoissonArrivals(25.0), MMPPArrivals(5.0, 500.0, mean_burst_len=4)],
        ids=["poisson", "mmpp"],
    )
    def test_same_seed_same_stream(self, proc):
        a = proc.inter_arrival_times(500, seed=7)
        b = proc.inter_arrival_times(500, seed=7)
        np.testing.assert_array_equal(a, b)
        c = proc.inter_arrival_times(500, seed=8)
        assert not np.array_equal(a, c)


class TestStatistics:
    def test_poisson_mean(self):
        gaps = PoissonArrivals(120.0).inter_arrival_times(40_000, seed=0)
        assert np.mean(gaps) == pytest.approx(120.0, rel=0.03)

    def test_poisson_is_memoryless_cv_one(self):
        gaps = PoissonArrivals(50.0).inter_arrival_times(40_000, seed=1)
        assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.05)

    def test_mmpp_mean_matches_stationary_mix(self):
        proc = MMPPArrivals(10.0, 1000.0, mean_burst_len=8, mean_quiet_len=2)
        gaps = proc.inter_arrival_times(60_000, seed=2)
        assert np.mean(gaps) == pytest.approx(proc.mean_period_ms(), rel=0.1)

    def test_mmpp_is_overdispersed(self):
        """Burstiness = CV well above Poisson's 1."""
        gaps = MMPPArrivals(10.0, 2000.0, mean_burst_len=8).inter_arrival_times(
            40_000, seed=3
        )
        assert np.std(gaps) / np.mean(gaps) > 1.5


class TestTrace:
    def test_round_trip_through_file(self):
        src = MMPPArrivals(20.0, 800.0)
        trace = TraceArrivals.record(src, 200, seed=5)
        buf = io.StringIO()
        trace.to_file(buf)
        buf.seek(0)
        back = TraceArrivals.from_file(buf)
        np.testing.assert_array_equal(
            trace.inter_arrival_times(200), back.inter_arrival_times(200)
        )

    def test_comments_and_blanks_skipped(self):
        text = "# header\n10.0\n\n20.0  # inline\n30.0\n"
        back = TraceArrivals.from_file(io.StringIO(text))
        assert back.gaps_ms == (10.0, 20.0, 30.0)

    def test_cycles_when_exhausted(self):
        t = TraceArrivals((1.0, 2.0))
        np.testing.assert_allclose(t.inter_arrival_times(5), [1, 2, 1, 2, 1])

    def test_empty_trace_rejected(self):
        with pytest.raises(ValueError):
            TraceArrivals(())


class TestValidationRegressions:
    """NaN rates and zero-length-burst degeneracies used to sail through the
    naive `<= 0` / `< 1` guards (every comparison with NaN is False) and
    then poison whole fleet scans; they must fail fast now."""

    NAN = float("nan")

    @pytest.mark.parametrize("bad", [NAN, float("inf"), 0.0, -1.0],
                             ids=["nan", "inf", "zero", "negative"])
    def test_rate_constants_rejected(self, bad):
        for ctor in (
            lambda: DeterministicArrivals(bad),
            lambda: JitteredArrivals(bad, 0.1),
            lambda: PoissonArrivals(bad),
            lambda: MMPPArrivals(bad, 10.0),
            lambda: MMPPArrivals(10.0, bad),
        ):
            with pytest.raises(ValueError):
                ctor()

    def test_mmpp_nan_and_zero_length_dwells_rejected(self):
        with pytest.raises(ValueError, match="zero-length bursts"):
            MMPPArrivals(5.0, 100.0, mean_burst_len=self.NAN)
        with pytest.raises(ValueError, match="zero-length bursts"):
            MMPPArrivals(5.0, 100.0, mean_quiet_len=0.0)
        with pytest.raises(ValueError, match="zero-length bursts"):
            MMPPArrivals(5.0, 100.0, mean_burst_len=0.5)

    def test_jittered_nan_jitter_rejected(self):
        with pytest.raises(ValueError):
            JitteredArrivals(40.0, self.NAN)
        with pytest.raises(ValueError):
            JitteredArrivals(40.0, -0.1)

    def test_trace_nan_gap_rejected(self):
        with pytest.raises(ValueError, match="finite"):
            TraceArrivals((10.0, self.NAN, 20.0))
        with pytest.raises(ValueError):
            TraceArrivals((float("inf"),))

    def test_trace_all_zero_gaps_rejected(self):
        with pytest.raises(ValueError, match="all zero"):
            TraceArrivals((0.0, 0.0, 0.0))
        # individual zero gaps (simultaneous arrivals) stay legal
        assert TraceArrivals((0.0, 5.0)).mean_period_ms() == 2.5

    def test_nan_never_reaches_the_samplers(self):
        """The regression scenario: a NaN rate propagating into sample_batch."""
        import jax

        proc = PoissonArrivals(10.0)
        t = np.asarray(proc.sample_batch(jax.random.PRNGKey(0), 4, 100.0))
        assert not np.any(np.isnan(t))


class TestJittered:
    def test_zero_jitter_is_deterministic(self):
        np.testing.assert_array_equal(
            JitteredArrivals(40.0, 0.0).inter_arrival_times(10, seed=3),
            DeterministicArrivals(40.0).inter_arrival_times(10, seed=3),
        )

    def test_gaps_non_negative_even_at_large_jitter(self):
        g = JitteredArrivals(10.0, 0.9).inter_arrival_times(5000, seed=4)
        assert np.all(g >= 0.0)

    def test_mean_period(self):
        proc = JitteredArrivals(25.0, 0.1)
        assert proc.mean_period_ms() == 25.0
        g = proc.inter_arrival_times(20_000, seed=5)
        assert np.mean(g) == pytest.approx(25.0, rel=0.01)


class TestFactory:
    def test_known_kinds(self):
        assert isinstance(make_process("deterministic", period_ms=10.0),
                          DeterministicArrivals)
        assert isinstance(make_process("jittered", period_ms=10.0, jitter=0.1),
                          JitteredArrivals)
        assert isinstance(make_process("poisson", mean_ms=10.0), PoissonArrivals)
        assert isinstance(make_process("bursty", burst_ms=1.0, quiet_ms=10.0),
                          MMPPArrivals)

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError):
            make_process("fractal")


class TestSampleBatch:
    """Vectorized per-device streams (fleet substrate): jax.random-seeded,
    padded, statistically consistent with the scalar generators."""

    def test_deterministic_exact_grid(self):
        import jax

        # half-open horizon [0, 200): t = 200 is excluded, matching the
        # bin_arrival_counts tick grid
        t = DeterministicArrivals(40.0).sample_batch(jax.random.PRNGKey(0), 3, 200.0)
        finite = np.isfinite(np.asarray(t))
        for row in np.asarray(t):
            np.testing.assert_allclose(row[np.isfinite(row)], [0, 40, 80, 120, 160])
        assert finite.sum() == 3 * 5

    def test_horizon_boundary_consistent_with_binning(self):
        import jax

        from repro.core.arrivals import bin_arrival_counts

        # period divides the horizon: every sampled arrival must land in a bin
        t = DeterministicArrivals(40.0).sample_batch(jax.random.PRNGKey(0), 2, 200.0)
        c = bin_arrival_counts(t, 200.0, 40.0)
        assert int(np.asarray(c).sum()) == int(np.isfinite(np.asarray(t)).sum())

    def test_first_arrival_at_zero_and_inf_padding(self):
        import jax

        for proc in (DeterministicArrivals(10.0), PoissonArrivals(10.0),
                     MMPPArrivals(5.0, 100.0)):
            t = np.asarray(proc.sample_batch(jax.random.PRNGKey(3), 4, 100.0))
            assert np.all(t[:, 0] == 0.0)
            assert np.all(np.isinf(t[~np.isfinite(t)]))
            # finite times are sorted and within the horizon
            for row in t:
                fin = row[np.isfinite(row)]
                assert np.all(np.diff(fin) >= 0)
                assert fin.max() <= 100.0

    def test_same_key_same_batch_and_rows_independent(self):
        import jax

        proc = PoissonArrivals(25.0)
        key = jax.random.PRNGKey(7)
        a = np.asarray(proc.sample_batch(key, 8, 1000.0))
        b = np.asarray(proc.sample_batch(key, 8, 1000.0))
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a[0], a[1])

    def test_poisson_mean_matches_scalar_statistics(self):
        import jax

        proc = PoissonArrivals(25.0)
        t = np.asarray(proc.sample_batch(jax.random.PRNGKey(0), 512, 20_000.0))
        with np.errstate(invalid="ignore"):    # inf padding → nan diffs
            gaps = np.diff(t, axis=1)
        gaps = gaps[np.isfinite(gaps)]
        scalar = np.concatenate(
            [proc.inter_arrival_times(400, seed=s) for s in range(4)]
        )
        assert np.mean(gaps) == pytest.approx(np.mean(scalar), rel=0.05)
        assert np.mean(gaps) == pytest.approx(proc.mean_period_ms(), rel=0.05)

    def test_mmpp_mean_and_burstiness_match_scalar(self):
        import jax

        proc = MMPPArrivals(burst_ms=5.0, quiet_ms=500.0)
        t = np.asarray(proc.sample_batch(jax.random.PRNGKey(1), 512, 50_000.0,
                                         max_arrivals=2048))
        with np.errstate(invalid="ignore"):    # inf padding → nan diffs
            gaps = np.diff(t, axis=1)
        gaps = gaps[np.isfinite(gaps)]
        scalar = np.concatenate(
            [proc.inter_arrival_times(1000, seed=s) for s in range(8)]
        )
        # horizon censoring clips the longest quiet gaps → generous band
        assert np.mean(gaps) == pytest.approx(np.mean(scalar), rel=0.15)
        # bursty: CV well above Poisson's 1 in both samplers
        assert np.std(gaps) / np.mean(gaps) > 1.5
        assert np.std(scalar) / np.mean(scalar) > 1.5

    def test_include_origin_false_drops_synchronized_start(self):
        import jax

        t = np.asarray(PoissonArrivals(50.0).sample_batch(
            jax.random.PRNGKey(2), 16, 1000.0, include_origin=False))
        assert not np.any(t[:, 0] == 0.0)

    @pytest.mark.parametrize("ndim", [1, 2])
    @pytest.mark.parametrize(
        "lengths",
        [range(40), (255, 256, 257), (4095, 4096, 4097), (70000,)],
        ids=["0-39", "255-257", "4095-4097", "70000"],
    )
    def test_prefix_sum_bit_identical_to_cumsum(self, lengths, ndim):
        """``sample_batch``'s arrival times are ``prefix_sum`` of the gaps;
        on the CPU they must equal ``jnp.cumsum``'s bit for bit, so the
        sampled streams do not depend on which of the two computes them."""
        import jax
        import jax.numpy as jnp

        from repro.core.arrivals import _prefix_sum_jit

        rng = np.random.default_rng(len(lengths))
        with jax.enable_x64():
            for n in lengths:
                shape = (n,) if ndim == 1 else (3, n)
                # gaps spanning six decades, so any other association of
                # the additions rounds differently
                gaps = rng.exponential(40.0, shape) * 10.0 ** rng.uniform(-3, 3, shape)
                x = jnp.asarray(gaps, jnp.float64)
                np.testing.assert_array_equal(
                    np.asarray(_prefix_sum_jit(x)), np.asarray(jnp.cumsum(x, axis=-1)),
                    err_msg=f"shape {shape}",
                )

    def test_invalid_args_rejected(self):
        import jax

        proc = PoissonArrivals(10.0)
        with pytest.raises(ValueError):
            proc.sample_batch(jax.random.PRNGKey(0), 0, 100.0)
        with pytest.raises(ValueError):
            proc.sample_batch(jax.random.PRNGKey(0), 1, -5.0)
        with pytest.raises(NotImplementedError):
            TraceArrivals((1.0,)).sample_batch(jax.random.PRNGKey(0), 1, 100.0)


class TestBinArrivalCounts:
    def test_counts_match_histogram(self):
        from repro.core.arrivals import bin_arrival_counts

        times = np.array([[0.0, 10.0, 39.9, 40.0, 75.0, np.inf]])
        c = np.asarray(bin_arrival_counts(times, 80.0, 40.0))
        assert c.shape == (2, 1)
        np.testing.assert_array_equal(c[:, 0], [3, 2])

    def test_inf_padding_and_out_of_horizon_ignored(self):
        from repro.core.arrivals import bin_arrival_counts

        times = np.array([[0.0, 500.0, np.inf], [20.0, 79.9, np.inf]])
        c = np.asarray(bin_arrival_counts(times, 80.0, 40.0))
        assert int(c.sum()) == 3
        np.testing.assert_array_equal(c, [[1, 1], [0, 1]])

    def test_total_conservation_with_sampler(self):
        import jax

        from repro.core.arrivals import bin_arrival_counts

        proc = PoissonArrivals(30.0)
        t = proc.sample_batch(jax.random.PRNGKey(5), 32, 5000.0)
        c = bin_arrival_counts(t, 5000.0, 10.0)
        finite = np.isfinite(np.asarray(t)) & (np.asarray(t) < 5000.0)
        assert int(np.asarray(c).sum()) == int(finite.sum())

    def test_invalid_args(self):
        from repro.core.arrivals import bin_arrival_counts

        with pytest.raises(ValueError):
            bin_arrival_counts(np.zeros((2, 3)), 100.0, 0.0)
        with pytest.raises(ValueError):
            bin_arrival_counts(np.zeros(3), 100.0, 10.0)
