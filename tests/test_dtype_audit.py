"""Scan-carry dtype regressions (repro.fleet.dtypes).

Before this audit existed the periodic/ensemble admission counters were
silently int64 on x64 hosts — twice the hot-loop carry traffic for a
counter that grows by at most 1 per step.  These tests pin the narrowed
int32 contract (the failing-before regression), prove the audit machinery
catches a promoting body, and pin the explicit overflow guard that
replaces int32's silent wrap-around at 2^31 steps.

Energies deliberately stay float64 (the oracle bit-identity and the 1e-9
ledger-conservation contracts are stated against the f64 scalar
simulator) — the audit pins that width too, so an accidental fp32
demotion fails as loudly as a promotion would.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from repro.fleet import INT32_STEP_LIMIT, fleet_mesh, run_periodic, uniform_fleet
from repro.fleet.dtypes import (
    ENSEMBLE_CARRY_DTYPES,
    PERIODIC_CARRY_DTYPES,
    ROUTED_CARRY_DTYPES,
    audit_scan_body,
    ensemble_carry_dtypes,
    periodic_carry_dtypes,
    routed_carry_dtypes,
    scan_carry_dtypes,
)


def params9():
    return uniform_fleet(
        9, strategies=("idle_waiting", "on_off", "adaptive"), e_budget_mj=2500.0
    )


class TestRealKernelCarries:
    def test_periodic_carry_is_int32_bool(self):
        """The failing-before pin: the admission counter rides the scan as
        int32 (it was int64 before the audit), the liveness flag as bool."""
        assert periodic_carry_dtypes(params9()) == PERIODIC_CARRY_DTYPES
        assert PERIODIC_CARRY_DTYPES == ("int32", "bool")

    def test_ensemble_carry_pinned(self):
        """Counter int32; energy/lifetime/idle accumulators stay float64 —
        not fp32 — per the ledger-conservation contract."""
        assert ensemble_carry_dtypes(params9()) == ENSEMBLE_CARRY_DTYPES
        assert ENSEMBLE_CARRY_DTYPES == (
            "int32", "bool", "float64", "float64", "float64"
        )

    def test_routed_carry_pinned(self):
        """FleetState keeps its documented int64 fleet-wide accumulators
        (deliberate — n_dropped can exceed 2^31 fleet-wide) and f64
        energies; queue cursors are int32."""
        assert routed_carry_dtypes(params9()) == ROUTED_CARRY_DTYPES
        assert ROUTED_CARRY_DTYPES["n_dropped"] == "int64"
        assert ROUTED_CARRY_DTYPES["q_head"] == "int32"
        assert ROUTED_CARRY_DTYPES["energy_mj"] == "float64"

    def test_no_silent_fp64_promotion_in_periodic(self):
        """Every carry leaf leaves one scan step with the dtype it entered
        with — lax.scan never has to widen the hot loop."""
        from repro.fleet.step import _periodic_body, _periodic_carry0, _periodic_limit

        p = params9()
        with enable_x64():
            rows = scan_carry_dtypes(
                _periodic_body(p, _periodic_limit(p)), _periodic_carry0(p)
            )
        assert all(din == dout for _, din, dout in rows), rows


class TestAuditMachinery:
    def test_catches_promoting_body(self):
        """A body that widens its int32 counter to int64 is rejected with
        the leaf named.  (Needs x64 enabled: without it jax truncates the
        int64 back down and no promotion happens — which is itself why the
        audit runs under enable_x64.)"""
        with enable_x64():
            def promoting(carry, _):
                n, alive = carry
                return (n.astype(jnp.int64) + 1, alive), None

            carry = (jnp.zeros((4,), jnp.int32), jnp.ones((4,), bool))
            with pytest.raises(TypeError, match="int32 -> int64"):
                audit_scan_body(promoting, carry, name="demo")

    def test_catches_structure_change(self):
        def restructuring(carry, _):
            n, alive = carry
            return (n, alive, n), None

        carry = (jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool))
        with pytest.raises(TypeError, match="structure"):
            scan_carry_dtypes(restructuring, carry)

    def test_stable_body_passes(self):
        def stable(carry, _):
            n, alive = carry
            return (n + jnp.int32(1), alive), None

        carry = (jnp.zeros((4,), jnp.int32), jnp.ones((4,), bool))
        assert audit_scan_body(stable, carry, name="ok") == []


class TestOverflowGuard:
    def test_limit_is_int32_max(self):
        assert INT32_STEP_LIMIT == 2**31 - 1
        assert INT32_STEP_LIMIT == np.iinfo(np.int32).max

    def test_run_periodic_refuses_past_int32(self):
        with pytest.raises(OverflowError, match="int32"):
            run_periodic(params9(), INT32_STEP_LIMIT + 1)

    def test_run_periodic_sharded_refuses_past_int32(self):
        from repro.fleet import run_periodic_sharded

        with pytest.raises(OverflowError, match="int32"):
            run_periodic_sharded(params9(), INT32_STEP_LIMIT + 1,
                                 mesh=fleet_mesh(1, 1))

    def test_run_periodic_ensemble_refuses_past_int32(self):
        """The guard fires before any gap sampling or allocation."""
        from repro.core.arrivals import JitteredArrivals
        from repro.mc import run_periodic_ensemble

        with pytest.raises(OverflowError, match="int32"):
            run_periodic_ensemble(
                params9(), JitteredArrivals(40.0, 0.1),
                INT32_STEP_LIMIT + 1, 2
            )

    def test_at_limit_is_not_an_error(self):
        """The guard is exclusive: n_steps == 2^31 − 1 is representable and
        must not raise (checked via the guard alone — nobody scans 2^31
        steps in a unit test)."""
        from repro.fleet.step import _check_step_count

        _check_step_count(INT32_STEP_LIMIT, "test")  # no raise
        with pytest.raises(OverflowError):
            _check_step_count(INT32_STEP_LIMIT + 1, "test")