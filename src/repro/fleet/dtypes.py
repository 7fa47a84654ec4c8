"""Scan-carry dtype audit for the vectorized fleet/MC kernels.

The hot loops (:func:`repro.fleet.step.run_periodic`, the gap-driven
ensemble scan in :mod:`repro.mc.ensemble`, and the routed tick kernel)
thread their state through ``jax.lax.scan`` carries.  Two silent failure
modes live there:

* **promotion** — a carry leaf that comes back wider than it went in
  (e.g. an int32 counter promoted to int64 by a mixed-dtype ``where``)
  doubles the hot-loop memory traffic without changing any test result;
* **wrap-around** — an int32 counter asked to count past 2^31 − 1 wraps
  silently.

This module pins the audited dtype contract:

* **counters** that can only grow by 1 per scan step (periodic/ensemble
  admitted-item counts) are **int32**, with an explicit
  :data:`~repro.fleet.step.INT32_STEP_LIMIT` overflow guard at every
  entry point — a horizon past 2^31 steps raises ``OverflowError``
  instead of wrapping;
* **energies and times stay float64 deliberately** — *not* fp32: the
  oracle bit-identity and 1e-9 ledger-conservation contracts are stated
  against the float64 scalar simulator, and the audit pins f64 explicitly
  so an accidental demotion fails just as loudly as a promotion;
* the routed :class:`~repro.fleet.state.FleetState` keeps **int64**
  fleet-wide accumulators (``n_dropped`` absorbs global drop counts that
  can exceed 2^31 fleet-wide) — pinned, documented width, not an accident.

``tests/test_dtype_audit.py`` asserts the real kernel bodies match these
specs and that :func:`audit_scan_body` catches a promoting body.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import enable_x64

from repro.fleet.state import FleetParams, FleetState

__all__ = [
    "PERIODIC_CARRY_DTYPES",
    "ENSEMBLE_CARRY_DTYPES",
    "ROUTED_CARRY_DTYPES",
    "scan_carry_dtypes",
    "audit_scan_body",
    "periodic_carry_dtypes",
    "ensemble_carry_dtypes",
    "routed_carry_dtypes",
]

#: Pinned carry dtypes of the periodic admission scan
#: (:func:`repro.fleet.step._periodic_body`): ``(n, alive)``.
PERIODIC_CARRY_DTYPES = ("int32", "bool")

#: Pinned carry dtypes of the gap-driven ensemble scan
#: (:func:`repro.mc.ensemble._periodic_ens_scan`):
#: ``(n, alive, cum_mj, lifetime_ms, idle_mj)``.
ENSEMBLE_CARRY_DTYPES = ("int32", "bool", "float64", "float64", "float64")

#: Pinned carry dtypes of the routed tick kernel's :class:`FleetState`,
#: in field order.  The i64 counters are deliberate (see module docstring).
ROUTED_CARRY_DTYPES = {
    "energy_mj": "float64",
    "idle_energy_mj": "float64",
    "n_served": "int64",
    "n_configs": "int64",
    "n_released": "int64",
    "n_dropped": "int64",
    "resident": "bool",
    "alive": "bool",
    "completion_ms": "float64",
    "queue_ms": "float64",
    "q_head": "int32",
    "q_len": "int32",
    "rr_ptr": "int32",
}


def _leaf_dtypes(tree) -> list[tuple[str, str]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), str(leaf.dtype)) for path, leaf in flat]


def scan_carry_dtypes(body, carry, x=None) -> list[tuple[str, str, str]]:
    """Abstractly evaluate one step of ``body`` and pair up carry dtypes.

    Returns ``[(leaf_path, dtype_in, dtype_out), ...]`` — no FLOPs run
    (``jax.eval_shape``), so auditing a million-device carry is free.
    """
    out = jax.eval_shape(lambda c, xx: body(c, xx)[0], carry, x)
    din, dout = _leaf_dtypes(carry), _leaf_dtypes(out)
    if [p for p, _ in din] != [p for p, _ in dout]:
        raise TypeError(
            "scan body changed the carry pytree structure: "
            f"{[p for p, _ in din]} -> {[p for p, _ in dout]}"
        )
    return [(p, a, b) for (p, a), (_, b) in zip(din, dout)]


def audit_scan_body(body, carry, x=None, name: str = "scan") -> list[str]:
    """Raise ``TypeError`` listing every carry leaf whose dtype changes
    across one scan step; returns the (empty) promotion list on success."""
    promoted = [
        f"{name}{path}: {a} -> {b}"
        for path, a, b in scan_carry_dtypes(body, carry, x)
        if a != b
    ]
    if promoted:
        raise TypeError(
            f"scan carry dtype drift in {name!r} (lax.scan would re-trace "
            f"or silently widen the hot loop): " + "; ".join(promoted)
        )
    return promoted


# ---------------------------------------------------------------------------
# Audits of the real kernel bodies
# ---------------------------------------------------------------------------
def periodic_carry_dtypes(params: FleetParams) -> tuple[str, ...]:
    """Audited carry dtypes of the periodic admission scan (stable, else
    raises)."""
    from repro.fleet.step import _periodic_body, _periodic_carry0, _periodic_limit

    with enable_x64():
        carry = _periodic_carry0(params)
        body = _periodic_body(params, _periodic_limit(params))
        audit_scan_body(body, carry, None, name="periodic")
        return tuple(str(c.dtype) for c in carry)


def ensemble_carry_dtypes(params: FleetParams) -> tuple[str, ...]:
    """Audited carry dtypes of the gap-driven ensemble scan."""
    from repro.mc.ensemble import _ens_body, _ens_carry0

    with enable_x64():
        from repro.fleet.step import _periodic_limit

        carry = _ens_carry0(params)
        body = _ens_body(params, _periodic_limit(params))
        n = params.n_devices
        g = jax.ShapeDtypeStruct((n,), jnp.float64)
        audit_scan_body(body, carry, (g, g), name="ensemble")
        return tuple(str(c.dtype) for c in carry)


def routed_carry_dtypes(params: FleetParams, queue_capacity: int = 4) -> dict[str, str]:
    """Audited carry dtypes of the routed tick kernel (direct arrivals)."""
    import dataclasses

    from repro.fleet.step import _routed_body

    with enable_x64():
        n = params.n_devices
        state0 = FleetState.init(n, queue_capacity)
        body = _routed_body(params, jnp.float64(1.0), None, False, queue_capacity)
        x = (
            jax.ShapeDtypeStruct((), jnp.int64),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        )
        audit_scan_body(body, state0, x, name="routed")
        return {
            f.name: str(getattr(state0, f.name).dtype)
            for f in dataclasses.fields(state0)
        }
