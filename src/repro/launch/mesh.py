"""Production meshes (deliverable e).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS for 512 host devices before any jax initialization, and tests/
benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axes, which with_sharding_constraint
    # (distributed/sharding.constrain) refuses; the repo shards with Auto axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod 16×16 (256 chips, "data","model") or multi-pod 2×16×16
    (512 chips, "pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """All available devices as a (data, model) mesh — used by tests and the
    CPU-scale examples (1×1 on this container)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
