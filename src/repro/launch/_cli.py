"""Shared scaffolding for the launch CLIs (sweep / fleet / optimize).

The three JSON-emitting launchers used to carry near-duplicate copies of the
same plumbing: axis parsing (``start:stop:step`` ranges and comma lists),
device/method name resolution, argparse boilerplate, timing metadata, and
the write-to-``--out``-or-stdout tail.  This module is the single home for
all of it; the launchers keep only their domain logic.

Nothing here imports jax at module scope — ``--help`` stays instant.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import platform
import sys
import time


def parse_axis(spec: str) -> list[float]:
    """'a:b:step' (stop-inclusive) or 'x,y,z' → list of floats.

    >>> parse_axis("10:40:10")
    [10.0, 20.0, 30.0, 40.0]
    >>> parse_axis("3,6,9")
    [3.0, 6.0, 9.0]
    """
    if ":" in spec:
        parts = [float(x) for x in spec.split(":")]
        if len(parts) != 3:
            raise argparse.ArgumentTypeError(f"range must be start:stop:step, got {spec!r}")
        start, stop, step = parts
        if step <= 0:
            raise argparse.ArgumentTypeError(f"step must be positive in {spec!r}")
        out = []
        x = start
        while x <= stop + 1e-9:
            out.append(round(x, 10))
            x += step
        return out
    return [float(x) for x in spec.split(",") if x]


def resolve_devices(spec: str):
    """Comma list of device names (or 'both') → tuple of FpgaDevice."""
    from repro.core.config_phase import DEVICES

    if spec == "both":
        return tuple(DEVICES.values())
    out = []
    for name in spec.split(","):
        if name not in DEVICES:
            raise SystemExit(f"unknown device {name!r}; known: {', '.join(DEVICES)} or 'both'")
        out.append(DEVICES[name])
    return tuple(out)


def resolve_methods(spec: str):
    """Comma list of Table-3 method names → tuple of IdlePowerMethod."""
    from repro.core.strategies import IdlePowerMethod

    return tuple(IdlePowerMethod(m) for m in spec.split(","))


def make_parser(
    prog: str,
    description: str,
    jit_flag: bool = True,
    calibrated_default: bool = False,
    out_default: str | None = None,
) -> argparse.ArgumentParser:
    """Uniform parser with the flags every launcher shares (--out,
    --calibrated/--no-calibrated, optionally --jit); launchers add their own
    on top."""
    ap = argparse.ArgumentParser(prog=prog, description=description)
    ap.add_argument("--out", default=out_default, metavar="PATH",
                    help="write JSON here"
                    + (" (default stdout)" if out_default is None else ""))
    if jit_flag:
        ap.add_argument("--jit", action="store_true",
                        help="XLA-fused kernels (faster, last-ulp drift vs the scalar oracle)")
    ap.add_argument("--calibrated", action="store_true", default=calibrated_default,
                    help="include the calibrated power-up overhead (DESIGN.md §2)")
    ap.add_argument("--no-calibrated", dest="calibrated", action="store_false")
    return ap


def powerup_overhead_mj(args) -> float:
    """--calibrated flag → overhead constant (0.0 when absent/false)."""
    from repro.core import energy_model as em

    return em.CALIBRATED_POWERUP_OVERHEAD_MJ if args.calibrated else 0.0


class Timer:
    """Tiny perf_counter context: ``with Timer() as t: ...; t.elapsed_s``."""

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        self.elapsed_s = 0.0
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed_s = time.perf_counter() - self._t0


def finish_payload(payload: dict, elapsed_s: float, **meta) -> dict:
    """Attach the uniform ``meta`` block (timing + launcher-specific keys)."""
    size = payload.get("size") or len(payload.get("records", [])) or None
    payload["meta"] = {
        "elapsed_s": round(elapsed_s, 6),
        "points_per_s": round(size / elapsed_s, 1) if size and elapsed_s > 0 else None,
        **meta,
    }
    return payload


#: Root of the checkout this module lives in (``src/repro/launch/..``).
REPO_ROOT = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "..")
)


def _git_sha() -> str | None:
    """HEAD SHA of the checkout, read from ``.git`` without starting a
    process; None outside a git checkout."""
    git = os.path.join(REPO_ROOT, ".git")
    try:
        with open(os.path.join(git, "HEAD")) as f:
            head = f.read().strip()
        if not head.startswith("ref: "):
            return head or None
        ref = head[len("ref: "):]
        if os.path.exists(os.path.join(git, ref)):
            with open(os.path.join(git, ref)) as f:
                return f.read().strip() or None
        with open(os.path.join(git, "packed-refs")) as f:
            for line in f:
                sha, _, name = line.strip().partition(" ")
                if name == ref:
                    return sha
    except OSError:
        return None
    return None


def device_info() -> dict:
    """The devices JAX computes on, as JAX reports them: the platform and
    ``device_kind`` of the first device, and how many there are."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


#: The checkout's persistent compile cache, used when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset.  A fixed path: the path is part of
#: the cache key, so a directory that moved between runs would never hit.
COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is left
    to JAX; otherwise the cache lives in ``.jax_cache/`` in the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def run_manifest(seed=None) -> dict:
    """Provenance block stamped into every emitted payload: git SHA,
    interpreter/library versions, the device JAX ran on, seed, wall-clock.
    The device fields are read from JAX, never guessed: a manifest that
    cannot name its device raises."""
    import jax
    import jaxlib
    import numpy

    now = datetime.datetime.now(datetime.timezone.utc)
    return {
        "git_sha": _git_sha(),
        "versions": {
            "python": platform.python_version(),
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "numpy": numpy.__version__,
        },
        "device": device_info(),
        "platform": platform.platform(),
        "seed": seed,
        "unix_time": round(now.timestamp(), 3),
        "timestamp": now.isoformat(timespec="seconds"),
    }


def emit(payload: dict, out: str | None, label: str = "payload") -> None:
    """JSON to ``out`` (with a stderr receipt) or stdout — the shared tail
    of every launcher's ``main``.  Stamps a :func:`run_manifest` into the
    payload (under ``"manifest"``) unless the launcher already did."""
    if isinstance(payload, dict) and "manifest" not in payload:
        seed = None
        config = payload.get("config")
        if isinstance(config, dict):
            seed = config.get("seed")
        payload["manifest"] = run_manifest(seed=seed)
    text = json.dumps(payload, indent=2)
    if out:
        with open(out, "w") as f:
            f.write(text)
        print(f"wrote {label} to {out}", file=sys.stderr)
    else:
        print(text)
