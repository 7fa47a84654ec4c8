"""Which device programs are the serving engine's decode step.

The engine jits ``functools.partial(prefill_fn, ...)`` and
``functools.partial(decode_fn, ...)``, which JAX names ``jit__unknown``, so
the trace tells them apart only by their hash.  Decode runs at one shape,
and many times for each prefill: the decode program is the engine program
that runs most often in the window.  Programs named after their functions
(``jit_decode_fn``) are taken by name.  Every other engine program is a
prefill.
"""
from __future__ import annotations

from collections import Counter

DECODE = "jit_decode_fn"
ENGINE = ("jit_prefill_fn", DECODE, "jit__unknown")


def engine_runs(trace) -> list[tuple[str, float, float]]:
    """Runs of the engine's programs in the window, by start."""
    return sorted((r for r in trace.named_modules() if r[0].startswith(ENGINE)),
                  key=lambda r: r[1])


def decode_program(trace):
    """The whole name of the decode program, or None."""
    runs = engine_runs(trace)
    for prefix in (DECODE, ""):
        counts = Counter(n for n, _, _ in runs if n.startswith(prefix))
        if counts:
            return counts.most_common(1)[0][0]
    return None


def decode_spans(trace) -> list[tuple[float, float]]:
    """Each stretch of decode runs between two other engine programs, from
    the start of its first run to the end of its last."""
    decode = decode_program(trace)
    spans, cur = [], None
    for name, s, e in engine_runs(trace):
        if name == decode:
            cur = (cur[0], e) if cur else (s, e)
        elif cur:
            spans.append(cur)
            cur = None
    if cur:
        spans.append(cur)
    return spans
