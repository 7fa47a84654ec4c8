"""Share of the decode spans in which the chip was idle.  A decode span runs
from the first decode program after a prefill to the last one before the
next prefill (the host's Python loop, the ``argmax`` between steps, and
dispatch all fall inside it); ``bench/engine_programs.py`` tells decode from
prefill."""
from bench.engine_programs import decode_spans


def read(run):
    if run.trace is None:
        return None
    spans = decode_spans(run.trace)
    total = sum(e - s for s, e in spans)
    if total <= 0:
        return None
    idle = sum(sum(b - a for a, b in run.trace.gaps(within=sp)) for sp in spans)
    return 100.0 * idle / total
