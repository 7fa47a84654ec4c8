"""Milliseconds per scan call in which the chip was idle while the host
converted the call's results to numpy: chip idle time inside the program's
``fleet/to_host`` spans in the window, over the window's ``bench/scan_call``
spans."""
from bench.program_spans import idle_in, spans


def read(run):
    if run.trace is None:
        return None
    calls = spans(run.trace, "bench/scan_call")
    to_host = spans(run.trace, "fleet/to_host")
    if not calls or not to_host:
        return None
    return 1000.0 * idle_in(run.trace, to_host) / len(calls)
