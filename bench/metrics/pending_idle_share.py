"""Share of the traced window in which the chip was idle while a request
was due or in flight: chip idle time outside every ``schedule/wait_arrival``
span (the scheduler sleeping until the next arrival), over the window."""
from bench.program_spans import idle_in, spans


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    waits = spans(run.trace, "schedule/wait_arrival")
    if not waits:
        return None
    idle = idle_in(run.trace, [run.trace.window]) - idle_in(run.trace, waits)
    return 100.0 * idle / run.trace.window_s
