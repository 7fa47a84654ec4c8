"""Seconds per bring-up putting int8 values and scales on the device and
dispatching the dequant kernel: the program's ``checkpoint/dequant`` spans
inside each ``bring_up`` span in the window.  The kernel's own device time
falls in ``checkpoint/to_host``, which waits for it."""
from bench.program_spans import per_bring_up


def read(run):
    return per_bring_up(run, "checkpoint/dequant")
