"""Seconds per bring-up moving weights device -> host -> device: the
program's ``checkpoint/to_host`` spans (each dequantized leaf pulled back,
after its kernel) and ``bring_up/to_device`` (the tree uploaded, until it is
on the device) inside each ``bring_up`` span in the window."""
from bench.program_spans import per_bring_up


def read(run):
    return per_bring_up(run, ("checkpoint/to_host", "bring_up/to_device"))
