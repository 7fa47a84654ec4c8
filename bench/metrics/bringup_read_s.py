"""Seconds per bring-up reading the checkpoint file and unpacking its
msgpack envelope: the program's ``checkpoint/read`` and ``checkpoint/unpack``
spans inside each ``bring_up`` span in the window."""
from bench.program_spans import per_bring_up


def read(run):
    return per_bring_up(run, ("checkpoint/read", "checkpoint/unpack"))
