"""Seconds per bring-up in the checkpoint codec: the program's
``checkpoint/decompress`` spans (every leaf's zstd decompress) inside each
``bring_up`` span in the window."""
from bench.program_spans import per_bring_up


def read(run):
    return per_bring_up(run, "checkpoint/decompress")
