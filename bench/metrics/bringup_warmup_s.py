"""Seconds per bring-up building the engine and running its warm-up request
(tracing and compiling the engine's programs, one prefill and one decode
step): the program's ``bring_up/warmup`` span inside each ``bring_up`` span
in the window.  ``bringup_compile_s`` is the compile inside it."""
from bench.program_spans import per_bring_up


def read(run):
    return per_bring_up(run, "bring_up/warmup")
