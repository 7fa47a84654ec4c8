"""Mean device time of one run of the decode program (see
``bench/engine_programs.py`` for how it is told from prefill)."""
from bench.engine_programs import decode_program


def read(run):
    if run.trace is None:
        return None
    name = decode_program(run.trace)
    runs = run.trace.modules(name) if name else []
    return 1000.0 * sum(e - s for s, e in runs) / len(runs) if runs else None
