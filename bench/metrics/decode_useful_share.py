"""Share of the engine's decode steps that produced a served token: the
first token comes from prefill, so a request of ``new_tokens`` tokens needs
``new_tokens - 1`` steps, over the ``generate/decode_step`` spans inside the
window's ``generate`` spans."""
from bench.program_spans import inside, spans


def read(run):
    if run.trace is None:
        return None
    gens = spans(run.trace, "generate")
    steps = len(inside(spans(run.trace, "generate/decode_step"), gens))
    if not steps:
        return None
    useful = (run.cell.workload["traffic"]["new_tokens"] - 1) * len(gens)
    return 100.0 * useful / steps
