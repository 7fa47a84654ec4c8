"""All decode time in the window over all decode steps that produced a
served token (``new_tokens - 1`` per request; the first token comes from
prefill): the engine's ``decode_s``, which ends when the last token is
ready."""


def read(run):
    steps = run.cell.workload["traffic"]["new_tokens"] - 1
    done = [r for r in run.records if "decode_s" in r]
    if not done or steps < 1:
        return None
    return 1000.0 * sum(r["decode_s"] for r in done) / (steps * len(done))
