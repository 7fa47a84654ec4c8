"""Share of the chip's bf16 peak that prefill reaches: the model operations
of every prefill in the window (counted from shapes, with the output head
only at the last position, whose logits prefill returns) over the engine's
``prefill_s``."""
from bench import counts


def read(run):
    done = [r for r in run.records if "prefill_s" in r]
    if not done:
        return None
    flops = sum(counts.prefill_flops(run.cell.config, r["batch"], r["prompt_len"]) for r in done)
    seconds = sum(r["prefill_s"] for r in done)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
