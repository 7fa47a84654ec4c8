"""Whole-request share of the chip's bf16 peak under On-Off: the model
operations of every finished request (its prefill and the decode steps that
produced its tokens) over the requests' arrival-to-last-token time.

Bring-up is most of that time, so this is the model's work over
``onoff_latency_s`` by design: it is the whole step's share of the peak that
bounds ``dequant_roofline``, and still reads when a change takes the dequant
kernel off the path and its roofline falls silent."""
from bench import counts


def read(run):
    new = run.cell.workload["traffic"]["new_tokens"]
    done = [r for r in run.records if "t_done" in r]
    if not done:
        return None
    flops = sum(counts.request_flops(run.cell.config, r["batch"], r["prompt_len"], new)
                for r in done)
    seconds = sum(r["t_done"] - r["due"] for r in done)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
