"""95th percentile (linear interpolation) over every request due in the
window of the time from its due time to its first token: the wait until
inference starts plus the engine's ``prefill_s``, which ends when the first
token's logits are on the device.  A request that never finished counts as
infinitely late."""
import numpy as np


def read(run):
    if not run.records:
        return None
    ttft = [1000.0 * (r["t_infer"] - r["due"] + r["prefill_s"]) if "prefill_s" in r
            else float("inf") for r in run.records]
    return float(np.percentile(ttft, 95))
