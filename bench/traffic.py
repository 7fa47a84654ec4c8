"""The one traffic generator: a cell's ``traffic`` parameters and a seed in,
arrival offsets and request sizes out.

Every seed gets the same multiset of sizes and of gaps between arrivals, in
an order drawn from the seed (or, with ``"order": "fixed"``, in one order
for every seed), so that seeds change which request comes when and what its
tokens are, never how much work a run holds.

Parameters (``bench/workloads/<cell>.json``, key ``traffic``):

``arrivals``
    ``"closed_loop"``: one client sends its next request when the previous
    one has finished (offsets are all 0, the count is open).
    ``"poisson"``: an open loop at ``rate_per_s``.  The ``n = round(rate *
    seconds)`` gaps are the exponential distribution's quantiles at
    ``(j + 1/2) / n``, shuffled: a stratified Poisson stream whose gaps
    follow ``-ln(1 - u) / rate``, the arithmetic of
    ``PoissonArrivals`` in ``repro.core.arrivals``.
``order``
    ``"seed"`` (the default): the order of gaps and sizes is drawn from the
    run's seed.  ``"fixed"``: one order, the same for every seed, so the
    queue sees the same arrivals in every run; the seed still draws the
    tokens, the weights and the checked sample.  A tail of an open-loop
    queue depends on the order of its arrivals far more than on their
    multiset, so a cell whose end-to-end metric is such a tail fixes it.
``batch``, ``prompt_lens``, ``new_tokens``
    Each request is ``batch`` prompts of one length, the lengths taking the
    values of ``prompt_lens`` in equal shares, and ``new_tokens`` greedy
    tokens are generated for each prompt.
"""
from __future__ import annotations

import math

import numpy as np

#: Streams drawn from one seed, kept apart.
_ORDER, _TOKENS, _WEIGHTS, _SAMPLE = range(4)
#: The seed of the one order that ``"order": "fixed"`` gives every run.
_FIXED_ORDER_SEED = 0


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose; any non-negative seed, however large."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def jax_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.key`` drawn from the run's seed."""
    return int(rng(seed, _WEIGHTS).integers(0, 2**31 - 1))


def schedule(traffic: dict, seed: int, seconds: float, closed_pool: int = 0) -> dict:
    """``{"offsets_s": [...], "prompt_lens": [...]}`` for one run.

    For a closed loop the offsets are all 0 and ``closed_pool`` requests
    are made (the client cycles through them)."""
    lens_set = [int(x) for x in traffic["prompt_lens"]]
    order = rng(_FIXED_ORDER_SEED if traffic.get("order", "seed") == "fixed" else seed, _ORDER)
    if traffic["arrivals"] == "poisson":
        rate = float(traffic["rate_per_s"])
        n = max(1, round(rate * seconds))
        u = (np.arange(n) + 0.5) / n
        gaps = order.permutation(-np.log1p(-u) / rate)
        offsets = np.cumsum(gaps).tolist()
    elif traffic["arrivals"] == "closed_loop":
        n = max(1, closed_pool)
        offsets = [0.0] * n
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    lens = (lens_set * math.ceil(n / len(lens_set)))[:n]
    lens = [int(x) for x in order.permutation(lens)]
    return {"offsets_s": offsets, "prompt_lens": lens}


def prompts(lens: list[int], batch: int, vocab: int, seed: int) -> list[np.ndarray]:
    """One ``(batch, len)`` int32 array of token ids per request."""
    g = rng(seed, _TOKENS)
    return [g.integers(0, vocab, (batch, n), dtype=np.int32) for n in lens]


def sample(n_items: int, k: int, seed: int, must: list[int] = ()) -> list[int]:
    """``k`` distinct indices of ``n_items``, drawn from the seed, that
    include ``must``."""
    picked = list(dict.fromkeys(int(i) for i in must))[:k]
    rest = [i for i in rng(seed, _SAMPLE).permutation(n_items).tolist() if i not in picked]
    return sorted(picked + rest[: max(0, k - len(picked))])
