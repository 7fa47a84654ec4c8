"""The traffic generator: deterministic per seed, and the same work for
every seed in another order."""
from __future__ import annotations

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repo on sys.path)

from bench import traffic

POISSON = {"arrivals": "poisson", "rate_per_s": 3.0, "batch": 8,
           "prompt_lens": [128, 256, 512], "new_tokens": 16}
SEEDS = [0, 7, 2**31 + 11, 2**40 + 3]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_schedule(seed):
    a = traffic.schedule(POISSON, seed, 51.0)
    b = traffic.schedule(POISSON, seed, 51.0)
    assert a == b
    pa = traffic.prompts(a["prompt_lens"], 8, 151936, seed)
    pb = traffic.prompts(b["prompt_lens"], 8, 151936, seed)
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
    assert all(p.dtype == np.int32 and p.min() >= 0 and p.max() < 151936 for p in pa)


def test_seeds_share_the_work_in_another_order():
    runs = [traffic.schedule(POISSON, s, 51.0) for s in SEEDS]
    n = round(3.0 * 51.0)
    for r in runs:
        assert len(r["offsets_s"]) == len(r["prompt_lens"]) == n
        assert sorted(r["prompt_lens"]) == sorted(runs[0]["prompt_lens"])
        gaps = np.diff([0.0] + r["offsets_s"])
        want = np.diff([0.0] + runs[0]["offsets_s"])
        np.testing.assert_allclose(np.sort(gaps), np.sort(want), rtol=1e-9, atol=1e-12)
        assert np.all(gaps > 0)
    assert runs[0]["prompt_lens"] != runs[1]["prompt_lens"]
    # the lengths take each value in equal shares
    assert {runs[0]["prompt_lens"].count(x) for x in (128, 256, 512)} == {n // 3}


def test_stratified_gaps_have_the_poisson_mean():
    r = traffic.schedule(POISSON, 3, 100.0)
    gaps = np.diff([0.0] + r["offsets_s"])
    assert abs(gaps.mean() * 3.0 - 1.0) < 0.01
    # exponential: the median gap is ln 2 / rate
    assert abs(np.median(gaps) - np.log(2) / 3.0) < 0.01


def test_closed_loop_and_sample():
    r = traffic.schedule({"arrivals": "closed_loop", "prompt_lens": [512]}, 5, 51.0,
                         closed_pool=6)
    assert r == {"offsets_s": [0.0] * 6, "prompt_lens": [512] * 6}
    picked = traffic.sample(100, 4, 9, must=[42])
    assert 42 in picked and len(set(picked)) == 4 and picked == traffic.sample(100, 4, 9, [42])
    assert traffic.jax_seed(2**40) == traffic.jax_seed(2**40) < 2**31


def test_fixed_order_gives_every_seed_the_same_arrivals():
    """With ``"order": "fixed"`` every seed sees the same gaps and sizes in
    the same order; the tokens still come from the seed."""
    fixed = {**POISSON, "order": "fixed"}
    runs = [traffic.schedule(fixed, s, 51.0) for s in SEEDS]
    assert all(r == runs[0] for r in runs)
    seeded = [traffic.schedule(POISSON, s, 51.0) for s in SEEDS]
    assert any(r != runs[0] for r in seeded)
    a = traffic.prompts(runs[0]["prompt_lens"][:2], 8, 151936, SEEDS[0])
    b = traffic.prompts(runs[0]["prompt_lens"][:2], 8, 151936, SEEDS[1])
    assert not np.array_equal(a[0], b[0])
