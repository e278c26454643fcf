"""Shared pieces of the traffic generators: fixed grids, seeded
dealing. Pure functions of (traffic file, seed, seconds)."""

from __future__ import annotations

import math
import random


def log_spaced(lo: float, hi: float, n: int) -> list[int]:
    """n whole numbers from lo to hi inclusive, evenly spaced in log."""
    if n == 1:
        return [int(round(hi))]
    return [int(round(lo * (hi / lo) ** (i / (n - 1)))) for i in range(n)]


def exp_quantile_gaps(rate: float, n: int) -> list[float]:
    """The n mid-quantiles of an exponential of the given rate: the
    same multiset of gaps for every seed, mean ~1/rate."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def fixed_permutation(n: int, key: int) -> list[int]:
    """A permutation that does NOT depend on --seed (pairs prompts
    with answers so that long prompts do not all get long answers)."""
    idx = list(range(n))
    random.Random(key).shuffle(idx)
    return idx


def deal_stratified(values: list, block: int, rng: random.Random) -> list:
    """Deal sorted ``values`` round-robin into blocks of ``block`` so
    every block spans the whole range, then let the seed shuffle inside
    each block and the order of the blocks. Same multiset for every
    seed; no seed can put all the long ones together."""
    ordered = sorted(
        values, key=lambda v: v["_key"] if isinstance(v, dict) else v)
    n_blocks = max(1, math.ceil(len(ordered) / block))
    blocks = [ordered[i::n_blocks] for i in range(n_blocks)]
    for b in blocks:
        rng.shuffle(b)
    rng.shuffle(blocks)
    return [v for b in blocks for v in b]
