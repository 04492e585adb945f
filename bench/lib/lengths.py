"""Request sets that do not change with the seed.

Lengths are the stratified quantiles of a stated distribution: the same
multiset in every run. Their pairing and order are drawn from the traffic
file's ``order_seed``: a replayed trace, the same requests in the same order
in every run. ``--seed`` draws the token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def stratified(dist: dict, n: int) -> list:
    """``n`` whole lengths: the quantiles (i + 1/2) / n of ``dist``, clipped
    to its ``min`` and ``max``. ``dist["shape"]`` is ``lognormal`` (``median``,
    ``sigma``) or ``uniform`` (over ``min``..``max``)."""
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if dist["shape"] == "lognormal":
            x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(p))
        elif dist["shape"] == "uniform":
            x = dist["min"] + p * (dist["max"] - dist["min"])
        else:
            raise ValueError(f"unknown length distribution {dist['shape']!r}")
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def request_set(traffic: dict, n: int, vocab: int, seed: int) -> list:
    """``n`` requests ``(prompt tokens, new tokens)``: prompt and output
    lengths are each the stratified set, paired and ordered by permutations
    drawn from ``traffic["order_seed"]`` (every mix states one); token ids
    are the seed's. No two prompts share a prefix (ids are independent draws
    from the whole vocabulary)."""
    rng = np.random.default_rng([int(seed), 0x72657173])
    order = np.random.default_rng([int(traffic["order_seed"]), 0x6F726472])
    prompts = np.array(stratified(traffic["prompt_len"], n))
    outputs = np.array(stratified(traffic["output_len"], n))
    prompts = prompts[order.permutation(n)]
    outputs = outputs[order.permutation(n)]
    return [(rng.integers(0, vocab, int(p)).tolist(), int(o))
            for p, o in zip(prompts, outputs)]


def paced_arrivals(rate: float, n: int, seed: int, jitter: float) -> list:
    """Due times of ``n`` requests evenly spaced at ``rate`` a second, each
    jittered by at most ``jitter`` (a share of a gap, half at the most; 0 is
    exactly periodic) either way from the seed, in order."""
    rng = np.random.default_rng([int(seed), 0x61727276])
    gap = 1.0 / rate
    return sorted(((i + 0.5) + rng.uniform(-jitter, jitter)) * gap
                  for i in range(n))
