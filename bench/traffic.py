"""The one traffic generator: a mix file's parameters and a seed in, the
requests of a run out.

A mix is drawn in blocks of ``block`` requests. Every block holds the
same multiset of prompt lengths (each bucket's weight times the block)
and of output lengths (the block's evenly spaced quantiles of the
output distribution); the seed shuffles each block and draws the token
ids. So every seed, and every stretch of a run, offers the same work in
another order, and prompt lengths come only from the buckets (the
program compiles one prefill per prompt length).
"""

from __future__ import annotations

import math

import numpy as np


def _quantile(dist: dict, q: float) -> int:
    lo, hi = dist["low"], dist["high"]
    if dist["dist"] == "uniform":
        return int(round(lo + q * (hi - lo)))
    if dist["dist"] == "loguniform":
        return int(round(math.exp(math.log(lo)
                                  + q * (math.log(hi) - math.log(lo)))))
    raise ValueError(f"unknown output distribution {dist['dist']!r}")


def block_lengths(mix: dict) -> tuple[list[int], list[int]]:
    """One block's prompt and output lengths, in a fixed order."""
    n = mix["block"]
    pl = mix["prompt_len"]
    prompts = []
    for value, weight in zip(pl["values"], pl["weights"]):
        count = weight * n
        if abs(count - round(count)) > 1e-9:
            raise ValueError(f"bucket weight {weight} x block {n} is not a "
                             "whole number of requests")
        prompts += [value] * int(round(count))
    if len(prompts) != n:
        raise ValueError("bucket weights do not sum to 1")
    outputs = [_quantile(mix["output_len"], (i + 0.5) / n) for i in range(n)]
    return prompts, outputs


def buckets(mix: dict) -> list[int]:
    return list(mix["prompt_len"]["values"])


def max_output(mix: dict) -> int:
    return max(block_lengths(mix)[1])


def make_requests(mix: dict, seed: int, vocab: int):
    """``[(prompt int32 [T], max_new)]`` for one run."""
    rng = np.random.default_rng(seed)
    prompts, outputs = block_lengths(mix)
    out = []
    for _ in range(-(-mix["requests"] // mix["block"])):
        for T, n in zip(rng.permutation(prompts), rng.permutation(outputs)):
            ids = rng.integers(0, vocab, int(T), dtype=np.int32)
            out.append((ids, int(n)))
    return out[:mix["requests"]]
