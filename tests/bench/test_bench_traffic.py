"""The traffic generator: deterministic in the seed, prompt lengths only
from the buckets, and the same work in every block of every seed."""

import json
from collections import Counter

import numpy as np
import pytest

from bench import traffic
from bench_tiny import ROOT

MIXES = ["longctx", "chat"]


def mix(name):
    path = ROOT / "bench" / "traffic" / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.make_requests(mix(name), 2**40 + 3, 1000)
    b = traffic.make_requests(mix(name), 2**40 + 3, 1000)
    assert len(a) == mix(name)["requests"]
    for (pa, na), (pb, nb) in zip(a, b):
        assert na == nb and np.array_equal(pa, pb)
    c = traffic.make_requests(mix(name), 2**40 + 4, 1000)
    assert any(not np.array_equal(pa, pc) for (pa, _), (pc, _) in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_prompt_lengths_are_buckets_and_blocks_match(name):
    m = mix(name)
    n = m["block"]
    want_p, want_o = traffic.block_lengths(m)
    for seed in (0, 7, 2**33 + 1):
        reqs = traffic.make_requests(m, seed, 1000)
        assert {len(p) for p, _ in reqs} <= set(traffic.buckets(m))
        for i in range(0, len(reqs) - n + 1, n):
            blk = reqs[i:i + n]
            assert Counter(len(p) for p, _ in blk) == Counter(want_p)
            assert Counter(k for _, k in blk) == Counter(want_o)
        assert all(0 <= p.min() and p.max() < 1000 for p, _ in reqs)


def test_block_weights_must_be_whole_requests():
    m = dict(mix("longctx"), block=7)
    with pytest.raises(ValueError):
        traffic.block_lengths(m)


def test_requests_fit_the_engine():
    for name in MIXES:
        m = mix(name)
        assert max(traffic.buckets(m)) + traffic.max_output(m) \
            <= m["engine"]["max_ctx"]
