"""The engine's logits against the plain float32 references, at small
widths on the CPU: prefill, admission into the paged pool or the SSM
state rows, and decoding through the cache with the carried logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench_tiny import tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("ref"))


def reference_logits(cell, params, tokens, cap):
    seq = np.zeros(cap, np.int32)
    seq[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = cell.model.hidden(cell.spec, params, jnp.asarray(seq))
        tied = cell.spec.get("tie_word_embeddings",
                             cell.spec.get("tie_embeddings"))
        w = params["embed"].T if tied else params["out"]
        lg = jnp.dot(h, w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return np.asarray(lg)[:, :cell.spec["vocab_size"]]


@pytest.mark.parametrize("name", ["tiny-dense.tiny", "tiny-ssd.tiny"])
def test_engine_logits_match_reference(root, name):
    from repro.runtime.serve import DecodeEngine, Request

    cell = harness.load_cell(root, name)
    V = cell.spec["vocab_size"]
    params = harness.make_params(cell, 2**35 + 11)
    eng = DecodeEngine(harness.program_config(cell), params, slots=3,
                       page_size=16, max_ctx=128, max_new_cap=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, n, dtype=np.int32) for n in (23, 40)]
    slots, seen = [], []
    for p in prompts:
        req = Request(prompt=p, max_new=12)
        slots.append(eng.admit(req, eng.prefill(req)))
    seen.append(np.asarray(eng.st["logits"])[:, :V])
    for _ in range(11):
        eng.run_wave(1)
        seen.append(np.asarray(eng.st["logits"])[:, :V])
        eng.commit_wave()
    buf = np.asarray(eng.st["buf"])
    for p, s in zip(prompts, slots):
        toks = np.concatenate([p, buf[s, :11]])
        ref = reference_logits(cell, params, toks, 128)
        got = np.stack([x[s] for x in seen])
        want = ref[len(p) - 1:len(p) + 11]
        scale = np.abs(want).max()
        assert np.abs(got - want).max() <= 1e-4 * scale, (
            name, np.abs(got - want).max(), scale)
        # greedy tokens are the reference's best at every position
        assert np.array_equal(buf[s, :11], want[:11].argmax(-1))
