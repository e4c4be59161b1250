"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ref
from repro.kernels.aggregate import aggregate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.ssm_step import mamba2_state_step
from repro.kernels.xor_code import (xor_decode, xor_decode_gather,
                                    xor_encode, xor_encode_gather, xor_fold)


# --------------------------------------------------------------------- #
# xor_code
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("m,n", [(2, 64), (3, 100), (5, 1024), (2, 1),
                                 (4, 4097)])
def test_xor_encode_matches_ref(m, n):
    rng = np.random.default_rng(m * 1000 + n)
    pk = rng.integers(0, 2**32, size=(m, n), dtype=np.uint32)
    got = xor_encode(jnp.asarray(pk), block=256)
    want = ref.xor_encode_ref(jnp.asarray(pk))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_xor_encode_involution():
    rng = np.random.default_rng(0)
    a = rng.integers(0, 2**32, size=(2, 300), dtype=np.uint32)
    enc = np.asarray(xor_encode(jnp.asarray(a)))
    np.testing.assert_array_equal(enc ^ a[0], a[1])


@pytest.mark.parametrize("R,m,n", [(1, 2, 64), (5, 3, 100), (16, 4, 1025),
                                   (3, 2, 1)])
def test_xor_fold_matches_ref(R, m, n):
    rng = np.random.default_rng(R * 100 + m * 10 + n)
    pk = rng.integers(0, 2**32, size=(R, m, n), dtype=np.uint32)
    got = xor_fold(jnp.asarray(pk), block=256)
    want = ref.xor_fold_ref(jnp.asarray(pk))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("R,m,n", [(1, 2, 64), (6, 4, 300), (4, 3, 1025)])
def test_xor_decode_matches_ref(R, m, n):
    rng = np.random.default_rng(R + m + n)
    pk = rng.integers(0, 2**32, size=(R, m, n), dtype=np.uint32)
    rv = rng.integers(0, 2**32, size=(R, n), dtype=np.uint32)
    mk = rng.integers(0, 2, size=(R, m)).astype(bool)
    got = xor_decode(jnp.asarray(rv), jnp.asarray(pk), jnp.asarray(mk),
                     block=256)
    want = ref.xor_decode_ref(jnp.asarray(rv), jnp.asarray(pk),
                              jnp.asarray(mk))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_xor_codec_roundtrip():
    """decode(encode) recovers the receiver's packet: Δ = XOR of m
    packets; cancelling m-1 of them leaves the remaining one."""
    rng = np.random.default_rng(42)
    R, m, n = 4, 3, 200
    pk = rng.integers(0, 2**32, size=(R, m, n), dtype=np.uint32)
    delta = xor_fold(jnp.asarray(pk), block=256)        # all m packets
    mask = np.ones((R, m), dtype=bool)
    mask[:, 0] = False                                   # cancel all but 0
    got = xor_decode(delta, jnp.asarray(pk), jnp.asarray(mask), block=256)
    np.testing.assert_array_equal(np.asarray(got), pk[:, 0])


# --------------------------------------------------------------------- #
# fused gather-XOR codec (single-pass encode/decode)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("P,pk,n,m", [(8, 64, 4, 3), (37, 200, 11, 4),
                                      (5, 1, 3, 2), (64, 1025, 9, 5)])
def test_xor_encode_gather_matches_ref(P, pk, n, m):
    rng = np.random.default_rng(P * 7 + pk + n + m)
    chunks = rng.integers(0, 2**32, size=(P, pk), dtype=np.uint32)
    idx = rng.integers(0, P, size=(n, m)).astype(np.int32)
    mask = rng.integers(0, 2, size=(n, m)).astype(bool)
    got = xor_encode_gather(jnp.asarray(chunks), jnp.asarray(idx),
                            jnp.asarray(mask), block=256)
    want = ref.xor_encode_gather_ref(jnp.asarray(chunks), jnp.asarray(idx),
                                     jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("P,pk,R,m", [(8, 64, 6, 3), (21, 130, 10, 4),
                                      (4, 1, 2, 2)])
def test_xor_decode_gather_matches_ref(P, pk, R, m):
    rng = np.random.default_rng(P + pk + R + m)
    chunks = rng.integers(0, 2**32, size=(P, pk), dtype=np.uint32)
    recv = rng.integers(0, 2**32, size=(R, pk), dtype=np.uint32)
    rsel = rng.permutation(R).astype(np.int32)
    idx = rng.integers(0, P, size=(R, m)).astype(np.int32)
    mask = rng.integers(0, 2, size=(R, m)).astype(bool)
    got = xor_decode_gather(jnp.asarray(recv), jnp.asarray(chunks),
                            jnp.asarray(rsel), jnp.asarray(idx),
                            jnp.asarray(mask), block=256)
    want = ref.xor_decode_gather_ref(jnp.asarray(recv), jnp.asarray(chunks),
                                     jnp.asarray(rsel), jnp.asarray(idx),
                                     jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gather_codec_roundtrip():
    """Fused encode then fused decode recovers the excluded packet:
    Δ = XOR of all m sources; cancelling m-1 of them leaves one."""
    rng = np.random.default_rng(7)
    P, pk, n, m = 30, 96, 5, 4
    chunks = rng.integers(0, 2**32, size=(P, pk), dtype=np.uint32)
    # distinct sources per row so the rows are invertible
    idx = np.stack([rng.choice(P, size=m, replace=False)
                    for _ in range(n)]).astype(np.int32)
    full = np.ones((n, m), dtype=bool)
    delta = xor_encode_gather(jnp.asarray(chunks), jnp.asarray(idx),
                              jnp.asarray(full), block=256)
    canc = full.copy()
    canc[:, 0] = False                          # cancel all but source 0
    rsel = np.arange(n, dtype=np.int32)
    got = xor_decode_gather(delta, jnp.asarray(chunks), jnp.asarray(rsel),
                            jnp.asarray(idx), jnp.asarray(canc), block=256)
    np.testing.assert_array_equal(np.asarray(got), chunks[idx[:, 0]])


def test_gather_codec_masked_zero_index():
    """Masked-off entries are AND-killed even when their baked index
    aliases a real row (the lowering bakes 0 for invalid sources)."""
    rng = np.random.default_rng(8)
    chunks = rng.integers(0, 2**32, size=(6, 40), dtype=np.uint32)
    idx = np.zeros((3, 4), dtype=np.int32)      # all alias row 0
    mask = np.zeros((3, 4), dtype=bool)
    got = xor_encode_gather(jnp.asarray(chunks), jnp.asarray(idx),
                            jnp.asarray(mask), block=256)
    np.testing.assert_array_equal(np.asarray(got), 0)


def test_gather_codec_rejects_bad_shapes():
    chunks = jnp.zeros((4, 8), jnp.uint32)
    with pytest.raises(TypeError):
        xor_encode_gather(chunks.astype(jnp.int32),
                          jnp.zeros((2, 2), jnp.int32),
                          jnp.ones((2, 2), bool))
    with pytest.raises(ValueError):
        xor_encode_gather(chunks, jnp.zeros((2, 2), jnp.int32),
                          jnp.ones((2, 3), bool))
    with pytest.raises(ValueError):
        xor_decode_gather(jnp.zeros((2, 8), jnp.uint32), chunks,
                          jnp.zeros((3,), jnp.int32),
                          jnp.zeros((2, 2), jnp.int32),
                          jnp.ones((2, 2), bool))


# --------------------------------------------------------------------- #
# aggregate
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("n,d,S", [(16, 8, 4), (100, 33, 7), (512, 256, 16),
                                   (7, 640, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_aggregate_matches_ref(n, d, S, dtype):
    rng = np.random.default_rng(n + d)
    vals = rng.standard_normal((n, d)).astype(dtype)
    ids = rng.integers(0, S, size=n).astype(np.int32)
    got = aggregate(jnp.asarray(vals), jnp.asarray(ids), S,
                    block_n=64, block_d=128)
    want = ref.aggregate_ref(jnp.asarray(vals), jnp.asarray(ids), S)
    # one-hot-matmul and segment_sum reduce in different f32 orders
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_aggregate_one_row_segments_bit_exact():
    """gamma = 1 (one row per segment) is an exact gather over a wide
    range of magnitudes: the trainer's bit-identity across sync modes
    rests on it, and the MXU only multiplies bf16."""
    rng = np.random.default_rng(3)
    vals = (rng.standard_normal((48, 640))
            * 10.0 ** rng.uniform(-25, 25, (48, 640))).astype(np.float32)
    vals[3, 7] = -0.0                   # a sum gives +0.0, as the ref does
    perm = rng.permutation(48).astype(np.int32)
    got = np.asarray(aggregate(jnp.asarray(vals), jnp.asarray(perm), 48))
    want = np.empty_like(vals)
    want[perm] = np.float32(0) + vals
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    ref_out = np.asarray(ref.aggregate_ref(jnp.asarray(vals),
                                           jnp.asarray(perm), 48))
    np.testing.assert_array_equal(got.view(np.uint32),
                                  ref_out.view(np.uint32))


def test_aggregate_commutativity():
    """Associativity/commutativity of the α-combiner (Def. 1): permuting
    rows must not change the aggregates."""
    rng = np.random.default_rng(1)
    vals = rng.standard_normal((50, 16)).astype(np.float32)
    ids = rng.integers(0, 5, size=50).astype(np.int32)
    perm = rng.permutation(50)
    a = aggregate(jnp.asarray(vals), jnp.asarray(ids), 5)
    b = aggregate(jnp.asarray(vals[perm]), jnp.asarray(ids[perm]), 5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6,
                               atol=1e-5)


# --------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------- #
ATTN_CASES = [
    # B, Hq, Hkv, Tq, Tk, D, causal, window, softcap
    (1, 2, 2, 64, 64, 16, True, None, None),
    (2, 4, 2, 32, 32, 32, True, None, None),        # GQA
    (1, 2, 1, 128, 128, 16, True, 32, None),        # sliding window
    (1, 2, 2, 64, 64, 16, True, None, 50.0),        # softcap (gemma2)
    (1, 4, 4, 48, 48, 16, False, None, None),       # bidirectional (encoder)
    (1, 2, 1, 1, 96, 16, True, None, None),         # decode: Tq=1, KV cache
    (1, 2, 2, 100, 100, 16, True, None, None),      # non-divisible lengths
    (1, 8, 2, 8, 72, 16, True, 24, None),           # decode-window combo
]


@pytest.mark.parametrize(
    "B,Hq,Hkv,Tq,Tk,D,causal,window,softcap", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, Hq, Hkv, Tq, Tk, D, causal, window,
                                     softcap, dtype):
    rng = np.random.default_rng(hash((B, Hq, Tq, Tk)) % 2**31)
    q = rng.standard_normal((B, Hq, Tq, D)).astype(dtype)
    k = rng.standard_normal((B, Hkv, Tk, D)).astype(dtype)
    v = rng.standard_normal((B, Hkv, Tk, D)).astype(dtype)
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, window=window, softcap=softcap,
                          block_q=32, block_k=32)
    want = ref.flash_attention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal,
        window=window, softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_rejects_bad_gqa():
    q = jnp.zeros((1, 3, 8, 4))
    k = v = jnp.zeros((1, 2, 8, 4))
    with pytest.raises(ValueError):
        flash_attention(q, k, v)


# --------------------------------------------------------------------- #
# ssd scan
# --------------------------------------------------------------------- #
SSD_CASES = [
    # B, T, H, P, S, chunk
    (1, 32, 2, 8, 4, 8),
    (2, 64, 1, 16, 8, 16),
    (1, 100, 2, 8, 4, 32),   # non-divisible T
    (1, 16, 3, 4, 16, 16),   # chunk == T
]


@pytest.mark.parametrize("B,T,H,P,S,chunk", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_scan_matches_ref(B, T, H, P, S, chunk, dtype):
    rng = np.random.default_rng(T + P)
    x = rng.standard_normal((B, T, H, P)).astype(dtype)
    a = (-np.abs(rng.standard_normal((B, T, H))) * 0.1).astype(dtype)
    b = rng.standard_normal((B, T, H, S)).astype(dtype) * 0.5
    c = rng.standard_normal((B, T, H, S)).astype(dtype) * 0.5
    got = ssd_scan(jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
                   jnp.asarray(c), chunk=chunk)
    want = ref.ssd_scan_ref(jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
                            jnp.asarray(c))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssd_scan_chunk_invariance():
    """The chunked evaluation must not depend on the chunk size."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((1, 64, 1, 8)).astype(np.float32)
    a = (-np.abs(rng.standard_normal((1, 64, 1))) * 0.2).astype(np.float32)
    b = rng.standard_normal((1, 64, 1, 4)).astype(np.float32)
    c = rng.standard_normal((1, 64, 1, 4)).astype(np.float32)
    outs = [np.asarray(ssd_scan(jnp.asarray(x), jnp.asarray(a),
                                jnp.asarray(b), jnp.asarray(c), chunk=ch))
            for ch in (8, 16, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# mamba2 decode state step (fused update + read-out, in place)
# --------------------------------------------------------------------- #
def _state_step_inputs(R, B, H, P, S, G, seed):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    state = f(R, B, H, P, S) * 0.5
    da = jnp.exp(-jnp.abs(f(R, B, H)) * 0.3)          # per layer
    return state, da, f(R, B, H, P), f(R, B, G, S), f(R, B, G, S)


@pytest.mark.parametrize("H,P,S", [(4, 8, 16), (4, 64, 128)])
@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("in_scan", [False, True])
def test_mamba2_state_step_matches_xla(in_scan, G, B, H, P, S):
    """The kernel against the XLA decode form on a stack of 3 layers: the
    updated layer and ``y`` to f32 tolerance, every other layer of the
    returned stack bitwise unchanged. ``in_scan``: called inside a
    ``lax.scan`` that carries the stack, as the model's layer scan does,
    over layers 2 then 0, so layer 1 must come back untouched."""
    R = 3
    state, da, xin, b, c = _state_step_inputs(R, B, H, P, S, G, B + G + P)
    tol = dict(rtol=1e-5, atol=1e-5)

    def run(step, layers):
        def body(st, l):
            st, y = step(st, l, da[l], xin[l], b[l], c[l])
            return st, y
        if in_scan:
            return jax.jit(lambda st: lax.scan(body, st, layers))(state)
        ys = []
        st = state
        for l in layers:
            st, y = body(st, l)
            ys.append(y)
        return st, jnp.stack(ys)

    if in_scan:
        cases = [jnp.asarray([2, 0])]
    else:
        cases = [jnp.asarray([l]) for l in range(R)]
    for layers in cases:
        got_st, got_y = run(mamba2_state_step, layers)
        want_st, want_y = run(ref.ssm_state_step_ref, layers)
        assert got_y.shape == (len(layers), B, H, P)
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                                   **tol)
        for l in range(R):
            if l in np.asarray(layers):
                np.testing.assert_allclose(np.asarray(got_st[l]),
                                           np.asarray(want_st[l]), **tol)
            else:
                np.testing.assert_array_equal(np.asarray(got_st[l]),
                                              np.asarray(state[l]))


def test_mamba2_state_step_rejects_bad_groups():
    state, da, xin, b, c = _state_step_inputs(2, 1, 4, 8, 16, 3, 0)
    with pytest.raises(ValueError):
        mamba2_state_step(state, 0, da[0], xin[0], b[0], c[0])
