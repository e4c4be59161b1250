"""Property tests: the fused gather-XOR codec is bit-identical to the
multipass jnp oracle across (q, k, d) configurations (DESIGN.md §10).

Three lanes are compared on the SAME schedule tables, full-array
bit-for-bit (including rows where the device is not a group member —
both codecs must produce identical don't-care bytes so executor
swaps can never change wire or output bits):

* ``codec="multipass"``   — gather → take_along_axis → fold oracle,
* ``codec="fused"`` jnp   — flat-index-table gather + masked fold,
* ``codec="fused"`` Pallas — ``xor_encode_gather``/``xor_decode_gather``
  (interpret on CPU/GPU, compiled Mosaic when the backend is TPU —
  ``interpret=None`` resolution).

The program is optionally pulled through the survivor-set (degraded)
re-lowering path of the schedule cache first: the fused tables must be
the ones the fault runtime's base program serves.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional test extra (pyproject.toml)
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.core.collective import _decode_stage, _encode_stage  # noqa: E402
from repro.core.schedule import ScheduleCache  # noqa: E402

_CACHE = ScheduleCache()  # private: don't pollute the global cache stats


def _codec_lanes():
    # (codec, use_kernels); use_kernels=None resolves to compiled Mosaic
    # on TPU and the interpreter elsewhere — "compiled-if-TPU"
    return [("multipass", False), ("fused", False), ("fused", None)]


def _stage_codec_outputs(program, stage, u32, me, k, pk, seed):
    """Run encode + decode of one stage under every codec lane."""
    T = program.stage_tables(stage)
    rng = np.random.default_rng(seed)
    recv = jnp.asarray(rng.integers(0, 2**32, size=(T.n, k - 1, pk),
                                    dtype=np.uint32))
    outs = []
    for codec, uk in _codec_lanes():
        use_kernels = (uk if uk is not None
                       else __import__("jax").default_backend() == "tpu")
        ctx, delta = _encode_stage(u32, T, me, k=k, pk=pk, codec=codec,
                                   use_kernels=use_kernels)
        chunk = _decode_stage(recv, ctx, T, me, k=k, pk=pk, codec=codec,
                              use_kernels=use_kernels)
        outs.append((codec, uk, np.asarray(delta), np.asarray(chunk)))
    return outs


def check_fused_codec_bit_identical(q, k, pk, seed, degraded) -> None:
    """delta and decoded chunks agree bit-for-bit across all lanes, for
    every probed device, both stages — programs served directly or via
    the survivor-set re-lowering."""
    d = pk * (k - 1)
    K, J_own = q * k, q ** (k - 2)
    program = _CACHE.program(q, k, Q=K, d=d)
    if degraded:
        # pull the program through the fault path: the degraded
        # re-lowering keys by survivor set and must hand back the SAME
        # base tables the fused codec reads
        deg = _CACHE.degraded(program, {0})
        # width variants of one configuration share ONE degraded
        # re-lowering (d is not in the key), so deg.base may be another
        # width-stamped view — but it must serve the same table objects
        assert deg.base.s1 is program.s1 and deg.base.s2 is program.s2
        assert deg.coded_rows  # some groups stay fully coded
        program = deg.base
    rng = np.random.default_rng(seed)
    u32 = jnp.asarray(rng.integers(0, 2**32, size=(J_own, k - 1, K, d),
                                   dtype=np.uint32))
    for stage in (1, 2):
        for me in {0, K // 2, K - 1}:
            ref = None
            for codec, uk, delta, chunk in _stage_codec_outputs(
                    program, stage, u32, me, k, pk, seed):
                if ref is None:
                    ref = (delta, chunk)
                    continue
                np.testing.assert_array_equal(
                    delta, ref[0],
                    err_msg=f"delta {codec}/uk={uk} s={me} stage={stage}")
                np.testing.assert_array_equal(
                    chunk, ref[1],
                    err_msg=f"chunk {codec}/uk={uk} s={me} stage={stage}")


@given(st.integers(2, 3), st.integers(3, 4), st.sampled_from([1, 2, 5]),
       st.integers(0, 10**6), st.booleans())
@settings(max_examples=12, deadline=None)
def test_fused_codec_bit_identical(q, k, pk, seed, degraded):
    check_fused_codec_bit_identical(q, k, pk, seed, degraded)


@pytest.mark.parametrize("q,k,pk", [(2, 3, 32), (2, 4, 16), (3, 3, 8),
                                    (2, 3, 8)])
def test_fused_codec_bit_identical_cases(q, k, pk):
    """Packets of up to 32 words, wider than the property test draws."""
    check_fused_codec_bit_identical(q, k, pk, seed=q * 100 + k * 10 + pk,
                                    degraded=False)


@given(st.integers(2, 3), st.integers(3, 4), st.integers(0, 10**6))
@settings(max_examples=10, deadline=None)
def test_fused_tables_wellformed(q, k, seed):
    """Structural invariants of the lowered index tables: every source
    index addresses a real packet row, masks match validity, and the
    baked round→slot selector is a per-row permutation of the recv rows
    wherever the device is a group member."""
    K, J_own = q * k, q ** (k - 2)
    program = _CACHE.program(q, k, Q=K, d=k - 1)
    P = J_own * (k - 1) * K * (k - 1)          # flat packet rows
    for stage in (1, 2):
        T = program.stage_tables(stage)
        n = T.n
        assert T.enc_src.shape == (K, n, k)
        assert T.dec_src.shape == (K, n, k - 1, k)
        assert T.dec_recv.shape == (K, n, k - 1)
        assert (T.enc_src >= 0).all() and (T.enc_src < P).all()
        assert (T.dec_src >= 0).all() and (T.dec_src < P).all()
        assert (T.dec_recv >= 0).all() and (T.dec_recv < n * (k - 1)).all()
        # invalid sources are baked to row 0 and masked off
        assert (T.enc_src[~T.src_ok] == 0).all()
        assert (T.dec_src[~T.dec_mask] == 0).all()
        # member rows: dec_recv is a permutation of that row's recv rows
        for s in range(K):
            for li in np.flatnonzero(T.valid[s])[:4]:
                want = set(range(li * (k - 1), (li + 1) * (k - 1)))
                assert set(T.dec_recv[s, li].tolist()) == want
                # exactly k-2 cancellation packets per decoded slot
                assert (T.dec_mask[s, li].sum(axis=1) == k - 2).all()
