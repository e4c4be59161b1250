"""Packed low-precision codec lane (DESIGN.md §12).

Three property families:

* **pack/unpack round-trip** — the word packing is a pure bit
  transport: every 16-bit pattern (NaNs with payloads, denormals,
  -0.0, odd trailing lanes) survives ``pack_payload`` →
  ``unpack_payload`` and a full XOR encode/decode exactly. Numpy and
  jnp packers must agree byte-for-byte (the engine oracle and the SPMD
  lane share one wire format).
* **cross-lane bit-identity** — bf16/f16 payloads produce the SAME
  wire words and decoded chunks on all three codec lanes (multipass
  oracle / fused jnp / fused Pallas-interpret u16 kernels), including
  programs pulled through the survivor-set (degraded) re-lowering.
* **full-shuffle parity** — a packed-lane SPMD shuffle equals the
  numpy engine bitwise per device (subprocess mesh), the same contract
  the f32 lane pins in tests/test_collective.py.

Property bodies are plain helpers: hypothesis fuzzes them when the
optional extra is installed (CI does), and a deterministic parametrized
sweep runs them everywhere.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp
import ml_dtypes

from repro.core.collective import (_decode_stage, _encode_stage,
                                   _from_wire, _wire_buffer,
                                   camr_collective_bytes, make_plan)
from repro.core.schedule import (ScheduleCache, pack_payload,
                                 payload_words, unpack_payload)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional test extra (pyproject.toml)
    HAVE_HYPOTHESIS = False

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE = ScheduleCache()  # private: don't pollute the global cache stats

#: adversarial 16-bit patterns: quiet/signalling NaNs with payloads,
#: +/-inf, +/-0, denormals (min and max), min/max normals
_SPECIAL_U16 = [0x7FC0, 0xFFC0, 0x7F81, 0x7F80, 0xFF80, 0x0000, 0x8000,
                0x0001, 0x8001, 0x007F, 0x0080, 0x7F7F, 0xFF7F]


def _np16(dtype: str) -> np.dtype:
    return np.dtype(ml_dtypes.bfloat16 if dtype == "bfloat16"
                    else np.float16)


def _bits16(rng, shape) -> np.ndarray:
    """Random u16 patterns with adversarial specials sprinkled in."""
    bits = rng.integers(0, 2**16, size=shape, dtype=np.uint16)
    sel = rng.random(shape) < 0.2
    bits[sel] = rng.choice(np.asarray(_SPECIAL_U16, np.uint16),
                           size=int(sel.sum()))
    return bits


# --------------------------------------------------------------------- #
# pack/unpack round-trip
# --------------------------------------------------------------------- #
def check_pack_roundtrip(d: int, k: int, bits, dtype: str) -> None:
    """Any bit pattern (NaN payloads, denormals, -0.0) survives the
    word packing exactly, for every d incl. odd trailing lanes."""
    dt = _np16(dtype)
    rng = np.random.default_rng(len(bits) + d)
    pat = np.asarray(bits, np.uint16)
    x = rng.choice(pat, size=(3, d)).astype(np.uint16).view(dt)
    w = pack_payload(x, k)
    wp = payload_words(d, 2, k)
    assert w.shape == (3, wp) and w.dtype == np.uint32
    assert wp % (k - 1) == 0                      # packets split evenly
    back = unpack_payload(w, dt, d)
    np.testing.assert_array_equal(back.view(np.uint16), x.view(np.uint16))
    # pad lanes are deterministic zeros (wire bytes are reproducible)
    lanes = np.ascontiguousarray(w).view(np.uint16)
    assert (lanes[:, d:] == 0).all()
    # the jnp packer produces the same wire words byte-for-byte
    jw = np.asarray(_wire_buffer(jnp.asarray(x), wp=wp, codec="multipass",
                                 use_kernels=False))
    np.testing.assert_array_equal(jw, w)
    # ...and the jnp unpacker restores the same bits
    jback = np.asarray(_from_wire(jnp.asarray(w), jnp.dtype(dtype), d))
    np.testing.assert_array_equal(jback.view(np.uint16), x.view(np.uint16))


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
@pytest.mark.parametrize("d,k", [(1, 3), (4, 3), (6, 3), (9, 4), (17, 5),
                                 (5, 4)])
def test_pack_roundtrip_cases(d, k, dtype):
    check_pack_roundtrip(d, k, _SPECIAL_U16, dtype)


def test_payload_words_lanes():
    # 32-bit lane: identity on already-divisible widths
    assert payload_words(8, 4, 3) == 8
    assert payload_words(9, 4, 4) == 9
    # 16-bit lane: half the words, padded to a packet multiple
    assert payload_words(8, 4, 3) == 2 * payload_words(8, 2, 3)
    assert payload_words(6, 2, 3) == 4      # ceil(6/2)=3 -> pad to 4
    assert payload_words(9, 2, 4) == 6      # odd d: ceil(9/2)=5 -> 6
    with pytest.raises(ValueError):
        payload_words(8, 1, 3)
    with pytest.raises(TypeError):
        pack_payload(np.zeros((2, 4), np.float32), 3)
    with pytest.raises(TypeError):
        unpack_payload(np.zeros((2, 4), np.uint16), np.float16, 4)


# --------------------------------------------------------------------- #
# cross-lane bit-identity (the packed mirror of test_codec_fused)
# --------------------------------------------------------------------- #
def _lane_outputs(program, stage, x16, me, k, pk, seed):
    """Encode+decode one stage under every packed codec lane."""
    T = program.stage_tables(stage)
    rng = np.random.default_rng(seed)
    recv = jnp.asarray(rng.integers(0, 2**32, size=(T.n, k - 1, pk),
                                    dtype=np.uint32))
    wp = pk * (k - 1)
    outs = []
    # (codec, use_kernels): u16 Pallas kernels run in interpret mode on
    # CPU via _resolve_interpret — the same lanes the f32 tests pin
    for codec, uk in (("multipass", False), ("fused", False),
                      ("fused", True)):
        wire = _wire_buffer(x16, wp=wp, codec=codec, use_kernels=uk)
        ctx, delta = _encode_stage(wire, T, me, k=k, pk=pk, codec=codec,
                                   use_kernels=uk)
        chunk = _decode_stage(recv, ctx, T, me, k=k, pk=pk, codec=codec,
                              use_kernels=uk)
        outs.append((codec, uk, np.asarray(delta), np.asarray(chunk)))
    return outs


def check_packed_codec_bit_identical(q, k, d, seed, degraded,
                                     dtype) -> None:
    """Wire deltas and decoded chunks agree bit-for-bit across the
    multipass / fused-jnp / fused-u16-kernel lanes for 16-bit payloads
    of arbitrary bit patterns (incl. NaN/denormal), for every probed
    device, both stages, healthy AND survivor-set-lowered programs."""
    d += (-d) % (k - 1)                    # plan requires (k-1) | d
    K, J_own = q * k, q ** (k - 2)
    program = _CACHE.program(q, k, Q=K, d=d)
    if degraded:
        deg = _CACHE.degraded(program, {0})
        assert deg.base.s1 is program.s1 and deg.base.s2 is program.s2
        program = deg.base
    rng = np.random.default_rng(seed)
    bits = _bits16(rng, (J_own, k - 1, K, d))
    x16 = jnp.asarray(bits.view(_np16(dtype)))
    pk = payload_words(d, 2, k) // (k - 1)
    for stage in (1, 2):
        for me in {0, K - 1}:
            ref = None
            for codec, uk, delta, chunk in _lane_outputs(
                    program, stage, x16, me, k, pk, seed):
                if ref is None:
                    ref = (delta, chunk)
                    continue
                np.testing.assert_array_equal(
                    delta, ref[0],
                    err_msg=f"delta {codec}/uk={uk} s={me} stage={stage}")
                np.testing.assert_array_equal(
                    chunk, ref[1],
                    err_msg=f"chunk {codec}/uk={uk} s={me} stage={stage}")


@pytest.mark.parametrize("q,k,d,degraded,dtype", [
    (2, 3, 2, False, "bfloat16"),
    (2, 3, 6, True, "bfloat16"),      # word pad (w=3 -> wp=4)
    (2, 4, 9, False, "float16"),      # odd trailing lane
    (3, 3, 4, True, "float16"),
    (2, 3, 64, False, "bfloat16"),    # wider packets: 4-16 words
    (2, 4, 48, False, "bfloat16"),
    (3, 3, 16, False, "bfloat16"),
    (2, 3, 16, False, "bfloat16"),
])
def test_packed_codec_bit_identical_cases(q, k, d, degraded, dtype):
    check_packed_codec_bit_identical(q, k, d, seed=q * 100 + d,
                                     degraded=degraded, dtype=dtype)


@pytest.mark.parametrize("q,k,d", [(2, 3, 64), (2, 4, 48), (3, 3, 16),
                                   (2, 3, 16)])
def test_packed_lane_bytes_on_wire(q, k, d):
    """The packed lane ships at most 0.55x the f32 lane's bytes on the
    wire for the same element payload: half, plus at most ``k-2`` pad
    words per shard."""
    plan = make_plan(q, k, d)
    f32 = camr_collective_bytes(plan, dtype=jnp.float32)["camr_total"]
    b16 = camr_collective_bytes(plan, dtype=jnp.bfloat16)["camr_total"]
    assert b16 <= 0.55 * f32, (b16, f32)


def check_packed_wire_mirrors_numpy(q, d, seed) -> None:
    """The jnp wire buffer equals the numpy ``pack_payload`` mirror,
    and unpacking restores the exact source bits — the XOR transport
    does no arithmetic on the packed lane."""
    k = 3
    K, J_own = q * k, q ** (k - 2)
    rng = np.random.default_rng(seed)
    bits = _bits16(rng, (J_own, k - 1, K, d))
    x16 = jnp.asarray(bits.view(ml_dtypes.bfloat16))
    wp = payload_words(d, 2, k)
    wire = _wire_buffer(x16, wp=wp, codec="multipass", use_kernels=False)
    np.testing.assert_array_equal(
        np.asarray(wire), pack_payload(np.asarray(x16), k))
    flat = np.asarray(wire).reshape(-1, wp)
    back = unpack_payload(flat, ml_dtypes.bfloat16, d)
    np.testing.assert_array_equal(
        back.reshape(bits.shape).view(np.uint16), bits)


@pytest.mark.parametrize("q,d,seed", [(2, 4, 0), (3, 6, 1), (2, 2, 2)])
def test_packed_wire_mirrors_numpy_cases(q, d, seed):
    check_packed_wire_mirrors_numpy(q, d, seed)


# --------------------------------------------------------------------- #
# hypothesis fuzz lanes over the same properties (CI installs the extra)
# --------------------------------------------------------------------- #
if HAVE_HYPOTHESIS:
    _u16 = st.one_of(st.sampled_from(_SPECIAL_U16),
                     st.integers(0, 0xFFFF))

    @given(st.integers(1, 17), st.integers(3, 5),
           st.lists(_u16, min_size=1, max_size=64),
           st.sampled_from(["bfloat16", "float16"]))
    @settings(max_examples=60, deadline=None)
    def test_pack_roundtrip_hypothesis(d, k, bits, dtype):
        check_pack_roundtrip(d, k, bits, dtype)

    @given(st.integers(2, 3), st.integers(3, 4),
           st.sampled_from([2, 3, 9]), st.integers(0, 10**6),
           st.booleans(), st.sampled_from(["bfloat16", "float16"]))
    @settings(max_examples=10, deadline=None)
    def test_packed_codec_bit_identical_hypothesis(q, k, d, seed,
                                                   degraded, dtype):
        check_packed_codec_bit_identical(q, k, d, seed, degraded, dtype)

    @given(st.integers(2, 3), st.sampled_from([2, 4, 6]),
           st.integers(0, 10**5))
    @settings(max_examples=8, deadline=None)
    def test_packed_wire_mirrors_numpy_hypothesis(q, d, seed):
        check_packed_wire_mirrors_numpy(q, d, seed)


# --------------------------------------------------------------------- #
# full-shuffle parity vs the engine (subprocess mesh)
# --------------------------------------------------------------------- #
_RUN_PACKED = """
import numpy as np, jax, jax.numpy as jnp, ml_dtypes
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh, shard_map
from repro.core.collective import (camr_shuffle, make_plan,
                                   scatter_contributions)
from repro.core.engine import CAMRConfig, CAMREngine

q, k, d = {q}, {k}, {d}
K = q * k
plan = make_plan(q, k, d)
rng = np.random.default_rng({seed})
bg = rng.standard_normal((plan.J, k, K, d)).astype(
    np.float32).astype(ml_dtypes.bfloat16)
contribs = scatter_contributions(plan, bg)
mesh = make_mesh((K,), ('camr',))

eng = CAMREngine(CAMRConfig(q=q, k=k, gamma=1), lambda job, sf: sf)
res = eng.run([[bg[j, t] for t in range(k)] for j in range(plan.J)])
want = np.empty((K, plan.J, d), ml_dtypes.bfloat16)
for s in range(K):
    for j in range(plan.J):
        want[s, j] = res[s][(j, s)]

for codec, uk in (('fused', True), ('fused', False),
                  ('multipass', False)):
    def body(c, codec=codec, uk=uk):
        return camr_shuffle(plan, c[0], axis_name='camr', codec=codec,
                            use_kernels=uk)[None]
    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P('camr'),
                          out_specs=P('camr')))
    got = np.asarray(f(jnp.asarray(contribs)))
    assert got.dtype == ml_dtypes.bfloat16, got.dtype
    np.testing.assert_array_equal(got.view(np.uint16),
                                  want.view(np.uint16),
                                  err_msg=f'{{codec}}/uk={{uk}}')
print('OK')
"""


def _run_subprocess(code: str, ndev: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


@pytest.mark.parametrize("q,k,d", [(2, 3, 8), (2, 3, 6), (2, 4, 9)])
def test_packed_shuffle_matches_engine_bitwise(q, k, d):
    """bf16 SPMD shuffle == numpy engine, BITWISE, per device — even
    widths, widths needing word pad (d=6, k=3) and odd trailing lanes
    (d=9), on all three codec lanes."""
    out = _run_subprocess(_RUN_PACKED.format(q=q, k=k, d=d, seed=q * 10 + d),
                          ndev=q * k)
    assert "OK" in out
