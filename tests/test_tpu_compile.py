"""The Pallas kernels compile for a TPU v5e at real widths.

Compiled ahead of time for a described, unattached ``v5e:2x2`` chip with
``interpret=False``: the chip's compiler refuses what interpret mode
cannot see (block shapes off the (8, 128) tiling, layouts XLA and Mosaic
disagree on, broadcasts Mosaic does not implement). Nothing runs, so
these tests say nothing about results or times — the interpret-mode
tests in test_kernels.py and friends check values.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and the tests of this file run in the process that loads it.
"""

import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import xor_code as X
from repro.kernels.aggregate import aggregate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.ssm_step import mamba2_state_step

PK = 65536                      # u32 words per codec packet
N, M, P = 27, 4, 972            # make_plan(3, 4, d) stage-1 geometry


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_has_kernel(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


u32, u16, i32, b = jnp.uint32, jnp.uint16, jnp.int32, jnp.bool_

CODEC = {
    "xor_encode": (lambda p: X.xor_encode(p, interpret=False),
                   [((M, PK), u32)]),
    "xor_fold": (lambda p: X.xor_fold(p, interpret=False),
                 [((N, M, PK), u32)]),
    "xor_decode": (lambda r, p, k: X.xor_decode(r, p, k, interpret=False),
                   [((N, PK), u32), ((N, M, PK), u32), ((N, M), b)]),
    "xor_encode_gather": (
        lambda c, i, k: X.xor_encode_gather(c, i, k, interpret=False),
        [((P, PK), u32), ((N, M), i32), ((N, M), b)]),
    "xor_decode_gather": (
        lambda r, c, s, i, k: X.xor_decode_gather(r, c, s, i, k,
                                                  interpret=False),
        [((3 * N, PK), u32), ((P, PK), u32), ((3 * N,), i32),
         ((3 * N, M), i32), ((3 * N, M), b)]),
    "xor_encode_gather16": (
        lambda c, i, k: X.xor_encode_gather16(c, i, k, interpret=False),
        [((P, 2 * PK), u16), ((N, M), i32), ((N, M), b)]),
    "xor_decode_gather16": (
        lambda r, c, s, i, k: X.xor_decode_gather16(r, c, s, i, k,
                                                    interpret=False),
        [((3 * N, 2 * PK), u16), ((P, 2 * PK), u16), ((3 * N,), i32),
         ((3 * N, M), i32), ((3 * N, M), b)]),
}


@pytest.mark.parametrize("name", sorted(CODEC))
def test_codec_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CODEC[name]
    _compile_has_kernel(one_chip, fn, *shapes)


@pytest.mark.parametrize("width", [5, 300, 1500, 4097])
def test_gather_codec_compiles_at_odd_packet_widths(one_chip, width):
    """Short packets are one whole block; long ones a run of full
    (8, 128) u32 / (16, 128) u16 tiles plus zero padding."""
    _compile_has_kernel(
        one_chip,
        lambda r, c, c16, s, i, k: (
            X.xor_decode_gather(r, c, s, i, k, interpret=False),
            X.xor_encode_gather16(c16, i, k, interpret=False)),
        ((6, width), u32), ((24, width), u32), ((24, 2 * width), u16),
        ((6,), i32), ((6, 3), i32), ((6, 3), b))


def test_aggregate_compiles_for_v5e(one_chip):
    _compile_has_kernel(
        one_chip, lambda v, s: aggregate(v, s, 48, interpret=False),
        ((4096, 4096), jnp.float32), ((4096,), i32))


def test_ssd_scan_compiles_for_v5e(one_chip):
    """mamba2-1.3b widths: 64 heads of 64, state 128, T 4096."""
    x = ((1, 4096, 64, 64), jnp.float32)
    a = ((1, 4096, 64), jnp.float32)
    bc = ((1, 4096, 64, 128), jnp.float32)
    _compile_has_kernel(
        one_chip, lambda *t: ssd_scan(*t, interpret=False), x, a, bc, bc)


def test_mamba2_state_step_compiles_for_v5e(one_chip):
    """mamba2-1.3b's decode state step: layer l of the 48-layer stack of
    16 rows x 64 heads x [64, 128] f32 updated in place, y read out."""
    f32 = jnp.float32
    _compile_has_kernel(
        one_chip, lambda *t: mamba2_state_step(*t, interpret=False),
        ((48, 16, 64, 64, 128), f32), ((), i32), ((16, 64), f32),
        ((16, 64, 64), f32), ((16, 1, 128), f32), ((16, 1, 128), f32))


def test_flash_attention_compiles_for_v5e(one_chip):
    """gemma2-2b widths: 8 query / 4 KV heads of 256, T 4096, softcap."""
    q = ((1, 8, 4096, 256), jnp.bfloat16)
    kv = ((1, 4, 4096, 256), jnp.bfloat16)
    _compile_has_kernel(
        one_chip,
        lambda q, k, v: flash_attention(q, k, v, softcap=50.0,
                                        interpret=False), q, kv, kv)


def _pool_copies(hlo: str, shape: str):
    """``(in_entry, instruction, result type, operand type)`` for every
    ``copy`` or ``broadcast`` in ``hlo`` whose result has type
    ``shape`` (types with their layouts)."""
    types, out, entry = {}, [], False
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            types, entry = {}, line.startswith("ENTRY")
            continue
        m = re.match(r"\s*(?:ROOT\s+)?(%\S+) = (\S+) ([\w-]+)\((%[^,)]+)?",
                     line)
        if not m:
            continue
        types[m.group(1)] = m.group(2)
        if m.group(3) in ("copy", "broadcast") and \
                m.group(2).startswith(shape + "{"):
            out.append((entry, line.strip()[:160], m.group(2),
                        types.get(m.group(4) or "")))
    return out


def test_granite_wave_writes_the_kv_pool_in_place(one_chip):
    """granite-3-2b's serving wave at full width, 3 slots x 4,096
    positions in pages of 16: no decode step copies the stacked KV pool
    ``bf16[40,769,8,16,64]``. The only pool copies are at the
    executable's boundary, one into and one out of the loop's layout per
    pool: relayouts from the device's default layout (pages in lanes)."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.runtime.serve import DecodeEngine

    cfg = get_config("granite_3_2b")
    waves = []

    def state():
        # the engine under eval_shape: shapes only, nothing allocated
        eng = DecodeEngine(cfg, None, slots=3, page_size=16, max_ctx=4096,
                           max_new_cap=512)
        waves.append(eng._wave_fn)
        return eng.st

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    st = jax.eval_shape(state)
    params = jax.eval_shape(functools.partial(lm.init_params, cfg),
                            jax.random.PRNGKey(0))
    assert st["cache"]["0_attn"]["self"]["k"].shape == (40, 769, 8, 16, 64)
    compiled = waves[0].lower(
        on_chip(params), on_chip(st),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    print("granite wave, 3 x 4096: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes, "peak",
          mem.argument_size_in_bytes + mem.output_size_in_bytes
          - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    copies = _pool_copies(compiled.as_text(), "bf16[40,769,8,16,64]")
    assert all(entry for entry, _, _, _ in copies), copies
    assert len(copies) <= 4, copies
    for _, line, typ, src in copies:
        assert " copy(" in line and src is not None, line
        assert typ != src, f"a pool copy that keeps its layout: {line}"


def test_mamba2_wave_writes_both_states_in_place(one_chip):
    """mamba2-1.3b's serving wave on the published Mamba2 block at full
    width, 16 slots x 768 positions: no decode step copies the stacked
    f32 SSM state ``f32[48,16,64,64,128]`` or conv window
    ``bf16[48,16,3,4352]``. The state is not copied at all; the conv
    window (20 MB) is relaid out once into and once out of the loop, at
    the executable's boundary."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.runtime.serve import DecodeEngine

    cfg = get_config("mamba2_1p3b").replace(
        pattern=("mamba2",), vocab=50277, tie_embeddings=True,
        residual_in_fp32=True, norm_eps=1e-5)
    waves = []

    def state():
        eng = DecodeEngine(cfg, None, slots=16, page_size=16, max_ctx=768,
                           max_new_cap=256)
        waves.append(eng._wave_fn)
        return eng.st

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    st = jax.eval_shape(state)
    params = jax.eval_shape(functools.partial(lm.init_params, cfg),
                            jax.random.PRNGKey(0))
    ent = st["cache"]["0_mamba2"]
    assert ent["state"].shape == (48, 16, 64, 64, 128)
    assert ent["conv"].shape == (48, 16, 3, 4352)
    compiled = waves[0].lower(
        on_chip(params), on_chip(st),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    print("mamba2 wave, 16 x 768: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    # the XLA form's wave held 1,713,526,272 bytes of temporaries, nearly
    # all of them w_in's relayout; the fused step adds none of a row's
    # size (2 MiB a layer), only XLA's small copy slots, 16 KiB each
    assert mem.temp_size_in_bytes <= 1_713_526_272 + (1 << 20)
    hlo = compiled.as_text()
    # the decode state step is the fused kernel, not XLA's two passes
    assert "tpu_custom_call" in hlo and "mamba2_state_step" in hlo
    assert not _pool_copies(hlo, "f32[48,16,64,64,128]")
    copies = _pool_copies(hlo, "bf16[48,16,3,4352]")
    assert all(entry for entry, _, _, _ in copies), copies
    assert len(copies) <= 2, copies
    for _, line, typ, src in copies:
        assert " copy(" in line and src is not None, line
        assert typ != src, f"a window copy that keeps its layout: {line}"


def test_hybrid_wave_writes_pool_and_states_in_place(one_chip):
    """granite-4.0-h-micro's serving wave at full width, 16 slots x
    4,096 positions in pages of 16: one attention slot's K/V pool
    ``bf16[4,4097,8,16,64]`` beside nine Mamba2 slots' f32 SSM states
    ``f32[4,16,64,64,128]`` and conv windows ``bf16[4,16,3,4352]``, all in
    the carried cache. No decode step copies any of them: the states are
    not copied at all, the pool and the windows at most once into and
    once out of the loop's layout each, at the executable's boundary."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.runtime.serve import DecodeEngine

    cfg = get_config("granite_4_h_micro")
    waves = []

    def state():
        eng = DecodeEngine(cfg, None, slots=16, page_size=16, max_ctx=4096,
                           max_new_cap=512)
        waves.append(eng._wave_fn)
        return eng.st

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    st = jax.eval_shape(state)
    params = jax.eval_shape(functools.partial(lm.init_params, cfg),
                            jax.random.PRNGKey(0))
    cache = st["cache"]
    assert cache["5_attn"]["self"]["k"].shape == (4, 4097, 8, 16, 64)
    mamba = [n for n in cache if n.endswith("_mamba2")]
    assert len(mamba) == 9
    for n in mamba:
        assert cache[n]["state"].shape == (4, 16, 64, 64, 128)
        assert cache[n]["conv"].shape == (4, 16, 3, 4352)
    compiled = waves[0].lower(
        on_chip(params), on_chip(st),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    print("hybrid wave, 16 x 4096: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "mamba2_state_step" in hlo
    assert not _pool_copies(hlo, "f32[4,16,64,64,128]")
    for shape, most in (("bf16[4,4097,8,16,64]", 4),
                        ("bf16[4,16,3,4352]", 18)):
        copies = _pool_copies(hlo, shape)
        assert all(entry for entry, _, _, _ in copies), copies
        assert len(copies) <= most, (shape, copies)
        for _, line, typ, src in copies:
            assert " copy(" in line and src is not None, line
            assert typ != src, f"a copy that keeps its layout: {line}"
