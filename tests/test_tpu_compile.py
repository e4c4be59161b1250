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

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import xor_code as X
from repro.kernels.aggregate import aggregate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

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


def test_flash_attention_compiles_for_v5e(one_chip):
    """gemma2-2b widths: 8 query / 4 KV heads of 256, T 4096, softcap."""
    q = ((1, 8, 4096, 256), jnp.bfloat16)
    kv = ((1, 4, 4096, 256), jnp.bfloat16)
    _compile_has_kernel(
        one_chip,
        lambda q, k, v: flash_attention(q, k, v, softcap=50.0,
                                        interpret=False), q, kv, kv)
