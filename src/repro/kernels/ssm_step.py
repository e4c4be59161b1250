"""Pallas TPU kernel: one Mamba2 decode step on the layer-stacked state.

For every row ``r`` and head ``h`` of layer ``l`` (group ``g`` of ``h``):

    h'[l, r, h] = exp(a)[r, h] * h[l, r, h] + xin[r, h] (x) b[r, g]   [P, S]
    y[r, h]     = sum_S c[r, g] * h'[l, r, h]                          [P]

in one pass over the state: a row's ``[H, P, S]`` block of layer ``l``
comes into VMEM once, is updated, goes back through an aliased output
into the same buffer, and ``y`` is reduced from the updated values while
they are still in registers. XLA alone cannot fuse an in-place
dynamic-update-slice with a reduction over the rows it writes, so its
form reads the layer's state a second time for ``y`` (DESIGN.md §18).

Tiling: grid over the rows; the layer comes in as a scalar-prefetch
operand and picks the block in the index map, so the other layers of the
stack are never touched. Heads are taken ``hc`` at a time, ``hc * P``
state rows (128 at ``P = 64``): ``xin`` arrives lane-dense as one
``[1, hc * P]`` row per chunk, and a sublane broadcast plus one
transpose turns it into the ``[hc * P, S]`` lane broadcast the outer
product needs; the read-out transposes ``c * h'`` back and sums over
sublanes, so ``y`` leaves lane-dense too. The per-head decay is a scalar
read from SMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret

__all__ = ["mamba2_state_step"]

_LANE = 128


def _heads_per_chunk(heads_per_group: int, P: int) -> int:
    """Heads read as one ``[hc * P, S]`` chunk: a lane's worth of state
    rows where ``P`` divides 128, never straddling a group."""
    return math.gcd(heads_per_group, max(1, _LANE // P))


def _state_step_kernel(layer_ref, da_ref, x_ref, b_ref, c_ref, h_ref,
                       o_ref, y_ref, *, hc: int, heads_per_group: int):
    del layer_ref                                # used by the index maps
    i = pl.program_id(0)
    P, S = h_ref.shape[-2:]
    x = x_ref[...]                               # [H / hc, hc * P]
    b = b_ref[...]                               # [G, S]
    c = c_ref[...]
    for k in range(x.shape[0]):
        g = k * hc // heads_per_group
        bg, cg = b[g:g + 1], c[g:g + 1]          # [1, S]
        # row j of xb holds xin of state row j, broadcast over S
        xb = jnp.broadcast_to(x[k:k + 1], (S, hc * P)).T
        w = []
        for j in range(hc):
            h = k * hc + j
            new = h_ref[h] * da_ref[i, h] + xb[j * P:(j + 1) * P] * bg
            o_ref[h] = new
            w.append(new * cg)
        w = jnp.concatenate(w, axis=0)           # [hc * P, S]
        y_ref[k:k + 1] = jnp.sum(w.T, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba2_state_step(state: jnp.ndarray, layer, da: jnp.ndarray,
                      xin: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, *,
                      interpret: bool | None = None):
    """Update layer ``layer`` of ``state: f32[R, B, H, P, S]`` in place and
    read ``y`` out of the updated state in the same pass.

    ``da: f32[B, H]`` is the step's decay ``exp(a)``, ``xin: f32[B, H, P]``
    the step's input (``x * dt``), ``b``, ``c: f32[B, G, S]`` with ``G``
    dividing ``H`` (head ``h`` reads group ``h // (H // G)``). Returns
    ``(state, y)``: the whole stack with only layer ``layer`` rewritten
    (the state buffer is aliased to the output), and ``y: f32[B, H, P]``.
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    R, B, H, P, S = state.shape
    G = b.shape[1]
    if H % G or b.shape != (B, G, S) or c.shape != (B, G, S):
        raise ValueError(f"b, c {b.shape}, {c.shape} do not fit state "
                         f"{state.shape} in groups dividing {H} heads")
    if da.shape != (B, H) or xin.shape != (B, H, P):
        raise ValueError(f"da {da.shape}, xin {xin.shape} do not fit "
                         f"state {state.shape}")
    hc = _heads_per_chunk(H // G, P)
    f32 = jnp.float32
    block = pl.BlockSpec((None, None, H, P, S),
                         lambda i, l_ref: (l_ref[0], i, 0, 0, 0))
    new_state, y = pl.pallas_call(
        functools.partial(_state_step_kernel, hc=hc,
                          heads_per_group=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((None, H // hc, hc * P),
                             lambda i, _: (i, 0, 0)),
                pl.BlockSpec((None, G, S), lambda i, _: (i, 0, 0)),
                pl.BlockSpec((None, G, S), lambda i, _: (i, 0, 0)),
                block,
            ],
            out_specs=[
                block,
                pl.BlockSpec((None, H // hc, hc * P),
                             lambda i, _: (i, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((B, H // hc, hc * P), f32)],
        input_output_aliases={5: 0},
        name="mamba2_state_step",
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), da.astype(f32),
      xin.astype(f32).reshape(B, H // hc, hc * P), b.astype(f32),
      c.astype(f32), state)
    return new_state, y.reshape(B, H, P)
