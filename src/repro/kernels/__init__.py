"""Pallas TPU kernels (interpret-validated on CPU) + jnp reference oracles.

Layout per kernel: ``<name>.py`` holds the ``pl.pallas_call`` + BlockSpec
tiling; ``ref.py`` the pure-jnp oracle; ``ops.py`` the jit'd dispatch
wrappers the models call.
"""

from . import ops, ref
from .aggregate import aggregate
from .flash_attention import flash_attention
from .ssd_scan import ssd_scan
from .ssm_step import mamba2_state_step
from .xor_code import xor_encode, xor_fold, xor_decode

__all__ = ["ops", "ref", "aggregate", "flash_attention", "ssd_scan",
           "mamba2_state_step", "xor_encode", "xor_fold", "xor_decode"]
