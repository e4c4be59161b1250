"""mamba2-1.3b [ssm]: 48L d_model=2048 attention-free vocab=50280,
ssm_state=128 — SSD (state-space duality) [arXiv:2405.21060; unverified].

``pattern=("ssm",)`` is the simplified SSD block: no depthwise conv1d,
no dt bias, no gated RMSNorm, D applied to ``x * dt``. The published
Mamba2 block is the ``mamba2`` kind (DESIGN.md §18): the benchmark's
``mamba2-1.3b`` configuration selects it by ``pattern`` from this
config. The SSD core (the compute hot-spot) is kernels/ssd_scan.py on
the Pallas lane and ``kernels/ref.ssd_chunked`` on the XLA lane.
"""

from repro.configs import ModelConfig

CONFIG = ModelConfig(
    name="mamba2-1.3b",
    family="ssm",
    n_layers=48,
    d_model=2048,
    n_heads=1,                 # unused (attention-free)
    n_kv_heads=1,
    d_ff=0,
    vocab=50280,
    pattern=("ssm",),
    ssm_state=128,
    ssm_heads=64,              # d_inner 4096 / headdim 64
    ssm_d_inner=4096,
    ssm_chunk=64,              # see zamba2_2p7b.py note
    microbatches=2,
)
