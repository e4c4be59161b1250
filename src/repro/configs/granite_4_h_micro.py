"""granite-4.0-h-micro [hybrid]: 40L d_model=2048, 36 Mamba2 + 4 GQA
attention layers (32H, kv=8, NoPE), SwiGLU d_ff=8192 after every mixer,
vocab=100352, ssm_state=128 [hf:ibm-granite/granite-4.0-h-micro;
config.json, model_type granitemoehybrid].

Pattern: 5 Mamba2 sublayers, 1 attention sublayer, 4 Mamba2 sublayers,
repeated 4x (attention at layers 5, 15, 25, 35). Every sublayer is a
mixer followed by ``norm2`` + MLP: the ``mamba2`` kind gains its MLP from
``d_ff > 0``. The decode cache carries paged K/V for the attention slot
beside f32 SSM state and conv windows for the nine Mamba2 slots
(DESIGN.md §19). Granite's scalars: embeddings x12, every sublayer
output x0.22 before its residual add, attention softmax scale 1/64,
logits divided by 8."""

from repro.configs import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab=100352,
    pattern=("mamba2",) * 5 + ("attn",) + ("mamba2",) * 4,
    rope_theta=None,            # position_embedding_type: nope
    attn_scale=0.015625,        # attention_multiplier
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    tie_embeddings=True,
    norm_eps=1e-5,
    ssm_state=128,
    ssm_heads=64,               # d_inner 4096 / headdim 64
    ssm_d_inner=4096,
    ssm_conv=4,
    ssm_groups=1,
    # prefill of 3,072 tokens on a v5e: 328 ms at chunk 64, 232 at 128,
    # 265 at 256 (published mamba_chunk_size 256: an execution choice)
    ssm_chunk=128,
)
