"""Architecture configs (assigned pool) + shape specs + registry.

Every architecture is a :class:`ModelConfig`; ``reduced(cfg)`` derives the
small same-family variant used by CPU smoke tests. ``input_specs`` builds
the ShapeDtypeStruct stand-ins the dry-run lowers against (no allocation).
"""

from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

__all__ = ["ModelConfig", "ShapeSpec", "SHAPES", "ARCHS", "get_config",
           "reduced", "list_archs", "shape_supported"]


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec
    n_layers: int               # total sublayers (pattern * repeats)
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    # layer stacking: `pattern` is the repeating unit of sublayer kinds
    #   'attn'        causal (optionally windowed) attention + MLP/MoE
    #   'local'       sliding-window attention + MLP (gemma2 alternation)
    #   'ssm'         simplified SSD block (no conv, no gated norm)
    #   'mamba2'      the published Mamba2 block: fused input projection,
    #                 depthwise causal conv, SSD, gated RMSNorm; followed
    #                 by norm2 + MLP when d_ff > 0 (granitemoehybrid)
    #   'shared_attn' attention block with weights SHARED across repeats
    pattern: tuple = ("attn",)
    rope_theta: float | None = 1e4      # None: no rotary (NoPE)
    window: int | None = None           # SWA width for 'attn' layers
    local_window: int | None = None     # width for 'local' layers
    attn_softcap: float | None = None
    final_softcap: float | None = None
    mlp_act: str = "swiglu"             # swiglu | geglu
    tie_embeddings: bool = False
    scale_embed: bool = False           # gemma2 sqrt(d) embedding scale
    # granite-style scalars; None leaves the path as it is
    attn_scale: float | None = None     # softmax scale (None: hd ** -0.5)
    embedding_multiplier: float | None = None   # on the embedded tokens
    residual_multiplier: float | None = None    # on each sublayer output
    logits_scaling: float | None = None         # logits divided by it
    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25
    moe_shard_mode: str = "ep"          # ep | tp  (see layers.spec_moe)
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_d_inner: int = 0
    ssm_conv: int = 4                   # 'mamba2': causal conv width
    ssm_groups: int = 1                 # 'mamba2': B/C groups (ngroups)
    residual_in_fp32: bool = False      # residual stream kept in f32
    norm_eps: float = 1e-6              # every RMSNorm's epsilon
    # enc-dec
    n_enc_layers: int = 0
    # modality frontend stub
    frontend: str | None = None         # vit | audio
    frontend_dim: int = 0               # precomputed feature dim
    frontend_len: int = 0               # prefix length (vlm patches)
    # numerics / execution
    dtype: str = "bfloat16"
    use_pallas: bool = False
    remat: str = "block"                # none | block
    loss_chunk: int = 1024              # vocab-logit seq chunking
    microbatches: int = 1               # grad-accumulation inside train_step
    scan_unroll: bool = False           # unroll scans (trip-true HLO cost
    #                                     analysis in the dry-run; scanned
    #                                     form is the production default)
    attn_block: int = 1024              # XLA-lane flash block size
    ssm_chunk: int = 256                # XLA-lane SSD chunk length
    # paper integration: gradient sync mode for the data-parallel axis
    grad_sync: str = "allreduce"        # allreduce | camr
    grad_sync_dtype: str = "float32"    # float32 | bfloat16 — bf16 syncs
    #                                     gradients on the packed 16-bit
    #                                     codec lane at half the bytes,
    #                                     f32 master params (DESIGN.md
    #                                     §12; MultiModelCAMRTrainer and
    #                                     launch/train.py
    #                                     --grad-sync-dtype)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        """Embedding/logit table padded to 128 (vocab-parallel sharding +
        MXU alignment — Megatron-style). Logits beyond ``vocab`` are
        sliced off in the loss and by consumers."""
        return -(-self.vocab // 128) * 128

    @property
    def repeats(self) -> int:
        assert self.n_layers % len(self.pattern) == 0, (
            f"{self.name}: n_layers {self.n_layers} not a multiple of "
            f"pattern {self.pattern}")
        return self.n_layers // len(self.pattern)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # parameter count (for 6ND model-FLOPs roofline accounting)
    def param_count(self, active_only: bool = False) -> int:
        d, f, hd = self.d_model, self.d_ff, self.hd
        attn = d * self.n_heads * hd * 2 + d * self.n_kv_heads * hd * 2
        mlp = 3 * d * f
        if self.n_experts:
            e = self.experts_per_token if active_only else self.n_experts
            mlp = 3 * d * f * e + d * self.n_experts  # experts + router
        di, H, S = self.ssm_d_inner, self.ssm_heads, self.ssm_state
        ssm = 2 * d * di + d * 2 * S + d * H + di * d  # B/C group-shared
        conv = di + 2 * self.ssm_groups * S
        mamba2 = (d * (di + conv + H) + di * d       # in_proj, out_proj
                  + (self.ssm_conv + 1) * conv      # conv weight + bias
                  + 3 * H + di)                     # dt_bias, A, D, norm
        per = {"attn": attn + mlp, "local": attn + mlp,
               "shared_attn": attn + mlp, "ssm": ssm + d,
               "mamba2": mamba2 + d + (mlp + d if f else 0)}
        reps = self.repeats
        total = 0
        for kind in self.pattern:
            n = reps if kind != "shared_attn" else 1  # shared weights
            total += per[kind] * n
        total += self.n_enc_layers * (attn + 3 * d * f)
        total += self.vocab * d * (1 if self.tie_embeddings else 2)
        return int(total)


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

ARCHS = [
    "internvl2_26b", "mixtral_8x7b", "moonshot_v1_16b_a3b", "internlm2_20b",
    "gemma2_2b", "mistral_large_123b", "granite_3_2b", "zamba2_2p7b",
    "mamba2_1p3b", "seamless_m4t_large_v2", "granite_4_h_micro",
]


def get_config(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{name.replace('-', '_')}")
    return mod.CONFIG


def list_archs() -> list[str]:
    return list(ARCHS)


def shape_supported(cfg: ModelConfig, shape: ShapeSpec) -> tuple[bool, str]:
    """long_500k only for sub-quadratic archs (DESIGN.md §6)."""
    if shape.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return False, ("full/global-attention arch: 500k ctx needs a "
                       "per-layer 500k KV cache + quadratic prefill "
                       "(see DESIGN.md §6)")
    return True, ""


# --------------------------------------------------------------------- #
# reduced configs for CPU smoke tests
# --------------------------------------------------------------------- #
def reduced(cfg: ModelConfig) -> ModelConfig:
    """Small same-family variant: few layers, tiny widths/tables."""
    kw = dict(
        n_layers=2 * len(cfg.pattern), d_model=64, n_heads=4, n_kv_heads=2,
        # no MLP stays none: a 'mamba2' sublayer gains one from d_ff > 0
        head_dim=16, d_ff=128 if cfg.d_ff else 0, vocab=256,
        dtype="float32", loss_chunk=64,
        microbatches=1,
    )
    if cfg.n_experts:
        # capacity 8x: no token drops -> deterministic consistency tests
        kw.update(n_experts=4, experts_per_token=2,
                  moe_capacity_factor=8.0)
    if cfg.ssm_state:
        kw.update(ssm_state=16, ssm_heads=4, ssm_d_inner=128)
    if cfg.n_enc_layers:
        kw.update(n_enc_layers=2)
    if cfg.frontend:
        kw.update(frontend_dim=24, frontend_len=8)
    if cfg.local_window:
        kw.update(local_window=32)
    if cfg.window:
        kw.update(window=32)
    return cfg.replace(**kw)


# --------------------------------------------------------------------- #
# dry-run input specs (ShapeDtypeStructs; no allocation)
# --------------------------------------------------------------------- #
def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """Stand-ins for every model input of the step function for
    (cfg, shape). See repro.models.lm for the matching step signatures."""
    from repro.models import lm  # late import; jax-touching module

    B, T = shape.global_batch, shape.seq_len
    i32, f = jnp.int32, cfg.jdtype
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch = {"tokens": sds((B, T), i32), "labels": sds((B, T), i32)}
        if cfg.frontend == "vit":
            batch["patches"] = sds((B, cfg.frontend_len, cfg.frontend_dim),
                                   f)
        if cfg.frontend == "audio":
            batch["frames"] = sds((B, T, cfg.frontend_dim), f)
        return {"batch": batch}
    if shape.kind == "prefill":
        batch = {"tokens": sds((B, T), i32)}
        if cfg.frontend == "vit":
            batch["patches"] = sds((B, cfg.frontend_len, cfg.frontend_dim),
                                   f)
        if cfg.frontend == "audio":
            batch["frames"] = sds((B, T, cfg.frontend_dim), f)
        return {"batch": batch}
    # decode: one new token against a full-length cache
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, B, T))
    return {"tokens": sds((B, 1), i32), "cache": cache,
            "cache_index": sds((), i32)}
