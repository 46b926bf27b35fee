"""Architecture config schema + shape registry.

Each assigned architecture is one frozen :class:`ArchConfig` in its own
module under ``repro/configs`` (``--arch <id>`` resolves through
:func:`repro.configs.get`).  A config fully determines parameter shapes,
sharding specs and the lowered programs; the *same* dataclass powers the
full-scale dry-run and the reduced smoke tests (:meth:`ArchConfig.smoke`).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax.numpy as jnp


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | ssm | moe | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0                # 0 => d_model // n_heads

    # attention details
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e6
    local_window: int = 2048       # window for "local" blocks

    norm_eps: float = 1e-6         # every RMSNorm's epsilon

    # latent attention (MLA, no q compression): each token caches one
    # vector of kv_lora_rank + qk_rope_head_dim values per layer; 0 => GQA
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0

    # depth plan: the first `first_dense` layers are dense ("attn"); then
    # `pattern` tiles the rest of the depth, leftover layers taking the
    # pattern prefix.  Each maximal run of equal kinds becomes one scanned
    # segment (see models/blocks.py).
    first_dense: int = 0
    pattern: tuple = ("attn",)

    # MoE: the router chooses top_k of router_experts (0 => n_experts);
    # this layer holds the first n_experts of them and computes their part
    # of the result, dropless.  Gates: "softmax" over the router's logits,
    # or "sigmoid" scores chosen with a per-expert bias (DeepSeek-V3's
    # noaux_tc) — either normalised over the k chosen and times
    # routed_scale.  n_shared_experts run as one SwiGLU FFN of that many
    # times d_ff_expert on every token.
    n_experts: int = 0
    router_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    router_score: str = "softmax"
    routed_scale: float = 1.0
    n_shared_experts: int = 0
    router_aux_weight: float = 0.01

    # cross-attention context (vlm) / encoder-decoder (audio)
    n_ctx_tokens: int = 0          # stub modality tokens fed to cross-attn
    encoder_layers: int = 0        # > 0 => enc-dec; encoder runs `pattern`=enc

    # ssm / recurrent
    conv_width: int = 4
    mlstm_chunk: int = 64
    proj_factor: float = 2.0       # xLSTM mLSTM up-projection
    rglru_c: float = 8.0           # Griffin's fixed decay sharpness

    # the paper's technique: binary (XNOR-Net) projections
    quant: str = "none"            # "none" | "xnor"

    # numerics / serving
    dtype: Any = jnp.bfloat16
    kv_cache_dtype: str = "bf16"   # "bf16" | "i8" (fixed-point decode cache)
    kv_i8_scale: float = 32.0      # fixed-point scale for the i8 cache
                                   # (RMS-normed/RoPE'd |k| < ~4; 32 gives
                                   # ~2% rounding)
    block_size: int = 16           # paged KV-cache tokens per block
    prefill_chunk: int = 32        # chunked-prefill piece size (serve)
    fused_decode: str = "auto"     # decode-path kernel fusion (DESIGN.md §18):
                                   # "auto" (fused Pallas kernels on real TPU,
                                   # unfused bit-exact twin elsewhere) |
                                   # "on"/"kernel" | "off"/"ref"; the
                                   # REPRO_FUSED_DECODE env var overrides
    supports_long_context: bool = False
    notes: str = ""

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)
        assert self.n_heads % self.n_kv_heads == 0, (self.n_heads, self.n_kv_heads)
        assert self.router_score in ("softmax", "sigmoid"), self.router_score

    # --- depth plan ---------------------------------------------------------
    def layer_kinds(self) -> list[str]:
        rest = self.n_layers - self.first_dense
        reps = -(-rest // len(self.pattern))
        return ["attn"] * self.first_dense + list((self.pattern * reps)[:rest])

    def segments(self) -> list[tuple[str, int]]:
        """Maximal runs of equal block kinds -> scanned segments."""
        segs: list[tuple[str, int]] = []
        for k in self.layer_kinds():
            if segs and segs[-1][0] == k:
                segs[-1] = (k, segs[-1][1] + 1)
            else:
                segs.append((k, 1))
        return segs

    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    def encoder_segments(self) -> list[tuple[str, int]]:
        """Encoder depth plan for enc-dec archs ([] otherwise).  Kind names
        are config data here — consumers stay generic over them."""
        return [("enc", self.encoder_layers)] if self.is_encdec() else []

    # --- derived ------------------------------------------------------------
    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def cache_heads(self) -> int:
        """Heads of the KV cache: one latent vector, or the KV heads."""
        return 1 if self.latent else self.n_kv_heads

    @property
    def cache_k_dim(self) -> int:
        """Width of a cached key: d_head, or the latent and its rotary key
        padded to whole 128-lane tiles (576 -> 640), as the chip lays a
        576-wide array out anyway; the pad lanes hold zeros."""
        if self.latent:
            return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128
        return self.d_head

    @property
    def cache_v_dim(self) -> int:
        """Width of a cached value: none where the latent serves as both."""
        return 0 if self.latent else self.d_head

    @property
    def n_router_experts(self) -> int:
        return self.router_experts or self.n_experts

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to 256 so embedding/head shard over 16-way TP
        (standard practice; pad tokens never appear as labels)."""
        return -(-self.vocab // 256) * 256

    def smoke(self, **over) -> "ArchConfig":
        """Reduced same-family config for CPU smoke tests."""
        scale: dict[str, Any] = dict(
            n_layers=max(2, min(4, len(self.pattern))),
            d_model=64,
            n_heads=4,
            n_kv_heads=max(1, 4 // self.q_per_kv) if self.q_per_kv <= 4 else 1,
            d_head=16,
            d_ff=128,
            vocab=256,
            local_window=32,
            mlstm_chunk=8,
            block_size=8,
            prefill_chunk=8,
            name=self.name + "-smoke",
        )
        if self.n_experts:
            scale.update(n_experts=4, top_k=min(self.top_k, 2), d_ff_expert=32,
                         router_experts=8 if self.router_experts else 0)
        if self.latent:
            scale.update(kv_lora_rank=32, qk_rope_head_dim=8,
                         qk_nope_head_dim=16, v_head_dim=16)
        if self.first_dense:
            scale.update(first_dense=1)
        if self.n_ctx_tokens:
            scale.update(n_ctx_tokens=16)
        if self.encoder_layers:
            scale.update(encoder_layers=2)
        # keep the full pattern so every block kind is exercised
        if len(self.pattern) > 1:
            scale["n_layers"] = len(self.pattern)
        scale["n_layers"] += scale.get("first_dense", 0)
        scale.update(over)
        return dataclasses.replace(self, **scale)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str            # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k":    ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k":  ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k":   ShapeConfig("long_500k", "decode", 524288, 1),
}


def shape_applicable(cfg: ArchConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Spec rules: long_* only for sub-quadratic archs."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("full-attention arch: 524k dense KV decode is the "
                       "quadratic regime sub-quadratic archs exist to avoid "
                       "(DESIGN.md §5)")
    return True, ""
