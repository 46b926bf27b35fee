"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, config.json;
``model_type`` deepseek_v3].

27 layers of hidden size 2048: latent attention (MLA) with 16 heads,
kv_lora_rank 512, a 64-dim rotary key and 128-dim nope keys and values, no
q compression; layer 0 a dense SwiGLU FFN of 11,264, layers 1-26 MoE: a
sigmoid router (``noaux_tc``: a per-expert bias used only to choose, one
group) over 64 routed experts of width 1,408, top-6, gates normalised over
the six and scaled by 2.446, plus 2 shared experts.  RMSNorm eps 1e-5,
rope theta 50,000 without scaling, vocabulary 163,840, untied head.

The deployment is expert parallelism over 8 chips per layer: each chip
holds every layer's attention, the shared experts, the routers and the
vocabulary, and 8 of the 64 routed experts of each MoE layer.  This config
is one chip's share, experts 0-7 (``n_experts`` 8 held, the router's
``router_experts`` 64 as published).  ``n_kv_heads`` and ``d_ff`` hold the
published ``num_key_value_heads`` and ``intermediate_size``; the cache's
single latent head follows from ``kv_lora_rank``.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab=163840,
    kv_lora_rank=512, qk_rope_head_dim=64, qk_nope_head_dim=128,
    v_head_dim=128, norm_eps=1e-5,
    first_dense=1, pattern=("moe",),
    n_experts=8, router_experts=64, top_k=6, d_ff_expert=1408,
    router_score="sigmoid", routed_scale=2.446, n_shared_experts=2,
    rope_theta=50000.0,
)
