"""A generic softmax-routed MoE preset at Moonlight-like widths: every layer
MoE, 64 experts of 1,408, top-6, GQA with 16 KV heads, no shared experts,
no latent cache, no leading dense layer.

It is *not* Moonlight-16B-A3B, whose published config this system serves
as ``moonlight-16b-a3b`` (``configs/moonlight_16b_a3b.py``: 27 layers,
latent attention, sigmoid routing, shared experts, a dense first layer).
It stays registered because the benchmark's CPU tests of a MoE tree added
as files (``bench/tests/test_bench_any_config.py``) build their tiny
softmax-routed model on it.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="moonshot-v1-16b-a3b", family="moe",
    n_layers=48, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab=163840,
    pattern=("moe",),
    n_experts=64, top_k=6, d_ff_expert=1408,
    rope_theta=50000.0,
)
