"""Moonlight-16B-A3B against its plain reference (``references/
deepseek_v3.py``) at a tiny size on the CPU, on the benchmark's seeded
draws: logits of prefill and then decode through the paged latent cache,
the expert layer's shares adding up to the uncut layer, and the harness's
hold of the configuration file over the system's config."""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bench_tiny
import harness
import model_weights as mw
from repro.models import lm, moe

CONF = json.loads(
    (bench_tiny.BENCH / "configs" / "moonlight-16b-a3b.json").read_text())
# the file's keys at a tiny size, every mechanism kept: a dense first layer,
# two MoE layers of 4 held experts of the router's 8, top-2, 2 shared
SIZES = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=4, intermediate_size=128, vocab_size=256,
             kv_lora_rank=32, qk_rope_head_dim=8, qk_nope_head_dim=16,
             v_head_dim=16, n_routed_experts=4, router_experts=8,
             num_experts_per_tok=2, moe_intermediate_size=32)
OVERRIDES = dict(d_model=64, n_layers=3, n_heads=4, n_kv_heads=4, d_ff=128,
                 vocab=256, kv_lora_rank=32, qk_rope_head_dim=8,
                 qk_nope_head_dim=16, v_head_dim=16, n_experts=4,
                 router_experts=8, top_k=2, d_ff_expert=32, block_size=8,
                 prefill_chunk=8)
SEED = 2**32 + 7


def _ref():
    return harness.load_module(
        bench_tiny.BENCH / "references" / "deepseek_v3.py",
        "bench_reference_deepseek_v3")


def tiny_conf(**over) -> dict:
    return dict(CONF, **dict(SIZES, **over))


def _system(conf, dtype):
    """The system's config for ``conf`` and its served tree, drawn as the
    harness draws it (bf16 values) and held in ``dtype``."""
    cfg = harness.build_cfg(conf, OVERRIDES)
    params = mw.make_served(SEED, lm.packed_abstract_params(cfg),
                            conf["init"], conf["vocab_size"])
    params = jax.tree.map(lambda a: a.astype(dtype)
                          if a.dtype == jnp.bfloat16 else a, params)
    return dataclasses.replace(cfg, dtype=dtype), params


def _served_logits(cfg, params, prompt, rest):
    """Logits of the system through its serving path: the prompt in chunks
    of ``prefill_chunk`` into a paged latent pool, then one token at a time
    through the paged decode step (both the unfused chains on the CPU)."""
    bs, c = cfg.block_size, cfg.prefill_chunk
    s_max = len(prompt) + len(rest) + 1
    w = -(-s_max // bs)
    state = lm.paged_decode_state_spec(cfg, 1, s_max, n_blocks=1 + w,
                                       block_size=bs, abstract=False)
    tables = {"full": jnp.arange(1, 1 + w, dtype=jnp.int32)[None]}
    out = []
    for j in range(0, len(prompt), c):
        piece = np.zeros((1, c), np.int32)
        n = min(c, len(prompt) - j)
        piece[0, :n] = prompt[j:j + n]
        lg, state = lm.prefill_chunk_step(cfg, params, jnp.asarray(piece),
                                          state, jnp.int32(0), jnp.int32(n),
                                          tables, fresh=jnp.asarray(j == 0))
    out.append(lg[0, 0])
    for t in rest:
        lg, state, counts = lm.paged_decode_step(
            cfg, params, jnp.asarray([[t]], jnp.int32), state, tables)
        out.append(lg[0, 0])
    assert int(counts[0]) == 2 * cfg.top_k        # two MoE layers, one row
    return np.asarray(jnp.stack(out)[:, :cfg.vocab], np.float32)


@pytest.mark.parametrize("dtype,within", [(jnp.float32, True),
                                          (jnp.bfloat16, False)],
                         ids=["f32", "bf16"])
def test_prefill_and_paged_decode_match_the_reference(dtype, within):
    """The tolerance, 1e-5 of the largest logit, is 50 times what float32
    rounding leaves between the system (absorbed latent attention, paged
    cache, chunked prefill) and the reference (expanded, whole sequence):
    2e-7 here; the system held in bfloat16 misses it by three orders, so a
    stray bfloat16 anywhere on the float32 path would fail it."""
    conf = tiny_conf()
    cfg, params = _system(conf, dtype)
    rng = np.random.default_rng(3)
    seq = rng.integers(0, 256, 21, dtype=np.int32)
    prompt, rest = seq[:13], seq[13:]
    got = _served_logits(cfg, params, prompt, rest)
    want = _ref().logits_at(conf, SEED, [seq], [np.arange(12, 21)])["ref"][0]
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert bool(rel < 1e-5) is within, rel


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Two chips' shares of 4 experts each, their shared experts counted
    once, give the uncut reference's layer over all 8 (float32 both)."""
    conf = tiny_conf(n_routed_experts=8)
    cfg = dataclasses.replace(harness.build_cfg(conf, dict(OVERRIDES,
                                                           n_experts=8)),
                              dtype=jnp.float32)
    ref = _ref()
    dims = ref.Dims.of(conf)
    key = mw.root_key(SEED)
    w = ref._draw_layer(dims, key, jnp.int32(0), True)
    h = jax.random.normal(jax.random.PRNGKey(1), (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref._moe(dims, h, w, "ref")
        p = {"router": w["router"], "router_bias": w["bias"],
             "shared_w1": w["moe_shared_w1"], "shared_w3": w["moe_shared_w3"],
             "shared_w2": w["moe_shared_w2"]}
        shared = moe._swiglu(h, p["shared_w1"], p["shared_w3"],
                             p["shared_w2"])
        total = -shared
        share = dataclasses.replace(cfg, n_experts=4)
        for first in (0, 4):
            p |= {n: w[f"moe_{n}"][first:first + 4] for n in ("w1", "w3",
                                                              "w2")}
            y, _, counts = moe.moe_ffn(share, p, h[None], first=first)
            total = total + y[0]
            assert int(counts[0]) == 24 * 2
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_the_file_matches_its_system_config():
    cfg = harness.build_cfg(CONF)
    assert (cfg.n_experts, cfg.n_router_experts, cfg.n_shared_experts,
            cfg.kv_lora_rank, cfg.first_dense) == (8, 64, 2, 512, 1)


@pytest.mark.parametrize("key", ["n_shared_experts", "kv_lora_rank",
                                 "qk_rope_head_dim", "v_head_dim"])
def test_a_file_whose_mechanism_differs_is_refused(key):
    conf = dict(CONF)
    conf[key] += 1
    with pytest.raises(harness.Refused, match=key):
        harness.build_cfg(conf)


def test_the_pool_probes_read_the_latent_pool():
    """One block of the served pool: 27 layers of one latent slab, 576
    values stored at 640 lanes, 16 tokens, bf16."""
    cfg = harness.build_cfg(CONF)
    assert harness.kv_block_bytes(cfg, 16) == 27 * 640 * 16 * 2


@pytest.fixture(scope="module")
def ml_root(tmp_path_factory):
    """A root with the ml-decode cell's own files at a tiny size: the config
    file's keys cut as above, a backlog of 6 requests on 4 slots."""
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench_ml"))
    bench = root / "bench"
    (bench / "configs" / "moonlight-16b-a3b.json").write_text(
        json.dumps(tiny_conf()))
    traffic = json.loads((bench / "traffic" / "decode_long.json").read_text())
    traffic.update(arrivals={"kind": "backlog", "requests": 6}, slots=4,
                   prompt={"dist": "uniform", "min": 8, "max": 24},
                   output={"dist": "lognormal", "median": 12, "sigma": 0.3,
                           "min": 6, "max": 20})
    (bench / "traffic" / "decode_long.json").write_text(json.dumps(traffic))
    cell = json.loads((bench / "cells" / "ml-decode.json").read_text())
    cell["prefill_chunk"] = OVERRIDES["prefill_chunk"]
    (bench / "cells" / "ml-decode.json").write_text(json.dumps(cell))
    return root


def test_a_tiny_ml_decode_run_is_correct_and_counts_its_experts(ml_root):
    result, checks = harness.run_cell(
        "ml-decode", 2**31 + 21, 1.5, False, t_process=0.0, root=ml_root,
        require_tpu=False, cfg_overrides=OVERRIDES)
    assert result["correct"], checks
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    assert result["run"]["decode_steps"] >= 1
