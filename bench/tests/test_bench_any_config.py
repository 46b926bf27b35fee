"""The harness takes a served model that is not Qwen3 as new files alone:
a configuration file that maps the system's MoE leaves in ``init.roles`` is
drawn, sized, checked and counted with no edit under ``bench/``, while the
qwen3 configurations keep every bit they had."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import shutil
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bench_tiny
import harness
import model_weights as mw
import roofline
from repro.core.xnor_layers import PackedLinear
from repro.models import lm
from test_bench_run import _alter_tokens

PEAKS = json.loads((bench_tiny.BENCH / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]

# sha256 of the served tree at bench_tiny's sizes, seed 0, as the draws
# made it before configuration files could name roles
SERVED_DIGESTS = {
    "qwen3-4b-xnor":
        "a14c10504613a5539afcf54d500a810fa7ed24192878ccb7f67659a3f38be7e6",
    "qwen3-4b":
        "3be818cfd76ee49052cd2a07c196f35a471e9970bf635c21fa55fa0c8dae1628",
}

# a dense first layer, then two layers of the system's ``moe`` kind
MOE_OVERRIDES = dict(bench_tiny.OVERRIDES, n_layers=3,
                     pattern=("attn", "moe", "moe"), n_experts=4, top_k=2,
                     d_ff_expert=32)
MOE_ROLES = {"moe_router": "router", "moe_w1": "linear_in",
             "moe_w3": "linear_in", "moe_w2": "ffn_out"}
MOE_CELL = "tiny-moe"


def moe_conf(binarized: bool = True) -> dict:
    conf = json.loads(
        (bench_tiny.BENCH / "configs" / "qwen3-4b-xnor.json").read_text())
    conf.update(bench_tiny.SIZES, num_hidden_layers=3, rope_theta=50000,
                n_routed_experts=4, num_experts_per_tok=2,
                moe_intermediate_size=32, first_k_dense_replace=1,
                arch="moonshot-v1-16b-a3b" + ("+xnor" if binarized else ""),
                binarized=binarized, reference="tiny_moe")
    conf["init"] = dict(conf["init"], router={"dist": "normal", "scale": 1.0},
                        roles=dict(MOE_ROLES))
    return conf


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _served(conf, overrides, seed=0):
    cfg = harness.build_cfg(conf, overrides)
    return mw.make_served(seed, lm.packed_abstract_params(cfg), conf["init"],
                          conf["vocab_size"], PackedLinear)


# (a) the qwen3 draws are the same bits ------------------------------------


@pytest.mark.parametrize("config", sorted(SERVED_DIGESTS))
def test_qwen3_served_tree_keeps_its_bits(config):
    conf = json.loads(
        (bench_tiny.BENCH / "configs" / f"{config}.json").read_text())
    conf.update(bench_tiny.SIZES)
    assert _digest(_served(conf, bench_tiny.OVERRIDES)) == \
        SERVED_DIGESTS[config]


# (b) a MoE tree is drawn by the roles its file names ----------------------


@pytest.mark.parametrize("binarized", [False, True])
def test_moe_leaves_are_drawn_by_their_roles_one_expert_at_a_time(binarized):
    conf = moe_conf(binarized)
    init = conf["init"]
    tree = _served(conf, MOE_OVERRIDES, seed=5)
    key = mw.root_key(5)
    moe = tree["seg1_moe"]
    assert moe["moe_w1"].shape == (2, 4, 64, 32)
    for layer in range(2):
        router = mw.dense_leaf(key, "seg1_moe/moe_router", layer, (64, 4),
                               jnp.float32, init)
        np.testing.assert_array_equal(moe["moe_router"][layer], router)
        for e in range(4):
            for name, shape in (("moe_w1", (64, 32)), ("moe_w2", (32, 64))):
                alone = mw.dense_leaf(key, f"seg1_moe/{name}", layer, shape,
                                      jnp.bfloat16, init, index=e)
                np.testing.assert_array_equal(moe[name][layer, e], alone)
    # the down projection takes the ffn_out rule its file names: the same
    # draws as under linear_in, at 0.06 of the scale
    as_in = dict(init, roles=dict(MOE_ROLES, moe_w2="linear_in"))
    w2_in = mw.dense_leaf(key, "seg1_moe/moe_w2", 0, (4, 32, 64),
                          jnp.float32, as_in)
    w2 = mw.dense_leaf(key, "seg1_moe/moe_w2", 0, (4, 32, 64), jnp.float32,
                       init)
    np.testing.assert_allclose(w2, 0.06 * w2_in, rtol=1e-6)
    # the dense first layer keeps the qwen3 defaults
    if binarized:
        planes, beta = mw.packed_leaf(key, "seg0_attn/ffn_w2", 0, 128, 64,
                                      init)
        np.testing.assert_array_equal(tree["seg0_attn"]["ffn_w2"].pb[0],
                                      planes)
        np.testing.assert_array_equal(tree["seg0_attn"]["ffn_w2"].beta[0],
                                      beta)
    else:
        w = mw.dense_leaf(key, "seg0_attn/ffn_w2", 0, (128, 64),
                          jnp.bfloat16, init)
        np.testing.assert_array_equal(tree["seg0_attn"]["ffn_w2"][0], w)


def test_fewer_experts_held_keep_the_held_experts_values():
    conf = moe_conf()
    conf["n_routed_experts"] = 2
    few = _served(conf, dict(MOE_OVERRIDES, n_experts=2), seed=5)
    full = _served(moe_conf(), MOE_OVERRIDES, seed=5)
    for name in ("moe_w1", "moe_w2", "moe_w3"):
        np.testing.assert_array_equal(few["seg1_moe"][name],
                                      full["seg1_moe"][name][:, :2])


def test_packed_leaves_with_an_expert_axis_are_drawn_one_slice_at_a_time():
    init = moe_conf()["init"]
    sds = jax.ShapeDtypeStruct
    abstract = {"seg1_moe": {"moe_w1": PackedLinear(
        sds((2, 3, 32, 2), jnp.uint32), sds((2, 3, 32), jnp.float32), k=64)}}
    got = mw.make_served(9, abstract, init, 256, PackedLinear)
    got = got["seg1_moe"]["moe_w1"]
    assert got.pb.shape == (2, 3, 32, 2) and got.k == 64
    key = mw.root_key(9)
    for layer in range(2):
        for e in range(3):
            planes, beta = mw.packed_leaf(key, "seg1_moe/moe_w1", layer, 64,
                                          32, init, index=e)
            np.testing.assert_array_equal(got.pb[layer, e], planes)
            np.testing.assert_array_equal(got.beta[layer, e], beta)


@pytest.mark.parametrize("leaf", ["moe_router", "moe_w2"])
def test_an_unmapped_leaf_is_refused_by_name(leaf):
    conf = moe_conf()
    del conf["init"]["roles"][leaf]
    with pytest.raises(KeyError, match=leaf):
        _served(conf, MOE_OVERRIDES)


def test_a_role_naming_no_rule_is_refused():
    conf = moe_conf()
    conf["init"]["roles"]["moe_router"] = "no_such_rule"
    with pytest.raises(KeyError, match="moe_router"):
        _served(conf, MOE_OVERRIDES)


# (c) pool probes read the system's own bytes per block --------------------


def _old_kv_block_bytes(cfg, block_size):
    return cfg.n_layers * 2 * cfg.n_kv_heads * block_size * cfg.d_head * 2


def _spec_pool_bytes(cfg, n):
    spec = lm.paged_decode_state_spec(cfg, 4, 64, n_blocks=n,
                                      block_size=cfg.block_size)
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(spec))


@pytest.mark.parametrize("arch,overrides", [
    ("qwen3-4b+xnor", {}), ("qwen3-4b", {}),
    ("qwen3-4b+xnor", bench_tiny.OVERRIDES)])
def test_kv_block_bytes_of_qwen3_is_the_old_formula(arch, overrides):
    import repro.configs as configs
    cfg = dataclasses.replace(configs.get(arch), **overrides)
    assert harness.kv_block_bytes(cfg, cfg.block_size) == \
        _old_kv_block_bytes(cfg, cfg.block_size)


@pytest.mark.parametrize("kv_dtype", ["bf16", "i8"])
def test_kv_block_bytes_of_the_tiny_moe_is_the_spec_s(kv_dtype):
    cfg = dataclasses.replace(harness.build_cfg(moe_conf(), MOE_OVERRIDES),
                              kv_cache_dtype=kv_dtype)
    per_block = (_spec_pool_bytes(cfg, 7) - _spec_pool_bytes(cfg, 3)) // 4
    assert harness.kv_block_bytes(cfg, cfg.block_size) == per_block
    # three layers of K and V, one KV head, 8 tokens of 16
    assert per_block == 3 * 2 * 8 * 16 * (1 if kv_dtype == "i8" else 2)


# (d) the config check compares the expert keys ----------------------------


def test_the_tiny_moe_file_matches_its_system_config():
    cfg = harness.build_cfg(moe_conf(), MOE_OVERRIDES)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff_expert) == (4, 2, 32)


@pytest.mark.parametrize("key", ["n_routed_experts", "num_experts_per_tok",
                                 "moe_intermediate_size"])
def test_a_file_whose_experts_differ_is_refused(key):
    conf = moe_conf()
    conf[key] += 1
    with pytest.raises(harness.Refused, match=key):
        harness.build_cfg(conf, MOE_OVERRIDES)


def test_optional_keys_are_compared_where_given_and_required_ones_always():
    conf = moe_conf()
    for key in ("head_dim", "rope_theta"):
        del conf[key]
    harness.build_cfg(conf, MOE_OVERRIDES)
    del conf["vocab_size"]
    with pytest.raises(harness.Refused, match="vocab_size"):
        harness.build_cfg(conf, MOE_OVERRIDES)


@pytest.mark.parametrize("key", ["num_key_value_heads", "intermediate_size",
                                 "num_experts_per_tok",
                                 "moe_intermediate_size"])
def test_a_key_the_yardstick_reads_and_the_file_lacks_is_refused(key):
    conf = moe_conf()
    del conf[key]
    with pytest.raises(harness.Refused, match=key):
        harness.build_cfg(conf, MOE_OVERRIDES)


def test_a_derived_head_dim_is_held_to_the_system_s():
    """Without ``head_dim`` the yardstick counts hidden_size / heads (16
    here), which has to be the system's head width too."""
    conf = moe_conf()
    del conf["head_dim"]
    with pytest.raises(harness.Refused, match="head_dim"):
        harness.build_cfg(conf, dict(MOE_OVERRIDES, d_head=32))


@pytest.mark.parametrize("extra", [
    {"n_shared_experts": 2},
    {"kv_lora_rank": 32, "qk_rope_head_dim": 8, "qk_nope_head_dim": 16,
     "v_head_dim": 16}], ids=["shared_experts", "latent_cache"])
def test_a_key_the_system_has_no_field_for_is_refused(extra):
    """The system's config has no shared experts and no latent cache: a
    file that counts them is refused, not read past."""
    with pytest.raises(harness.Refused, match=sorted(extra)[0]):
        harness.build_cfg(dict(moe_conf(), **extra), MOE_OVERRIDES)


@pytest.mark.parametrize("plan", [
    {"first_k_dense_replace": 0}, {"first_k_dense_replace": 2},
    {"moe_layer_freq": 2}], ids=["no_dense", "two_dense", "every_other"])
def test_a_layer_plan_unlike_the_system_s_is_refused(plan):
    with pytest.raises(harness.Refused, match="moe layers"):
        harness.build_cfg(dict(moe_conf(), **plan), MOE_OVERRIDES)


# (e) the yardstick reads the work from the file's keys --------------------


def _old_projections(dims):
    d, ff = dims["hidden_size"], dims["intermediate_size"]
    q = dims["num_attention_heads"] * dims["head_dim"]
    kv = dims["num_key_value_heads"] * dims["head_dim"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff), (ff, d)]


def _old_model_ops(dims, tokens, ctx_sum):
    per_layer = sum(k * n for k, n in _old_projections(dims))
    params = (dims["num_hidden_layers"] * per_layer
              + dims["hidden_size"] * dims["vocab_size"])
    att = (4 * dims["num_hidden_layers"] * dims["num_attention_heads"]
           * dims["head_dim"])
    return 2.0 * params * tokens + att * ctx_sum


def _old_xnor_step_floor_s(dims, rows, peaks):
    return dims["num_hidden_layers"] * sum(
        roofline.xnor_linear_floor_s(rows, k, n, peaks)
        for k, n in _old_projections(dims))


def _old_paged_attn_floor_s(dims, ctx_sum, rows, peaks):
    kv = dims["num_key_value_heads"] * dims["head_dim"]
    q = dims["num_attention_heads"] * dims["head_dim"]
    per_layer = 2 * 2 * kv * ctx_sum + 2 * 2 * q * rows
    return dims["num_hidden_layers"] * per_layer / peaks["hbm_bytes_per_s"]


QWEN3 = {k: v for k, v in json.loads(
    (bench_tiny.BENCH / "configs" / "qwen3-4b-xnor.json").read_text()).items()
    if isinstance(v, (bool, int, float))}
# Moonlight-16B-A3B's published sizes (huggingface.co/moonshotai/
# Moonlight-16B-A3B, config.json)
MOONLIGHT = {"hidden_size": 2048, "num_hidden_layers": 27,
             "num_attention_heads": 16, "num_key_value_heads": 16,
             "intermediate_size": 11264, "vocab_size": 163840,
             "kv_lora_rank": 512, "qk_rope_head_dim": 64,
             "qk_nope_head_dim": 128, "v_head_dim": 128,
             "n_routed_experts": 64, "num_experts_per_tok": 6,
             "n_shared_experts": 2, "moe_intermediate_size": 1408,
             "first_k_dense_replace": 1, "moe_layer_freq": 1}


@pytest.mark.parametrize("dims", [QWEN3, dict(QWEN3, num_hidden_layers=3),
                                  {**bench_tiny.SIZES}])
@pytest.mark.parametrize("tokens,ctx_sum,rows", [(1, 0, 1), (32, 17763, 32),
                                                 (4096, 32 * 1001.5, 7)])
def test_yardstick_on_qwen3_keys_is_the_old_formulas(dims, tokens, ctx_sum,
                                                     rows):
    assert roofline.model_ops(dims, tokens, ctx_sum) == \
        _old_model_ops(dims, tokens, ctx_sum)
    assert roofline.paged_attn_floor_s(dims, ctx_sum, rows, PEAKS) == \
        _old_paged_attn_floor_s(dims, ctx_sum, rows, PEAKS)
    assert roofline.xnor_step_floor_s(dims, rows, PEAKS) == \
        _old_xnor_step_floor_s(dims, rows, PEAKS)


def test_yardstick_on_moe_keys_counts_the_routed_and_shared_experts():
    d, h = 2048, 16
    mla = 2048 * h * 192 + 2048 * 576 + 512 * h * 256 + h * 128 * 2048
    dense = mla + 3 * 2048 * 11264
    moe = mla + (6 + 2) * 3 * 2048 * 1408 + 2048 * 64
    params = dense + 26 * moe + d * 163840
    assert roofline.layer_counts(MOONLIGHT) == (1, 26)
    assert roofline.matmul_params(MOONLIGHT) == params
    att = 27 * 2 * h * (192 + 128)
    assert roofline.model_ops(MOONLIGHT, 10, 1000) == \
        2.0 * params * 10 + att * 1000
    # never all 64 experts of a token's layer
    assert params < dense + 26 * (mla + 64 * 3 * 2048 * 1408)
    # a MoE layer's binarized linears are its attention projections alone
    attn_only = sum(roofline.xnor_linear_floor_s(32, k, n, PEAKS)
                    for k, n in roofline.attn_projections(MOONLIGHT))
    ffn = sum(roofline.xnor_linear_floor_s(32, k, n, PEAKS)
              for k, n in roofline.ffn_projections(2048, 11264))
    assert roofline.xnor_step_floor_s(MOONLIGHT, 32, PEAKS) == \
        pytest.approx(27 * attn_only + ffn, rel=1e-12)


def test_yardstick_on_mla_keys_counts_the_latent_bytes():
    assert roofline.kv_bytes_per_token(MOONLIGHT) == 2 * (512 + 64)
    want = 27 * (2 * 576 * 5000.0 + 2 * 16 * (192 + 128) * 32) / \
        PEAKS["hbm_bytes_per_s"]
    assert roofline.paged_attn_floor_s(MOONLIGHT, 5000.0, 32, PEAKS) == \
        pytest.approx(want, rel=1e-12)
    gqa = dict(MOONLIGHT, kv_lora_rank=None)
    assert roofline.kv_bytes_per_token(gqa) == 2 * 2 * 16 * 128


# (f) a whole run of the tiny MoE config, added as files -------------------


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench_moe"))
    bench = root / "bench"
    (bench / "configs" / "tiny_moe.json").write_text(json.dumps(moe_conf()))
    shutil.copy(bench_tiny.BENCH / "tests" / "tiny_moe_reference.py",
                bench / "references" / "tiny_moe.py")
    shutil.copy(bench / "cells" / f"{bench_tiny.DECODE_CELL}.json",
                bench / "cells" / f"{MOE_CELL}.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_moe", "source": "test",
                            "file": "bench/configs/tiny_moe.json",
                            "reduced": [], "why": "CPU test"})
    spec["workloads"].append({"name": MOE_CELL, "config": "tiny_moe",
                              "traffic": "tiny_decode", "chips": 1,
                              "why": "CPU test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if bench_tiny.DECODE_CELL in m.get("workloads", []):
            m["workloads"].append(MOE_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_moe_config_added_as_files_runs_to_its_result_line(moe_root):
    """Drawn, sized and run by the harness as it stands; ``correct`` is not
    asserted: the system's MoE drops tokens at an expert's capacity, which
    the dropless reference does not."""
    result, checks = harness.run_cell(
        MOE_CELL, 2**31 + 3, 1.5, False, t_process=time.monotonic(),
        root=moe_root, require_tpu=False, cfg_overrides=MOE_OVERRIDES)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    assert result["run"]["decode_steps"] >= 1
    assert result["run"]["pool"] == {"n_blocks": 1 + 4 * 6}
    gap = result["checks"]["widest_gap"]["value"]
    assert np.isfinite(gap) and gap >= 0.0
    assert result["checks"]["compared_tokens_min"]["value"] >= 1


# (g) a limit on the mean gap, on a tiny in-flight run ---------------------


@pytest.fixture(scope="module")
def inflight_root(tmp_path_factory):
    """tiny-decode (a backlog with requests in flight) judged by the limit
    the steady-state decode cell's readings set: a mean gap of 0.005."""
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench_inflight"))
    path = root / "bench" / "cells" / f"{bench_tiny.DECODE_CELL}.json"
    settings = json.loads(path.read_text())
    settings["limits"] = {"mean_gap": 0.005}
    path.write_text(json.dumps(settings))
    return root


@pytest.mark.parametrize("fault", [None, _alter_tokens],
                         ids=["sound", "tokens_altered"])
def test_a_mean_gap_limit_passes_a_sound_run_and_fails_altered_tokens(
        inflight_root, fault):
    result, checks = harness.run_cell(
        bench_tiny.DECODE_CELL, 2**31 + 11, 1.5, False,
        t_process=time.monotonic(), root=inflight_root, require_tpu=False,
        cfg_overrides=bench_tiny.OVERRIDES, fault=fault)
    assert set(result["checks"]) == {"mean_gap", "compared_tokens_min"}
    assert result["correct"] is (fault is None), checks
