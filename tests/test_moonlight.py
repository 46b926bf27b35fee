"""Moonlight-16B-A3B's mechanisms in the system (the comparisons with the
plain reference live in ``bench/tests/test_bench_deepseek_v3.py``): the
layer plan, the latent pool, the latent decode kernel and the grouped
expert kernel (interpreted) against their jnp twins, the serving counters
of routed and held expert choices, and the ``+xnor`` serving refusal."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.kernels import ops
from repro.models import lm, moe
from repro.serve import ServeEngine, synthetic_trace


def _smoke(**over):
    cfg = configs.get("moonlight-16b-a3b").smoke(dtype=jnp.float32, **over)
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(7))


def test_layer_plan_is_one_dense_layer_then_experts():
    cfg = configs.get("moonlight-16b-a3b")
    assert cfg.segments() == [("attn", 1), ("moe", 26)]
    assert (cfg.n_experts, cfg.n_router_experts, cfg.top_k) == (8, 64, 6)
    assert configs.get("llama4-scout-17b-a16e").segments() == [("moe", 48)]


def test_latent_pool_holds_one_vector_per_token_and_layer():
    """576 values (512 latent + 64 rotary) at 640 lanes under one cache
    head, and no V."""
    cfg = configs.get("moonlight-16b-a3b")
    spec = lm.paged_decode_state_spec(cfg, 32, 5632, n_blocks=9,
                                      block_size=16)
    for seg, (kind, n) in zip(spec.seg_states, cfg.segments()):
        assert seg.k.shape == (n, 9, 1, 16, 640)
        assert seg.v.shape == (n, 9, 1, 16, 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_latent_decode_kernel_matches_its_twin(dtype):
    """The Pallas kernel over a latent pool, interpreted, against the
    one-shot oracle: 16 query heads on one 640-lane slab, values from its
    first 512 lanes, positions in mid-block, at a block's end and past the
    first chunk of the walk.  Tolerances: float32 summation order (f32),
    one bfloat16 rounding of the output (bf16)."""
    b, h, lat, r, bs, w = 3, 16, 640, 512, 16, 20
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (b, 1, h, lat), jnp.float32).astype(dtype)
    pool = jax.random.normal(k2, (1 + b * w, 1, bs, lat),
                             jnp.float32).astype(dtype)
    table = jax.random.permutation(k3, jnp.arange(1, 1 + b * w,
                                                  dtype=jnp.int32))
    table = table.reshape(b, w)
    pos = jnp.asarray([5, 15, 300], jnp.int32)
    args = (q, pool, None, table, pos)
    kw = dict(scale=192**-0.5, v_dim=r)
    got = ops.paged_decode(*args, impl="interpret", **kw)
    want = ops.paged_decode(*args, impl="ref", **kw)
    assert got.shape == (b, 1, h, r)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


def test_grouped_expert_kernel_matches_the_dense_twin(monkeypatch):
    """The expert layer's grouped form through the Pallas kernel,
    interpreted, against the plain form: every held expert over every row
    (float32 both; the kernel's tiles change only summation order)."""
    cfg, params = _smoke()
    p = jax.tree.map(lambda a: a[0], params["seg1_moe"])
    p = {k[len("moe_"):]: v for k, v in p.items() if k.startswith("moe_")}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
    valid = jnp.arange(24)[None, :] < jnp.asarray([[20], [24]])
    plain = moe.moe_ffn(cfg, p, x, valid)
    monkeypatch.setattr(ops, "_FORCE", "interpret")
    grouped = moe.moe_ffn(cfg, p, x, valid, grouped=True)
    np.testing.assert_allclose(grouped[0], plain[0], rtol=1e-5, atol=1e-5)
    assert [int(c) for c in grouped[2]] == [int(c) for c in plain[2]]
    assert int(plain[2][0]) == 44 * cfg.top_k


def test_serving_counts_routed_and_held_expert_choices():
    """Each decode step adds, over the MoE layers, top_k choices per row
    that decodes; of those the held ones, and the held experts touched."""
    cfg, params = _smoke()
    n_moe = sum(n for kind, n in cfg.segments() if kind == "moe")
    eng = ServeEngine(cfg, params, slots=2, s_max=24)
    for r in synthetic_trace(3, cfg.vocab, seed=5, prompt_lens=(4, 9),
                             new_tokens=(3, 5)):
        eng.submit(r)
    eng.run()
    st = eng.stats
    assert st.moe_choices_routed == st.decode_rows * cfg.top_k * n_moe
    assert 0 < st.moe_choices_held < st.moe_choices_routed
    assert 0 < st.moe_experts_touched <= min(
        st.moe_choices_held, st.decode_steps * n_moe * cfg.n_experts)


def test_dense_models_count_no_expert_choices():
    cfg = configs.get("qwen3-4b").smoke(dtype=jnp.float32)
    eng = ServeEngine(cfg, lm.init_params(cfg, jax.random.PRNGKey(0)),
                      slots=2, s_max=16)
    for r in synthetic_trace(2, cfg.vocab, seed=1, prompt_lens=(4,),
                             new_tokens=(3,)):
        eng.submit(r)
    eng.run()
    assert eng.stats.decode_steps > 0
    assert (eng.stats.moe_choices_routed, eng.stats.moe_choices_held,
            eng.stats.moe_experts_touched) == (0, 0, 0)


def test_a_binarized_latent_model_trains_but_is_not_served():
    cfg, params = _smoke(quant="xnor")
    tokens = jnp.zeros((1, 8), jnp.int32)
    loss, _ = lm.loss_fn(cfg, params, {"tokens": tokens, "labels": tokens})
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="latent attention"):
        ServeEngine(cfg, params, slots=1, s_max=16)
    _, state = lm.prefill(cfg, params, tokens, None, s_max=16)
    with pytest.raises(NotImplementedError, match="wkv_b"):
        lm.decode_step(cfg, params, tokens[:, :1], state)
