"""Fused paged-decode kernel + fused XNOR linear parity suite (DESIGN.md §18).

Two layers of contract:

* kernel vs oracle — ``kernels/paged_attn.py`` (interpret mode) against the
  pure-jnp one-shot-softmax oracle ``kernels/ref.py::paged_decode`` across
  monotone tables, window rings (including recycling past the ring
  capacity), ragged table tails (pos mid-block), GQA groups, bf16 and the
  i8 KV pool; plus the fused XNOR linear against its unfused chain.  These
  are allclose pins: the online-softmax recurrence equals one-shot softmax
  exactly in real arithmetic but not bit-for-bit in floats.

* engine tokens — a paged engine decoding with ``REPRO_FUSED_DECODE=on``
  (the Pallas kernel on the decode path) produces the same tokens as with
  ``off`` (the unfused chain) across the paged arch families, float and
  packed residency, and the i8 KV cache.  With the env var unset the
  dispatch itself guarantees bitwise identity on CPU CI (``auto`` resolves
  to the unfused twin — ``test_fused_mode_resolution``), so the existing
  cross-layout pins (paged == dense, prefix on == off, migration identity)
  are untouched in both ``REPRO_KERNEL_IMPL`` modes.

Runs in whichever kernel mode CI selects.
"""

import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.core import bitpack, xnor_layers
from repro.kernels import ops, paged_attn, ref
from repro.models import lm
from repro.roofline import analysis
from repro.serve import ServeEngine, synthetic_trace

# paged attn families: dense GQA / local-window ring / enc-dec / vlm
# (xlstm is pure-recurrent — no paged pool, the kernel never engages — and
# rides along to pin that the dispatch is a no-op there)
SWEEP_ARCHS = ["qwen3-4b", "recurrentgemma-2b", "whisper-tiny",
               "llama-3.2-vision-11b", "xlstm-350m"]

RNG = np.random.default_rng(0)


def _case(*, b=3, kv=2, g=2, dh=16, bs=8, w=5, dtype=jnp.float32, i8=False,
          seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, kv, g, dh)), dtype)
    ck = rng.standard_normal((1 + b * w, kv, bs, dh))
    cv = rng.standard_normal((1 + b * w, kv, bs, dh))
    scale, out_scale = dh ** -0.5, 1.0
    if i8:
        ck = np.clip(np.round(ck * 32.0), -127, 127).astype(np.int8)
        cv = np.clip(np.round(cv * 32.0), -127, 127).astype(np.int8)
        scale, out_scale = scale / 32.0, 1.0 / 32.0
    else:
        ck = ck.astype(dtype)
        cv = cv.astype(dtype)
    table = jnp.asarray(rng.permutation(b * w).reshape(b, w) + 1, jnp.int32)
    return q, jnp.asarray(ck), jnp.asarray(cv), table, float(scale), \
        float(out_scale)


def _parity(q, ck, cv, table, pos, *, window, scale, out_scale, tol):
    got = paged_attn.paged_decode_attention(
        q, ck, cv, table, jnp.asarray(pos, jnp.int32), window=window,
        scale=scale, out_scale=out_scale, interpret=True)
    want = ref.paged_decode(q, ck, cv, table, jnp.asarray(pos, jnp.int32),
                            window=window, scale=scale, out_scale=out_scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_kernel_full_monotone(dtype, tol):
    q, ck, cv, table, scale, out_scale = _case(dtype=dtype)
    # ragged tails: positions mid-block and at block boundaries
    _parity(q, ck, cv, table, [0, 17, 39], window=0, scale=scale,
            out_scale=out_scale, tol=tol)
    _parity(q, ck, cv, table, [7, 8, 24], window=0, scale=scale,
            out_scale=out_scale, tol=tol)


@pytest.mark.parametrize("pos", [[3, 17, 39],     # before first wrap
                                 [40, 41, 57],    # at/just past capacity
                                 [45, 80, 113]])  # multiple wraps
def test_kernel_window_ring_recycling(pos):
    q, ck, cv, table, scale, out_scale = _case()
    _parity(q, ck, cv, table, pos, window=12, scale=scale,
            out_scale=out_scale, tol=2e-5)


def test_kernel_i8_kv():
    q, ck, cv, table, scale, out_scale = _case(i8=True)
    assert ck.dtype == jnp.int8
    _parity(q, ck, cv, table, [5, 19, 38], window=0, scale=scale,
            out_scale=out_scale, tol=2e-5)
    _parity(q, ck, cv, table, [45, 80, 113], window=12, scale=scale,
            out_scale=out_scale, tol=2e-5)


def test_kernel_gqa_groups():
    q, ck, cv, table, scale, out_scale = _case(kv=1, g=4)
    _parity(q, ck, cv, table, [2, 13, 31], window=0, scale=scale,
            out_scale=out_scale, tol=2e-5)


def test_kernel_is_one_dispatch():
    """The fused path traces to exactly one pallas_call; the unfused
    oracle chain is strictly more dispatches."""
    import functools
    q, ck, cv, table, scale, out_scale = _case()
    pos = jnp.asarray([3, 17, 39], jnp.int32)
    fused = functools.partial(paged_attn.paged_decode_attention, window=0,
                              scale=scale, interpret=True)
    unfused = functools.partial(ref.paged_decode, window=0, scale=scale)
    nf = analysis.dispatch_count(jax.make_jaxpr(fused)(q, ck, cv, table, pos))
    nu = analysis.dispatch_count(
        jax.make_jaxpr(unfused)(q, ck, cv, table, pos))
    assert nf == 1
    assert nu > nf


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_ops_paged_decode_dispatch(impl, monkeypatch):
    """ops.paged_decode runs the oracle under ref and the kernel only when
    the interpreter is asked for — never an implicit interpreter."""
    monkeypatch.setattr(ops, "_FORCE", "")
    q, ck, cv, table, scale, out_scale = _case()
    pos = jnp.asarray([3, 17, 39], jnp.int32)
    want = ref.paged_decode(q, ck, cv, table, pos, scale=scale)
    got = ops.paged_decode(q, ck, cv, table, pos, scale=scale, impl=impl)
    nf = analysis.dispatch_count(jax.make_jaxpr(
        lambda *a: ops.paged_decode(*a, scale=scale, impl=impl))(
            q, ck, cv, table, pos))
    if impl == "ref":
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert nf > 1
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert nf == 1


def test_fused_mode_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_FUSED_DECODE", raising=False)
    # with no override, CPU backends keep the bit-exact unfused twin
    if jax.default_backend() != "tpu":
        assert ops.fused_mode("auto") == "ref"
    assert ops.fused_mode("off") == "ref"
    assert ops.fused_mode("unfused") == "ref"
    assert ops.fused_mode("on") == "kernel"
    assert ops.fused_mode("fused") == "kernel"
    with pytest.raises(ValueError):
        ops.fused_mode("bogus")
    # env var wins over the config value, and is read per call
    monkeypatch.setenv("REPRO_FUSED_DECODE", "on")
    assert ops.fused_mode("off") == "kernel"
    monkeypatch.setenv("REPRO_FUSED_DECODE", "off")
    assert ops.fused_mode("on") == "ref"


# ---------------------------------------------------------------------------
# fused XNOR linear (binarize + popcount GEMM + alpha/beta epilogue)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(5, 70, 9), (17, 200, 33), (128, 64, 128),
                                   (130, 4200, 128)])
def test_xnor_fused_matches_unfused_chain(m, k, n):
    """Fused kernel vs the three-dispatch chain, including ragged K (not a
    word multiple) and a K wide enough that the row block narrows to bound
    the staged words (130 rows in two blocks of 120).  The ref impl of the
    fused op is bit-identical to the chain; the kernel is allclose (its
    alpha mean associates differently) with bit-identical integer dots by
    construction."""
    x = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((n, k)), jnp.float32)
    pb, beta = xnor_layers.pack_weights(w, impl="ref")
    alpha = jnp.mean(jnp.abs(x), axis=-1)
    # integer dots are exact whichever impl REPRO_KERNEL_IMPL forces
    dots = ops.xnor_matmul(ops.binarize(x, impl="ref")[0], pb, k, impl="ref")
    chain = dots.astype(jnp.float32) * alpha[:, None] * beta[None, :]
    # the oracle directly — REPRO_KERNEL_IMPL=interpret overrides impl="ref"
    # at the ops layer, and the kernel's alpha is only allclose to the chain
    fused_ref = ref.xnor_linear_fused(x, pb, beta, k)
    assert np.array_equal(np.asarray(fused_ref), np.asarray(chain))
    fused_k = ops.xnor_linear_fused(x, pb, beta, k, impl="interpret")
    np.testing.assert_allclose(np.asarray(fused_k), np.asarray(chain),
                               rtol=2e-5, atol=2e-5)


def test_xnor_fused_exact_on_pm1():
    """±1 activations make alpha = 1 exactly — fused output must be the
    exact integer dot scaled by beta, bitwise across impls."""
    x = jnp.asarray(RNG.choice([-1.0, 1.0], (8, 96)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((6, 96)), jnp.float32)
    pb, beta = xnor_layers.pack_weights(w, impl="ref")
    a = ref.xnor_linear_fused(x, pb, beta, 96)
    b = ops.xnor_linear_fused(x, pb, beta, 96, impl="interpret")
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_prepacked_layer_fused_mode(monkeypatch):
    """xnor_linear_prepacked under REPRO_FUSED_DECODE=on routes through the
    fused kernel and stays allclose to the unfused default."""
    x = jnp.asarray(RNG.standard_normal((2, 7, 48)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((48, 10)), jnp.float32)
    pl = xnor_layers.pack_linear(w, impl="ref")
    monkeypatch.delenv("REPRO_FUSED_DECODE", raising=False)
    base = xnor_layers.xnor_linear_prepacked(x, pl.pb, pl.beta, pl.k)
    monkeypatch.setenv("REPRO_FUSED_DECODE", "on")
    fused = xnor_layers.xnor_linear_prepacked(x, pl.pb, pl.beta, pl.k)
    assert fused.shape == base.shape == (2, 7, 10)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# engine tokens: fused decode == unfused decode
# ---------------------------------------------------------------------------


def _engine_tokens(name, monkeypatch, fused, *, pack=False, **over):
    monkeypatch.setenv("REPRO_FUSED_DECODE", fused)
    cfg = configs.get(name).smoke(dtype=jnp.float32, **over)
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % 2**31)
    params = lm.init_params(cfg, key)
    eng = ServeEngine(cfg, params, slots=2, s_max=24, pack=pack, paged=True)
    for r in synthetic_trace(4, cfg.vocab, seed=3,
                             n_ctx_tokens=cfg.n_ctx_tokens,
                             d_model=cfg.d_model):
        eng.submit(r)
    rep = eng.run()
    return {rid: rep.tokens(rid).tolist() for rid in rep.sessions}


@pytest.mark.parametrize("name", SWEEP_ARCHS)
def test_fused_engine_tokens(name, monkeypatch):
    on = _engine_tokens(name, monkeypatch, "on")
    off = _engine_tokens(name, monkeypatch, "off")
    assert on == off, f"{name}: fused decode tokens diverge from unfused"


def test_fused_engine_tokens_packed(monkeypatch):
    on = _engine_tokens("qwen2-7b+xnor", monkeypatch, "on", pack=True)
    off = _engine_tokens("qwen2-7b+xnor", monkeypatch, "off", pack=True)
    assert on == off


def test_fused_engine_tokens_i8(monkeypatch):
    on = _engine_tokens("qwen3-4b", monkeypatch, "on",
                        kv_cache_dtype="i8")
    off = _engine_tokens("qwen3-4b", monkeypatch, "off",
                        kv_cache_dtype="i8")
    assert on == off


def test_auto_mode_is_bitwise_off_on_cpu(monkeypatch):
    """The production default: with no override and no TPU, ``auto`` decodes
    through the identical program as ``off`` — this is what keeps every
    pre-existing cross-layout token pin bitwise in both CI modes."""
    if jax.default_backend() == "tpu":
        pytest.skip("auto resolves to the kernel on TPU")
    auto = _engine_tokens("qwen3-4b", monkeypatch, "auto")
    off = _engine_tokens("qwen3-4b", monkeypatch, "off")
    assert auto == off


# ---------------------------------------------------------------------------
# property: random block-table layouts (hypothesis)
# ---------------------------------------------------------------------------

def _random_layout_case(b, w, bs, seed, ring):
    """Kernel == oracle for any permutation of pool blocks into tables,
    any per-slot position (including far past the ring capacity), any
    block geometry.  The table walk must be fully layout-agnostic."""
    rng = np.random.default_rng(seed)
    kv, g, dh = 1, 2, 8
    cap = w * bs
    q = jnp.asarray(rng.standard_normal((b, kv, g, dh)), jnp.float32)
    n_blocks = 1 + b * w
    ck = jnp.asarray(rng.standard_normal((n_blocks, kv, bs, dh)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((n_blocks, kv, bs, dh)), jnp.float32)
    table = jnp.asarray(rng.permutation(b * w).reshape(b, w) + 1, jnp.int32)
    pos = rng.integers(0, 3 * cap if ring else cap, size=(b,))
    window = int(rng.integers(1, cap + 1)) if ring else 0
    _parity(q, ck, cv, table, pos.tolist(), window=window,
            scale=dh ** -0.5, out_scale=1.0, tol=2e-5)


try:                                             # optional dep, like
    from hypothesis import given, settings, strategies as st  # noqa: E501
except ImportError:                              # test_kernels_properties.py
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_random_block_table_layouts():
        pass
else:
    @given(st.integers(1, 4),        # slots
           st.integers(1, 4),        # blocks per table
           st.integers(1, 16),       # block size
           st.integers(0, 1000),     # layout seed
           st.booleans())            # window ring?
    @settings(max_examples=25, deadline=None)
    def test_random_block_table_layouts(b, w, bs, seed, ring):
        _random_layout_case(b, w, bs, seed, ring)


def test_random_block_table_layouts_pinned():
    """A deterministic slice of the property sweep so the layout-agnostic
    claim is exercised even where hypothesis is unavailable."""
    for b, w, bs, seed, ring in [(1, 1, 1, 0, False), (3, 4, 8, 1, False),
                                 (4, 2, 16, 2, True), (2, 3, 5, 3, True)]:
        _random_layout_case(b, w, bs, seed, ring)
