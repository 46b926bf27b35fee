"""Fused paged-decode kernel + fused XNOR linear parity suite (DESIGN.md §18).

Two layers of contract:

* kernel vs oracle — ``kernels/paged_attn.py`` (interpret mode) against the
  pure-jnp one-shot-softmax oracle ``kernels/ref.py::paged_decode`` across
  monotone tables, window rings (including recycling past the ring
  capacity), ragged table tails (pos mid-block), tables of several
  chunks (live length mid-chunk and on a chunk edge, a prime width,
  zeroed dead rows), GQA/MQA groups, bf16 and the i8 KV pool; the chunk
  rule against a brute-force count; a layer-stacked pool read at each of
  its layers (GQA bf16, i8, a window ring, a latent pool); plus the fused
  XNOR linear against its unfused chain.  These
  are allclose pins: the online-softmax recurrence equals one-shot softmax
  exactly in real arithmetic but not bit-for-bit in floats.

* engine tokens — a paged engine decoding with ``REPRO_FUSED_DECODE=on``
  (the Pallas kernel on the decode path) produces the same tokens as with
  ``off`` (the unfused chain) across the paged arch families, float and
  packed residency, and the i8 KV cache.  With the env var unset the
  dispatch itself guarantees bitwise identity on CPU CI (the platform picks
  the unfused twin — ``test_fused_mode_resolution``), so the existing
  token pins (paged == the reference loop, prefix on == off, migration
  identity) are untouched in both ``REPRO_KERNEL_IMPL`` modes.

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


LAYER0 = jnp.zeros((1,), jnp.int32)


def _parity(q, ck, cv, table, pos, *, window, scale, out_scale, tol):
    """Kernel against oracle over a one-layer pool (a stack of one)."""
    got = paged_attn.paged_decode_attention(
        q, ck[None], cv[None], LAYER0, table, jnp.asarray(pos, jnp.int32),
        window=window, scale=scale, out_scale=out_scale, interpret=True)
    want = ref.paged_decode(q, ck[None], cv[None], LAYER0, table,
                            jnp.asarray(pos, jnp.int32), window=window,
                            scale=scale, out_scale=out_scale)
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


# geometries whose tables span several chunks of P blocks (kernels/
# paged_attn.py's chunk rule): W=48 of 16-token blocks walks 3 chunks of
# P=16 (256 tokens each); a prime W=17 falls back to P=1
CHUNKED = {
    "mid_chunk": dict(w=48, pos=[300, 530, 700]),
    "chunk_edge": dict(w=48, pos=[255, 256, 511]),
    "prime_w": dict(w=17, pos=[0, 100, 271]),
    "pos_0": dict(w=48, pos=[0, 0, 1]),
    "dead_rows": dict(w=48, pos=[0, 400, 767], dead=(0, 2)),
    "ring_before_wrap": dict(w=48, pos=[100, 500, 766], window=300),
    "ring_at_wrap": dict(w=48, pos=[767, 768, 769], window=300),
    "ring_after_wrap": dict(w=48, pos=[900, 1300, 2000], window=300),
    "i8": dict(w=48, pos=[40, 300, 767], i8=True),
    "i8_ring": dict(w=48, pos=[200, 800, 1600], window=300, i8=True),
    "mqa": dict(w=48, pos=[17, 300, 600], kv=1, g=8),
    "mqa_ring": dict(w=48, pos=[500, 900, 1500], window=600, kv=1, g=8),
}


@pytest.mark.parametrize("name", list(CHUNKED))
def test_kernel_chunked_walk(name):
    """Several chunks per row: the walk's dead-chunk rule and its double
    buffer across chunks and rows, against the oracle.  Dead-slot rows are
    zeroed tables (every column the trash block)."""
    c = dict(CHUNKED[name])
    pos, window, dead = c.pop("pos"), c.pop("window", 0), c.pop("dead", ())
    q, ck, cv, table, scale, out_scale = _case(bs=16, **c)
    if dead:
        table = table.at[jnp.asarray(dead)].set(0)
    bs, w = ck.shape[2], table.shape[1]
    p = paged_attn.chunk_blocks(w, bs, ck.shape[1] * bs * ck.shape[3]
                                * ck.dtype.itemsize)
    assert p == (1 if w == 17 else 16)
    _parity(q, ck, cv, table, pos, window=window, scale=scale,
            out_scale=out_scale, tol=2e-5)


# a pool of three layers, each its own draw, read at each layer in turn
LAYER_CASES = {
    "gqa_bf16": dict(dtype=jnp.bfloat16, pos=[3, 17, 39], tol=3e-2),
    "i8": dict(i8=True, pos=[5, 19, 38], tol=2e-5),
    "ring": dict(pos=[45, 80, 113], window=12, tol=2e-5),
    "latent": dict(latent=True, pos=[0, 13, 39], tol=2e-5),
}


def _stacked_case(n_layers, *, latent=False, **c):
    """(q, ck, cv, table, scale, out_scale, v_dim) over a layer-stacked
    pool whose layers hold different draws.  A latent pool is one slab
    of 256 lanes per token under 4 query heads, values its first 128."""
    if latent:
        rng = np.random.default_rng(0)
        b, h, lat, bs, w = 3, 4, 256, 8, 5
        q = jnp.asarray(rng.standard_normal((b, 1, h, lat)), jnp.float32)
        ck = jnp.asarray(rng.standard_normal(
            (n_layers, 1 + b * w, 1, bs, lat)), jnp.float32)
        table = jnp.asarray(rng.permutation(b * w).reshape(b, w) + 1,
                            jnp.int32)
        return q, ck, None, table, lat ** -0.5, 1.0, 128
    draws = [_case(seed=l, **c) for l in range(n_layers)]
    q, _, _, table, scale, out_scale = draws[0]
    ck = jnp.stack([d[1] for d in draws])
    cv = jnp.stack([d[2] for d in draws])
    return q, ck, cv, table, scale, out_scale, 0


@pytest.mark.parametrize("name", list(LAYER_CASES))
def test_kernel_reads_the_layer_it_is_given(name):
    """The kernel handed the whole layer-stacked pool and layer l equals
    the oracle over layer l's pool alone, for every l; the layers'
    outputs differ, so a kernel reading another layer would fail."""
    c = dict(LAYER_CASES[name])
    pos = jnp.asarray(c.pop("pos"), jnp.int32)
    window, tol = c.pop("window", 0), c.pop("tol")
    q, ck, cv, table, scale, out_scale, v_dim = _stacked_case(3, **c)
    kw = dict(window=window, scale=scale, out_scale=out_scale, v_dim=v_dim)
    outs = []
    for layer in range(ck.shape[0]):
        got = paged_attn.paged_decode_attention(
            q, ck, cv, jnp.asarray([layer], jnp.int32), table, pos,
            interpret=True, **kw)
        want = ref.paged_decode(q, ck[layer][None],
                                None if cv is None else cv[layer][None],
                                LAYER0, table, pos, **kw)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        outs.append(np.asarray(got, np.float32))
    assert not np.allclose(outs[0], outs[1], rtol=tol, atol=tol)


def _brute_walk(pos, *, p, bs, w, window):
    """(blocks a walk must read, blocks holding attended keys) of one row
    at ``pos``, from the oracle's key mask."""
    cap = w * bs
    kslot = np.arange(cap)
    if window:
        valid = (pos % cap - kslot) % cap < min(window, pos + 1)
    else:
        valid = kslot <= pos
    cols = valid.reshape(w, bs).any(axis=1)
    chunks = cols.reshape(w // p, p).any(axis=1)
    return int(chunks.sum()) * p, int(cols.sum())


def test_chunk_rule():
    """qwen3-4b's bf16 pool (W=160, 16-token blocks, 8 KV heads of dh
    128) walks 10 chunks of P=16; a prime W falls back to P=1, a slab too
    large for the scratch budget shrinks P, and the engine's block counts
    match a brute-force count of the oracle's mask: live exactly, read
    never below what a live chunk needs, and exactly that on monotone
    tables."""
    slab = 8 * 16 * 128 * 2
    assert paged_attn.chunk_blocks(160, 16, slab) == 16
    assert paged_attn.chunk_blocks(157, 16, slab) == 1
    assert paged_attn.chunk_blocks(160, 16, 4 * slab) == 8
    assert paged_attn.chunk_blocks(5, 8, 100) == 5
    for w, bs, window in ((160, 16, 0), (12, 4, 0), (12, 4, 10),
                          (160, 16, 2048), (17, 16, 0), (9, 5, 13)):
        p = paged_attn.chunk_blocks(w, bs, slab)
        pos = np.arange(0, 3 * w * bs if window else w * bs, 7)
        read, live = paged_attn.walk_blocks(pos, p=p, bs=bs, w=w,
                                            window=window)
        for r, l, x in zip(read, live, pos):
            need, held = _brute_walk(int(x), p=p, bs=bs, w=w, window=window)
            assert l == held and r >= need
            if not window:
                assert r == need
        # the kernel's scalar form and the engine's array form agree
        assert int(paged_attn.live_chunks(jnp.int32(pos[-1]), p=p, bs=bs,
                                          w=w)) * p == read[-1]


def test_kernel_is_one_dispatch():
    """The fused path traces to exactly one pallas_call; the unfused
    oracle chain is strictly more dispatches."""
    import functools
    q, ck, cv, table, scale, out_scale = _case()
    pos = jnp.asarray([3, 17, 39], jnp.int32)
    args = (q, ck[None], cv[None], LAYER0, table, pos)
    fused = functools.partial(paged_attn.paged_decode_attention, window=0,
                              scale=scale, interpret=True)
    unfused = functools.partial(ref.paged_decode, window=0, scale=scale)
    nf = analysis.dispatch_count(jax.make_jaxpr(fused)(*args))
    nu = analysis.dispatch_count(jax.make_jaxpr(unfused)(*args))
    assert nf == 1
    assert nu > nf


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_ops_paged_decode_dispatch(impl, monkeypatch):
    """ops.paged_decode runs the oracle under ref and the kernel only when
    the interpreter is asked for — never an implicit interpreter."""
    monkeypatch.setattr(ops, "_FORCE", "")
    q, ck, cv, table, scale, out_scale = _case()
    pos = jnp.asarray([3, 17, 39], jnp.int32)
    args = (q, ck[None], cv[None], LAYER0, table, pos)
    want = ref.paged_decode(*args, scale=scale)
    got = ops.paged_decode(*args, scale=scale, impl=impl)
    nf = analysis.dispatch_count(jax.make_jaxpr(
        lambda *a: ops.paged_decode(*a, scale=scale, impl=impl))(*args))
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
        assert ops.fused_mode() == "ref"
    # the env var is the one switch, read per call
    monkeypatch.setenv("REPRO_FUSED_DECODE", "on")
    assert ops.fused_mode() == "kernel"
    monkeypatch.setenv("REPRO_FUSED_DECODE", "off")
    assert ops.fused_mode() == "ref"
    monkeypatch.setenv("REPRO_FUSED_DECODE", "fused")
    with pytest.raises(ValueError):
        ops.fused_mode()


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
    if fused is None:                   # the platform's choice
        monkeypatch.delenv("REPRO_FUSED_DECODE", raising=False)
    else:
        monkeypatch.setenv("REPRO_FUSED_DECODE", fused)
    cfg = configs.get(name).smoke(dtype=jnp.float32, **over)
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % 2**31)
    params = lm.init_params(cfg, key)
    eng = ServeEngine(cfg, params, slots=2, s_max=24, pack=pack)
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
    """The production default: with no override and no TPU, the engine
    decodes through the identical program as ``off`` — this is what keeps
    every token pin bitwise in both CI modes."""
    if jax.default_backend() == "tpu":
        pytest.skip("the platform picks the kernel on TPU")
    auto = _engine_tokens("qwen3-4b", monkeypatch, None)
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
                                 (4, 2, 16, 2, True), (2, 3, 5, 3, True),
                                 (3, 36, 16, 4, False), (2, 40, 16, 5, True)]:
        _random_layout_case(b, w, bs, seed, ring)
