"""Block-paged KV cache + chunked prefill + prefix caching (DESIGN.md
§14–§15).

The contract under test: the paged serve path — shared block pool,
per-slot block tables, fixed-size chunked prefill — is *token-identical*
to the per-request reference loop (``lm.prefill`` + ``lm.decode_step``)
for every arch family that caches attention state
(dense / local-window / enc-dec / vlm) and for the pure-recurrent archs
(whose per-slot state stays dense by design) and for MoE archs, whose
dropless routing makes each token's output its own (softmax-routed GQA,
and sigmoid-routed latent attention with held and shared experts).
§15 extends the contract: serving a shared-prefix trace with the prefix
cache on is token-identical to serving it with the cache off, across the
same arch sweep, float/packed residency and the i8 KV cache, with every
divergence point (block boundary, mid-block, full-prompt hit, mid-prefill
donor) costing exactly one copy-on-write copy.  Runs in whichever
REPRO_KERNEL_IMPL mode CI selects, so both kernel modes cover the sweep.
BlockPool and PrefixIndex are pure host logic, unit-tested without a
model (random-interleaving properties live in test_serve_properties.py).
"""

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import lm
from repro.serve import (BlockPool, PrefixIndex, Request, ServeEngine,
                         synthetic_trace)
from serve_reference import ref_decode, ref_tokens

# dense / local+recurrent / enc-dec / vlm / pure-recurrent / MoE (GQA, and
# latent attention with held and shared experts) — the identity sweep the
# acceptance criteria pin
SWEEP_ARCHS = ["qwen3-4b", "recurrentgemma-2b", "whisper-tiny",
               "llama-3.2-vision-11b", "xlstm-350m", "llama4-scout-17b-a16e",
               "moonlight-16b-a3b"]


def _setup(name, **over):
    cfg = configs.get(name).smoke(dtype=jnp.float32, **over)
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % 2**31)
    return cfg, lm.init_params(cfg, key)


def _run(cfg, params, trace, *, slots=2, s_max=24, pack=True,
         n_blocks=0, seed=0, temperature=0.0, prefix_cache=True):
    eng = ServeEngine(cfg, params, slots=slots, s_max=s_max, pack=pack,
                      n_blocks=n_blocks, seed=seed,
                      temperature=temperature, prefix_cache=prefix_cache)
    for r in trace:
        eng.submit(r)
    report = eng.run()
    toks = {rid: report.tokens(rid).tolist() for rid in report.sessions}
    return toks, eng


# ---------------------------------------------------------------------------
# BlockPool: pure allocation bookkeeping
# ---------------------------------------------------------------------------


def test_block_pool_alloc_lowest_first_deterministic():
    pool = BlockPool(8)
    assert pool.capacity == 7 and pool.available == 7 and pool.in_use == 0
    a = pool.alloc(0, 3)
    assert a == [1, 2, 3]            # block 0 reserved (trash), lowest first
    b = pool.alloc(1, 2)
    assert b == [4, 5]
    pool.free(0)
    assert pool.available == 5
    # freed ids return sorted: the next alloc reuses the lowest again
    assert pool.alloc(2, 3) == [1, 2, 3]
    assert pool.in_use == 5


def test_block_pool_oom_and_free_reclaims_all():
    pool = BlockPool(5)
    pool.alloc(7, 2)
    pool.alloc(7, 1)                 # same request grows its hold
    assert pool.held(7) == [1, 2, 3]
    with pytest.raises(RuntimeError):
        pool.alloc(8, 2)             # only 1 free
    assert pool.free(7) == 3         # eviction reclaims every held block
    assert pool.available == pool.capacity
    assert pool.free(7) == 0         # idempotent
    with pytest.raises(ValueError):
        BlockPool(1)                 # trash block alone is not a pool
    with pytest.raises(ValueError):
        pool.alloc(9, -1)


# ---------------------------------------------------------------------------
# paged == reference token identity (the tentpole contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", SWEEP_ARCHS)
def test_paged_matches_dense_tokens(name):
    """Property-style sweep: same seeded mixed-length trace through the
    block-paged engine and the per-request reference loop -> identical
    tokens per request (chunked prefill + table gather/scatter vs
    exact-length prefill + contiguous cache)."""
    cfg, params = _setup(name)
    trace = synthetic_trace(5, cfg.vocab, seed=2, prompt_lens=(4, 6, 9),
                            new_tokens=(3, 6), n_ctx_tokens=cfg.n_ctx_tokens,
                            d_model=cfg.d_model)
    paged, eng = _run(cfg, params, trace)
    assert paged == ref_tokens(cfg, params, trace, 24)
    assert eng.stats.prefills == len(trace)
    if eng.blocks is not None:
        assert eng.blocks.in_use == 0        # every eviction returned blocks
        assert eng.stats.blocks_peak > 0


def test_paged_packed_residency_matches_dense_and_float():
    """Both resident modes run on the paged layout: packed-paged equals
    float-paged equals the packed reference loop token-for-token."""
    cfg, params = _setup("qwen2-7b+xnor")
    trace = synthetic_trace(4, cfg.vocab, seed=6, prompt_lens=(4, 7),
                            new_tokens=(3, 5))
    ref_packed = ref_tokens(cfg, lm.pack_params(cfg, params), trace, 24)
    paged_packed, _ = _run(cfg, params, trace, pack=True)
    paged_float, _ = _run(cfg, params, trace, pack=False)
    assert ref_packed == paged_packed == paged_float


@pytest.mark.parametrize("name", ["qwen3-4b", "whisper-tiny"])
def test_paged_matches_dense_i8_cache(name):
    """The fixed-point i8 cache runs on the paged layout and in the
    reference loop and stays identical — including enc-dec, whose
    reference self-cache must be allocated i8 for _kv_from_seq's scaled
    words to be decoded with the correction."""
    cfg, params = _setup(name)
    cfg = dataclasses.replace(cfg, kv_cache_dtype="i8")
    trace = synthetic_trace(4, cfg.vocab, seed=8, prompt_lens=(4, 7),
                            new_tokens=(3, 5), n_ctx_tokens=cfg.n_ctx_tokens,
                            d_model=cfg.d_model)
    paged, eng = _run(cfg, params, trace)
    ref = {r.rid: ref_decode(cfg, params, r, 24) for r in trace}
    assert paged == {rid: toks for rid, (toks, _) in ref.items()}
    for state in [eng._state] + [st for _, st in ref.values()]:
        kv = jax.tree.leaves(state.seg_states)[0]
        assert kv.dtype == jnp.int8


def test_paged_moe_deterministic_across_slot_counts():
    """Dropless MoE is schedule-independent: identical tokens whatever the
    slot count, greedy and sampled."""
    cfg, params = _setup("llama4-scout-17b-a16e")
    trace_args = dict(seed=3, prompt_lens=(4, 6, 9), new_tokens=(3, 5))

    def run(slots, temperature):
        trace = synthetic_trace(5, cfg.vocab, **trace_args)
        toks, _ = _run(cfg, params, trace, slots=slots,
                       temperature=temperature, seed=11)
        return toks

    assert run(1, 0.0) == run(2, 0.0) == run(4, 0.0)
    assert run(1, 0.7) == run(3, 0.7)


def test_paged_local_window_ring_recycles_blocks():
    """A prompt much longer than the window: the ring holds only
    ceil((window + C - 1) / bs) blocks however long the prompt — blocks
    that fall out of the window are recycled, never accumulated — and the
    tokens still match the reference loop's rolling buffer."""
    cfg, params = _setup("recurrentgemma-2b", local_window=8)
    widths = lm.paged_table_widths(cfg, 32, cfg.block_size,
                                   cfg.prefill_chunk)
    assert set(widths) == {"win"}            # no full-attention layers
    assert widths["win"] == 2                # (8 + 8 - 1) tokens over 8-blocks
    rng = np.random.default_rng(0)
    from repro.serve import Request
    trace = [Request(rid=0, prompt=rng.integers(0, cfg.vocab, 20),
                     max_new_tokens=5),
             Request(rid=1, prompt=rng.integers(0, cfg.vocab, 23),
                     max_new_tokens=4)]
    paged, eng = _run(cfg, params, trace, s_max=32)
    assert paged == ref_tokens(cfg, params, trace, 32)
    # 2 slots x 2-block ring is the whole worst case, prompt length be damned
    assert eng.stats.blocks_peak <= 2 * widths["win"]


# ---------------------------------------------------------------------------
# chunked prefill: one program for any prompt-length mix
# ---------------------------------------------------------------------------


def test_chunked_prefill_traces_one_program():
    """A mixed-length trace compiles exactly one prefill program and one
    decode program."""
    cfg, params = _setup("qwen3-4b")
    trace = synthetic_trace(6, cfg.vocab, seed=4, prompt_lens=(3, 5, 9, 11),
                            new_tokens=(2, 4))
    lens = {r.prompt.shape[0] for r in trace}
    assert len(lens) >= 3                    # the mix is genuinely mixed
    _, eng = _run(cfg, params, trace)
    assert eng.stats.prefill_traces == 1
    assert eng.stats.decode_traces == 1
    assert eng.stats.prefill_chunks >= eng.stats.prefills


def test_chunked_prefill_interleaves_with_decode():
    """A long prompt admitted beside a decoding request is consumed one
    chunk per engine step — the decode batch advances between chunks
    instead of stalling head-of-line — and tokens still match the
    reference loop (the mid-prefill slot rides the decode batch inertly:
    recurrent state frozen, KV writes trash-routed)."""
    from repro.serve import Request

    cfg, params = _setup("recurrentgemma-2b")       # recurrent + local attn
    rng = np.random.default_rng(5)
    c = cfg.prefill_chunk
    long_p, short_p = 5 * c, 3
    trace = [Request(rid=0, prompt=rng.integers(0, cfg.vocab, short_p),
                     max_new_tokens=8),
             Request(rid=1, prompt=rng.integers(0, cfg.vocab, long_p),
                     max_new_tokens=3)]
    s_max = long_p + 8
    eng = ServeEngine(cfg, params, slots=2, s_max=s_max)
    for r in trace:
        eng.submit(r)
    eng.step()
    # step 1: both admitted; each advanced exactly ONE chunk; the short
    # prompt (1 chunk) finished prefill and decoded, the long one did not
    assert eng.stats.prefill_chunks == 2
    assert eng.stats.decode_steps == 1
    assert len(eng.sessions[0].tokens) == 2          # prefill tok + 1 decode
    assert len(eng.sessions[1].tokens) == 0          # still prefilling
    for _ in range(3):
        eng.step()
    # the short request decoded every step while the long prefill ran
    assert eng.stats.prefill_chunks == 5
    assert len(eng.sessions[0].tokens) == 5
    while eng.step():
        pass
    paged = {rid: eng.sessions[rid].tokens for rid in eng.sessions}
    assert paged == ref_tokens(cfg, params, trace, s_max)


def test_paged_oom_backpressure_serializes_and_completes():
    """A pool sized for one request at a time: admissions serialize behind
    block availability (FIFO head waits, nobody starves), every request
    completes, and the tokens are unchanged."""
    cfg, params = _setup("qwen3-4b")
    trace = synthetic_trace(4, cfg.vocab, seed=7, prompt_lens=(4, 6),
                            new_tokens=(4, 6))
    need = max(-(-(r.prompt.shape[0] + r.max_new_tokens - 1)
                 // cfg.block_size) for r in trace)
    free_run, _ = _run(cfg, params, trace, slots=2)
    tight, eng = _run(cfg, params, trace, slots=2,
                      n_blocks=need + 1)
    assert tight == free_run
    assert all(s.done for s in eng.sessions.values())
    assert eng.stats.blocks_peak <= need
    assert eng.blocks.in_use == 0
    # queue-wait is visible: later requests waited for blocks/slots
    waits = [s.queue_wait for s in eng.sessions.values()]
    assert all(w == w for w in waits)        # no NaN: everyone was admitted
    assert max(waits) > min(waits)


def test_paged_submit_rejects_impossible_request():
    cfg, params = _setup("qwen3-4b")
    from repro.serve import Request
    eng = ServeEngine(cfg, params, slots=1, s_max=64,
                      n_blocks=3)
    with pytest.raises(ValueError, match="blocks"):
        eng.submit(Request(rid=0, prompt=np.arange(30), max_new_tokens=20))


# ---------------------------------------------------------------------------
# paged layout plumbing
# ---------------------------------------------------------------------------


def test_paged_decode_state_spec_shapes():
    cfg = configs.get("qwen3-4b").smoke()
    st = lm.paged_decode_state_spec(cfg, 3, 24, n_blocks=10, block_size=8,
                                    abstract=True)
    assert st.pos.shape == (3,) and st.pos.dtype == jnp.int32
    assert st.ctx is None
    pool = st.seg_states[0].k                # stacked per layer
    n_layers = cfg.segments()[0][1]
    assert pool.shape == (n_layers, 10, cfg.n_kv_heads, 8, cfg.d_head)


def test_paged_table_widths():
    cfg = configs.get("qwen3-4b").smoke()            # attn only
    assert lm.paged_table_widths(cfg, 48, 8, 8) == {"full": 6}
    cfg = configs.get("recurrentgemma-2b").smoke()   # local only (window 32)
    assert lm.paged_table_widths(cfg, 256, 8, 8) == {"win": 5}  # 39 tokens
    cfg = configs.get("xlstm-350m").smoke()          # no KV cache at all
    assert lm.paged_table_widths(cfg, 48, 8, 8) == {}


def test_engine_stats_block_occupancy_quantities():
    from repro.serve import EngineStats

    st = EngineStats(blocks_total=10)
    for u in (2, 6, 4):
        st.observe_blocks(u)
    assert st.blocks_peak == 6
    assert st.blocks_in_use == 4
    assert st.blocks_mean == pytest.approx(4.0)
    assert st.block_utilization == pytest.approx(0.4)
    assert EngineStats().block_utilization == 0.0


def test_report_ttft_and_queue_wait_quantiles():
    cfg, params = _setup("qwen3-4b")
    trace = synthetic_trace(3, cfg.vocab, seed=1, prompt_lens=(4, 6),
                            new_tokens=(3,))
    eng = ServeEngine(cfg, params, slots=1, s_max=16)
    for r in trace:
        eng.submit(r)
    report = eng.run()
    ttft = report.ttft_quantiles((0.5, 0.95))
    qw = report.queue_wait_quantiles((0.5, 0.95))
    lat = report.latency_quantiles((0.5, 0.95))
    assert 0.0 <= qw[0.5] <= ttft[0.5] <= lat[0.5]
    assert ttft[0.95] <= lat[0.95]
    for s in report.sessions.values():       # queue_wait <= ttft per session
        assert s.queue_wait <= s.ttft


# ---------------------------------------------------------------------------
# BlockPool refcounts + idle tier (DESIGN.md §15 host bookkeeping)
# ---------------------------------------------------------------------------


def test_block_pool_share_refcount_lifecycle():
    pool = BlockPool(8)
    a = pool.alloc(0, 3)                     # [1, 2, 3], ref 1 each
    pool.share(1, a)                         # rid 1 maps them read-only
    assert [pool.refcount(b) for b in a] == [2, 2, 2]
    assert pool.free(0) == 3                 # donor leaves; blocks stay held
    assert [pool.refcount(b) for b in a] == [1, 1, 1]
    assert pool.available == 4 and pool.in_use == 3
    assert pool.free(1) == 3                 # uncached -> straight to free
    assert pool.available == 7 and pool.idle == 0
    with pytest.raises(RuntimeError, match="free"):
        pool.share(2, [1])                   # sharing a free block is a bug
    with pytest.raises(ValueError):
        pool.share(2, [0])                   # the trash block, ever
    pool.alloc(3, 1)
    pool.share(4, [1])
    with pytest.raises(RuntimeError, match="already holds"):
        pool.share(4, [1])


def test_block_pool_cached_blocks_idle_then_evict_lru():
    pool = BlockPool(8)
    a = pool.alloc(0, 3)                     # [1, 2, 3]
    for b in a:
        pool.set_cached(b)
    pool.free(0)
    # cached blocks park idle (resident, not in use) instead of freeing
    assert pool.available == 4 and pool.idle == 3 and pool.in_use == 0
    assert pool.reclaimable == 7
    assert pool.idle_blocks == [1, 2, 3]     # LRU = release order
    pool.share(1, [2])                       # revive from idle
    assert pool.idle_blocks == [1, 3] and pool.refcount(2) == 1
    assert pool.cached(2)
    pool.free(1)
    assert pool.idle_blocks == [1, 3, 2]     # re-idled last -> evicted last
    assert pool.evict_idle(2) == [1, 3]
    assert not pool.cached(1) and pool.available == 6
    with pytest.raises(RuntimeError, match="idle"):
        pool.evict_idle(2)                   # only block 2 is left idle
    assert pool.alloc(5, 6) == [1, 3, 4, 5, 6, 7]   # evicted ids reusable
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(6, 1)                     # idle blocks need evict first


def test_block_pool_drop_single_hold_cow_path():
    pool = BlockPool(6)
    a = pool.alloc(0, 2)
    pool.set_cached(a[0])
    pool.share(1, a)
    pool.drop(1, a[0])                       # rid 1 lets go of one block
    assert pool.refcount(a[0]) == 1 and pool.held(1) == [a[1]]
    with pytest.raises(KeyError):
        pool.drop(1, a[0])                   # no double-drop
    pool.free(0)
    assert pool.idle_blocks == [a[0]]        # cached -> idle on last release
    with pytest.raises(RuntimeError, match="not held"):
        pool.set_cached(a[0])                # caching needs a live holder


# ---------------------------------------------------------------------------
# PrefixIndex: content-addressed chain matching (host logic, no model)
# ---------------------------------------------------------------------------


def test_prefix_index_chain_lookup_and_divergence():
    idx = PrefixIndex(4)
    donor = np.arange(12, dtype=np.int32)    # 3 full blocks
    for bid, (key, parent, toks) in zip([5, 6, 7], idx.chain(donor)):
        assert idx.register(key, parent, bid, toks)
    assert len(idx) == 3
    # full match walks the whole chain; no continuation block exists
    ids, n_full, child = idx.lookup(donor)
    assert ids == [5, 6, 7] and n_full == 3 and child is None
    # divergence mid block 1: one full block + the divergence block with
    # its common-token count
    probe = np.array([0, 1, 2, 3, 4, 5, 99, 99], np.int32)
    ids, n_full, child = idx.lookup(probe)
    assert ids == [5] and n_full == 1 and child == (6, 2)
    # boundary divergence: the continuation block matches 0 extra tokens
    probe = np.array([0, 1, 2, 3, 99, 99, 99, 99], np.int32)
    assert idx.lookup(probe) == ([5], 1, (6, 0))
    # nothing shared at all
    assert idx.lookup(np.full(8, 77, np.int32)) == ([], 0, (5, 0))
    # keep-first: a second registration of the same content no-ops
    key, parent, toks = idx.chain(donor)[0]
    assert not idx.register(key, parent, 9, toks)
    assert idx.lookup(donor)[0] == [5, 6, 7]


def test_prefix_index_eviction_orphans_descendants():
    idx = PrefixIndex(4)
    donor = np.arange(8, dtype=np.int32)
    for bid, (key, parent, toks) in zip([3, 4], idx.chain(donor)):
        idx.register(key, parent, bid, toks)
    idx.drop_block(3)                        # evict the chain head
    assert len(idx) == 1
    # the orphaned child is unreachable (its parent key now misses) ...
    assert idx.lookup(donor) == ([], 0, None)
    # ... until re-registering the head restores the chain, child and all
    key, parent, toks = idx.chain(donor)[0]
    assert idx.register(key, parent, 9, toks)
    assert idx.lookup(donor) == ([9, 4], 2, None)


def test_prefix_index_ctx_keys_the_chain_root():
    idx = PrefixIndex(4)
    toks = np.arange(4, dtype=np.int32)
    ctx_a = np.ones((2, 3), np.float32)
    ctx_b = np.zeros((2, 3), np.float32)
    key, parent, blk = idx.chain(toks, ctx_a)[0]
    idx.register(key, parent, 5, blk)
    # same tokens under a different (or no) modality context never match
    assert idx.lookup(toks, ctx_a)[0] == [5]
    assert idx.lookup(toks, ctx_b) == ([], 0, None)
    assert idx.lookup(toks, None) == ([], 0, None)


# ---------------------------------------------------------------------------
# prefix caching: cross-arch sharing identity (the §15 tentpole contract)
# ---------------------------------------------------------------------------

# which sweep archs can share prefixes: recurrent carries and local window
# rings cannot be rebuilt from cached blocks, so the engine auto-disables
ELIGIBLE = {"qwen3-4b": True, "recurrentgemma-2b": False,
            "whisper-tiny": True, "llama-3.2-vision-11b": True,
            "xlstm-350m": False, "llama4-scout-17b-a16e": True,
            "moonlight-16b-a3b": True}


def _shared_trace(cfg, seed=13):
    return synthetic_trace(6, cfg.vocab, seed=seed, prompt_lens=(3, 5),
                           new_tokens=(3, 5), prefix_frac=0.9, prefix_len=9,
                           n_ctx_tokens=cfg.n_ctx_tokens,
                           d_model=cfg.d_model)


@pytest.mark.parametrize("name", SWEEP_ARCHS)
def test_prefix_sharing_identity_sweep(name):
    """A 90%-shared-prefix trace is token-identical with the prefix cache
    on vs off, for every paged arch family — and the cache genuinely
    engages where it is sound (skipped tokens, shared blocks) while the
    recurrent/window-ring archs take the documented disabled path."""
    cfg, params = _setup(name)
    trace = _shared_trace(cfg)
    off, _ = _run(cfg, params, trace, prefix_cache=False)
    on, eng = _run(cfg, params, trace, prefix_cache=True)
    assert on == off
    assert eng.prefix_caching == ELIGIBLE[name]
    if ELIGIBLE[name]:
        assert eng.stats.prefix_hits > 0
        assert eng.stats.prefix_tokens > 0
        assert 0.0 < eng.stats.prefix_hit_rate < 1.0
        assert eng.stats.blocks_per_request > 0
        # the trash block is never registered or cached
        assert 0 not in eng._prefix._by_block
        assert not eng.blocks.cached(0)
    else:
        assert eng.stats.prefix_hits == 0
        assert eng.stats.cow_copies == 0
    if eng.blocks is not None:
        assert eng.blocks.in_use == 0        # only idle cached blocks remain


def test_prefix_sharing_identity_packed_residency():
    """Sharing under packed-weight residency: the cached KV a request maps
    was produced by the same packed kernels, so identity must hold
    packed-on == packed-off == float-off."""
    cfg, params = _setup("qwen2-7b+xnor")
    trace = _shared_trace(cfg, seed=21)
    packed_off, _ = _run(cfg, params, trace, pack=True,
                         prefix_cache=False)
    packed_on, eng = _run(cfg, params, trace, pack=True,
                          prefix_cache=True)
    float_off, _ = _run(cfg, params, trace, pack=False,
                        prefix_cache=False)
    assert packed_on == packed_off == float_off
    assert eng.stats.prefix_hits > 0


@pytest.mark.parametrize("name", ["qwen3-4b", "whisper-tiny"])
def test_prefix_sharing_identity_i8_cache(name):
    """Sharing over the fixed-point i8 KV cache: the donor's quantized
    words are bitwise what the sharer would have written, so identity
    holds with no requantization drift."""
    cfg, params = _setup(name)
    cfg = dataclasses.replace(cfg, kv_cache_dtype="i8")
    trace = _shared_trace(cfg, seed=8)
    off, _ = _run(cfg, params, trace, prefix_cache=False)
    on, eng = _run(cfg, params, trace, prefix_cache=True)
    assert on == off
    assert eng.stats.prefix_hits > 0


def test_prefix_sharing_moe_deterministic_replay():
    """MoE shares prefixes too (its KV is ordinary paged state) and, being
    dropless, is token-identical to the cache-off path; replaying the same
    shared trace through an identically configured engine — sharing, COW,
    LRU eviction and all — reproduces the tokens and the counts exactly."""
    cfg, params = _setup("llama4-scout-17b-a16e")

    def run(prefix_cache=True):
        toks, eng = _run(cfg, params, _shared_trace(cfg, seed=3),
                         slots=2, prefix_cache=prefix_cache)
        return toks, eng

    t1, e1 = run()
    t2, e2 = run()
    assert t1 == t2 == run(prefix_cache=False)[0]
    assert e1.prefix_caching and e1.stats.prefix_hits > 0
    assert e1.stats.prefix_hits == e2.stats.prefix_hits
    assert e1.stats.cow_copies == e2.stats.cow_copies


# ---------------------------------------------------------------------------
# adversarial divergence points: exactly one COW each (EngineStats-pinned)
# ---------------------------------------------------------------------------


def _engine(cfg, params, *, slots=1, s_max=40, prefix_cache=True,
            n_blocks=0):
    return ServeEngine(cfg, params, slots=slots, s_max=s_max,
                       prefix_cache=prefix_cache, n_blocks=n_blocks)


def _serve(eng, rid, prompt, new=4):
    eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=new))
    eng.run()
    return list(eng.sessions[rid].tokens)


def test_prefix_divergence_points_cost_exactly_one_cow():
    """Divergence at a block boundary, one token after it, mid-block, and
    a full-prompt hit: each admission maps the donor's blocks, triggers
    exactly ONE copy-on-write copy, skips to the divergence point, and
    produces the same tokens as a cache-off engine.  A donor replay after
    a divergent sharer proves no COW bleed back into shared blocks."""
    cfg, params = _setup("qwen3-4b")
    bs = cfg.block_size
    rng = np.random.default_rng(0)
    donor = rng.integers(0, cfg.vocab, 3 * bs).astype(np.int32)
    diff = (donor + 1) % cfg.vocab           # divergent everywhere
    probes = {
        "boundary": np.concatenate([donor[:2 * bs], diff[:bs]]),
        "one_after_boundary": np.concatenate([donor[:2 * bs + 1],
                                              diff[:bs - 1]]),
        "mid_block": np.concatenate([donor[:2 * bs + 4], diff[:bs - 4]]),
        "full_hit": donor.copy(),
    }
    expect_skip = {"boundary": 2 * bs, "one_after_boundary": 2 * bs + 1,
                   "mid_block": 2 * bs + 4, "full_hit": 3 * bs - 1}

    ref = _engine(cfg, params, prefix_cache=False)
    eng = _engine(cfg, params, prefix_cache=True)
    donor_ref = _serve(ref, 0, donor)
    assert _serve(eng, 0, donor) == donor_ref
    assert eng.stats.cow_copies == 0         # the cold donor never COWs
    for i, (case, probe) in enumerate(probes.items(), start=1):
        cow0, skip0 = eng.stats.cow_copies, eng.stats.prefix_tokens
        toks = _serve(eng, i, probe)
        assert toks == _serve(ref, i, probe), case
        assert eng.stats.cow_copies - cow0 == 1, case
        assert eng.stats.prefix_tokens - skip0 == expect_skip[case], case
    # no bleed: the donor's cached blocks survived four divergent sharers
    assert _serve(eng, 99, donor.copy()) == donor_ref


def test_prefix_sharing_with_mid_prefill_donor():
    """A request can share blocks a *still-prefilling* donor has already
    written (registration follows the one-chunk-per-step dispatch order):
    the sharer diverges mid-block inside the donor's registered region,
    costs exactly one COW, and both match their cache-off tokens."""
    cfg, params = _setup("qwen3-4b")
    bs, c = cfg.block_size, cfg.prefill_chunk
    rng = np.random.default_rng(1)
    donor = rng.integers(0, cfg.vocab, 5 * c).astype(np.int32)
    probe = np.concatenate([donor[:bs + 4],
                            (donor[bs + 4:2 * bs] + 1) % cfg.vocab])

    def staggered(prefix_cache):
        eng = _engine(cfg, params, slots=2, s_max=48,
                      prefix_cache=prefix_cache)
        eng.submit(Request(rid=0, prompt=donor, max_new_tokens=4))
        eng.step()
        eng.step()          # donor has dispatched 2 chunks -> 2 full blocks
        eng.submit(Request(rid=1, prompt=probe, max_new_tokens=4))
        while eng.step():
            pass
        return {rid: eng.sessions[rid].tokens for rid in eng.sessions}, eng

    on, eng = staggered(True)
    off, _ = staggered(False)
    assert on == off
    assert eng.stats.cow_copies == 1
    assert eng.stats.prefix_tokens == bs + 4


def test_prefix_disabled_inside_local_window_ring():
    """The window-ring exception (§15): ring blocks are recycled in place,
    so their contents are never registrable — a shared-prefix trace on a
    local-attention arch runs with sharing auto-disabled, zero COWs, and
    tokens identical to an engine with the cache explicitly off."""
    cfg, params = _setup("recurrentgemma-2b", local_window=8)
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab, 20).astype(np.int32)
    trace = [Request(rid=i,
                     prompt=np.concatenate(
                         [shared, rng.integers(0, cfg.vocab, 3)
                          .astype(np.int32)]),
                     max_new_tokens=4) for i in range(3)]
    off, _ = _run(cfg, params, trace, s_max=32,
                  prefix_cache=False)
    on, eng = _run(cfg, params, trace, s_max=32,
                   prefix_cache=True)
    assert on == off
    assert not eng.prefix_caching
    assert eng.stats.cow_copies == 0 and eng.stats.prefix_hits == 0


def test_prefix_ctx_mismatch_never_shares():
    """Identical token prefixes under different modality contexts must not
    share (the chain root folds a ctx digest): same audio hits, different
    audio misses, and the same-ctx replay reproduces the same tokens."""
    cfg, params = _setup("whisper-tiny")
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, 2 * cfg.block_size).astype(np.int32)
    ctx_a = rng.standard_normal((cfg.n_ctx_tokens, cfg.d_model)) \
        .astype(np.float32) * 0.1
    ctx_b = rng.standard_normal((cfg.n_ctx_tokens, cfg.d_model)) \
        .astype(np.float32) * 0.1
    # pool wide enough that neither foreign-ctx admission evicts rid 0's
    # cached chain before the same-ctx replay arrives
    eng = _engine(cfg, params, s_max=24, n_blocks=16)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=4, ctx=ctx_a))
    eng.run()
    eng.submit(Request(rid=1, prompt=prompt, max_new_tokens=4, ctx=ctx_b))
    eng.run()
    assert eng.stats.prefix_hits == 0        # different ctx: no sharing
    eng.submit(Request(rid=2, prompt=prompt, max_new_tokens=4, ctx=ctx_a))
    eng.run()
    assert eng.stats.prefix_hits == 1        # same ctx: full-prompt hit
    assert eng.sessions[2].tokens == eng.sessions[0].tokens


def test_prefix_eviction_under_pool_pressure_lru():
    """A tight pool: cached prefixes are evicted LRU under allocation
    pressure (never while held), the index entries drop with them, and a
    later replay of the evicted prompt simply misses — correctness is
    unchanged, greedy replay reproduces the donor's tokens."""
    cfg, params = _setup("qwen3-4b")
    bs = cfg.block_size
    rng = np.random.default_rng(4)
    donor = rng.integers(0, cfg.vocab, 2 * bs).astype(np.int32)
    eng = _engine(cfg, params, s_max=32, n_blocks=5)   # 4 allocatable
    donor_toks = _serve(eng, 0, donor)
    assert eng.stats.prefix_cached_blocks > 0
    # unrelated requests churn the pool until the donor's entries evict
    for i in range(1, 4):
        _serve(eng, i, rng.integers(0, cfg.vocab, 2 * bs).astype(np.int32))
    assert eng.stats.prefix_evictions > 0
    replay = _serve(eng, 9, donor.copy())
    assert replay == donor_toks              # miss or hit, tokens identical
    assert eng.blocks.in_use == 0


def test_prefix_gate_excludes_blocks_the_plan_itself_revives():
    """Admission must not count idle blocks the plan's own share() will
    revive as evictable: a donor finishes leaving its 2-block prefix idle
    in a 4-block pool, then a same-prompt request needing 4 blocks total
    arrives.  Sharing would revive both idle blocks and leave only 2
    evictable for 3 fresh — the old gate passed it (need 3 <= reclaimable
    4) and crashed evict_idle mid-run.  With nothing in flight the head
    degrades to a wholly-fresh plan instead of deadlocking, and the
    tokens match a cache-off engine."""
    cfg, params = _setup("qwen3-4b")
    bs = cfg.block_size
    rng = np.random.default_rng(11)
    donor = rng.integers(0, cfg.vocab, 2 * bs).astype(np.int32)
    ref = _engine(cfg, params, prefix_cache=False)
    eng = _engine(cfg, params, n_blocks=5)     # 4 allocatable
    assert _serve(eng, 0, donor, new=1) == _serve(ref, 0, donor, new=1)
    assert eng.blocks.idle == 2                # prefix parked, pool drained
    new = 2 * bs + 1                           # 4 blocks total, 3 fresh
    toks = _serve(eng, 1, donor.copy(), new=new)
    assert toks == _serve(ref, 1, donor.copy(), new=new)
    assert all(s.done for s in eng.sessions.values())
    assert eng.blocks.in_use == 0


def test_prefix_sharer_allocates_under_pressure_while_prefix_idle():
    """The sharer itself allocates under pool pressure while its shared
    prefix sits idle: sharing survives (the plan fits once its revived
    blocks are excluded from the evictable count) and the fresh
    allocation evicts an unrelated idle block — not the revived prefix —
    with no crash and cache-off-identical tokens."""
    cfg, params = _setup("qwen3-4b")
    bs = cfg.block_size
    rng = np.random.default_rng(12)
    donor = rng.integers(0, cfg.vocab, 2 * bs).astype(np.int32)
    other = rng.integers(0, cfg.vocab, 2 * bs).astype(np.int32)
    ref = _engine(cfg, params, prefix_cache=False)
    eng = _engine(cfg, params, n_blocks=7)     # 6 allocatable
    assert _serve(eng, 0, donor, new=1) == _serve(ref, 0, donor, new=1)
    assert _serve(eng, 1, other, new=1) == _serve(ref, 1, other, new=1)
    assert eng.blocks.idle == 4 and eng.blocks.available == 2
    new = 2 * bs + 1           # 3 fresh after the COW credit, 2 free
    hits0, evict0 = eng.stats.prefix_hits, eng.stats.prefix_evictions
    toks = _serve(eng, 2, donor.copy(), new=new)
    assert toks == _serve(ref, 2, donor.copy(), new=new)
    assert eng.stats.prefix_hits == hits0 + 1          # sharing survived
    assert eng.stats.prefix_evictions == evict0 + 1    # one unrelated evict
    assert eng.blocks.in_use == 0


def test_blocked_head_plan_recomputed_only_on_index_change():
    """While the FIFO head waits (here: on the single slot), its prefix
    plan is memoized on the index generation instead of re-hashing the
    whole prompt every engine step."""
    cfg, params = _setup("qwen3-4b")
    bs = cfg.block_size
    rng = np.random.default_rng(13)
    eng = _engine(cfg, params, slots=1)
    calls = 0
    orig = eng._prefix_plan

    def counting(req):
        nonlocal calls
        calls += 1
        return orig(req)

    eng._prefix_plan = counting
    eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, bs)
                       .astype(np.int32), max_new_tokens=24))
    eng.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab, bs)
                       .astype(np.int32), max_new_tokens=2))
    eng.run()
    assert eng.stats.decode_steps >= 20      # rid 1 was head for many steps
    assert calls <= 4                        # not once per step


def test_engine_stats_prefix_quantities():
    from repro.serve import EngineStats

    st = EngineStats()
    assert st.prefix_hit_rate == 0.0 and st.blocks_per_request == 0.0
    st.prompt_tokens, st.prefix_tokens = 40, 10
    st.prefills, st.fresh_blocks = 4, 6
    assert st.prefix_hit_rate == pytest.approx(0.25)
    assert st.blocks_per_request == pytest.approx(1.5)


def test_synthetic_trace_prefix_knobs():
    """prefix_frac/prefix_len: seeded, schedule-independent, and a pure
    extension — per-request draws are bit-identical to the base trace, the
    shared group gets the same prefix (and one shared ctx object)."""
    base = synthetic_trace(8, 256, seed=5, prompt_lens=(4, 6))
    mixed = synthetic_trace(8, 256, seed=5, prompt_lens=(4, 6),
                            prefix_frac=0.75, prefix_len=9)
    again = synthetic_trace(8, 256, seed=5, prompt_lens=(4, 6),
                            prefix_frac=0.75, prefix_len=9)
    shared = [r for r, b in zip(mixed, base)
              if r.prompt.shape[0] == b.prompt.shape[0] + 9]
    assert 0 < len(shared) < len(mixed)      # a genuine mix at 0.75
    prefix = shared[0].prompt[:9]
    for r, b in zip(mixed, base):
        if r.prompt.shape[0] == b.prompt.shape[0]:     # unshared request
            assert np.array_equal(r.prompt, b.prompt)
        else:
            assert np.array_equal(r.prompt[:9], prefix)
            assert np.array_equal(r.prompt[9:], b.prompt)
        assert r.max_new_tokens == b.max_new_tokens
    for r, r2 in zip(mixed, again):          # fully deterministic
        assert np.array_equal(r.prompt, r2.prompt)
    # ctx archs: the shared group shares ONE ctx object (sharing is keyed
    # per-ctx, so distinct ctx objects would never share)
    vl = synthetic_trace(8, 256, seed=5, prompt_lens=(4,), n_ctx_tokens=2,
                         d_model=4, prefix_frac=0.75, prefix_len=9)
    ctxs = [r.ctx for r in vl if r.prompt.shape[0] == 13]
    assert all(c is ctxs[0] for c in ctxs)
