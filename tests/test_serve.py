"""Continuous-batching serve engine + packed-weight residency tests
(DESIGN.md §13).

Scheduler bookkeeping is exercised as pure host logic (SlotPool); the
engine is checked token-for-token against a per-request static reference
(heterogeneous prompts sharing a batch must not change any request's
tokens); the packed serve path is checked *bit-exact* against the float
sign path for every arch that binarizes linears, with the float weights
asserted absent from the resident tree.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.checkpoint import ckpt
from repro.core.xnor_layers import PackedLinear
from repro.models import lm
from repro.models import params as pdefs
from repro.serve import Request, ServeEngine, Session, SlotPool, synthetic_trace

ARCHS = sorted(configs.ALL)


def _setup(name, seed_salt="", **over):
    cfg = configs.get(name).smoke(dtype=jnp.float32, **over)
    key = jax.random.PRNGKey(zlib.crc32((name + seed_salt).encode()) % 2**31)
    params = lm.init_params(cfg, key)
    return cfg, params


def _ref_generate(cfg, params, req, s_max):
    """Static per-request greedy reference (eager prefill + decode loop)."""
    ctx = None if req.ctx is None else jnp.asarray(np.asarray(req.ctx)[None])
    lg, st = lm.prefill(cfg, params, jnp.asarray(req.prompt[None]), ctx,
                        s_max=s_max)
    tok = jnp.argmax(lg[..., :cfg.vocab][:, -1], -1).astype(jnp.int32)[:, None]
    out = [int(tok[0, 0])]
    for _ in range(req.max_new_tokens - 1):
        lg, st = lm.decode_step(cfg, params, tok, st)
        tok = jnp.argmax(lg[..., :cfg.vocab][:, -1],
                         -1).astype(jnp.int32)[:, None]
        out.append(int(tok[0, 0]))
    return out


# ---------------------------------------------------------------------------
# SlotPool: pure scheduling bookkeeping
# ---------------------------------------------------------------------------


def _sess(rid):
    return Session(Request(rid=rid, prompt=np.array([1]), max_new_tokens=1),
                   t_submit=0.0)


def test_slot_pool_fifo_admission_lowest_slot():
    pool = SlotPool(2)
    for rid in range(4):
        pool.submit(_sess(rid))
    s0, slot0 = pool.admit()
    s1, slot1 = pool.admit()
    assert (s0.request.rid, slot0) == (0, 0)
    assert (s1.request.rid, slot1) == (1, 1)
    assert not pool.admissible()          # full
    pool.evict(slot0)
    assert pool.free_slots == [0]
    s2, slot2 = pool.admit()
    assert (s2.request.rid, slot2) == (2, 0)   # FIFO into the freed slot


def test_slot_pool_lowest_free_slot_reused_first():
    pool = SlotPool(3)
    for rid in range(6):
        pool.submit(_sess(rid))
    slots = [pool.admit()[1] for _ in range(3)]
    assert slots == [0, 1, 2]
    pool.evict(2)
    pool.evict(0)
    assert pool.free_slots == [0, 2]      # kept sorted: lowest first
    assert pool.admit()[1] == 0
    assert pool.admit()[1] == 2


def test_slot_pool_errors_and_idle():
    pool = SlotPool(1)
    with pytest.raises(RuntimeError):
        pool.admit()                      # empty queue
    pool.submit(_sess(0))
    _, slot = pool.admit()
    pool.submit(_sess(1))
    with pytest.raises(RuntimeError):
        pool.admit()                      # no free slot
    with pytest.raises(KeyError):
        pool.evict(slot + 1)
    assert not pool.idle()
    pool.evict(slot)
    pool.admit()
    pool.evict(slot)
    assert pool.idle()
    with pytest.raises(ValueError):
        SlotPool(0)


# ---------------------------------------------------------------------------
# engine: heterogeneous batches match the per-request static reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["qwen3-4b", "recurrentgemma-2b",
                                  "whisper-tiny"])
def test_engine_matches_static_reference(name):
    """Mixed prompt lengths / budgets sharing a batch: every request's
    tokens equal its standalone static decode (dense, local-window
    rolling cache, and enc-dec cross-attn state all scattered per slot)."""
    cfg, params = _setup(name)
    trace = synthetic_trace(5, cfg.vocab, seed=2, prompt_lens=(4, 6, 9),
                            new_tokens=(3, 6),
                            n_ctx_tokens=cfg.n_ctx_tokens,
                            d_model=cfg.d_model)
    eng = ServeEngine(cfg, params, slots=2, s_max=24)
    for r in trace:
        eng.submit(r)
    report = eng.run()
    assert report.prefills == len(trace)
    for r in trace:
        want = _ref_generate(cfg, params, r, s_max=24)
        assert report.tokens(r.rid).tolist() == want, r.rid
        sess = report.sessions[r.rid]
        assert sess.finish_reason == "length"
        assert sess.t_submit <= sess.t_admit <= sess.t_first <= sess.t_done
    assert eng.pool.idle()


def test_engine_single_slot_reuses_and_preserves_order():
    cfg, params = _setup("qwen3-4b")
    trace = synthetic_trace(3, cfg.vocab, seed=5, prompt_lens=(4, 7),
                            new_tokens=(2, 4))
    eng = ServeEngine(cfg, params, slots=1, s_max=16)
    for r in trace:
        eng.submit(r)
    report = eng.run()
    admits = sorted(report.sessions.values(), key=lambda s: s.t_admit)
    assert [s.request.rid for s in admits] == [0, 1, 2]   # FIFO through 1 slot
    for r in trace:
        assert report.tokens(r.rid).tolist() == _ref_generate(
            cfg, params, r, s_max=16)


def test_engine_eos_eviction():
    """EOS terminates a request early (including at prefill) and frees the
    slot for the queue; non-EOS requests run to budget."""
    cfg, params = _setup("qwen3-4b")
    trace = synthetic_trace(4, cfg.vocab, seed=9, prompt_lens=(4, 6, 8),
                            new_tokens=(6,))
    refs = {r.rid: _ref_generate(cfg, params, r, s_max=20) for r in trace}
    eos = refs[0][1]      # second token of request 0 -> it must stop at 2
    eng = ServeEngine(cfg, params, slots=2, s_max=20, eos_id=eos)
    for r in trace:
        eng.submit(r)
    report = eng.run()
    for r in trace:
        want = refs[r.rid]
        if eos in want:
            want = want[:want.index(eos) + 1]
            assert report.sessions[r.rid].finish_reason == "eos"
        else:
            assert report.sessions[r.rid].finish_reason == "length"
        assert report.tokens(r.rid).tolist() == want, r.rid
    assert len(report.tokens(0)) == 2
    assert eng.pool.idle()


def test_engine_deterministic_across_slot_counts():
    """Sampling keys depend on (request, step) only: the same seeded trace
    gives identical tokens whatever the slot count / schedule."""
    cfg, params = _setup("qwen3-4b")

    def run(slots):
        eng = ServeEngine(cfg, params, slots=slots, s_max=20,
                          temperature=0.7, seed=11)
        for r in synthetic_trace(5, cfg.vocab, seed=3, prompt_lens=(4, 6),
                                 new_tokens=(3, 5)):
            eng.submit(r)
        rep = eng.run()
        return {rid: rep.tokens(rid).tolist() for rid in rep.sessions}

    a, b, c = run(1), run(2), run(4)
    assert a == b == c


def test_engine_submit_validation():
    cfg, params = _setup("qwen3-4b")
    eng = ServeEngine(cfg, params, slots=1, s_max=8)
    eng.submit(Request(rid=0, prompt=np.arange(4), max_new_tokens=5))
    with pytest.raises(ValueError):       # duplicate rid
        eng.submit(Request(rid=0, prompt=np.arange(4), max_new_tokens=2))
    with pytest.raises(ValueError):       # prompt + budget - 1 > s_max
        eng.submit(Request(rid=1, prompt=np.arange(6), max_new_tokens=4))
    with pytest.raises(ValueError):
        Request(rid=2, prompt=np.arange(4), max_new_tokens=0)


def test_report_quantiles_nan_safe_with_nothing_finished():
    """A report over only in-flight (or zero) sessions must answer every
    quantile helper with NaN — never raise, and never a fake 0.0 that a
    dashboard or CI gate would read as "instant".  The replicated router
    aggregates per-replica reports mid-drill, where a replica can
    legitimately have nothing finished yet."""
    from repro.serve import ServeReport

    live = Session(Request(rid=0, prompt=np.arange(4), max_new_tokens=3),
                   t_submit=100.0)     # never admitted, never finished
    for sessions in ({}, {0: live}):
        rep = ServeReport(sessions=sessions, wall=0.5, decode_steps=0,
                          prefills=0)
        for qs in (rep.latency_quantiles(), rep.ttft_quantiles(),
                   rep.ttft_step_quantiles(), rep.queue_wait_quantiles()):
            assert set(qs) == {0.5, 0.95}
            assert all(np.isnan(v) for v in qs.values()), (sessions, qs)
    assert rep.generated == 0 and rep.tok_per_s == 0.0


def test_report_quantiles_ignore_in_flight_sessions():
    """Finished sessions dominate the quantiles; in-flight ones (NaN
    latency/ttft) are dropped from the sample, not poisoning it."""
    from repro.serve import ServeReport

    done = Session(Request(rid=1, prompt=np.arange(4), max_new_tokens=2),
                   t_submit=10.0)
    done.t_admit, done.t_first, done.t_done = 11.0, 12.0, 14.0
    done.finish_reason = "length"
    live = Session(Request(rid=2, prompt=np.arange(4), max_new_tokens=2),
                   t_submit=10.0)
    rep = ServeReport(sessions={1: done, 2: live}, wall=1.0,
                      decode_steps=0, prefills=0)
    lat = rep.latency_quantiles()
    assert lat[0.5] == pytest.approx(4.0) and lat[0.95] == pytest.approx(4.0)
    assert rep.ttft_quantiles()[0.5] == pytest.approx(2.0)
    assert rep.queue_wait_quantiles()[0.5] == pytest.approx(1.0)


def test_generate_wrapper_matches_static_loop():
    """serve_step.generate (now an engine wrapper) is token-identical to
    the historical static-batch loop for greedy decoding."""
    from repro.train import serve_step

    cfg, params = _setup("qwen3-4b")
    key = jax.random.PRNGKey(8)
    B, P, N = 3, 6, 5
    prompt = jax.random.randint(key, (B, P), 0, cfg.vocab)
    got = np.asarray(serve_step.generate(cfg, params, prompt, N))
    for i in range(B):
        req = Request(rid=i, prompt=np.asarray(prompt[i]), max_new_tokens=N)
        assert got[i].tolist() == _ref_generate(cfg, params, req, s_max=P + N)


def test_decode_state_spec_per_slot_pos():
    cfg = configs.get("qwen3-4b").smoke()
    st = lm.decode_state_spec(cfg, 3, 16, abstract=True, per_slot_pos=True)
    assert st.pos.shape == (3,) and st.pos.dtype == jnp.int32
    st0 = lm.decode_state_spec(cfg, 3, 16, abstract=True)
    assert st0.pos.shape == ()


# ---------------------------------------------------------------------------
# packed-weight residency: bit-exactness + float absence
# ---------------------------------------------------------------------------


# a latent-attention model trains under +xnor but is not served binarized
# (its decode folds wkv_b into q: test_moonlight.py pins the refusal)
PACKED_ARCHS = [a for a in ARCHS if not configs.get(a).latent]


@pytest.mark.parametrize("name", PACKED_ARCHS)
def test_packed_serve_path_bit_exact(name):
    """Full-model prefill+decode logits from prepacked weights are
    bit-identical to the float sign path, for every arch served under
    +xnor (runs in whichever REPRO_KERNEL_IMPL mode CI selects)."""
    cfg, params = _setup(name + "+xnor")
    assert cfg.quant == "xnor"
    packed = lm.pack_params(cfg, params)
    key = jax.random.PRNGKey(3)
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab)
    ctx = None
    if cfg.n_ctx_tokens:
        ctx = jax.random.normal(key, (2, cfg.n_ctx_tokens, cfg.d_model),
                                jnp.float32) * 0.1
    lf, sf = lm.prefill(cfg, params, tokens[:, :5], ctx, s_max=10)
    lp, sp = lm.prefill(cfg, packed, tokens[:, :5], ctx, s_max=10)
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lp))
    for t in range(5, 7):
        lf, sf = lm.decode_step(cfg, params, tokens[:, t:t+1], sf)
        lp, sp = lm.decode_step(cfg, packed, tokens[:, t:t+1], sp)
        np.testing.assert_array_equal(np.asarray(lf), np.asarray(lp))


def test_packed_params_hold_no_float_binary_weights():
    """The packed-residency contract: every binarizable linear's float
    weight is absent from the serve tree (only uint32 planes + f32 beta
    remain), and the resident footprint shrinks."""
    cfg, params = _setup("qwen2-7b+xnor")
    packed = lm.pack_params(cfg, params)
    defs = lm.param_defs(cfg)
    flat_defs = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, pdefs.ParamDef))[0]
    flat_params = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    # the engine keeps the packed tree resident, not the float one
    eng = ServeEngine(cfg, params, slots=1, s_max=8)
    n_bin = 0
    for tree in (packed, eng.params):
        flat_packed = dict(jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, PackedLinear))[0])
        for path, d in flat_defs:
            leaf = flat_packed[path]
            if d.binarize:
                n_bin += 1
                assert isinstance(leaf, PackedLinear), path
                assert leaf.pb.dtype == jnp.uint32
                assert leaf.beta.dtype == jnp.float32
                n, k, m = d.shape
                assert leaf.pb.shape == (n, m, -(-k // 32))
                assert leaf.beta.shape == (n, m)
                assert leaf.k == k      # true K rides as static aux data
            else:
                assert not isinstance(leaf, PackedLinear), path
                np.testing.assert_array_equal(np.asarray(leaf),
                                              np.asarray(flat_params[path]))
    assert n_bin > 0
    fbytes = sum(np.asarray(l).nbytes for l in jax.tree.leaves(params))
    pbytes = sum(np.asarray(l).nbytes for l in jax.tree.leaves(packed))
    assert pbytes < fbytes


def test_pack_params_identity_for_unquantized():
    cfg, params = _setup("qwen3-4b")
    assert lm.pack_params(cfg, params) is params


def test_pack_params_idempotent_and_composes_with_restore_packed(tmp_path):
    """A tree loaded via restore_packed can feed consumers that pack by
    default (ServeEngine): pack() passes PackedLinear leaves through."""
    cfg, params = _setup("qwen2-7b+xnor")
    p1 = lm.pack_params(cfg, params)
    p2 = lm.pack_params(cfg, p1)
    assert jax.tree.structure(p1) == jax.tree.structure(p2)
    assert all(a is b for a, b in zip(jax.tree.leaves(p1),
                                      jax.tree.leaves(p2)))
    ckpt.save(str(tmp_path), 1, params)
    loaded, _ = ckpt.restore_packed(str(tmp_path), None, cfg)

    def run(tree):
        eng = ServeEngine(cfg, tree, slots=1, s_max=12)
        eng.submit(Request(rid=0, prompt=np.arange(4), max_new_tokens=3))
        return eng.run().tokens(0).tolist()

    assert run(loaded) == run(params)


def test_prepacked_width_mismatch_raises():
    """The true-K aux check is a raise (survives python -O), not an assert:
    word-rounded width mismatches must never mis-correct the popcount."""
    from repro.core import xnor_layers

    pl = xnor_layers.pack_linear(jnp.ones((8, 3)))
    assert pl.k == 8
    with pytest.raises(ValueError, match="true K"):
        xnor_layers.xnor_linear_prepacked(jnp.ones((2, 6)), pl.pb, pl.beta,
                                          valid_k=pl.k)


def test_engine_serves_packed_exactly_as_float():
    """End-to-end: packed-resident engine emits the same tokens as the
    float-weight engine on the same trace."""
    cfg, params = _setup("qwen2-7b+xnor")
    trace_args = dict(seed=6, prompt_lens=(4, 7), new_tokens=(3, 5))

    def run(pack):
        eng = ServeEngine(cfg, params, slots=2, s_max=16, pack=pack)
        for r in synthetic_trace(4, cfg.vocab, **trace_args):
            eng.submit(r)
        rep = eng.run()
        return {rid: rep.tokens(rid).tolist() for rid in rep.sessions}

    assert run(True) == run(False)


def test_restore_packed_matches_pack_params(tmp_path):
    cfg, params = _setup("xlstm-350m+xnor")
    ckpt.save(str(tmp_path), 1, params)
    got, step = ckpt.restore_packed(str(tmp_path), None, cfg)
    want = lm.pack_params(cfg, params)
    assert step == 1
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_packed_passthrough_unquantized(tmp_path):
    cfg, params = _setup("qwen3-4b")
    ckpt.save(str(tmp_path), 3, params)
    got, _ = ckpt.restore_packed(str(tmp_path), 3, cfg)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_device_places_params_and_state():
    """``device=`` commits the resident params and decode state there, and
    the engine still serves the same tokens as an unplaced one."""
    cfg, params = _setup("qwen3-4b")
    dev = jax.devices()[-1]
    trace = synthetic_trace(3, cfg.vocab, seed=2)
    toks = []
    for device in (None, dev):
        eng = ServeEngine(cfg, params, slots=2, s_max=24, device=device)
        if device is not None:
            assert eng.resident_devices() == {dev}
        for r in trace:
            eng.submit(r)
        rep = eng.run()
        toks.append({r.rid: rep.tokens(r.rid).tolist() for r in trace})
    assert toks[0] == toks[1]


def _next_logits(eng, depth=None):
    """(slots, 1, padded vocab) logits of the engine's next decode step, from
    a one-off program over its decode arguments (nothing is donated), with
    the model cut to ``depth`` layers by ``lm.truncate``."""
    params, tokens, state, tables, active = eng._decode_args()[:5]
    cfg = eng.cfg
    if depth is not None:
        cfg, params, state = lm.truncate(cfg, params, state, depth)
    step = jax.jit(lambda p, t, s, tb, a: lm.paged_decode_step(
        cfg, p, t, s, tb, active=a)[0])
    return np.asarray(step(params, tokens, state, tables, active), np.float32)


def _engine_mid_decode(name):
    cfg, params = _setup(name)
    eng = ServeEngine(cfg, params, slots=2, s_max=24)
    for r in synthetic_trace(2, cfg.vocab, seed=4, prompt_lens=(4,),
                             new_tokens=(6,)):
        eng.submit(r)
    while not eng.stats.decode_steps:
        eng.step()
    return cfg, eng


@pytest.mark.parametrize("name", ["qwen3-4b", "qwen2-7b+xnor"])
def test_peek_logits_is_what_the_next_step_samples(name):
    """Logits read from the engine's decode arguments leave it untouched and
    their greedy argmax is the token the next decode step emits; the
    full-depth cut is the model."""
    cfg, eng = _engine_mid_decode(name)
    logits = _next_logits(eng)[:, 0, :cfg.vocab]
    np.testing.assert_array_equal(
        logits, _next_logits(eng, depth=cfg.n_layers)[:, 0, :cfg.vocab])
    assert _next_logits(eng, depth=1).shape == (2, 1, cfg.padded_vocab)
    before = {rid: list(s.tokens) for rid, s in eng.sessions.items()}
    eng.step()
    for rid, s in eng.sessions.items():
        assert s.tokens[len(before[rid])] == int(np.argmax(logits[s.slot]))


@pytest.mark.parametrize("name", ["recurrentgemma-2b", "xlstm-350m"])
def test_truncate_cuts_across_segments(name):
    """``lm.truncate`` on a multi-segment arch: each cut keeps the leading
    layers of every segment it reaches and drops the rest, and the cut model
    decodes."""
    cfg, eng = _engine_mid_decode(name)
    params, _, state = eng._decode_args()[:3]
    full = _next_logits(eng)
    for depth in range(1, cfg.n_layers + 1):
        cut_cfg, cut_params, cut_state = lm.truncate(cfg, params, state, depth)
        segs = cut_cfg.segments()
        assert sum(n for _, n in segs) == depth
        assert {k for k in cut_params if k.startswith("seg")} == {
            f"seg{i}_{kind}" for i, (kind, _) in enumerate(segs)}
        for (kind, n), st in zip(segs, cut_state.seg_states, strict=True):
            assert all(x.shape[0] == n for x in jax.tree.leaves(st))
        logits = _next_logits(eng, depth)
        assert logits.shape == full.shape and np.all(np.isfinite(logits))
    np.testing.assert_array_equal(_next_logits(eng, cfg.n_layers), full)
    with pytest.raises(ValueError):
        lm.truncate(cfg, params, state, cfg.n_layers + 1)
