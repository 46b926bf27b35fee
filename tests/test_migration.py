"""Acceptance suite for live session migration (DESIGN.md §17).

The contract under test: a session exported mid-flight from one engine —
through an **encrypted checkpoint** on disk, restored on a different
engine against a spec that engine derives from nothing but the request —
finishes with tokens **bit-identical** to a run that never moved.  This
must hold mid-decode and mid-chunked-prefill, for float and packed
residency, across arch families with genuinely different paged state:
full-attention KV, sliding-window rings, recurrent carries, and enc-dec
cross-attention ctx-KV.  Sampling runs at temperature > 0 throughout, so
identity leans on the engine's (rid, token index) seed contract rather
than greedy argmax luck.
"""

import numpy as np
import pytest

import repro.configs as configs
from repro.checkpoint import ckpt
from repro.core.incremental import DigestCache
from repro.models import lm
from repro.serve import Request, ServeEngine, synthetic_trace

# one family each: dense GQA attention, recurrent hybrid (carries +
# window rings), enc-dec audio (cross-attn ctx-KV), xLSTM (pure
# recurrent matrix memory)
ARCHS = ["qwen3-4b", "recurrentgemma-2b", "whisper-tiny", "xlstm-350m"]


def _setup(arch: str):
    import jax

    cfg = configs.get(arch).smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk(cfg, params, **kw):
    base = dict(slots=2, s_max=48, seed=0, pack=False, temperature=0.8)
    base.update(kw)
    return ServeEngine(cfg, params, **base)


def _trace(cfg, n, *, plens=(6, 11, 17), ntoks=(5, 9), seed=3):
    return synthetic_trace(n, cfg.vocab, seed=seed, prompt_lens=plens,
                           new_tokens=ntoks, n_ctx_tokens=cfg.n_ctx_tokens,
                           d_model=cfg.d_model)


def _baseline(cfg, params, trace, **kw):
    eng = _mk(cfg, params, **kw)
    for r in trace:
        eng.submit(r)
    rep = eng.run()
    return {r.rid: list(rep.tokens(r.rid)) for r in trace}


def _ship(src, dst, rid, req, d, *, step=1, cache=None, key="mig-test"):
    """export -> encrypted (delta) checkpoint -> restore-against-spec ->
    import -> release: the exact hop the router's migrate() performs."""
    wire = src.export_session(rid)
    if step == 1:
        ckpt.save(d, step, wire, root_key=key)
        if cache is not None:
            cache.digests(wire)
            cache.mark_saved()
    else:
        ckpt.save_delta(d, step, wire, root_key=key, cache=cache)
    like = dst.export_spec(req)
    restored, _ = ckpt.restore(d, step, like, root_key=key)
    dst.import_session(req, restored)
    src.release_migrated(rid)


def _mid_decode_session(eng, max_steps=12):
    """Step until some admitted session has emitted tokens but is neither
    finished nor still prefilling — the mid-decode capture point."""
    for _ in range(max_steps):
        eng.step()
        for slot, sess in eng.pool.active.items():
            if sess.tokens and not sess.done and slot not in eng._prefilling:
                return sess
    raise AssertionError("trace never produced a mid-decode session")


def _finish_and_collect(trace, engines, where):
    reps = {k: e.run() for k, e in engines.items()}
    return {r.rid: list(reps[where(r.rid)].tokens(r.rid)) for r in trace}


@pytest.mark.parametrize("arch", ARCHS)
def test_migration_identity_mid_decode(arch, tmp_path):
    cfg, params = _setup(arch)
    trace = _trace(cfg, 4)
    want = _baseline(cfg, params, trace)

    a = _mk(cfg, params)
    for r in trace:
        a.submit(r)
    sess = _mid_decode_session(a)
    rid = sess.request.rid
    b = _mk(cfg, params)
    _ship(a, b, rid, sess.request, str(tmp_path / "wire"))

    assert rid not in a.sessions          # source forgot the session...
    assert b.sessions[rid].tokens == sess.tokens   # ...dst resumed it
    got = _finish_and_collect(trace, {"a": a, "b": b},
                              lambda r: "b" if r == rid else "a")
    assert got == want, f"{arch}: migration mid-decode changed tokens"


@pytest.mark.parametrize("arch", ARCHS)
def test_migration_identity_mid_chunked_prefill(arch, tmp_path):
    cfg, params = _setup(arch)
    # prompts span 3 chunks (prefill_chunk is 8 in smoke configs): after
    # one engine step the head session is mid-prefill, chunk cursor > 0
    trace = _trace(cfg, 3, plens=(20, 23), ntoks=(6, 8))
    want = _baseline(cfg, params, trace)

    a = _mk(cfg, params)
    for r in trace:
        a.submit(r)
    a.step()
    assert a._prefilling, "prompt did not span multiple prefill chunks"
    slot = next(iter(a._prefilling))
    sess = a.pool.active[slot]
    rid = sess.request.rid
    b = _mk(cfg, params)
    _ship(a, b, rid, sess.request, str(tmp_path / "wire"))

    # the destination picks the prefill up at the exact chunk boundary
    assert b._prefilling, "import dropped the chunked-prefill progress"
    got = _finish_and_collect(trace, {"a": a, "b": b},
                              lambda r: "b" if r == rid else "a")
    assert got == want, f"{arch}: migration mid-prefill changed tokens"


def test_migration_identity_packed_residency(tmp_path):
    """On a +xnor arch with pack=True the resident weights are uint32
    sign-planes and the migrated KV was written by the popcount GEMM —
    identity must survive packed residency too."""
    cfg, params = _setup("qwen2-7b+xnor")
    assert cfg.quant == "xnor"
    trace = _trace(cfg, 4)
    want = _baseline(cfg, params, trace, pack=True)

    a = _mk(cfg, params, pack=True)
    for r in trace:
        a.submit(r)
    sess = _mid_decode_session(a)
    rid = sess.request.rid
    b = _mk(cfg, params, pack=True)
    _ship(a, b, rid, sess.request, str(tmp_path / "wire"))
    got = _finish_and_collect(trace, {"a": a, "b": b},
                              lambda r: "b" if r == rid else "a")
    assert got == want, "packed-residency migration changed tokens"


def test_double_migration_delta_chain(tmp_path):
    """A -> B -> A: hop 2 rides ckpt.save_delta against the per-rid
    DigestCache the first hop primed, so unchanged leaves (prompt, any
    still-identical KV) resolve through the chain instead of being
    re-stored — and the bounced session still finishes bit-identical."""
    cfg, params = _setup("qwen3-4b")
    trace = _trace(cfg, 3, plens=(6, 10), ntoks=(14, 18))
    want = _baseline(cfg, params, trace)

    a = _mk(cfg, params)
    for r in trace:
        a.submit(r)
    sess = _mid_decode_session(a)
    rid = sess.request.rid
    b = _mk(cfg, params)
    d = str(tmp_path / "wire")
    cache = DigestCache()
    _ship(a, b, rid, sess.request, d, step=1, cache=cache)
    for _ in range(2):                    # B decodes a couple of tokens
        b.step()
    assert not b.sessions[rid].done, "budget too small to bounce back"
    _ship(b, a, rid, sess.request, d, step=2, cache=cache)

    # the delta hop stored strictly less than the full first hop
    npz = {p.name: p.stat().st_size for p in (tmp_path / "wire").iterdir()
           if p.suffix == ".npz"}
    assert npz["ckpt_00000002.npz"] < npz["ckpt_00000001.npz"], npz

    got = _finish_and_collect(trace, {"a": a, "b": b}, lambda r: "a")
    assert got == want, "A->B->A double migration changed tokens"


def test_migration_wire_is_encrypted(tmp_path):
    """The wire is unreadable without the root key: restoring with a
    wrong key must fail, not silently produce a corrupt session."""
    cfg, params = _setup("qwen3-4b")
    trace = _trace(cfg, 2)
    a = _mk(cfg, params)
    for r in trace:
        a.submit(r)
    sess = _mid_decode_session(a)
    rid = sess.request.rid
    wire = a.export_session(rid)
    d = str(tmp_path / "wire")
    ckpt.save(d, 1, wire, root_key="right-key")

    b = _mk(cfg, params)
    like = b.export_spec(sess.request)
    with pytest.raises(Exception):
        ckpt.restore(d, 1, like, root_key="wrong-key")
    # prompt tokens must not appear in the clear anywhere on disk
    blob = b"".join(p.read_bytes() for p in (tmp_path / "wire").iterdir())
    assert sess.request.prompt.astype(np.int32).tobytes() not in blob


def test_release_migrated_returns_capacity(tmp_path):
    """After the hop the source engine's slot and blocks are genuinely
    free again: a new request admits into the vacated capacity."""
    cfg, params = _setup("qwen3-4b")
    trace = _trace(cfg, 2)
    a = _mk(cfg, params, slots=2)
    for r in trace:
        a.submit(r)
    sess = _mid_decode_session(a)
    rid = sess.request.rid
    in_use_before = a.blocks.in_use

    b = _mk(cfg, params)
    _ship(a, b, rid, sess.request, str(tmp_path / "wire"))
    assert a.blocks.in_use < in_use_before
    assert a.pool.free_slots, "migration did not free the source slot"

    late = Request(rid=99, prompt=np.arange(5) % cfg.vocab,
                   max_new_tokens=4)
    a.submit(late)
    rep_a, rep_b = a.run(), b.run()
    assert rep_a.sessions[99].done and rep_b.sessions[rid].done
