#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, at qwen3-4b width.

One process drives, through the entry points a user calls:

  device   a TPU is present, the kernel dispatch resolves to compiled
           Pallas and the fused decode kernels are on;
  kernels  every main-path Pallas kernel against its ``ref`` twin on the
           chip, at qwen3-4b widths: binarize, xnor_matmul, the fused XNOR
           linear (both FFN directions, M = slots), paged decode attention
           (bf16 and i8 pools), and digest / stream cipher / bulk XNOR on a
           256 MiB buffer;
  serve    packed ``qwen3-4b+xnor`` (36 layers, d 2560, ff 9728, vocab
           151936, random weights from ``--seed``) in the continuous-
           batching engine built as ``repro.launch.serve`` builds it: 8
           slots, 16 requests, fused kernels in the decode program, every
           fused kernel call of one full-depth decode step against its
           ``ref`` oracle on the same inputs, and that step's logits with
           the fused kernels on against off;
  ckpt     the paper's application on the served packed tree: device
           digests through ``CimEngine`` against host digests, an encrypted
           save -> check -> restore round trip, and a one-leaf delta save.

``--chips 4`` runs only the four-chip phase instead: a 4-replica ``Router``
(one replica per chip, with a kill drill that forces migrations) against
one ``ServeEngine`` in the same process, and a ``ShardedCimEngine`` digest
of the packed tree against the host digest.

Each phase prints ``PHASE <name> OK`` with its checks and its wall seconds;
those are set-up and smoke timings, not measurements.  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  With
no TPU, outside the repository, or when any check fails, the script exits
non-zero and prints no result.

  python chip_smoke.py [--chips 4] [--seed 0]
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-4b+xnor"
SLOTS = 8
N_REQUESTS = 16
PROMPT_LENS = (64, 128, 256, 512)
NEW_TOKENS = 32
PREFIX_LEN = 256                    # shared leading tokens ...
PREFIX_FRAC = 0.5                   # ... on about half of the requests
KILL_AT = 6                         # router step of the four-chip kill drill
STREAM_WORDS = 64 * 2**20           # 256 MiB of uint32 for the stream kernels

# Tolerances.  Integer and bit outputs are compared exactly everywhere.
# The ref path averages |x| in the input dtype (bf16) while the pack kernel
# averages in f32: one bf16 ulp apart at most.
ALPHA_RTOL = 2.0**-8
# Paged attention: both paths compute in f32 and round the output to bf16;
# the online softmax associates differently, so they may differ by about
# one bf16 ulp of the output.
ATTN_RTOL = ATTN_ATOL = 2.0**-6
# The served model's next decode step (its own weights and cached state),
# on the slots it serves, errors as max |difference| over max |reference|.
# Each fused kernel call at full depth is held to its ``ref`` oracle on that
# layer's own inputs: attention to ATTN_ATOL, the fused linear to one bf16
# ulp of alpha (its ±1 dots are exact; alpha is the one value the two
# round apart).
LINEAR_RTOL = 2.0**-7
# End to end, the fused step's logits against the unfused chain's
# (``fused_decode="off"``) are read at each depth cut and held at the first
# layer only.  A binarized layer turns a rounding-level difference in an
# input near zero into a flipped sign bit, which moves its outputs by about
# 2/sqrt(K) of their scale, and random binary layers compound flipped bits
# with depth: on a v5e two routes with no kernel at all (the oracles at
# default against highest matmul precision) part as far at 36 layers as
# the kernels do.  One layer: 0.040-0.052 of the largest logit over eight
# readings on a v5e.
LOGIT_ATOL = 0.1
LOGIT_DEPTHS = (1, 2, 4, 9, 18, 36)


def _say(msg: str) -> None:
    print(msg, flush=True)


def _phase(name: str, fn, *args):
    t0 = time.monotonic()
    result, detail = fn(*args)
    _say(f"PHASE {name} OK wall_s={time.monotonic() - t0:.1f} {detail}")
    return result


def _exact(what: str, got, want) -> None:
    import jax.numpy as jnp
    if got.shape != want.shape or not bool(jnp.array_equal(got, want)):
        raise AssertionError(f"{what}: pallas differs from ref")


def _close(what: str, got, want, rtol: float, atol: float) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                               err_msg=what)
    return err


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_check(cfg):
    import jax
    from repro.kernels import ops
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform})")
    impl, fused = ops._resolve("auto"), ops.fused_mode(cfg.fused_decode)
    if impl != "pallas" or fused != "kernel":
        raise SystemExit(f"chip_smoke: kernel impl {impl!r}, fused decode "
                         f"{fused!r}; the smoke run needs pallas / kernel")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    return info, (f"kind={dev.device_kind!r} count={info['count']} "
                  f"impl={impl} fused={fused}")


def kernel_parity(cfg, seed: int, stream_words: int = STREAM_WORDS):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import bitpack, xnor_layers
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    d, ff = cfg.d_model, cfg.d_ff
    notes = []

    for shape in ((SLOTS, d), (ff, d), (d, ff)):
        x = jax.random.normal(next(keys), shape, jnp.bfloat16)
        p_k, a_k = ops.binarize(x, impl="pallas")
        p_r, a_r = ops.binarize(x, impl="ref")
        _exact(f"binarize {shape} planes", p_k, p_r)
        _close(f"binarize {shape} alpha", a_k, a_r, ALPHA_RTOL, 0.0)
    notes.append("binarize=bitwise")

    for k, n in ((d, ff), (ff, d)):
        kw = bitpack.packed_width(k)
        pa = jax.random.bits(next(keys), (SLOTS, kw), jnp.uint32)
        pb = jax.random.bits(next(keys), (n, kw), jnp.uint32)
        _exact(f"xnor_matmul K{k} N{n}",
               ops.xnor_matmul(pa, pb, k, impl="pallas"),
               ops.xnor_matmul(pa, pb, k, impl="ref"))
    notes.append("xnor_matmul=bitwise")

    errs = []
    for k, n in ((d, ff), (ff, d)):
        w = jax.random.normal(next(keys), (n, k), jnp.bfloat16)
        pb, beta = xnor_layers.pack_weights(w)
        x = jax.random.normal(next(keys), (SLOTS, k), jnp.bfloat16)
        pm1 = jnp.where(x >= 0, 1.0, -1.0).astype(jnp.bfloat16)
        # ±1 activations make alpha exactly 1: the output is the exact
        # integer dot times beta
        _exact(f"xnor_linear_fused K{k} N{n} ±1",
               ops.xnor_linear_fused(pm1, pb, beta, k, impl="pallas"),
               ops.xnor_linear_fused(pm1, pb, beta, k, impl="ref"))
        got = ops.xnor_linear_fused(x, pb, beta, k, impl="pallas")
        want = ops.xnor_linear_fused(x, pb, beta, k, impl="ref")
        scale = float(jnp.max(jnp.abs(want)))
        errs.append(_close(f"xnor_linear_fused K{k} N{n}", got, want,
                           ALPHA_RTOL, ALPHA_RTOL * scale) / scale)
    notes.append(f"xnor_linear_fused=±1 exact, rel_err={max(errs):.2e}")

    kv, g, dh, bs = cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head, cfg.block_size
    w_blocks = -(-(PREFIX_LEN + max(PROMPT_LENS) + NEW_TOKENS) // bs)
    n_blocks = 1 + SLOTS * w_blocks
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(SLOTS * w_blocks)
                        .reshape(SLOTS, w_blocks) + 1, jnp.int32)
    pos = jnp.asarray(rng.integers(0, w_blocks * bs, SLOTS), jnp.int32)
    q = jax.random.normal(next(keys), (SLOTS, kv, g, dh), jnp.bfloat16)
    for pool in ("bf16", "i8"):
        shape = (n_blocks, kv, bs, dh)
        if pool == "i8":
            ck = jax.random.randint(next(keys), shape, -127, 127, jnp.int8)
            cv = jax.random.randint(next(keys), shape, -127, 127, jnp.int8)
            scale, out_scale = dh**-0.5 / cfg.kv_i8_scale, 1 / cfg.kv_i8_scale
        else:
            ck = jax.random.normal(next(keys), shape, jnp.bfloat16)
            cv = jax.random.normal(next(keys), shape, jnp.bfloat16)
            scale, out_scale = dh**-0.5, 1.0
        for window in (0, cfg.local_window):
            args = (q, ck, cv, table, pos)
            kw = dict(window=window, scale=scale, out_scale=out_scale)
            _close(f"paged_decode {pool} window={window}",
                   ops.paged_decode(*args, impl="pallas", **kw),
                   ref.paged_decode(*args, **kw), ATTN_RTOL, ATTN_ATOL)
    notes.append("paged_decode=allclose(bf16,i8)")

    a = jax.random.bits(next(keys), (stream_words,), jnp.uint32)
    b = jax.random.bits(next(keys), (stream_words,), jnp.uint32)
    key = jnp.asarray([0x1234, 0x5678], jnp.uint32)
    _exact("digest", ops.digest(a, impl="pallas"), ops.digest(a, impl="ref"))
    _exact("stream_cipher",
           ops.stream_cipher(a, key, counter=7, impl="pallas"),
           ops.stream_cipher(a, key, counter=7, impl="ref"))
    _exact("bulk_op xnor", ops.bulk_op(a, b, "xnor", impl="pallas"),
           ops.bulk_op(a, b, "xnor", impl="ref"))
    notes.append(f"digest/cipher/bulk=bitwise on {stream_words * 4 >> 20} MiB")
    return None, " ".join(notes)


def _trace(cfg, seed: int):
    from repro.serve import synthetic_trace
    trace = synthetic_trace(N_REQUESTS, cfg.vocab, seed=seed,
                            prompt_lens=PROMPT_LENS, new_tokens=(NEW_TOKENS,),
                            prefix_frac=PREFIX_FRAC, prefix_len=PREFIX_LEN)
    return trace, PREFIX_LEN + max(PROMPT_LENS) + NEW_TOKENS


def _init_params(cfg, seed: int):
    """Random float weights from ``seed``: ``lm.init_params`` as one
    compiled program (the values differ from its eager form's)."""
    import jax
    from repro.models import lm
    init_key, _ = jax.random.split(jax.random.PRNGKey(seed))
    init = jax.jit(lm.init_params, static_argnums=0)
    return jax.block_until_ready(init(cfg, init_key))


def _bytes_in_use() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return f"{stats.get('bytes_in_use', 0) / 2**30:.2f}GiB"


def _check_tokens(cfg, report, trace) -> None:
    for r in trace:
        s = report.sessions[r.rid]
        toks = report.tokens(r.rid)
        if not s.done or len(toks) != r.max_new_tokens:
            raise AssertionError(f"request {r.rid} incomplete: "
                                 f"{len(toks)}/{r.max_new_tokens} tokens")
        if toks.min() < 0 or toks.max() >= cfg.vocab:
            raise AssertionError(f"request {r.rid}: token outside vocab")


@contextlib.contextmanager
def _fused(mode: str):
    """Trace inside with the fused decode ``mode`` ("on" or "off")."""
    prev = os.environ.get("REPRO_FUSED_DECODE")
    os.environ["REPRO_FUSED_DECODE"] = mode
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_FUSED_DECODE", None)
        else:
            os.environ["REPRO_FUSED_DECODE"] = prev


@contextlib.contextmanager
def _witnessed(errs: dict):
    """Trace inside with each fused kernel call also run through its ``ref``
    oracle on the same inputs.  Every run of the traced program appends each
    call's max |kernel - oracle| / max |oracle| to ``errs["attn"]`` or
    ``errs["linear"]``; the program goes on with the kernel's output."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    real = {"attn": ops.paged_decode, "linear": ops.xnor_linear_fused}

    def witness(name):
        def call(*args, **kw):
            got = real[name](*args, **kw)
            want = real[name](*args, **{**kw, "impl": "ref"})
            got32, want32 = got.astype(jnp.float32), want.astype(jnp.float32)
            err = (jnp.max(jnp.abs(got32 - want32))
                   / jnp.max(jnp.abs(want32)))
            jax.debug.callback(lambda e: errs[name].append(float(e)), err)
            return got
        return call

    ops.paged_decode, ops.xnor_linear_fused = witness("attn"), witness("linear")
    try:
        yield
    finally:
        ops.paged_decode, ops.xnor_linear_fused = real["attn"], real["linear"]


def _decode_step(eng, depth: int):
    """The engine's next decode step, cut to its first ``depth`` layers, as
    one compiled program over its live arguments (nothing is donated, so
    the engine does not advance), traced under the settings in force."""
    import jax
    from repro.models import lm
    params, tokens, state, tables, active = eng._decode_args()[:5]
    cfg, params, state = lm.truncate(eng.cfg, params, state, depth)
    step = jax.jit(lambda p, t, s, tb, a: lm.paged_decode_step(
        cfg, p, t, s, tb, active=a)[0])
    args = (params, tokens, state, tables, active)
    return step.lower(*args).compile(), args


def _gap(a, b) -> tuple[float, float]:
    """max |a - b| over max |b|, and the share of rows whose argmax agrees."""
    import numpy as np
    err = float(np.max(np.abs(a - b))) / float(np.max(np.abs(b)))
    return err, float(np.mean(np.argmax(a, -1) == np.argmax(b, -1)))


def _check_decode_numerics(eng) -> tuple[str, int]:
    """Holds every fused kernel call of the next full-depth decode step to
    its oracle on the layer's own inputs, and the step's logits, fused
    against unfused, at the first layer; reads the latter at each of
    LOGIT_DEPTHS.  Returns the detail and the count of Pallas kernels in
    the full-depth fused step."""
    import jax
    import numpy as np
    n = eng.cfg.n_layers
    errs = {"attn": [], "linear": []}
    with _fused("on"), _witnessed(errs):
        prog, args = _decode_step(eng, n)
    jax.block_until_ready(prog(*args))
    jax.effects_barrier()
    parts = []
    for name, tol in (("attn", ATTN_ATOL), ("linear", LINEAR_RTOL)):
        if not errs[name]:
            raise AssertionError(f"the decode step made no fused {name} call")
        worst = max(errs[name])
        if not worst <= tol:
            raise AssertionError(f"fused {name} kernel vs oracle on served "
                                 f"inputs: {worst:.3e} of the largest > {tol}")
        parts.append(f"{name}_calls={len(errs[name])} {name}_vs_oracle="
                     f"{worst:.2e}(tol {tol:.2e})")

    depths = sorted({d for d in LOGIT_DEPTHS if d < n} | {n})
    row, n_kernels = [], 0
    for depth in depths:
        logits = {}
        for mode in ("on", "off"):
            with _fused(mode):
                prog, args = _decode_step(eng, depth)
            if mode == "on" and depth == n:
                n_kernels = prog.as_text().count("tpu_custom_call")
            out = np.asarray(prog(*args), np.float32)[eng._active, 0,
                                                      :eng.cfg.vocab]
            if not np.all(np.isfinite(out)):
                raise AssertionError(f"non-finite logits, fused {mode}, "
                                     f"depth {depth}")
            logits[mode] = out
        err, top1 = _gap(logits["on"], logits["off"])
        _say(f"  logits fused-vs-unfused depth={depth} "
             f"max_abs_err/max_abs={err:.3e} top1_agree={top1:.3f}")
        row.append(f"d{depth}:{err:.2e}/{top1:.2f}")
        if depth == 1 and not err <= LOGIT_ATOL:
            raise AssertionError(f"decode logits fused vs unfused at depth 1:"
                                 f" {err:.3e} of the largest > {LOGIT_ATOL}")
    parts.append(f"logits_fused_vs_unfused(depth:rel_err/top1)="
                 f"{','.join(row)} d1_tol={LOGIT_ATOL}")
    return " ".join(parts), n_kernels


def serve(cfg, seed: int):
    import jax
    from repro.serve import ServeEngine

    t0 = time.monotonic()
    params = _init_params(cfg, seed)
    t_init = time.monotonic() - t0
    trace, s_max = _trace(cfg, seed)
    t0 = time.monotonic()
    eng = ServeEngine(cfg, params, slots=SLOTS, s_max=s_max, seed=seed)
    del params                      # the float tree: only packed leaves stay
    jax.block_until_ready(eng.params)
    t_pack = time.monotonic() - t0
    _say(f"  setup init_s={t_init:.1f} pack_s={t_pack:.1f} "
         f"resident_after_pack={_bytes_in_use()}")
    for r in trace:
        eng.submit(r)
    compared, n_kernels, setup, t_compare = "", 0, {}, 0.0
    t_run = time.monotonic()
    busy = True
    while busy:
        decoded, t0 = eng.stats.decode_steps, time.monotonic()
        busy = eng.step()
        # the first step compiles the prefill-chunk program, the first
        # decode step the decode program
        if not setup:
            setup["first_step_s"] = time.monotonic() - t0
        if not decoded and eng.stats.decode_steps:
            setup["first_decode_step_s"] = time.monotonic() - t0
        # compared once, when the next decode step serves every slot
        if not compared and eng.stats.decode_steps and eng._active.all():
            t0 = time.monotonic()
            compared, n_kernels = _check_decode_numerics(eng)
            t_compare = time.monotonic() - t0
    wall = time.monotonic() - t_run - t_compare
    _say("  setup " + " ".join(f"{k}={v:.1f}" for k, v in setup.items())
         + f" logit_compare_s={t_compare:.1f}")
    report = eng.run()              # already drained: collects the report
    _check_tokens(cfg, report, trace)
    if not compared:
        raise AssertionError("no decode step served every slot")
    if not n_kernels:
        raise AssertionError("decode program has no tpu_custom_call")
    st = report.stats
    shared = max(collections.Counter(
        tuple(r.prompt[:PREFIX_LEN]) for r in trace).values())
    detail = (f"arch={cfg.name} requests={len(report.sessions)}/{len(trace)} "
              f"complete tokens={report.generated} prefix_requests={shared} "
              f"prefix_hits={st.prefix_hits} decode_steps={st.decode_steps} "
              f"prefill_chunks={st.prefill_chunks} "
              f"decode_tpu_custom_calls={n_kernels} {compared} "
              f"run_wall_s={wall:.1f}")
    return (eng, trace, s_max), detail


def ckpt_roundtrip(eng, seed: int):
    import jax
    import numpy as np
    from repro.checkpoint import ckpt
    from repro.core import verify
    from repro.core.engine import CimEngine
    from repro.core.incremental import DigestCache

    tree = eng.params
    cim = CimEngine()
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    host = {verify.leaf_key(p): np.asarray(x) for p, x in flat}
    dev = jax.tree_util.tree_flatten_with_path(
        verify.tree_digest(tree, engine=cim))[0]
    for p, dig in dev:
        k = verify.leaf_key(p)
        if not np.array_equal(np.asarray(dig), verify.np_digest(host[k])):
            raise AssertionError(f"device digest != host digest at {k}")
    nbytes = sum(a.nbytes for a in host.values())
    root_key = f"chip-smoke-{seed}"
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        cache = DigestCache(engine=cim)
        t0 = time.monotonic()
        ckpt.save(d, 1, tree, root_key=root_key, engine=cim)
        cache.digests(tree)
        cache.mark_saved()
        t_save = time.monotonic() - t0
        ok, bad = ckpt.check(d, 1, root_key=root_key, engine=cim)
        if not ok:
            raise AssertionError(f"check failed on {bad}")
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            tree)
        t0 = time.monotonic()
        restored, _ = ckpt.restore(d, 1, like, root_key=root_key, engine=cim)
        t_restore = time.monotonic() - t0
        for p, x in jax.tree_util.tree_flatten_with_path(restored)[0]:
            k = verify.leaf_key(p)
            if np.asarray(x).tobytes() != host[k].tobytes():
                raise AssertionError(f"restored leaf {k} differs")
        del restored
        touched = "embed/final_norm"
        tree2 = dict(tree)
        tree2["embed"] = dict(tree["embed"])
        tree2["embed"]["final_norm"] = tree["embed"]["final_norm"].at[0].add(1)
        man = ckpt.save_delta(d, 2, tree2, root_key=root_key, cache=cache)
        stored = [k for k, m in man["leaves"].items() if m["stored_in"] == 2]
        if stored != [touched]:
            raise AssertionError(f"delta stored {stored}, want [{touched}]")
        ok, bad = ckpt.check(d, 2, root_key=root_key, engine=cim)
        if not ok:
            raise AssertionError(f"delta check failed on {bad}")
    detail = (f"leaves={len(host)} bytes={nbytes} device_digest==host_digest "
              f"encrypted save/check/restore byte-identical "
              f"delta_stored={stored} save_s={t_save:.1f} "
              f"restore_s={t_restore:.1f}")
    return None, detail


def four_chips(cfg, seed: int):
    import jax
    import numpy as np
    from repro.core import verify
    from repro.core.engine import ShardedCimEngine
    from repro.launch.mesh import make_engine_mesh
    from repro.models import lm
    from repro.serve import Router, ServeEngine

    n = len(jax.devices())
    if n != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, JAX found {n}")
    packed = lm.pack_params(cfg, _init_params(cfg, seed))
    trace, s_max = _trace(cfg, seed)

    single = ServeEngine(cfg, packed, slots=SLOTS, s_max=s_max, seed=seed)
    for r in trace:
        single.submit(r)
    want = single.run()
    _check_tokens(cfg, want, trace)
    del single

    def check_placement():
        for h in router.replicas:
            devs = h.engine.resident_devices()
            if devs != {h.device}:
                raise AssertionError(f"replica {h.index} resident on {devs}, "
                                     f"not {h.device}")
        if len({h.device for h in router.replicas}) != 4:
            raise AssertionError("replicas share a device")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_mig_") as d:
        router = Router(cfg, packed, 4, slots=SLOTS, s_max=s_max,
                        ckpt_dir=d, seed=seed)
        check_placement()
        for r in trace:
            router.submit(r)
        got = router.run(kill_at=KILL_AT)
        check_placement()
    _check_tokens(cfg, got, trace)
    diverged = [r.rid for r in trace
                if not np.array_equal(got.tokens(r.rid), want.tokens(r.rid))]
    if diverged:
        raise AssertionError(f"router tokens differ from one engine: "
                             f"{diverged}")
    if not got.migrations:
        raise AssertionError("the kill drill migrated nothing")
    if got.scrub_corruptions:
        raise AssertionError(f"{got.scrub_corruptions} scrubber corruptions")

    sharded = verify.tree_digest(packed,
                                 engine=ShardedCimEngine(make_engine_mesh(4)))
    for (p, dig), (_, leaf) in zip(
            jax.tree_util.tree_flatten_with_path(sharded)[0],
            jax.tree_util.tree_flatten_with_path(packed)[0]):
        if not np.array_equal(np.asarray(dig),
                              verify.np_digest(np.asarray(leaf))):
            raise AssertionError(f"sharded digest != host digest at "
                                 f"{verify.leaf_key(p)}")
    detail = (f"replicas=4 devices={sorted(str(h.device) for h in router.replicas)} "
              f"tokens==single_engine requests={len(trace)} "
              f"migrations={len(got.migrations)} killed={got.killed} "
              f"scrub_passes={got.scrub_passes} corruptions=0 "
              f"sharded_digest==host_digest")
    return None, detail


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip router/sharded phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch import compile_cache
    except ImportError:
        print("chip_smoke: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    _say(f"compile cache: {compile_cache.enable()}")
    import repro.configs as configs
    cfg = configs.get(ARCH)

    info = _phase("device", device_check, cfg)
    if args.chips == 4:
        _phase("four_chips", four_chips, cfg, args.seed)
    else:
        _phase("kernels", kernel_parity, cfg, args.seed)
        eng, _, _ = _phase("serve", serve, cfg, args.seed)
        _phase("ckpt", ckpt_roundtrip, eng, args.seed)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
