"""The continuous-batching engine on the block-paged KV layout, float vs
packed weights, fused vs unfused decode, prefix cache on vs off
(DESIGN.md §13–§15, §18).

Serve paths over the same seeded mixed-length request trace:

  cont/paged   — the engine: shared block pool + per-slot block tables +
                 chunked prefill, float weights;
  paged/packed — the same engine with packed-weight residency (xnor
                 archs: binary filters as uint32 sign-planes, float
                 weights absent);
  paged/fused, paged/unfused — the engine under the platform's decode
                 dispatch and with ``REPRO_FUSED_DECODE=off``, each with
                 the AOT decode-step roofline audit.

A second table runs a 90%-shared-prefix trace (the system-prompt regime)
through the paged engine with the content-addressed prefix cache on vs
off (DESIGN.md §15): hit rate, fresh blocks per request, copy-on-write
copies, and TTFT side by side.

Reported per path: useful tok/s (requested tokens / wall), p50/p95
per-request latency, p50/p95 TTFT, resident param bytes, and block-pool
utilization (mean/peak blocks in use).  ``--smoke`` shrinks the trace and
asserts that fused decode keeps pace with unfused with sane roofline
columns, and that on the shared trace prefix caching yields bit-identical
tokens with lower TTFT p50 (gated in engine steps — schedule depth — since
wall time on the smoke model is dispatch overhead, not prefill compute)
and fewer fresh blocks per request — wired into CI in both kernel modes.

Run:  PYTHONPATH=src python benchmarks/serve_throughput.py [--smoke]
"""

from __future__ import annotations

import argparse
import contextlib
import os

import numpy as np


def run_engine(cfg, params, trace, slots: int, s_max: int, pack: bool,
               seed: int, n_blocks: int = 0, prefix_cache: bool = True,
               audit: bool = False):
    from repro.serve import ServeEngine

    eng = ServeEngine(cfg, params, slots=slots, s_max=s_max, seed=seed,
                      pack=pack, n_blocks=n_blocks,
                      prefix_cache=prefix_cache)
    for r in trace:
        eng.submit(r)
    report = eng.run()
    lat = report.latency_quantiles((0.5, 0.95))
    ttft = report.ttft_quantiles((0.5, 0.95))
    out = {"wall": report.wall, "tok_per_s": report.tok_per_s,
           "p50": lat[0.5], "p95": lat[0.95],
           "ttft50": ttft[0.5], "ttft95": ttft[0.95],
           "param_bytes": _tree_bytes(eng.params),
           "stats": report.stats}
    if audit:
        # AOT roofline audit of the decode step (nothing re-runs): achieved
        # vs analytic-minimum bytes + jaxpr dispatch count (DESIGN.md §18)
        out["roofline"] = eng.decode_roofline()
    return out, report


def _tree_bytes(tree) -> int:
    import jax

    return sum(np.asarray(l).nbytes for l in jax.tree.leaves(tree))


@contextlib.contextmanager
def _fused_env(mode: str | None):
    """Run inside with ``REPRO_FUSED_DECODE`` set to ``mode`` (None: unset,
    the platform's choice); the caller's setting is restored afterwards."""
    prev = os.environ.pop("REPRO_FUSED_DECODE", None)
    if mode is not None:
        os.environ["REPRO_FUSED_DECODE"] = mode
    try:
        yield
    finally:
        os.environ.pop("REPRO_FUSED_DECODE", None)
        if prev is not None:
            os.environ["REPRO_FUSED_DECODE"] = prev


def _bench(arch: str, smoke: bool, slots: int, requests: int, seed: int,
           quiet: bool = False):
    """All serve paths over one trace; returns the table rows.

    ``quiet`` suppresses the human-readable table — benchmarks/run.py
    consumes stdout as CSV, so the suite entry must not print into it.
    """
    def say(*a):
        if not quiet:
            print(*a)
    import jax
    import repro.configs as configs
    from repro.models import lm
    from repro.serve import synthetic_trace

    cfg = configs.get(arch)
    # s_max is the max context the engine *supports*; the trace's requests
    # sit well below it — the over-provisioning regime block paging exists
    # for
    plens, ntoks, s_max = (4, 8, 12), (4, 6, 10), 48
    if smoke:
        cfg = cfg.smoke()
    else:
        plens, ntoks, s_max = (16, 32, 64), (16, 32), 256
    n_req = requests or (10 if smoke else 16)
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    trace = synthetic_trace(n_req, cfg.vocab, seed=seed,
                            prompt_lens=plens, new_tokens=ntoks,
                            n_ctx_tokens=cfg.n_ctx_tokens,
                            d_model=cfg.d_model)

    say(f"# serve_throughput arch={cfg.name} slots={slots} "
          f"requests={n_req} (prompts {plens}, budgets {ntoks}, "
          f"s_max={s_max})")
    float_bytes = _tree_bytes(params)

    rows = []
    eng_p, _ = run_engine(cfg, params, trace, slots, s_max, pack=False,
                          seed=seed)
    rows.append(("cont/paged", eng_p, eng_p["param_bytes"]))
    if cfg.quant == "xnor":
        eng_pp, _ = run_engine(cfg, params, trace, slots, s_max, pack=True,
                               seed=seed)
        rows.append(("paged/packed", eng_pp, eng_pp["param_bytes"]))

    say(f"{'path':<13s} {'tok/s':>9s} {'wall s':>8s} {'p50 ms':>8s} "
          f"{'p95 ms':>8s} {'ttft50':>8s} {'ttft95':>8s} "
          f"{'resident MB':>12s} {'blk util':>9s}")
    for name, r, nbytes in rows:
        st = r["stats"]
        util = (f"{st.block_utilization:>8.0%}"
                if st.blocks_total else f"{'—':>8s}")
        say(f"{name:<13s} {r['tok_per_s']:>9.1f} {r['wall']:>8.2f} "
              f"{r['p50']*1e3:>8.0f} {r['p95']*1e3:>8.0f} "
              f"{r['ttft50']*1e3:>8.0f} {r['ttft95']*1e3:>8.0f} "
              f"{nbytes/2**20:>12.2f} {util}")
    if cfg.quant == "xnor":
        say(f"packed residency: {float_bytes/rows[-1][2]:.1f}x smaller "
              f"resident params than float")
    fused_rows = _bench_fused(cfg, params, trace, slots, s_max, seed,
                              quiet=quiet)
    return rows, fused_rows


def _bench_fused(cfg, params, trace, slots: int, s_max: int, seed: int,
                 quiet: bool = False):
    """Fused vs. unfused decode dispatch on the paged engine (§18).

    ``paged/fused`` runs the production dispatch (``REPRO_FUSED_DECODE``
    unset: the single-dispatch Pallas kernels on real TPU, their bit-exact
    unfused twin on CPU backends — so on CPU CI the two rows execute the
    same program and differ only in noise); ``paged/unfused`` forces the
    chain with ``REPRO_FUSED_DECODE=off``.
    Each row carries the AOT decode-step roofline audit: achieved bytes vs
    the analytic floor, and the jaxpr dispatch count.  Kernel-level fused
    timings (where CPU interprets the kernel) live in
    ``paged_decode_bench.py``.
    """
    from repro.roofline import report as rreport

    def say(*a):
        if not quiet:
            print(*a)

    # each row is the best of three runs, the two modes alternating: a
    # smoke row's wall is mostly its engine's compiles, which vary from run
    # to run by more than the gate's slack
    runs = {"paged/fused": [], "paged/unfused": []}
    for _ in range(3):
        for name, mode in (("paged/fused", None), ("paged/unfused", "off")):
            with _fused_env(mode):
                r, _ = run_engine(cfg, params, trace, slots, s_max,
                                  pack=False, seed=seed, audit=True)
            runs[name].append(r)
    rows = [(name, max(rs, key=lambda r: r["tok_per_s"]))
            for name, rs in runs.items()]
    say(rreport.serve_decode_header())
    for name, r in rows:
        say(rreport.serve_decode_row(f"{name} ({r['tok_per_s']:.1f} tok/s)",
                                     r["roofline"]))
    return rows


def _check_fused_smoke(rows) -> None:
    """--smoke gates for the fused/unfused decode rows."""
    fused, unfused = rows[0][1], rows[1][1]
    # on CPU CI both rows run the same program (unset, the kernel engages
    # on real TPU only), so >= holds up to scheduler noise; the slack
    # keeps the gate meaningful without flaking on equal-program jitter
    assert fused["tok_per_s"] >= 0.9 * unfused["tok_per_s"], (
        f"fused decode ({fused['tok_per_s']:.1f} tok/s) slower than "
        f"unfused ({unfused['tok_per_s']:.1f} tok/s)")
    for _, r in rows:
        rf = r["roofline"]
        assert rf["achieved_bytes"] >= rf["roofline_bytes"] > 0, (
            "roofline floor above achieved bytes — the analytic model or "
            "the cost analysis is wrong")


def _bench_prefix(arch: str, smoke: bool, slots: int, requests: int,
                  seed: int, quiet: bool = False):
    """Prefix caching on a shared-prompt trace (DESIGN.md §15).

    90% of requests open with one long common prefix — the system-prompt
    regime prefix caching exists for.  The same trace runs through two
    otherwise identical paged engines, prefix cache on vs off; the cache
    skips the shared blocks' prefill chunks and maps them copy-on-write,
    so TTFT and fresh blocks per request both drop while tokens stay
    bit-identical (sharing reuses the exact KV the donor wrote).
    """
    def say(*a):
        if not quiet:
            print(*a)
    import jax
    import repro.configs as configs
    from repro.models import lm
    from repro.serve import synthetic_trace

    cfg = configs.get(arch)
    if smoke:
        cfg = cfg.smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    bs = cfg.block_size
    plens, ntoks = ((4, 8), (4, 6)) if smoke else ((16, 32), (16, 32))
    prefix_len = 16 * bs
    n_req = requests or (10 if smoke else 16)
    s_max = prefix_len + max(plens) + max(ntoks)
    s_max += (-s_max) % bs
    # two slots: a short first admission wave, so the donor's blocks are
    # registered before most sharers arrive — the steady-state regime a
    # production prefix cache lives in
    p_slots = 2
    n_blocks = 1 + (p_slots + 2) * (s_max // bs)
    trace = synthetic_trace(n_req, cfg.vocab, seed=seed,
                            prompt_lens=plens, new_tokens=ntoks,
                            n_ctx_tokens=cfg.n_ctx_tokens,
                            d_model=cfg.d_model,
                            prefix_frac=0.9, prefix_len=prefix_len)
    say(f"# prefix caching arch={cfg.name} slots={p_slots} "
          f"requests={n_req} (90% share a {prefix_len}-token prefix, "
          f"suffixes {plens}, budgets {ntoks}, "
          f"n_blocks={n_blocks - 1}x{bs}tok)")

    rows = []
    for name, on in (("paged/prefix", True), ("paged/no-prefix", False)):
        r, rep = run_engine(cfg, params, trace, p_slots, s_max, pack=False,
                            seed=seed, n_blocks=n_blocks, prefix_cache=on)
        r["report"] = rep
        stp = rep.ttft_step_quantiles((0.5, 0.95))
        r["ttft_steps50"], r["ttft_steps95"] = stp[0.5], stp[0.95]
        rows.append((name, r))

    say(f"{'path':<15s} {'tok/s':>9s} {'ttft50':>8s} {'stp50':>6s} "
          f"{'stp95':>6s} {'hit rate':>9s} {'blk/req':>8s} {'cow':>4s} "
          f"{'evict':>6s}")
    for name, r in rows:
        st = r["stats"]
        say(f"{name:<15s} {r['tok_per_s']:>9.1f} {r['ttft50']*1e3:>8.0f} "
              f"{r['ttft_steps50']:>6.0f} {r['ttft_steps95']:>6.0f} "
              f"{st.prefix_hit_rate:>8.0%} {st.blocks_per_request:>8.2f} "
              f"{st.cow_copies:>4d} {st.prefix_evictions:>6d}")
    return rows


def _check_prefix_smoke(rows) -> None:
    """--smoke gates for the shared-trace column."""
    on, off = rows[0][1], rows[1][1]
    for rid in on["report"].sessions:
        assert np.array_equal(on["report"].tokens(rid),
                              off["report"].tokens(rid)), (
            f"rid {rid}: prefix-cached tokens diverge from uncached")
    st_on, st_off = on["stats"], off["stats"]
    assert st_on.prefix_hit_rate > 0.5, (
        f"90%-shared trace only hit {st_on.prefix_hit_rate:.0%} of "
        f"prompt tokens in the prefix cache")
    assert st_off.prefix_hits == 0
    assert st_on.blocks_per_request < st_off.blocks_per_request, (
        f"prefix caching did not reduce fresh blocks per request "
        f"({st_on.blocks_per_request:.2f} vs {st_off.blocks_per_request:.2f})")
    # TTFT gated in engine steps (schedule depth): the smoke model is so
    # small that wall TTFT is per-step dispatch/sync overhead, pure machine
    # noise; the step count is deterministic and is what wall time tracks
    # once prefill compute dominates (skipping 16 shared blocks drops p50
    # from ~42 to ~24 steps on this trace)
    assert on["ttft_steps50"] < off["ttft_steps50"], (
        f"prefix-cached TTFT p50 ({on['ttft_steps50']:.0f} engine steps) "
        f"not below uncached ({off['ttft_steps50']:.0f}) on a 90%-shared "
        f"trace")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b+xnor")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine slot count")
    ap.add_argument("--requests", type=int, default=0,
                    help="trace length (0: 16, or 10 under --smoke)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    _, fused_rows = _bench(args.arch, args.smoke, args.slots, args.requests,
                           args.seed)
    prefix_rows = _bench_prefix(args.arch, args.smoke, args.slots,
                                args.requests, args.seed)

    if args.smoke:
        _check_fused_smoke(fused_rows)
        _check_prefix_smoke(prefix_rows)
        print("smoke OK: fused decode >= unfused with sane roofline "
              "columns, and prefix caching cuts TTFT and blocks/request on "
              "a 90%-shared trace at identical tokens")
    return 0


def run():
    """benchmarks/run.py entry: (name, us_per_call, derived) CSV rows —
    us_per_call is wall microseconds per useful token on the smoke trace."""
    rows, fused_rows = _bench("qwen2-7b+xnor", True, 2, 8, 0, quiet=True)
    for name, r, nbytes in rows:
        us = 1e6 / max(r["tok_per_s"], 1e-9)
        st = r["stats"]
        util = (f" blk_util={st.block_utilization:.2f}"
                if st.blocks_total else "")
        yield (name.replace("/", "_"), us,
               f"tok/s={r['tok_per_s']:.1f} resident_mb="
               f"{nbytes/2**20:.2f}{util}")
    for name, r in fused_rows:
        rf = r["roofline"]
        pct = 100.0 * rf["roofline_bytes"] / max(rf["achieved_bytes"], 1)
        yield (name.replace("/", "_"), 1e6 / max(r["tok_per_s"], 1e-9),
               f"tok/s={r['tok_per_s']:.1f} "
               f"achieved_bytes={rf['achieved_bytes']:.0f} "
               f"roofline_bytes={rf['roofline_bytes']:.0f} "
               f"roofline_pct={pct:.1f} dispatches={rf['dispatches']}")
    for name, r in _bench_prefix("qwen2-7b+xnor", True, 2, 8, 0, quiet=True):
        st = r["stats"]
        yield (name.replace("/", "_").replace("-", "_"),
               r["ttft50"] * 1e6,
               f"ttft50_steps={r['ttft_steps50']:.0f} "
               f"hit_rate={st.prefix_hit_rate:.2f} "
               f"blk_per_req={st.blocks_per_request:.2f} "
               f"cow={st.cow_copies}")


if __name__ == "__main__":
    raise SystemExit(main())
