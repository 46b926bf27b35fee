"""Serving entry point: the continuous-batching engine over a synthetic
mixed-length request trace (container scale uses --smoke reduced configs).

Requests draw prompt length and token budget independently, so slots free
at staggered times and admission (prefill interleaved with decode) runs
throughout.  Reports aggregate throughput and per-request latency
quantiles.

``--prefix-len N`` gives a ``--prefix-frac`` fraction of the trace a
shared N-token leading prefix (the system-prompt regime); the paged
engine's content-addressed prefix cache (DESIGN.md §15) then skips the
shared blocks' prefill and reports hit rate + fresh blocks per request.
``--no-prefix-cache`` A/Bs it off.

``--workload`` picks what to serve (DESIGN.md §16).  ``lm`` (default) is
the synthetic chat trace above; ``transcribe`` streams synthetic audio
through :class:`TranscriptionService` on an enc-dec arch; ``classify``
batches stripe images through :class:`ClassifierService` (defaults to the
paper's xnor-cnn arch, trained in-process).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b+xnor \
      --smoke --slots 4 --requests 16 --new-tokens 16 --prefix-len 64
  PYTHONPATH=src python -m repro.launch.serve --arch whisper-tiny \
      --smoke --workload transcribe --streams 3 --windows 2
  PYTHONPATH=src python -m repro.launch.serve --arch xnor-cnn \
      --workload classify --requests 32
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

import repro.configs as configs
from repro.launch import compile_cache
from repro.models import lm
from repro.serve import (ClassifierService, ServeEngine,
                         TranscriptionService, synthetic_audio_trace,
                         synthetic_trace)


def _run_transcribe(cfg, params, args) -> int:
    """Streaming transcription over synthetic audio (DESIGN.md §16)."""
    svc = TranscriptionService(
        cfg, params, slots=args.slots,
        s_max=args.s_max or 32,
        tokens_per_window=max(2, args.new_tokens),
        temperature=args.temperature, seed=args.seed,
        pack=not args.no_pack)
    streams = synthetic_audio_trace(
        args.streams, args.windows, n_ctx_tokens=cfg.n_ctx_tokens,
        d_model=cfg.d_model, seed=args.seed)
    t0 = time.time()
    transcripts = svc.transcribe(streams)
    dt = time.time() - t0
    total = sum(len(t) for t in transcripts.values())
    print(f"arch={cfg.name} workload=transcribe streams={args.streams} "
          f"windows={args.windows} slots={args.slots}")
    print(f"  {total} transcript tokens in {dt:.2f}s "
          f"({total / max(dt, 1e-9):.1f} tok/s; "
          f"{svc.stats.prefills} window sessions, "
          f"{svc.stats.decode_steps} decode steps)")
    for sid in sorted(transcripts):
        print(f"  stream {sid}: {transcripts[sid][:12]}"
              f"{'...' if len(transcripts[sid]) > 12 else ''}")
    return 0


def _run_classify(cfg, args) -> int:
    """Batched XNOR-CNN classification (DESIGN.md §16, paper Fig. 6)."""
    from repro.models import bcnn

    svc = ClassifierService(cfg=cfg, slots=args.slots,
                            pack=not args.no_pack, seed=args.seed)
    n = max(args.requests, 1)
    imgs, y = bcnn.synthetic_images(jax.random.PRNGKey(args.seed + 1), n)
    t0 = time.time()
    pred = svc.classify(np.asarray(imgs))
    dt = time.time() - t0
    acc = float(np.mean(pred == np.asarray(y)))
    print(f"arch={cfg.name} workload=classify images={n} "
          f"slots={args.slots} packed={not args.no_pack}")
    print(f"  train acc {svc.train_acc:.2f}; serve acc {acc:.2f}; "
          f"{n / max(dt, 1e-9):.1f} images/s "
          f"({svc.stats.prefills} one-shot sessions, "
          f"{svc.stats.decode_steps} decode steps)")
    return 0


def _run_replicated(cfg, params, trace, s_max, args) -> int:
    """The replicated tier (DESIGN.md §17): N engine replicas behind the
    least-loaded router, optional kill-a-replica drill mid-run, encrypted
    migration checkpoints, background integrity scrubbing."""
    import contextlib
    import tempfile

    from repro.serve import Router

    with contextlib.ExitStack() as stack:
        ckpt_dir = args.ckpt_dir or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="serve_mig_"))
        router = Router(cfg, params, args.replicas, slots=args.slots,
                        s_max=s_max, ckpt_dir=ckpt_dir,
                        epoch_steps=args.epoch_steps, eos_id=args.eos_id,
                        temperature=args.temperature, seed=args.seed,
                        pack=not args.no_pack, block_size=args.block_size,
                        prefill_chunk=args.prefill_chunk,
                        n_blocks=args.n_blocks,
                        prefix_cache=not args.no_prefix_cache)
        for r in trace:
            router.submit(r)
        rep = router.run(kill_at=args.kill_at or None)
    sr = rep.serve_report()
    lat = sr.latency_quantiles((0.5, 0.95))
    ttft = sr.ttft_quantiles((0.5, 0.95))
    print(f"arch={cfg.name} replicas={args.replicas} "
          f"slots={args.slots}/replica requests={len(trace)} "
          f"kill_at={args.kill_at or '—'}")
    print(f"  generated {rep.generated} tokens in {rep.wall:.2f}s "
          f"-> {rep.tok_per_s:.1f} tok/s across replicas")
    print(f"  latency p50={lat[0.5]*1e3:.0f}ms p95={lat[0.95]*1e3:.0f}ms; "
          f"ttft p50={ttft[0.5]*1e3:.0f}ms p95={ttft[0.95]*1e3:.0f}ms")
    print(f"  migrations: {len(rep.migrations)} "
          f"(killed {rep.killed or 'none'}); "
          f"stragglers observed: {len(rep.straggler_events)}")
    print(f"  scrubber: {rep.scrub_passes} passes, "
          f"{sum(r.scrub_weight_leaves for r in rep.replicas)} weight "
          f"leaves + {sum(r.scrub_idle_blocks for r in rep.replicas)} idle "
          f"blocks verified, {rep.scrub_corruptions} corruptions")
    done = sum(1 for s in rep.sessions.values() if s.done)
    print(f"  completed {done}/{len(trace)}")
    return 0 if done == len(trace) and rep.scrub_corruptions == 0 else 1


def main() -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="max prompt length in the trace")
    ap.add_argument("--new-tokens", type=int, default=16,
                    help="max per-request token budget in the trace")
    ap.add_argument("--s-max", type=int, default=0,
                    help="resident cache capacity (0: prompt+new)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--no-pack", action="store_true",
                    help="serve quant archs from float weights (A/B)")
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged-KV tokens per block (0: cfg.block_size)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill piece size (0: cfg.prefill_chunk)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="shared block-pool size (0: slots x full tables)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable content-addressed prefix caching (A/B)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared leading tokens in the trace (0: none)")
    ap.add_argument("--prefix-frac", type=float, default=0.9,
                    help="fraction of requests opening with the shared "
                         "prefix (with --prefix-len)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve replicas (>1: the replicated tier with "
                         "least-loaded routing and live migration, §17)")
    ap.add_argument("--kill-at", type=int, default=0,
                    help="router step of the kill-a-replica drill "
                         "(0: no drill; needs --replicas > 1)")
    ap.add_argument("--epoch-steps", type=int, default=8,
                    help="integrity-scrubber cadence in router steps "
                         "(0: off; --replicas > 1)")
    ap.add_argument("--ckpt-dir", default="",
                    help="migration checkpoint directory (default: a "
                         "temp dir; --replicas > 1)")
    ap.add_argument("--workload", choices=("lm", "transcribe", "classify"),
                    default="lm",
                    help="what to serve: chat trace, streaming "
                         "transcription, or image classification")
    ap.add_argument("--streams", type=int, default=3,
                    help="audio streams (--workload transcribe)")
    ap.add_argument("--windows", type=int, default=2,
                    help="windows per stream (--workload transcribe)")
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.smoke()

    if args.workload == "classify":
        return _run_classify(cfg, args)

    init_key, _ = jax.random.split(jax.random.PRNGKey(args.seed))
    params = lm.init_params(cfg, init_key)

    if args.workload == "transcribe":
        return _run_transcribe(cfg, params, args)
    pl = max(4, args.prompt_len)
    nt = max(2, args.new_tokens)
    trace = synthetic_trace(
        args.requests, cfg.vocab, seed=args.seed,
        prompt_lens=tuple(sorted({max(2, pl // 4), max(3, pl // 2), pl})),
        new_tokens=tuple(sorted({max(2, nt // 2), nt})),
        n_ctx_tokens=cfg.n_ctx_tokens, d_model=cfg.d_model,
        prefix_frac=args.prefix_frac, prefix_len=args.prefix_len)
    s_max = args.s_max or (args.prefix_len + pl + nt)

    if args.replicas > 1:
        return _run_replicated(cfg, params, trace, s_max, args)

    eng = ServeEngine(cfg, params, slots=args.slots, s_max=s_max,
                      eos_id=args.eos_id, temperature=args.temperature,
                      seed=args.seed, pack=not args.no_pack,
                      block_size=args.block_size,
                      prefill_chunk=args.prefill_chunk,
                      n_blocks=args.n_blocks,
                      prefix_cache=not args.no_prefix_cache)
    for r in trace:
        eng.submit(r)
    report = eng.run()
    lat = report.latency_quantiles((0.5, 0.95))
    ttft = report.ttft_quantiles((0.5, 0.95))
    qwait = report.queue_wait_quantiles((0.5, 0.95))
    packed = (not args.no_pack) and cfg.quant == "xnor"
    print(f"arch={cfg.name} slots={args.slots} requests={len(trace)} "
          f"packed={packed}")
    print(f"  generated {report.generated} tokens in {report.wall:.2f}s "
          f"-> {report.tok_per_s:.1f} tok/s "
          f"({report.prefills} prefills, {report.decode_steps} decode steps)")
    print(f"  latency p50={lat[0.5]*1e3:.0f}ms p95={lat[0.95]*1e3:.0f}ms")
    # queue-wait is the scheduling share of TTFT (time spent waiting for a
    # slot / for blocks); the remainder is prefill compute — reported
    # separately so backpressure and compute cost are distinguishable
    print(f"  ttft    p50={ttft[0.5]*1e3:.0f}ms p95={ttft[0.95]*1e3:.0f}ms "
          f"(queue-wait p50={qwait[0.5]*1e3:.0f}ms "
          f"p95={qwait[0.95]*1e3:.0f}ms)")
    st = report.stats
    if st.blocks_total:
        print(f"  blocks: peak {st.blocks_peak}/{st.blocks_total} "
              f"mean {st.blocks_mean:.1f} "
              f"(util {st.block_utilization:.0%}); "
              f"prefill traces {st.prefill_traces} "
              f"({st.prefill_chunks} chunks)")
        # hit rate = prompt tokens whose prefill was skipped via cached
        # blocks; blocks/request = fresh allocations per admission (shared
        # blocks are mapped, not allocated)
        print(f"  prefix cache: "
              f"{'on' if eng.prefix_caching else 'off'}; "
              f"hit rate {st.prefix_hit_rate:.0%} "
              f"({st.prefix_hits}/{st.prefills} prompts, "
              f"{st.prefix_tokens}/{st.prompt_tokens} tokens), "
              f"{st.blocks_per_request:.2f} fresh blocks/request, "
              f"{st.cow_copies} cow, {st.prefix_evictions} evictions")
    done = sum(1 for s in report.sessions.values() if s.done)
    first = trace[0]
    print(f"  completed {done}/{len(trace)}; first request tokens: "
          f"{np.asarray(report.tokens(first.rid))[:8].tolist()}...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
