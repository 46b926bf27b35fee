"""Replicated serving tier with a kill-a-replica fault drill
(DESIGN.md §17).

The same seeded mixed-length trace runs twice:

  single      — one ServeEngine (the PR-5/6 serving path), the token
                baseline;
  replicated  — a Router over N replicas (least-loaded admission, each
                replica on its own launch.mesh sub-mesh slice), with the
                kill drill: at ``--kill-at`` router steps the most-loaded
                replica dies mid-flight, its queued sessions are
                resubmitted and its admitted sessions drain onto the
                survivors as encrypted migration checkpoints
                (ckpt.save / save_delta + restore against a derived spec).

Because the engine's sampling contract folds (rid, token index) — never
slot or batch composition — into every draw, and migration moves the
session's exact device state (paged KV blocks by table row, recurrent
carries, position, chunked-prefill progress), the replicated run must
produce bit-identical tokens per request, kill or no kill.  The
background integrity scrubber (incremental DigestCache over resident
packed weights + idle cached KV blocks) runs every ``--epoch-steps``
router steps throughout.

``--smoke`` asserts: every request finishes, zero token divergence vs
the single-engine baseline, at least one session actually migrated,
at least one scrubber pass covered the resident packed weights, and no
corruption was reported — wired into CI in both kernel modes.

Run:  PYTHONPATH=src python benchmarks/serve_replicated.py [--smoke]
"""

from __future__ import annotations

import argparse
import os
import tempfile

import numpy as np


def _setup(arch: str, smoke: bool, seed: int):
    import jax
    import repro.configs as configs
    from repro.models import lm

    cfg = configs.get(arch)
    if smoke:
        cfg = cfg.smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params


def _trace(cfg, n_req: int, smoke: bool, seed: int):
    from repro.serve import synthetic_trace

    plens, ntoks = ((4, 7, 11), (4, 6, 9)) if smoke else ((16, 32), (16, 32))
    return synthetic_trace(n_req, cfg.vocab, seed=seed, prompt_lens=plens,
                           new_tokens=ntoks, n_ctx_tokens=cfg.n_ctx_tokens,
                           d_model=cfg.d_model), plens, ntoks


def run_single(cfg, params, trace, slots: int, s_max: int, seed: int,
               pack: bool = True):
    from repro.serve import ServeEngine

    eng = ServeEngine(cfg, params, slots=slots, s_max=s_max, seed=seed,
                      pack=pack)
    for r in trace:
        eng.submit(r)
    return eng.run()


def run_replicated(cfg, params, trace, *, replicas: int, slots: int,
                   s_max: int, seed: int, kill_at: int | None,
                   epoch_steps: int, ckpt_dir: str, pack: bool = True):
    from repro.serve import Router

    router = Router(cfg, params, replicas, slots=slots, s_max=s_max,
                    seed=seed, pack=pack, ckpt_dir=ckpt_dir,
                    epoch_steps=epoch_steps)
    for r in trace:
        router.submit(r)
    return router.run(kill_at=kill_at)


def _ckpt_bytes(ckpt_dir: str) -> int:
    total = 0
    for root, _, files in os.walk(ckpt_dir):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return total


def _bench(arch: str, smoke: bool, replicas: int, slots: int, requests: int,
           kill_at: int, epoch_steps: int, seed: int, quiet: bool = False):
    def say(*a):
        if not quiet:
            print(*a)

    cfg, params = _setup(arch, smoke, seed)
    n_req = requests or (10 if smoke else 24)
    trace, plens, ntoks = _trace(cfg, n_req, smoke, seed)
    s_max = max(plens) + max(ntoks) + 4

    base = run_single(cfg, params, trace, slots, s_max, seed)
    with tempfile.TemporaryDirectory(prefix="serve_mig_") as d:
        rep = run_replicated(cfg, params, trace, replicas=replicas,
                             slots=slots, s_max=s_max, seed=seed,
                             kill_at=kill_at, epoch_steps=epoch_steps,
                             ckpt_dir=d)
        wire_bytes = _ckpt_bytes(d)

    say(f"# serve_replicated arch={cfg.name} replicas={replicas} "
        f"slots={slots}/replica requests={n_req} kill_at={kill_at} "
        f"epoch={epoch_steps}")
    say(f"{'path':<12s} {'tok/s':>9s} {'wall s':>8s} {'migrations':>11s} "
        f"{'scrubs':>7s} {'corrupt':>8s}")
    say(f"{'single':<12s} {base.tok_per_s:>9.1f} {base.wall:>8.2f} "
        f"{'—':>11s} {'—':>7s} {'—':>8s}")
    say(f"{'replicated':<12s} {rep.tok_per_s:>9.1f} {rep.wall:>8.2f} "
        f"{len(rep.migrations):>11d} {rep.scrub_passes:>7d} "
        f"{rep.scrub_corruptions:>8d}")
    say(f"  drill: killed replica {rep.killed}, "
        f"{len(rep.migrations)} migration checkpoint(s) "
        f"({wire_bytes / 2**10:.0f} KiB encrypted wire), "
        f"{len(rep.straggler_events)} straggler observations")
    divergent = [rid for rid in base.sessions
                 if rep.sessions[rid].tokens != base.sessions[rid].tokens]
    say(f"  identity: {len(base.sessions) - len(divergent)}/"
        f"{len(base.sessions)} requests bit-identical to the single-engine "
        f"baseline")
    return cfg, base, rep, divergent, wire_bytes


def _check_smoke(cfg, base, rep, divergent) -> None:
    assert set(rep.sessions) == set(base.sessions)
    unfinished = [rid for rid, s in rep.sessions.items() if not s.done]
    assert not unfinished, (
        f"kill drill left requests unfinished: {unfinished}")
    assert not divergent, (
        f"tokens diverged from the single-engine baseline after the kill "
        f"drill: rids {divergent}")
    assert rep.killed, "drill did not kill a replica"
    assert rep.migrations, (
        "drill killed a replica but migrated no admitted session — the "
        "trace must keep the victim busy at kill time")
    assert rep.scrub_passes >= 1, "no scrubber pass ran"
    weight_leaves = sum(r.scrub_weight_leaves for r in rep.replicas)
    assert weight_leaves > 0, (
        "scrubber pass covered no resident weight leaves")
    assert rep.scrub_corruptions == 0, (
        f"scrubber reported {rep.scrub_corruptions} corruptions on an "
        f"uncorrupted run")
    assert cfg.quant == "xnor", (
        "smoke gate expects an xnor arch so the scrubbed residency is the "
        "packed form")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b+xnor")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=2,
                    help="slots per replica")
    ap.add_argument("--requests", type=int, default=0,
                    help="trace length (0: 24, or 10 under --smoke)")
    ap.add_argument("--kill-at", type=int, default=6,
                    help="router step of the kill drill (0: no kill)")
    ap.add_argument("--epoch-steps", type=int, default=4,
                    help="scrubber cadence in router steps")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg, base, rep, divergent, _ = _bench(
        args.arch, args.smoke, args.replicas, args.slots, args.requests,
        args.kill_at or None, args.epoch_steps, args.seed)
    if args.smoke:
        _check_smoke(cfg, base, rep, divergent)
        print("smoke OK: kill drill finished every in-flight request with "
              "zero token divergence vs the single engine, and the "
              "integrity scrubber passed over the resident packed weights")
    return 0


def run():
    """benchmarks/run.py entry: (name, us_per_call, derived) CSV rows —
    us_per_call is wall microseconds per generated token."""
    cfg, base, rep, divergent, wire_bytes = _bench(
        "qwen2-7b+xnor", True, 2, 2, 8, 5, 4, 0, quiet=True)
    yield ("single", 1e6 / max(base.tok_per_s, 1e-9),
           f"tok/s={base.tok_per_s:.1f}")
    yield ("replicated_kill", 1e6 / max(rep.tok_per_s, 1e-9),
           f"tok/s={rep.tok_per_s:.1f} migrations={len(rep.migrations)} "
           f"divergent={len(divergent)} scrubs={rep.scrub_passes} "
           f"corrupt={rep.scrub_corruptions} "
           f"wire_kib={wire_bytes / 2**10:.0f}")


if __name__ == "__main__":
    raise SystemExit(main())
