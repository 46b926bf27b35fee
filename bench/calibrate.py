#!/usr/bin/env python3
"""Readings from which a cell's limits and offered rate are set, on the chip.

Not part of a benchmark run.  Each mode runs in one process, cell after
cell and seed after seed, and writes one JSON line per reading to standard
output and to ``--out``:

  --seeds 11,12,...   the program's gaps per seed, and with ``--control``
                      the float8 control's, at the cell's own size and load
                      over a short window; each judged against the cell's
                      limits as a run judges (``correct``)
  --rates r1,r2,...   the knee sweep of an open-loop cell: for each offered
                      rate, how the backlog of due but unadmitted requests
                      moves across the window, with the tails
  --describe          one traced window: the trace's planes, lines and most
                      frequent event names, and its reduction

  python3 bench/calibrate.py --workload q3x-chat --seeds 11,12 --seconds 20
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def backlog_slope(win, step_s: float = 1.0) -> tuple[float, float, float]:
    """Least-squares slope (requests/s) of the backlog of requests due but
    not admitted, sampled every ``step_s`` across the window; and the
    backlog at its start and end."""
    import numpy as np
    ts = np.arange(win.t_open, win.t_close, step_s)
    def backlog(t):
        return sum(1 for r in win.recs if r.due <= t
                   and (r.t_admit is None or r.t_admit > t))
    ys = np.array([backlog(t) for t in ts], float)
    slope = float(np.polyfit(ts - ts[0], ys, 1)[0]) if len(ts) > 1 else 0.0
    return slope, float(ys[0]), float(ys[-1])


def summary(win) -> dict:
    import numpy as np
    import harness
    out = {"window_s": win.seconds, "steps": win.steps,
           "requests_due": len(win.due_in_window()),
           "decode_steps": win.delta("decode_steps"),
           "prefill_chunks": win.delta("prefill_chunks"),
           "out_tok_s": harness.out_tok_s(win),
           "generator_late_s": win.gen_late_s,
           "pool": win.pool,
           "longest_steps_ms": [[1e3 * s, i] for s, i in win.longest_steps]}
    ttft, itl = harness.ttft_ms(win), harness.itl_ms(win)
    for name, vals, qs in (("ttft_ms", ttft, (50, 90)),
                           ("itl_ms", itl, (50, 95))):
        for q in qs:
            out[f"{name}_p{q}"] = harness.pct(vals, q) if vals else None
        out[f"{name}_n"] = len(vals)
    log = [(t, n) for t, _, n in win.decode_log
           if win.t_open < t <= win.t_close]
    if len(log) > 1:
        out["step_interval_ms_p50"] = float(
            np.median(np.diff([t for t, _ in log])) * 1e3)
        out["decoding_slots_mean"] = float(np.mean([n for _, n in log]))
    out["completed_rps"] = len(harness.completed(win)) / win.seconds
    if win.stats_open["prompt_tokens"] != win.stats_close["prompt_tokens"]:
        out["prefix_token_share"] = 100.0 * win.delta(
            "prefix_tokens") / win.delta("prompt_tokens")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True,
                    help="comma-separated cells, run one after another")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--out", default=".bench_out/calibrate.jsonl")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness
    import trace_reduce

    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)

    def say(msg):
        print(f"[{time.monotonic() - T_PROCESS:8.1f}s] {msg}", file=sys.stderr,
              flush=True)

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        with out.open("a") as f:
            f.write(line + "\n")

    for workload in args.workload.split(","):
        cell = harness.resolve(workload)
        dev = harness.device_info(cell.chips)
        cfg = harness.build_cfg(cell.conf)
        seeds = [int(s) for s in args.seeds.split(",") if s]
        for seed in seeds:
            say(f"{workload} seed {seed}: set-up and window")
            t0 = time.monotonic()
            m = harness.measure(cell, cfg, seed, args.seconds, dev,
                                t_process=t0)
            t1 = time.monotonic()
            say(f"{workload} seed {seed}: reference")
            modes = ("ref", "fp8") if args.control else ("ref",)
            gaps = harness.gaps(cell, seed, m.prompts, m.tokens, modes)
            n_tok = int(sum(len(t) for t in m.tokens))
            correct = {mode: harness.judge(cell, read, n_tok)[0]
                       for mode, read in gaps.items()}
            emit({"workload": workload, "seed": seed, "gaps": gaps,
                  "correct": correct, "compared_tokens": n_tok,
                  "compared_requests": len(m.tokens),
                  "setup_s": m.setup_s, "check_s": time.monotonic() - t1,
                  "memory_peak_bytes": m.memory_peak_bytes,
                  **summary(m.win)})
        for rate in [float(r) for r in args.rates.split(",") if r]:
            c = copy.deepcopy(cell)
            c.traffic["arrivals"]["rate_rps"] = rate
            say(f"{workload} rate {rate}")
            t0 = time.monotonic()
            m = harness.measure(c, cfg, 1000 + int(rate * 1000),
                                args.seconds, dev, t_process=t0)
            slope, b0, b1 = backlog_slope(m.win)
            emit({"workload": workload, "rate_rps": rate,
                  "backlog_slope_rps": slope, "backlog_start": b0,
                  "backlog_end": b1, **summary(m.win)})
        if args.describe:
            say(f"{workload}: traced window")
            tdir = harness.trace_dir_for(cell, 7)
            shutil.rmtree(tdir, ignore_errors=True)
            t0 = time.monotonic()
            m = harness.measure(cell, cfg, 7, args.seconds, dev,
                                t_process=t0, trace_dir=tdir)
            t1 = time.monotonic()
            red = trace_reduce.reduce_dir(tdir)
            emit({"workload": workload, "describe": trace_reduce.describe(tdir),
                  "reduce_s": time.monotonic() - t1,
                  "reduced": {k: v for k, v in red.items()},
                  "trace_bytes": sum(p.stat().st_size
                                     for p in tdir.glob("**/*") if p.is_file()),
                  **summary(m.win)})
            shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
