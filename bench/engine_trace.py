#!/usr/bin/env python3
"""The serving engine's own spans and scopes in a JAX profiler trace.

The engine (``serve/engine.py``) writes host spans named ``serve.*`` with
``jax.profiler.TraceAnnotation``, on the clock of the device planes:
``serve.step`` (a ``StepTraceAnnotation`` numbered by the engine's step),
``serve.admit``, ``serve.prefill.dispatch``, ``serve.prefill.sync``,
``serve.decode.prepare``, ``serve.decode.dispatch``, ``serve.decode.sync``
and ``serve.decode.commit``.  A span with metadata may be named
``name#k=v#``; only the part before ``#`` names it.  The step programs
carry ``jax.named_scope`` scopes (``SCOPES``) in their ops' metadata.

Beside ``trace_reduce`` (whose window, busy time and tables this leaves
as they are), this reduction gives:

  idle_by_span   device idle seconds by the innermost host span, harness's
                 or engine's, that holds each gap's middle (``untraced``:
                 none holds it)
  idle_gaps      the ten longest such gaps, labelled
  engine         per engine span: count, total and self seconds
  host_turns     each stretch from the end of a ``serve.decode.sync`` to the
                 start of the next ``serve.decode.dispatch``, in seconds:
                 the host's turn, with no decode program queued
  per_scope      decode-program device seconds per model scope, ops that no
                 scope claims as ``unscoped``, and ``per_scope_ops``, the
                 same by scope and op stem; left out where no op is known
                 by its scope
  read_lag       per pair of consecutive decode executions, the nearest
  launch_lag     ``serve.decode.sync`` end less the first's last op end, and
                 the second's first op start less the nearest
                 ``serve.decode.dispatch`` start, in seconds; with the host
                 turn between them they make up the device's idle time
                 between the two, and an offset of the device's clock
                 against the host's moves them apart and leaves their sum

The TPU trace's op events name their instruction but carry no metadata,
so an op's scope comes from the compiled decode program's text
(``scope_map``), where each instruction's ``op_name`` holds its scopes.

Run a cell's window once with the profiler on (set-up as ``bench/run.py``
makes it; no correctness check) and print both reductions and the
window's counters as JSON:

  python3 bench/engine_trace.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import bisect  # noqa: E402
import collections  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

PREFIX = "serve."
SCOPES = ("embed", "attn.kv_write", "attn.core", "mlp", "head", "sample")
PROGRAM = "decode_fn"


def span_name(name: str) -> str:
    """``serve.admit#rid=3#`` -> ``serve.admit``."""
    return name.split("#", 1)[0]


def scope_of(path: str) -> str | None:
    """The innermost model scope named in an op's ``op_name`` path."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*?op_name="([^"]*)"',
                          re.M)


def instruction(name: str) -> str:
    """``%fusion.12 = bf16[8] fusion(...)`` -> ``fusion.12``."""
    return name[1:].split(" ", 1)[0] if name.startswith("%") else name


def scope_map(hlo_text: str) -> dict:
    """{instruction name: innermost scope} of a compiled program's text."""
    out = {}
    for name, op_name in _INSTRUCTION.findall(hlo_text):
        s = scope_of(op_name)
        if s is not None:
            out[name] = s
    return out


def load(path, scopes: dict | None = None) -> tuple[dict, list]:
    """Device and host events of one ``.xplane.pb`` file, as
    ``trace_reduce.load`` gives them, with the engine's spans among the
    host's and, per device plane, ``op_scopes``: beside each op event its
    scope by ``scopes`` ({instruction name: scope}), None where it has
    none."""
    from jax.profiler import ProfileData
    scopes = scopes or {}
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if trace_reduce._DEVICE.match(plane.name):
            d = {"ops": [], "modules": [], "op_scopes": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    d["modules"].extend((e.name, e.start_ns, e.duration_ns)
                                        for e in line.events)
                elif line.name == "XLA Ops":
                    for e in line.events:
                        d["ops"].append((e.name, e.start_ns, e.duration_ns))
                        d["op_scopes"].append(scopes.get(instruction(e.name)))
            devices[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name in trace_reduce.HOST_SPANS
                            or e.name.startswith(PREFIX))
    return devices, host


def _idle_gaps(devices: dict, lo: int, hi: int) -> list | None:
    """[start, end) idle stretches of the first plane with ops in the
    window, as ``trace_reduce`` finds them; None where no plane has."""
    for plane in sorted(devices):
        iv = [(s, s + d) for _, s, d in devices[plane]["ops"]
              if s < hi and s + d > lo]
        if not iv:
            continue
        gaps, prev = [], lo
        for s, e in trace_reduce.union(iv) + [[hi, hi]]:
            s, e = max(s, lo), min(e, hi)
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        return gaps
    return None


def _self_times(spans: list) -> dict:
    """Per engine span name: [count, total ns, self ns]; self time is the
    span's less that of the spans directly inside it."""
    out = collections.defaultdict(lambda: [0, 0, 0])
    stack: list = []
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1],
                                                     -spans[i][2]))
    own = [b - a for _, a, b in spans]
    for i in order:
        _, a, b = spans[i]
        while stack and spans[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    for i, (n, a, b) in enumerate(spans):
        if n.startswith(PREFIX):
            o = out[n]
            o[0] += 1
            o[1] += b - a
            o[2] += own[i]
    return out


def _per_scope(devices: dict, lo: int, hi: int) -> dict | None:
    """Decode-program device seconds per scope and op stem (ops in no
    scope: ``unscoped``), summed over the planes."""
    if not any(any(d.get("op_scopes", ())) for d in devices.values()):
        return None
    out = collections.defaultdict(collections.Counter)
    for d in devices.values():
        mods = sorted((s, s + t) for n, s, t in d["modules"]
                      if PROGRAM in trace_reduce.stem(n))
        starts = [m[0] for m in mods]
        scopes = d.get("op_scopes") or [None] * len(d["ops"])
        for (n, s, t), scope in zip(d["ops"], scopes):
            if not lo <= s < hi or trace_reduce.stem(n) in \
                    trace_reduce.CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue
            out[scope or "unscoped"][trace_reduce.stem(n)] += t * 1e-9
    return {k: dict(v) for k, v in out.items()}


def _lags(devices: dict, engine: list, lo: int, hi: int) -> dict:
    """Per pair of consecutive decode executions in the window (first
    plane that ran one): the nearest sync end less the first's last op
    end, and the second's first op start less the nearest dispatch
    start."""
    reads = [b for n, a, b in engine if n == "serve.decode.sync"]
    starts = [a for n, a, b in engine if n == "serve.decode.dispatch"]
    for plane in sorted(devices):
        d = devices[plane]
        runs = sorted((s, s + t) for n, s, t in d["modules"]
                      if PROGRAM in trace_reduce.stem(n) and lo <= s < hi)
        if not runs:
            continue
        edges = [[None, None] for _ in runs]
        run_starts = [r[0] for r in runs]
        for _, s, t in d["ops"]:
            i = bisect.bisect_right(run_starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                e = edges[i]
                e[0] = s if e[0] is None else min(e[0], s)
                e[1] = s + t if e[1] is None else max(e[1], s + t)
        pairs = [(x[1], y[0]) for x, y in zip(edges, edges[1:])
                 if x[1] is not None and y[0] is not None]
        if not (pairs and reads and starts):
            return {}
        return {"read_lag": [(min(reads, key=lambda r: abs(r - e)) - e)
                             * 1e-9 for e, _ in pairs],
                "launch_lag": [(s - min(starts, key=lambda a: abs(s - a)))
                               * 1e-9 for _, s in pairs]}
    return {}


def reduce_events(devices: dict, host: list) -> dict:
    """``devices`` as ``load`` gives them, ``host``: [(span name,
    start_ns, dur_ns)], the harness's and the engine's.  The window is the
    harness spans' (as in ``trace_reduce``); {} where it has none or no
    device op falls in it."""
    harness = [(n, s, s + d) for n, s, d in host
               if n in trace_reduce.HOST_SPANS]
    if not harness:
        return {}
    lo = min(s for _, s, _ in harness)
    hi = max(e for _, _, e in harness)
    gaps = _idle_gaps(devices, lo, hi)
    if gaps is None:
        return {}
    engine = [(span_name(n), s, s + d) for n, s, d in host
              if n.startswith(PREFIX)]
    spans = sorted(harness + engine, key=lambda t: t[1])
    labelled = []
    for s, e in gaps:
        mid = (s + e) / 2
        label = "untraced"
        for n, a, b in spans:
            if a > mid:
                break
            if a <= mid < b:
                label = n
        labelled.append((label, (e - s) * 1e-9))
    by_label = collections.Counter()
    for label, sec in labelled:
        by_label[label] += sec
    inside = [t for t in engine if lo <= t[1] < hi]
    syncs = sorted(b for n, a, b in inside if n == "serve.decode.sync")
    dispatches = sorted(a for n, a, b in inside
                        if n == "serve.decode.dispatch")
    turns = []
    for e in syncs:
        i = bisect.bisect_right(dispatches, e)
        if i < len(dispatches):
            turns.append((dispatches[i] - e) * 1e-9)
    out = {
        "idle_by_span": dict(by_label),
        "idle_gaps": [[n, s] for n, s in
                      sorted(labelled, key=lambda g: -g[1])[:10]],
        "engine": {n: {"count": c, "seconds": t * 1e-9,
                       "self_seconds": o * 1e-9}
                   for n, (c, t, o) in _self_times(
                       [t for t in harness + engine
                        if lo <= t[1] < hi]).items()},
        "host_turns": turns,
    }
    per_scope = _per_scope(devices, lo, hi)
    if per_scope is not None:
        out["per_scope"] = {k: sum(v.values()) for k, v in per_scope.items()}
        out["per_scope_ops"] = per_scope
    out.update(_lags(devices, inside, lo, hi))
    return out


def reduce_dir(trace_dir, scopes: dict | None = None) -> dict:
    """Both reductions of the newest trace under ``trace_dir``."""
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        return {}
    devices, host = load(files[-1], scopes)
    base = trace_reduce.reduce_events(
        devices, [h for h in host if h[0] in trace_reduce.HOST_SPANS])
    return {**base, **reduce_events(devices, host)}


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(1, str(HERE.parent / "src"))
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness
    cell = harness.resolve(args.workload)
    dev = harness.device_info(cell.chips)
    cfg = harness.build_cfg(cell.conf)
    tdir = harness.trace_dir_for(cell, args.seed)
    texts = []

    def decode_text(eng):           # called once the engine is warm
        texts.append(eng._paged_decode_program.lower(
            *eng._decode_args()).compile().as_text())
    m = harness.measure(cell, cfg, args.seed, args.seconds, dev,
                        t_process=T_PROCESS, trace_dir=tdir, fault=decode_text)
    win = m.win
    out = {"setup_s": m.setup_s, "out_tok_s": harness.out_tok_s(win),
           "counters": {k: win.delta(k) for k in win.stats_open},
           **reduce_dir(tdir, scope_map(texts[0]))}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
