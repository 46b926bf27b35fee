"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metric
readers need.

From each TPU plane (``/device:TPU:<n>``): its ``XLA Ops`` line gives the
device busy union and the device time per operation; its ``XLA Modules``
line gives one event per execution of a compiled program.  Each operation
is attributed to the program execution that contains it.  From the host
plane: the harness's spans (``submit``, ``step``, ``wait``, written with
``jax.profiler.TraceAnnotation``) bound the traced window and label each
idle gap of the device by what the host was doing at its middle.

Names are reduced to their stem: ``jit_decode_fn(17)`` to ``jit_decode_fn``,
``fusion.123`` to ``fusion``.
"""

from __future__ import annotations

import bisect
import collections
import pathlib
import re

HOST_SPANS = ("submit", "step", "wait")
# ops that only contain other ops (a scanned layer loop): kept in the busy
# union, left out of the per-op tables so their bodies are not counted twice
CONTAINERS = ("while", "conditional", "call")
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def stem(name: str) -> str:
    """``jit_decode_fn(17)`` -> ``jit_decode_fn``; an op event, which the
    TPU trace names by its whole HLO text (``%fusion.123 = bf16[...]
    fusion(...)``), -> its instruction's stem ``fusion``."""
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"(\.\d+|\.remat)+$", "", name)


def union(intervals) -> list:
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> float:
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in intervals)


def reduce_events(devices: dict, host: list) -> dict:
    """``devices``: {plane: {"ops": [(name, start_ns, dur_ns)], "modules":
    [...]}}; ``host``: [(span name, start_ns, dur_ns)].  Returns the
    reduction (seconds throughout)."""
    spans = [(n, s, s + d) for n, s, d in host if n in HOST_SPANS]
    if not spans:
        return {}
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    window = hi - lo
    busy_total, used = 0.0, 0
    ops = collections.defaultdict(lambda: [0, 0.0])
    programs = collections.defaultdict(lambda: [0, 0.0])
    per_program = collections.defaultdict(
        lambda: collections.defaultdict(lambda: [0, 0.0]))
    gaps = []
    for plane in sorted(devices):
        evs = devices[plane]
        op_iv = [(s, s + d) for _, s, d in evs["ops"] if s < hi and s + d > lo]
        if not op_iv:
            continue
        used += 1
        merged = union(op_iv)
        busy_total += _clip(merged, lo, hi)
        mods = sorted((s, s + d, stem(n)) for n, s, d in evs["modules"])
        starts = [m[0] for m in mods]
        for n, s, d in evs["modules"]:
            if lo <= s < hi:
                p = programs[stem(n)]
                p[0] += 1
                p[1] += d * 1e-9
        for n, s, d in evs["ops"]:
            if not lo <= s < hi or stem(n) in CONTAINERS:
                continue
            o = ops[stem(n)]
            o[0] += 1
            o[1] += d * 1e-9
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1]:
                q = per_program[mods[i][2]][stem(n)]
                q[0] += 1
                q[1] += d * 1e-9
        if used == 1:
            prev = lo
            for s, e in merged + [[hi, hi]]:
                s, e = max(s, lo), min(e, hi)
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
    if not used:
        return {}
    span_starts = sorted(spans, key=lambda t: t[1])
    labelled = []
    for s, e in gaps:
        mid = (s + e) / 2
        label = "untraced"
        for n, a, b in span_starts:
            if a > mid:
                break
            if a <= mid < b:
                label = n
        labelled.append((label, (e - s) * 1e-9))
    by_label = collections.Counter()
    for label, sec in labelled:
        by_label[label] += sec
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][1])[:10]
    top_gaps = sorted(labelled, key=lambda g: -g[1])[:10]
    return {
        "window_s": window * 1e-9,
        "busy_s": busy_total * 1e-9 / used,
        "programs": {k: {"count": v[0], "seconds": v[1]}
                     for k, v in programs.items()},
        "ops": {k: {"count": v[0], "seconds": v[1]} for k, v in ops.items()},
        "per_program": {p: {k: {"count": v[0], "seconds": v[1]}
                            for k, v in d.items()}
                        for p, d in per_program.items()},
        "idle_by_host_span": dict(by_label),
        "breakdown": {"device_ops": [[k, v[1]] for k, v in top_ops],
                      "idle_gaps": [[n, s] for n, s in top_gaps]},
    }


def load(path) -> tuple[dict, list]:
    """Device and host events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    d[key].extend((e.name, e.start_ns, e.duration_ns)
                                  for e in line.events)
            devices[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return devices, host


def reduce_dir(trace_dir) -> dict:
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        return {}
    return reduce_events(*load(files[-1]))


def describe(trace_dir, top: int = 40) -> dict:
    """What a trace holds, for reading one by hand: each plane's lines with
    their event counts, and each device line's most frequent event names."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        return {}
    pd = ProfileData.from_file(str(files[-1]))
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            first = min((e.start_ns for e in evs), default=None)
            lines[line.name] = {"events": len(evs), "first_ns": first,
                                "names": names.most_common(top)}
        out[plane.name] = lines
    return out
