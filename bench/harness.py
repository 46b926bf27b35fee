"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell needs is found by name (``BENCHMARK.json`` names it):

  bench/configs/<config>.json    sizes as run under the published keys
                                 (``build_cfg`` holds the system to them),
                                 the weights' ``init`` rules and the roles
                                 of the leaves (``model_weights``), the
                                 system's ``arch`` and the ``reference``
  bench/traffic/<mix>.json       parameters of the one traffic generator
  bench/cells/<cell>.json        the cell's limits and run settings
  bench/metrics/<metric>.py      a reader per per-layer metric; a metric
                                 named ``<base>.<cells>`` that has no file
                                 of its own is read by ``<base>.py``
  bench/references/<ref>.py      the plain reference a config names

The window drives the system's own serving entry, ``ServeEngine.submit``
and ``ServeEngine.step``, from a loop here: before each step it submits
every request whose due time has passed, and after each step it stamps each
newly visible output token with the host clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import heapq
import importlib.util
import json
import pathlib
import shutil
import sys
import time

import numpy as np

import roofline
import traffic_gen

ROOT = pathlib.Path(__file__).resolve().parents[1]
clock = time.monotonic


class Refused(RuntimeError):
    """The run cannot be made here (no chip, unknown device, bad cell)."""


# ---------------------------------------------------------------------------
# resolving a cell by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    traffic: dict
    settings: dict
    reference: str
    end_to_end: list
    per_layer: list
    root: pathlib.Path


def _load(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(path.parents[2])}")
    return json.loads(path.read_text())


def resolve(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _load(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = _load(root / configs[w["config"]]["file"])
    traffic = _load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    settings = _load(root / "bench" / "cells" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    for m in layer:
        if reader_path(root, m["name"]) is None:
            raise Refused(f"no reader bench/metrics/{m['name']}.py")
    ref = root / "bench" / "references" / f"{conf['reference']}.py"
    if not ref.is_file():
        raise Refused(f"no reference {ref.name}")
    return Cell(workload, int(w["chips"]), conf, traffic, settings,
                conf["reference"], e2e, layer, root)


def reader_path(root: pathlib.Path, metric: str) -> pathlib.Path | None:
    """The reader of ``metric``: its own file, else that of the name's part
    before its first dot (``decode_ms.offline`` is read by ``decode_ms``)."""
    for stem in (metric, metric.split(".")[0]):
        path = root / "bench" / "metrics" / f"{stem}.py"
        if path.is_file():
            return path
    return None


def load_module(path: pathlib.Path, name: str):
    """The module in ``path``, loaded once per process under ``name``."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_info(chips: int, root: pathlib.Path = ROOT) -> dict:
    """The chip this run measures; refuses anything but enough TPUs of a
    kind the peaks table knows."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    kind = devs[0].device_kind
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in peaks["devices"]:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs),
            "peaks": peaks["devices"][kind]}


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


# configuration-file keys -> the system config's fields that must hold the
# same numbers: every key the yardstick (``roofline``) reads, and
# ``rope_theta``.  A model that brings latent attention or shared experts
# names its fields so; the layer plan (``first_k_dense_replace``,
# ``moe_layer_freq``) is held to the system's block kinds instead.
SYSTEM_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "vocab_size": "vocab",
    "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
    "intermediate_size": "d_ff", "rope_theta": "rope_theta",
    "n_routed_experts": "n_experts", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "d_ff_expert",
    "n_shared_experts": "n_shared_experts", "kv_lora_rank": "kv_lora_rank",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "qk_nope_head_dim": "qk_nope_head_dim", "v_head_dim": "v_head_dim"}


def build_cfg(conf: dict, overrides: dict | None = None):
    """The system's config for ``conf`` (its ``arch``), held to the file,
    fail-closed: the yardstick must find every key it reads; each key of
    ``SYSTEM_FIELDS`` must equal the system's field at the number the
    yardstick counts with (``roofline.value``: the file's, else its
    default), a field the system lacks standing at that default; the
    yardstick's MoE layers must be the system's layers of a kind with
    routed experts; and ``binarized`` must match the ``+xnor`` axis."""
    import repro.configs as configs
    from repro.models import blocks, registry  # noqa: F401  (blocks registers the kinds)
    cfg = configs.get(conf["arch"])
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    try:
        roofline.model_ops(conf, 1, 1)
        roofline.kv_bytes_per_token(conf)
    except KeyError as e:
        raise Refused(f"{conf['arch']}: the config file lacks {e}, which "
                      "the yardstick reads") from None
    bad = {}
    for key, field in SYSTEM_FIELDS.items():
        try:
            want = roofline.value(conf, key)
        except KeyError:
            continue
        have = getattr(cfg, field, roofline.DEFAULTS.get(key))
        if want != have:
            bad[key] = (want, have)
    moe = tuple(i for i, kind in enumerate(cfg.layer_kinds())
                if registry.get(kind).contract.routed_experts)
    if roofline.moe_layers(conf) != moe:
        bad["moe layers"] = (roofline.moe_layers(conf), moe)
    if bad or bool(conf["binarized"]) != (cfg.quant == "xnor"):
        raise Refused(f"{conf['arch']} differs from its config file: {bad}")
    return cfg


def kv_block_bytes(cfg, block_size: int) -> int:
    """Bytes one more block adds to the system's paged serving state, from
    its own state spec: whatever its layers cache per token."""
    import jax
    from repro.models import lm

    def state_bytes(n):
        spec = lm.paged_decode_state_spec(cfg, 1, block_size, n_blocks=n,
                                          block_size=block_size)
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(spec))
    return state_bytes(2) - state_bytes(1)


# HBM left unplanned besides what is in use when the pool is sized: room for
# the small buffers the serving loop makes between programs, and for the
# allocator's fragmentation
HBM_SLACK = 0.03


def pool_blocks(cfg, params, engine_args: dict, worst: int) -> dict:
    """The most KV-pool blocks, up to ``worst``, with which every program
    that holds the pool fits the chip beside what is already there.

    Each program's need is the peak that its compiled memory analysis
    gives, which grows linearly with the pool's blocks (the pool as
    argument, and whatever the program copies of it); it is read from the
    engine's own chunk and decode programs compiled at two pools that fit
    whatever the programs copy (a quarter and an eighth of the free HBM's
    blocks), and drawn out to the chip's limit.  Off a TPU (the CPU tests)
    the pool takes ``worst``."""
    import jax
    dev = jax.local_devices()[0]
    stats = dev.memory_stats() or {}
    if dev.platform != "tpu" or "bytes_limit" not in stats:
        return {"n_blocks": worst}
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    large = max(2, min(worst, free // (4 * kv_block_bytes(cfg, cfg.block_size))))
    sizes = (large // 2, large)
    need = program_peaks(cfg, params, engine_args, sizes)
    gc.collect()
    stats = dev.memory_stats()
    weights = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    room = (stats["bytes_limit"] * (1 - HBM_SLACK)
            - (stats["bytes_in_use"] - weights))
    fit = min(sizes[0] + (room - p[0]) * (sizes[1] - sizes[0]) / (p[1] - p[0])
              for p in need.values())
    return {"n_blocks": int(min(worst, fit)), "hbm_limit": stats["bytes_limit"],
            "probe_blocks": list(sizes), "program_peaks": need}


def program_peaks(cfg, params, engine_args: dict, sizes) -> dict:
    """Per program of the engine that holds the KV pool, its compiled peak
    bytes with a pool of each of ``sizes`` blocks (nothing runs; the
    engine that makes the programs holds a pool of two blocks)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.serve import ServeEngine
    eng = ServeEngine(cfg, params, n_blocks=2, **engine_args)

    def at(n):
        spec = lm.paged_decode_state_spec(
            cfg, eng.slots, eng.s_max, n_blocks=n, block_size=eng.block_size)
        return jax.tree.map(lambda a, x: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=x.sharding), spec, eng._state)

    def zeros(*shape):
        return jnp.zeros(shape, jnp.int32)

    c = eng.prefill_chunk
    rows = {k: zeros(1, t.shape[1]) for k, t in eng._tables.items()}
    args = eng._decode_args()
    programs = {
        "chunk": lambda st: eng._chunk_program.lower(
            eng.params, zeros(1, c), st, jnp.int32(0), jnp.int32(c), rows,
            None, jnp.asarray(True), jnp.int32(0), eng._key, zeros(1)),
        "decode": lambda st: eng._paged_decode_program.lower(
            *args[:2], st, *args[3:])}
    return {name: [lower(at(n)).compile().memory_analysis()
                   .peak_memory_in_bytes for n in sizes]
            for name, lower in programs.items()}


def build_engine(cell: Cell, cfg, seed: int):
    """Weights drawn on the device, then the engine as the serving entry
    point builds it, with its KV pool sized to the memory they leave."""
    import jax
    from repro.core.xnor_layers import PackedLinear
    from repro.models import lm
    from repro.serve import ServeEngine
    import model_weights

    params = model_weights.make_served(
        seed, lm.packed_abstract_params(cfg), cell.conf["init"],
        cell.conf["vocab_size"], PackedLinear)
    jax.block_until_ready(params)
    args = dict(slots=cell.traffic["slots"],
                s_max=traffic_gen.max_context(cell.traffic), seed=seed,
                prefill_chunk=int(cell.settings.get("prefill_chunk", 0)))
    worst = 1 + args["slots"] * -(-args["s_max"] // cfg.block_size)
    pool = pool_blocks(cfg, params, args, worst)
    eng = ServeEngine(cfg, params, n_blocks=pool["n_blocks"], **args)
    return eng, pool


def warm_up(eng, vocab: int) -> None:
    """Runs every program the window uses once: the prefill chunk, the
    decode step and the copy-on-write block copy (the second request
    shares a block and a half of the first)."""
    from repro.serve import Request
    rng = np.random.default_rng(0)
    a = rng.integers(0, vocab, 2 * eng.block_size + eng.block_size // 2,
                     dtype=np.int32)
    b = np.concatenate([a[:eng.block_size + eng.block_size // 2],
                        rng.integers(0, vocab, eng.block_size,
                                     dtype=np.int32)])
    for i, prompt in enumerate((a, b)):
        eng.submit(Request(rid=-1 - i, prompt=prompt, max_new_tokens=3))
        while eng.step():
            pass


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    """What the harness saw of one request."""

    rid: int
    due: float                      # host clock
    prompt_len: int
    max_new: int
    shared: bool
    session: object = None
    stamps: list = dataclasses.field(default_factory=list)

    @property
    def t_admit(self):
        return None if self.session is None else self.session.t_admit


@dataclasses.dataclass
class Window:
    """The records of one run, for the metric readers."""

    t_open: float
    t_close: float
    recs: list
    stats_open: dict
    stats_close: dict
    decode_log: list                # (host time after step, sum ctx, n)
    steps: int
    dims: dict
    geometry: dict
    peaks: dict
    trace: dict | None = None
    gen_late_s: float = 0.0
    pool: dict = dataclasses.field(default_factory=dict)
    longest_steps: list = dataclasses.field(default_factory=list)
    t_end: float | None = None      # the end of the last step begun in it

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def delta(self, key: str) -> int:
        return self.stats_close[key] - self.stats_open[key]

    def due_in_window(self) -> list:
        return [r for r in self.recs if self.t_open <= r.due < self.t_close]


def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _stats(eng) -> dict:
    return {k: v for k, v in dataclasses.asdict(eng.stats).items()
            if isinstance(v, int)}


def drive(eng, cell: Cell, plan, seconds: float, trace_dir=None) -> dict:
    """The loop.  Returns the raw window: records, counters at open and
    close, the decode log and the generator's lateness."""
    from repro.serve import Request
    traced = trace_dir is not None
    backlog = cell.traffic["arrivals"]["kind"] == "backlog"
    ramp = 0.0 if backlog else float(cell.traffic["ramp_s"])
    slots = cell.traffic["slots"]
    t_start = clock()
    recs = [Rec(p.rid, t_start + p.due, len(p.prompt), p.max_new, p.shared)
            for p in plan]
    prompts = {p.rid: p.prompt for p in plan}
    live: list[Rec] = []
    nxt = 0
    late = 0.0
    log = []
    t_open = None if backlog else t_start + ramp
    t_close = None if backlog else t_open + seconds
    stats_open = None if backlog else {}
    tracing = False
    trace_s = float(cell.settings.get("trace_seconds", seconds))
    steps = 0
    t_end = None

    def submit_due(now):
        nonlocal nxt, late
        while nxt < len(recs) and recs[nxt].due <= now:
            r = recs[nxt]
            late = max(late, now - r.due)
            r.session = eng.submit(Request(rid=r.rid, prompt=prompts[r.rid],
                                           max_new_tokens=r.max_new))
            live.append(r)
            nxt += 1

    gaps = []                       # (seconds, step index) in the window
    while True:
        now = clock()
        if t_open is not None:
            if not stats_open and now >= t_open:
                stats_open = _stats(eng)
            if traced and not tracing and now >= t_open and trace_s > 0:
                import jax
                jax.profiler.start_trace(str(trace_dir))
                tracing, t_trace = True, clock()
            if tracing and clock() - t_trace >= trace_s:
                import jax
                jax.profiler.stop_trace()
                tracing, trace_s = False, 0.0
            if now >= t_close:
                t_end = now
                break
        with _annotate("submit", traced):
            submit_due(now)
        if eng.pool.idle():
            if t_close is None:
                break
            wake = recs[nxt].due if nxt < len(recs) else t_close
            with _annotate("wait", traced):
                time.sleep(max(0.0, min(wake, t_close) - clock()))
            continue
        decoding = [r for r in live if r.stamps and not r.session.done]
        ctx = sum(r.prompt_len + len(r.stamps) for r in decoding)
        before = eng.stats.decode_steps
        with _annotate("step", traced):
            eng.step()
        t = clock()
        if t_open is not None and t > t_open:
            steps += 1
            heapq.heappush(gaps, (t - now, steps))
            if len(gaps) > 3:
                heapq.heappop(gaps)
        if eng.stats.decode_steps > before:
            log.append((t, ctx, len(decoding)))
        still = []
        for r in live:
            n = len(r.session.tokens)
            if n > len(r.stamps):
                r.stamps.extend([t] * (n - len(r.stamps)))
            if not r.session.done:
                still.append(r)
        live = still
        if backlog and t_open is None:
            # the window opens once every slot decodes, or once the pool
            # admits no more and every admitted request decodes
            busy = [r for r in live if r.stamps]
            prefilling = [r for r in live if r.t_admit is not None
                          and not r.stamps]
            if len(busy) == slots or (busy and not prefilling
                                      and eng.pool.free_slots):
                t_open, stats_open, steps = t, _stats(eng), 0
                t_close = t_open + seconds
                log = []
    if tracing:
        import jax
        jax.profiler.stop_trace()
    return {"recs": recs, "t_open": t_open, "t_close": t_close,
            "t_end": t_end, "stats_open": stats_open,
            "stats_close": _stats(eng), "decode_log": log, "steps": steps, "late": late,
            "t_start": t_start, "prompts": prompts,
            "longest_steps": sorted(gaps, reverse=True)}


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------


def pct(values, q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_ms(win: Window) -> list:
    """Due time to first visible token, for every request due in the
    window; one still waiting at close enters at its age at close."""
    out = []
    for r in win.due_in_window():
        first = r.stamps[0] if r.stamps else win.t_close
        out.append((min(first, win.t_close) - r.due) * 1e3)
    return out


def itl_ms(win: Window) -> list:
    """Every gap between consecutive tokens of a request that closes in
    the window."""
    out = []
    for r in win.recs:
        s = r.stamps
        for a, b in zip(s, s[1:]):
            if win.t_open < b <= win.t_close:
                out.append((b - a) * 1e3)
    return out


def out_tok_s(win: Window) -> float:
    """Every token of the steps begun in the window, over all their time:
    the last step, which ends after the close, counts to its end."""
    end = win.t_close if win.t_end is None else win.t_end
    n = sum(1 for r in win.recs for t in r.stamps if win.t_open < t <= end)
    return n / (end - win.t_open)


def completed(win: Window) -> list:
    """The requests that served their last token in the window."""
    return [r for r in win.recs if r.stamps and len(r.stamps) == r.max_new
            and win.t_open < r.stamps[-1] <= win.t_close]


def end_to_end(win: Window, names) -> dict:
    units = {"itl_p95_ms": "ms", "out_tok_s": "tokens/s"}
    fns = {"itl_p95_ms": lambda: pct(itl_ms(win), 95),
           "out_tok_s": lambda: out_tok_s(win)}
    return {n: {"value": fns[n](), "unit": units[n]} for n in names
            if n in fns}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def pick_sample(win: Window, seed: int, want_tokens: int,
                max_requests: int) -> list:
    """Requests to compare: the one with the longest sequence, then others
    drawn from the seed, until ``want_tokens`` served tokens are in."""
    done = [r for r in win.recs if len(r.stamps) >= 1]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    longest = max(done, key=lambda r: (r.prompt_len + len(r.stamps), -r.rid))
    rest = [done[i] for i in rng.permutation(len(done)) if done[i] is not longest]
    shared = [r for r in rest if r.shared]
    order = [longest] + shared[:1] + [r for r in rest if r not in shared[:1]]
    out, n = [], 0
    for r in order:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.stamps)
    return out


def served_tokens(sample) -> list:
    return [np.asarray(r.session.tokens[:len(r.stamps)], np.int32)
            for r in sample]


def gaps(cell: Cell, seed: int, prompts, tokens, modes=("ref",)) -> dict:
    """Per mode, how far the compared tokens' reference logits lie below
    the reference's best at their positions: the widest such gap, and the
    mean over all compared tokens.  Under ``ref`` the compared tokens are
    the served ones; under a lower precision, the tokens that precision
    puts first."""
    ref = load_module(cell.root / "bench" / "references" / f"{cell.reference}.py",
                      f"bench_reference_{cell.reference}")
    seqs, reads = [], []
    for p, t in zip(prompts, tokens):
        seqs.append(np.concatenate([p, t[:-1]]))
        reads.append(np.arange(len(p) - 1, len(p) - 1 + len(t)))
    want = ("ref",) + tuple(m for m in modes if m != "ref")
    logits = ref.logits_at(cell.conf, seed, seqs, reads, want)
    out = {}
    for m in modes:
        below = []
        for lg_ref, lg_m, t in zip(logits["ref"], logits[m], tokens):
            pick = t if m == "ref" else np.argmax(lg_m, -1)
            best = lg_ref.max(-1)
            below.append(best - np.take_along_axis(lg_ref, pick[:, None],
                                                   -1)[:, 0])
        below = np.concatenate(below) if below else np.zeros(0)
        out[m] = {"widest_gap": float(below.max(initial=0.0)),
                  "mean_gap": float(below.mean()) if below.size else 0.0}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def trace_dir_for(cell: Cell, seed: int) -> pathlib.Path:
    return cell.root / ".bench_traces" / f"{cell.name}-{seed}"


def local_device(root: pathlib.Path = ROOT) -> dict:
    """The device of a run off the chip (the CPU tests of the harness),
    measured against the first row of the peaks table."""
    import jax
    d = jax.devices()[0]
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()),
            "peaks": next(iter(peaks["devices"].values()))}


@dataclasses.dataclass
class Measured:
    win: Window
    setup_s: float
    memory_peak_bytes: int
    prompts: list                   # of the requests the check compares
    tokens: list


def measure(cell: Cell, cfg, seed: int, seconds: float, dev: dict, *,
            t_process: float, trace_dir=None, fault=None) -> Measured:
    """Set-up, the window, and what the check needs; the engine is freed
    before this returns, so the reference can have the chip."""
    eng, pool = build_engine(cell, cfg, seed)
    warm_up(eng, cfg.vocab)
    if fault is not None:
        fault(eng)
    backlog = cell.traffic["arrivals"]["kind"] == "backlog"
    horizon = 0.0 if backlog else float(cell.traffic["ramp_s"]) + seconds
    plan = traffic_gen.generate(cell.traffic, seed, cfg.vocab, horizon)
    setup_s = clock() - t_process
    raw = drive(eng, cell, plan, seconds, trace_dir)
    peak = memory_peak_bytes() if dev["platform"] == "tpu" else 0
    geometry = {"slots": eng.slots, "s_max": eng.s_max,
                "block_size": eng.block_size, "n_blocks": eng.n_blocks,
                "prefill_chunk": eng.prefill_chunk}
    dims = {k: v for k, v in cell.conf.items()
            if isinstance(v, (bool, int, float))}
    win = Window(raw["t_open"], raw["t_close"], raw["recs"],
                 raw["stats_open"], raw["stats_close"], raw["decode_log"],
                 raw["steps"], dims, geometry, dev["peaks"],
                 gen_late_s=raw["late"], pool=pool,
                 longest_steps=raw["longest_steps"], t_end=raw["t_end"])
    sample = pick_sample(win, seed, cell.settings["check_tokens"],
                         cell.settings["check_requests"])
    tokens = served_tokens(sample)
    prompts = [raw["prompts"][r.rid] for r in sample]
    eng.params = eng._state = None
    del eng
    gc.collect()
    return Measured(win, setup_s, peak, prompts, tokens)


def judge(cell: Cell, read: dict, n_tok: int) -> tuple[bool, list]:
    """``correct``, and each number compared beside its limit."""
    checks = [(name, read[name], lim)
              for name, lim in cell.settings["limits"].items()]
    checks.append(("compared_tokens_min", n_tok,
                   cell.settings["check_min_tokens"]))
    return all(v <= lim for _, v, lim in checks[:-1]) and \
        n_tok >= cell.settings["check_min_tokens"], checks


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, root: pathlib.Path = ROOT,
             require_tpu: bool = True, cfg_overrides: dict | None = None,
             fault=None, control: bool = False) -> tuple[dict, list]:
    """One run.  Returns the result line (as a dict) and the check lines.

    ``require_tpu=False`` and ``cfg_overrides`` exist for the CPU tests of
    the harness; the command line never sets them.  ``fault``, also for
    tests only, is called with the engine before the window opens.
    ``control`` puts the float8 reference in the program's place: the
    tokens judged are those it puts first (its runs must not be correct).
    """
    cell = resolve(workload, root)
    dev = device_info(cell.chips, root) if require_tpu else local_device(root)
    cfg = build_cfg(cell.conf, cfg_overrides)
    tdir = None
    if trace:
        tdir = trace_dir_for(cell, seed)
        shutil.rmtree(tdir, ignore_errors=True)
    m = measure(cell, cfg, seed, seconds, dev, t_process=t_process,
                trace_dir=tdir, fault=fault)
    win = m.win
    if trace:
        import trace_reduce
        win.trace = trace_reduce.reduce_dir(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        metrics = {}
        for spec in cell.per_layer:
            reader = load_module(
                reader_path(cell.root, spec["name"]),
                "bench_metric_" + spec["name"].replace(".", "_"))
            v = reader.read(win)
            if v is not None:
                metrics[spec["name"]] = {"value": float(v),
                                         "unit": spec["unit"]}
    else:
        metrics = end_to_end(win, [e["name"] for e in cell.end_to_end])
        metrics["setup_s"] = {"value": m.setup_s, "unit": "s"}

    mode = "fp8" if control else "ref"
    t_check = clock()
    read = gaps(cell, seed, m.prompts, m.tokens, (mode,))[mode]
    check_s = clock() - t_check
    correct, checks = judge(cell, read, int(sum(len(t) for t in m.tokens)))
    backlog = cell.traffic["arrivals"]["kind"] == "backlog"
    attempted = (sum(1 for r in win.recs if r.session is not None)
                 if backlog else len(win.due_in_window()))
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": m.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if trace and win.trace:
        device["busy_s"] = win.trace["busy_s"]
        device["window_s"] = win.trace["window_s"]
        result["breakdown"] = win.trace["breakdown"]
    itl, ttft = itl_ms(win), ttft_ms(win)
    result["run"] = {
        "setup_s": m.setup_s, "generator_late_s": win.gen_late_s,
        "itl_p50_ms": pct(itl, 50) if itl else None, "itl_n": len(itl),
        "ttft_p50_ms": pct(ttft, 50) if ttft else None, "ttft_n": len(ttft),
        "out_tok_s": out_tok_s(win),
        "window_s": win.seconds,
        "measured_s": None if win.t_end is None else win.t_end - win.t_open,
        "steps": win.steps,
        "requests_due": len(win.due_in_window()),
        "decode_steps": win.delta("decode_steps"),
        "prefill_chunks": win.delta("prefill_chunks"),
        "completed": len(completed(win)),
        "longest_steps_ms": [[1e3 * s, i] for s, i in win.longest_steps],
        "pool": win.pool,
        "compared_requests": len(m.tokens), "check_s": check_s}
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result, checks
