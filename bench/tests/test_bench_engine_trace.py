"""The engine's spans in a trace: on a small synthetic trace, where they
leave ``trace_reduce``'s outputs as they are and label the idle gaps they
hold, and in a recorded trace of a real engine step."""

from __future__ import annotations

import pytest

import bench_tiny
import engine_trace
import trace_reduce

MS = 1_000_000
BASE_KEYS = ("window_s", "busy_s", "programs", "ops", "per_program")


def _events():
    """One engine step inside the harness's ``step`` span (1-19 ms); the
    device runs the decode program 5-12 ms and a fusion 16-17 ms."""
    harness = [("submit", 0, 1 * MS), ("step", 1 * MS, 18 * MS),
               ("wait", 19 * MS, 1 * MS)]
    engine = [("serve.step#_r=1,step_num=4#", 1 * MS, 18 * MS),
              ("serve.admit#rid=7#", 1 * MS, 1 * MS),
              ("serve.decode.prepare", 2 * MS, 2 * MS),
              ("serve.decode.dispatch", 4 * MS, 1 * MS),
              ("serve.decode.sync", 5 * MS, 8 * MS),
              ("serve.decode.commit", 13 * MS, 2 * MS),
              ("serve.decode.prepare", 15 * MS + MS // 2, MS // 2),
              ("serve.decode.dispatch", 16 * MS, 1 * MS),
              ("serve.decode.sync", 17 * MS, 2 * MS)]
    modules = [("jit_decode_fn(3)", 5 * MS, 7 * MS),
               ("jit_decode_fn(3)", 16 * MS, 1 * MS)]
    ops = [("paged_decode_attention", 5 * MS, 5 * MS),
           ("xnor_linear_fused.2", 10 * MS, 1 * MS),
           ("copy.4", 11 * MS, 1 * MS),
           ("fusion.9", 16 * MS, 1 * MS)]
    scopes = ["attn.core", "mlp", None, "head"]
    devices = {"/device:TPU:0": {"ops": ops, "modules": modules,
                                 "op_scopes": scopes}}
    return devices, harness, engine


def test_engine_spans_leave_the_base_reduction_as_it_is():
    devices, harness, engine = _events()
    alone = trace_reduce.reduce_events(devices, harness)
    both = trace_reduce.reduce_events(devices, harness + engine)
    for key in BASE_KEYS:
        assert both[key] == alone[key]
    assert alone["window_s"] == pytest.approx(20e-3)
    assert alone["busy_s"] == pytest.approx(8e-3)
    # the engine reduction adds keys of its own and none of the base's
    extra = engine_trace.reduce_events(devices, harness + engine)
    assert not set(extra) & set(alone)


def test_gaps_are_labelled_by_the_innermost_engine_span():
    devices, harness, engine = _events()
    red = engine_trace.reduce_events(devices, harness + engine)
    # idle: 0-5 ms (middle 2.5: prepare), 12-16 ms (middle 14: commit),
    # 17-20 ms (middle 18.5: sync, inside serve.step inside step)
    assert red["idle_by_span"] == {
        "serve.decode.prepare": pytest.approx(5e-3),
        "serve.decode.commit": pytest.approx(4e-3),
        "serve.decode.sync": pytest.approx(3e-3)}
    assert red["idle_gaps"][0] == ["serve.decode.prepare",
                                   pytest.approx(5e-3)]


def test_a_gap_outside_every_engine_span_keeps_step():
    devices, harness, engine = _events()
    # no engine span holds 13-15 ms now: the gap 12-16 ms is the harness's
    engine = [e for e in engine if not e[0].startswith("serve.decode.commit")
              and not e[0].startswith("serve.step")]
    red = engine_trace.reduce_events(devices, harness + engine)
    assert red["idle_by_span"]["step"] == pytest.approx(4e-3)
    # without the engine's spans every gap in eng.step() says ``step``
    red = engine_trace.reduce_events(devices, harness)
    assert red["idle_by_span"] == {"step": pytest.approx(12e-3)}


def test_a_gap_outside_the_harness_spans_is_untraced():
    devices, harness, engine = _events()
    harness = [h for h in harness if h[0] != "step"] + [
        ("step", 1 * MS, 11 * MS), ("step", 13 * MS, 6 * MS)]
    engine = [e for e in engine if not e[0].startswith(("serve.step",
                                                        "serve.decode.c"))]
    red = engine_trace.reduce_events(devices, harness + engine)
    # 12-16 ms: its middle, 14 ms, lies in the second step and no span of
    # the engine; move the second step to start at 15 ms and it is untraced
    assert red["idle_by_span"]["step"] == pytest.approx(4e-3)
    harness[-1] = ("step", 15 * MS, 4 * MS)
    red = engine_trace.reduce_events(devices, harness + engine)
    assert red["idle_by_span"]["untraced"] == pytest.approx(4e-3)


def test_host_turns_run_from_sync_end_to_the_next_dispatch():
    devices, harness, engine = _events()
    red = engine_trace.reduce_events(devices, harness + engine)
    # sync ends at 13 ms, the next dispatch starts at 16 ms; the last sync
    # (ending at 19 ms) has no dispatch after it
    assert red["host_turns"] == [pytest.approx(3e-3)]


def test_engine_spans_count_total_and_self_time():
    devices, harness, engine = _events()
    eng = engine_trace.reduce_events(devices, harness + engine)["engine"]
    # all but 15-15.5 ms of serve.step lies in the spans inside it
    assert eng["serve.step"] == {"count": 1, "seconds": pytest.approx(18e-3),
                                 "self_seconds": pytest.approx(0.5e-3)}
    assert eng["serve.admit"]["count"] == 1
    assert eng["serve.decode.prepare"] == {
        "count": 2, "seconds": pytest.approx(2.5e-3),
        "self_seconds": pytest.approx(2.5e-3)}
    assert eng["serve.decode.sync"]["seconds"] == pytest.approx(10e-3)
    assert set(eng) == {"serve.step", "serve.admit", "serve.decode.prepare",
                        "serve.decode.dispatch", "serve.decode.sync",
                        "serve.decode.commit"}


def test_per_scope_takes_the_decode_program_s_ops():
    devices, harness, engine = _events()
    red = engine_trace.reduce_events(devices, harness + engine)
    assert red["per_scope"] == {"attn.core": pytest.approx(5e-3),
                                "mlp": pytest.approx(1e-3),
                                "unscoped": pytest.approx(1e-3),
                                "head": pytest.approx(1e-3)}
    assert red["per_scope_ops"]["unscoped"] == {"copy": pytest.approx(1e-3)}
    del devices["/device:TPU:0"]["op_scopes"]
    assert "per_scope" not in engine_trace.reduce_events(devices,
                                                         harness + engine)


def test_lags_split_the_idle_between_executions_around_the_host_turn():
    devices, harness, engine = _events()
    # a one-op program the host dispatches while it prepares, 14-14.25 ms,
    # splits the idle but not the pair of decode executions around it
    d = devices["/device:TPU:0"]
    d["modules"].append(("jit_broadcast_in_dim(1)", 14 * MS, MS // 4))
    d["ops"].append(("broadcast.1", 14 * MS, MS // 4))
    d["op_scopes"].append(None)
    red = engine_trace.reduce_events(devices, harness + engine)
    # between the executions, 12-16 ms: the sync ends 1 ms after the first
    # one's last op and the next dispatch starts as the second's first op
    # does; with the 3 ms turn between them, the device's idle
    assert red["read_lag"] == [pytest.approx(1e-3)]
    assert red["launch_lag"] == [pytest.approx(0.0)]
    assert red["host_turns"] == [pytest.approx(3e-3)]
    # a device clock 2 ms ahead of the host's: the launch lag goes negative
    # and the read lag grows by as much; an idle stretch inside an
    # execution (10.5-11 ms) counts for nothing
    d = devices["/device:TPU:0"]
    d["ops"][1] = ("xnor_linear_fused.2", 10 * MS, MS // 2)
    d["ops"] = [(n, s - 2 * MS, t) for n, s, t in d["ops"]]
    d["modules"] = [(n, s - 2 * MS, t) for n, s, t in d["modules"]]
    red = engine_trace.reduce_events(devices, harness + engine)
    assert red["read_lag"] == [pytest.approx(3e-3)]
    assert red["launch_lag"] == [pytest.approx(-2e-3)]


def test_no_harness_spans_or_no_device_reads_nothing():
    devices, harness, engine = _events()
    assert engine_trace.reduce_events(devices, engine) == {}
    assert engine_trace.reduce_events({}, harness + engine) == {}


def test_scope_and_span_names():
    assert engine_trace.span_name("serve.admit#rid=3#") == "serve.admit"
    assert engine_trace.span_name("serve.decode.sync") == "serve.decode.sync"
    path = "jit(decode_fn)/while/body/attn.core/attn.kv_write/scatter"
    assert engine_trace.scope_of(path) == "attn.kv_write"
    assert engine_trace.scope_of("jit(decode_fn)/while/body/add") is None


@pytest.fixture(scope="module")
def tiny_engine():
    import dataclasses

    import jax
    from repro import configs
    from repro.models import lm
    from repro.serve import ServeEngine

    cfg = dataclasses.replace(configs.get("qwen3-4b+xnor"),
                              **bench_tiny.OVERRIDES)
    return ServeEngine(cfg, lm.init_params(cfg, jax.random.PRNGKey(0)),
                       slots=2, s_max=32)


def test_scope_map_of_the_compiled_decode_program(tiny_engine):
    eng = tiny_engine
    text = eng._paged_decode_program.lower(
        *eng._decode_args()).compile().as_text()
    scopes = engine_trace.scope_map(text)
    assert set(scopes.values()) == set(engine_trace.SCOPES)
    assert engine_trace.instruction("%fusion.12 = f32[8] fusion(%p)") == \
        "fusion.12"
    assert engine_trace.scope_map(
        '  ROOT %copy-done.3 = f32[2] copy-done(%c), metadata={op_name='
        '"jit(decode_fn)/while/body/attn.core/attn.kv_write/scatter"}') == \
        {"copy-done.3": "attn.kv_write"}


def test_recorded_engine_step_emits_its_spans_in_order(tmp_path, tiny_engine):
    """A real ``ServeEngine`` step on the tiny config, in a CPU profiler
    trace: admission, the one prefill chunk, its token read, then the
    decode step's four spans, all inside ``serve.step``."""
    import jax
    import numpy as np
    from repro.serve import Request

    eng = tiny_engine
    prompt = np.arange(1, 7, dtype=np.int32)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
    while eng.step():                   # compiles outside the trace
        pass
    eng.submit(Request(rid=1, prompt=prompt + 1, max_new_tokens=3))
    jax.profiler.start_trace(str(tmp_path))
    eng.step()
    jax.profiler.stop_trace()
    _, host = engine_trace.load(next(tmp_path.glob("**/*.xplane.pb")))
    spans = sorted((s, -d, engine_trace.span_name(n)) for n, s, d in host)
    assert [n for _, _, n in spans] == [
        "serve.step", "serve.admit", "serve.prefill.dispatch",
        "serve.prefill.sync", "serve.decode.prepare", "serve.decode.dispatch",
        "serve.decode.sync", "serve.decode.commit"]
    start, end = spans[0][0], spans[0][0] - spans[0][1]
    assert all(start <= s and s - d <= end for s, d, _ in spans[1:])
