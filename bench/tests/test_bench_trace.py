"""The trace reduction, on a small synthetic trace and on a recorded one."""

from __future__ import annotations

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import trace_reduce

MS = 1_000_000


def _events():
    # host: submit 0-1 ms, step 1-9 ms, wait 9-14 ms, step 14-20 ms
    host = [("submit", 0, 1 * MS), ("step", 1 * MS, 8 * MS),
            ("wait", 9 * MS, 5 * MS), ("step", 14 * MS, 6 * MS),
            ("unrelated", 0, 50 * MS)]
    modules = [("jit_decode_fn(3)", 2 * MS, 4 * MS),
               ("jit_chunk_fn(4)", 15 * MS, 3 * MS)]
    ops = [("fusion.1", 2 * MS, 2 * MS),
           ("xnor_linear_fused.2", 3 * MS, 2 * MS),     # overlaps fusion.1
           ("paged_decode_attention", 5 * MS, 1 * MS),
           ("fusion.7", 15 * MS, 3 * MS),
           ("copy", 25 * MS, 1 * MS)]                  # after the window
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}, host


def test_busy_union_window_and_programs():
    red = trace_reduce.reduce_events(*_events())
    assert red["window_s"] == pytest.approx(20e-3)
    # ops cover 2-6 ms and 15-18 ms: 7 ms busy, the overlap counted once
    assert red["busy_s"] == pytest.approx(7e-3)
    assert red["programs"]["jit_decode_fn"] == {"count": 1,
                                                "seconds": pytest.approx(4e-3)}
    dec = red["per_program"]["jit_decode_fn"]
    assert set(dec) == {"fusion", "xnor_linear_fused", "paged_decode_attention"}
    assert red["per_program"]["jit_chunk_fn"]["fusion"]["seconds"] == \
        pytest.approx(3e-3)
    assert "copy" not in red["ops"]


def test_idle_gaps_are_labelled_by_the_host_span():
    red = trace_reduce.reduce_events(*_events())
    # gaps: 0-2 ms (submit at 0.5... middle 1 ms -> step starts at 1 ms),
    # 6-15 ms (middle 10.5 ms: wait), 18-20 ms (middle 19 ms: step)
    assert red["idle_by_host_span"] == {
        "step": pytest.approx(4e-3), "wait": pytest.approx(9e-3)}
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["wait", pytest.approx(9e-3)]
    assert red["breakdown"]["device_ops"][0][0] in ("fusion",)


def test_two_chips_average_their_busy_time():
    devices, host = _events()
    devices["/device:TPU:1"] = {"ops": [("fusion", 0, 20 * MS)],
                                "modules": []}
    red = trace_reduce.reduce_events(devices, host)
    assert red["busy_s"] == pytest.approx((7e-3 + 20e-3) / 2)


def test_no_host_spans_or_no_device_reads_nothing():
    devices, host = _events()
    assert trace_reduce.reduce_events(devices, []) == {}
    assert trace_reduce.reduce_events({}, host) == {}


def test_stems():
    assert trace_reduce.stem("jit_decode_fn(17)") == "jit_decode_fn"
    assert trace_reduce.stem("fusion.123") == "fusion"
    assert trace_reduce.stem("custom-call.4.1") == "custom-call"


def test_recorded_cpu_trace_reads_host_spans(tmp_path):
    """A real profiler trace of a CPU run: the host spans are found, and
    with no TPU plane the reduction reads nothing rather than a zero."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for name in ("submit", "step"):
        with jax.profiler.TraceAnnotation(name):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    devices, host = trace_reduce.load(next(tmp_path.glob("**/*.xplane.pb")))
    assert {n for n, _, _ in host} == {"submit", "step"}
    assert devices == {}
    assert trace_reduce.reduce_dir(tmp_path) == {}
