"""The metric arithmetic: TTFT from due time, ITL over every gap, the
late-request rule, rates over the whole window, and the per-layer readers."""

from __future__ import annotations

import json
import types

import pytest

import bench_tiny
import harness
import roofline

DIMS = {"hidden_size": 2560, "num_hidden_layers": 36,
        "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
        "intermediate_size": 9728, "vocab_size": 151936, "binarized": True}
PEAKS = json.loads((bench_tiny.BENCH / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]
STATS = {"prompt_tokens": 0, "prefix_tokens": 0, "prefill_chunks": 0,
         "decode_steps": 0}


def _rec(rid, due, stamps, t_admit=None, prompt_len=100):
    r = harness.Rec(rid, due, prompt_len, 10, False, stamps=list(stamps))
    r.session = types.SimpleNamespace(t_admit=t_admit, done=False)
    return r


def _win(recs, t_open=10.0, t_close=20.0, log=(), stats_close=None,
         trace=None, geometry=None):
    return harness.Window(
        t_open, t_close, recs, dict(STATS), dict(stats_close or STATS),
        list(log), 0, DIMS,
        geometry or {"slots": 32, "prefill_chunk": 32, "block_size": 16},
        PEAKS, trace)


def _reader(name):
    return harness.load_module(harness.reader_path(bench_tiny.REPO, name),
                               "test_metric_" + name.replace(".", "_"))


def test_ttft_runs_from_due_time_with_the_late_rule():
    recs = [
        _rec(0, 9.0, [9.5, 9.6]),          # due before the window: out
        _rec(1, 11.0, [11.25, 11.5]),      # 250 ms
        _rec(2, 12.0, []),                 # still waiting at close: 8 s
        _rec(3, 19.0, [20.5]),             # first token after close: 1 s
        _rec(4, 20.0, [20.1]),             # due at close: out
    ]
    got = sorted(harness.ttft_ms(_win(recs)))
    assert got == pytest.approx([250.0, 1000.0, 8000.0])


def test_itl_covers_every_gap_closing_in_the_window():
    recs = [_rec(0, 0.0, [9.0, 10.5, 11.0, 13.0, 20.5]),
            _rec(1, 0.0, [15.0, 15.1])]
    got = sorted(harness.itl_ms(_win(recs)))
    # 9.0 -> 10.5 closes inside; 13.0 -> 20.5 closes after the close
    assert got == pytest.approx([100.0, 500.0, 1500.0, 2000.0])


@pytest.mark.parametrize("t_end, want", [(None, 3 / 10.0),
                                          (20.5, 4 / 10.5)])
def test_out_tok_s_is_over_the_whole_window(t_end, want):
    # with ``t_end`` the step begun before the close at 20.0 ends at 20.5:
    # its token and its time both count, so the rate moves with the step
    # time, not by whole steps
    recs = [_rec(0, 0.0, [9.0, 10.5, 11.0]), _rec(1, 0.0, [19.9, 20.1])]
    win = _win(recs)
    win.t_end = t_end
    assert harness.out_tok_s(win) == pytest.approx(want)


def test_end_to_end_units_and_percentiles():
    recs = [_rec(i, 0.0, [10.0 + 0.2 * j for j in range(i + 2)])
            for i in range(10)]
    m = harness.end_to_end(_win(recs), ["itl_p95_ms", "out_tok_s"])
    assert m["itl_p95_ms"] == {"value": pytest.approx(200.0), "unit": "ms"}
    assert m["out_tok_s"] == {"value": pytest.approx(5.5),
                              "unit": "tokens/s"}


def test_queue_wait_reader_and_its_late_rule():
    recs = [_rec(0, 11.0, [12.0], t_admit=11.5), _rec(1, 12.0, [], None)]
    got = _reader("queue_wait_p90_ms").read(_win(recs))
    assert got == pytest.approx(harness.pct([500.0, 8000.0], 90))


def test_prefix_share_reader():
    win = _win([], stats_close=dict(STATS, prompt_tokens=4000,
                                    prefix_tokens=1000))
    assert _reader("prefix_token_share").read(win) == pytest.approx(25.0)
    assert _reader("prefix_token_share").read(_win([])) is None


def _trace(decode_s, n_decode, xnor_s, attn_s, busy=8.0, window=10.0):
    return {"busy_s": busy, "window_s": window,
            "programs": {"jit_decode_fn": {"count": n_decode,
                                           "seconds": decode_s},
                         "jit_chunk_fn": {"count": 4, "seconds": 0.2}},
            "per_program": {"jit_decode_fn": {
                "xnor_linear_fused": {"count": 252 * n_decode,
                                      "seconds": xnor_s},
                "paged_decode_attention": {"count": 36 * n_decode,
                                           "seconds": attn_s}}}}


def test_trace_readers():
    log = [(11.0, 32 * 1000, 32), (12.0, 32 * 1002, 32)]
    n = 10
    floor_x = n * roofline.xnor_step_floor_s(DIMS, 32, PEAKS)
    floor_a = n * roofline.paged_attn_floor_s(DIMS, 32 * 1001, 32, PEAKS)
    win = _win([], log=log, trace=_trace(1.0, n, 4 * floor_x, 2 * floor_a))
    assert _reader("xnor_roofline").read(win) == pytest.approx(25.0)
    assert _reader("attn_roofline").read(win) == pytest.approx(50.0)
    assert _reader("decode_ms").read(win) == pytest.approx(100.0)
    assert _reader("chunk_ms").read(win) == pytest.approx(50.0)
    assert _reader("idle_share").read(win) == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["chunk_ms", "decode_ms", "xnor_roofline",
                                  "attn_roofline", "idle_share",
                                  "decode_ms.offline", "idle_share.offline",
                                  "xnor_roofline.offline",
                                  "attn_roofline.offline"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert _reader(name).read(_win([])) is None


def test_step_mfu_counts_decoded_tokens_and_chunk_rows():
    log = [(11.0, 32 * 500, 32), (12.0, 32 * 501, 32), (25.0, 10, 1)]
    win = _win([], log=log, stats_close=dict(STATS, prefill_chunks=3))
    want = roofline.model_ops(DIMS, 64 + 3 * 32, 32 * 1001) / (
        10.0 * PEAKS["bf16_flops_per_s"])
    assert _reader("step_mfu").read(win) == pytest.approx(100.0 * want)
    assert _reader("step_mfu.offline").read(_win([])) is None


def test_xnor_floor_is_memory_bound_for_one_row_and_int8_for_many():
    k, n = 2560, 9728
    one = roofline.xnor_linear_floor_s(1, k, n, PEAKS)
    assert one == pytest.approx((k * n / 8 + 4 * n + 2 * k + 4 * n)
                                / PEAKS["hbm_bytes_per_s"])
    many = roofline.xnor_linear_floor_s(4096, k, n, PEAKS)
    assert many == pytest.approx(2 * 4096 * k * n / PEAKS["int8_ops_per_s"])


def test_model_ops_counts_the_head_and_attention():
    dims = dict(DIMS, num_hidden_layers=1)
    per_tok = 2 * (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560
                   + 3 * 2560 * 9728 + 2560 * 151936)
    assert roofline.model_ops(dims, 1, 0) == per_tok
    assert roofline.model_ops(dims, 1, 10) == per_tok + 4 * 32 * 128 * 10
