"""The engine's decode and KV-pool counters over a tiny run, against what
the harness logs itself, and the readers of the metrics built on them."""

from __future__ import annotations

import time

import pytest

import bench_tiny
import harness

NEW = ("host_gap_ms", "batch_rows", "kv_block_use")
STATS = {"decode_steps": 0, "decode_rows": 0, "decode_ctx_tokens": 0,
         "kv_reserved_block_steps": 0, "kv_live_block_steps": 0,
         "host_turns": 0, "host_turn_ns": 0}


@pytest.fixture(scope="module")
def tiny_decode(tmp_path_factory):
    """A window of the backlog cell with requests in flight: some finish
    and refills prefill and join the decode batch inside it."""
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    cell = harness.resolve(bench_tiny.DECODE_CELL, root)
    cfg = harness.build_cfg(cell.conf, bench_tiny.OVERRIDES)
    return harness.measure(cell, cfg, 2**31 + 11, 1.5,
                           harness.local_device(root),
                           t_process=time.monotonic()).win


def test_decode_counters_agree_with_the_harness_log(tiny_decode):
    """The engine counts every row that decodes a token and the context it
    attends; the harness's log misses only a refill that finishes its
    prefill and decodes in the same step (two tokens stamped at once),
    which it has not yet seen decode."""
    win = tiny_decode
    joined = [r for r in win.recs if len(r.stamps) >= 2
              and r.stamps[0] == r.stamps[1] and r.stamps[0] > win.t_open]
    assert joined and win.delta("decode_steps") == len(win.decode_log)
    assert win.delta("decode_rows") == \
        sum(n for _, _, n in win.decode_log) + len(joined)
    assert win.delta("decode_ctx_tokens") == \
        sum(c for _, c, _ in win.decode_log) + \
        sum(r.prompt_len + 1 for r in joined)


def test_live_blocks_are_among_the_reserved(tiny_decode):
    win = tiny_decode
    live, held = (win.delta("kv_live_block_steps"),
                  win.delta("kv_reserved_block_steps"))
    assert 0 < live <= held
    assert win.delta("host_turns") == win.delta("decode_steps")
    assert win.delta("host_turn_ns") > 0


def _reader(name):
    return harness.load_module(harness.reader_path(bench_tiny.REPO, name),
                               "test_metric_" + name.replace(".", "_"))


def _win(close=None, open_=None):
    return harness.Window(10.0, 20.0, [], dict(open_ or STATS),
                          dict(close or STATS), [], 0, {}, {}, {})


@pytest.mark.parametrize("name", NEW + tuple(n + ".offline" for n in NEW))
def test_new_readers_read_nothing_in_an_empty_window(name):
    assert _reader(name).read(_win()) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_without_the_engine_s_counters(name):
    """A program without the counters (the one before them) reads
    nothing, rather than failing."""
    bare = {"decode_steps": 0}
    assert _reader(name).read(_win(bare, bare)) is None


def test_new_readers_on_a_hand_built_window():
    close = dict(STATS, decode_steps=10, decode_rows=300,
                 decode_ctx_tokens=300 * 370, kv_reserved_block_steps=8000,
                 kv_live_block_steps=2000, host_turns=9,
                 host_turn_ns=9 * 4_500_000)
    open_ = dict(STATS, decode_steps=2, decode_rows=44,
                 kv_reserved_block_steps=1600, kv_live_block_steps=400,
                 host_turns=1, host_turn_ns=1_000_000)
    win = _win(close, open_)
    assert _reader("batch_rows.offline").read(win) == pytest.approx(32.0)
    assert _reader("kv_block_use.offline").read(win) == pytest.approx(25.0)
    assert _reader("host_gap_ms.offline").read(win) == \
        pytest.approx((9 * 4.5 - 1.0) / 8)
