"""The engine's counters of the paged attention kernel's walk over a tiny
run, and the reader of ``attn_live_share``, which is built on them."""

from __future__ import annotations

import time

import pytest

import bench_tiny
import harness

STATS = {"decode_steps": 0, "decode_rows": 0,
         "decode_kv_blocks_read": 0, "decode_kv_blocks_live": 0}


@pytest.fixture(scope="module")
def tiny_decode(tmp_path_factory):
    """A window of the backlog cell with requests in flight."""
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    cell = harness.resolve(bench_tiny.DECODE_CELL, root)
    cfg = harness.build_cfg(cell.conf, bench_tiny.OVERRIDES)
    return harness.measure(cell, cfg, 2**31 + 13, 1.5,
                           harness.local_device(root),
                           t_process=time.monotonic()).win


def _reader(name):
    return harness.load_module(harness.reader_path(bench_tiny.REPO, name),
                               "test_metric_" + name.replace(".", "_"))


def _win(close=None, open_=None):
    return harness.Window(10.0, 20.0, [], dict(open_ or STATS),
                          dict(close or STATS), [], 0, {}, {}, {})


def test_the_attention_walk_reads_every_live_block(tiny_decode):
    """The kernel's walk reads at least the blocks that hold each decoding
    row's keys, and a row holds at least one."""
    win = tiny_decode
    live, read = (win.delta("decode_kv_blocks_live"),
                  win.delta("decode_kv_blocks_read"))
    assert 0 < win.delta("decode_rows") <= live <= read


@pytest.mark.parametrize("name", ["attn_live_share",
                                  "attn_live_share.offline"])
def test_live_share_reads_nothing_in_an_empty_window(name):
    assert _reader(name).read(_win()) is None


def test_live_share_reads_nothing_without_the_engine_s_counters():
    """A program without the counters (the one before them) reads
    nothing, rather than failing."""
    bare = {"decode_steps": 0}
    assert _reader("attn_live_share.offline").read(_win(bare, bare)) is None


def test_live_share_is_live_over_read_between_the_window_s_ends():
    close = dict(STATS, decode_steps=10, decode_rows=300,
                 decode_kv_blocks_read=9600, decode_kv_blocks_live=7200)
    open_ = dict(STATS, decode_steps=2, decode_rows=44,
                 decode_kv_blocks_read=1600, decode_kv_blocks_live=1200)
    assert _reader("attn_live_share.offline").read(_win(close, open_)) == \
        pytest.approx(75.0)
