"""The engine's per-decode-step counters: rows that decode and the context
they attend, the table blocks the attention kernel's walk reads for them
against those holding their keys, KV blocks held against blocks written (a
shared prefix block once), and the host's turn between a decode step's
token read and the next decode dispatch."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import lm
from repro.serve import EngineStats, Request, ServeEngine

COUNTERS = ("decode_rows", "decode_ctx_tokens", "decode_kv_blocks_read",
            "decode_kv_blocks_live", "kv_reserved_block_steps",
            "kv_live_block_steps", "host_turns")


@pytest.fixture(scope="module")
def model():
    cfg = configs.get("qwen3-4b").smoke(dtype=jnp.float32)
    assert (cfg.block_size, cfg.prefill_chunk) == (8, 8)
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(3))


def _step(eng) -> dict:
    """One engine step; what it added to each counter."""
    before = {k: getattr(eng.stats, k) for k in COUNTERS}
    eng.step()
    return {k: getattr(eng.stats, k) - before[k] for k in COUNTERS}


def test_a_shared_prefix_block_counts_once(model):
    """A (20-token prompt, 4 blocks reserved) decodes while B, sharing A's
    first two blocks, prefills its 12-token suffix in two chunks: the row
    mid-prefill rides along uncounted, its written blocks count, and the
    two blocks both rows map count once."""
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=2, s_max=40, prefix_cache=True)
    rng = np.random.default_rng(0)
    a = rng.integers(0, cfg.vocab, 20).astype(np.int32)
    b = np.concatenate([a[:16], (a[:12] + 1) % cfg.vocab]).astype(np.int32)
    eng.submit(Request(rid=0, prompt=a, max_new_tokens=10))
    assert [_step(eng)["decode_rows"] for _ in range(3)] == [0, 0, 1]
    eng.submit(Request(rid=1, prompt=b, max_new_tokens=10))
    # B admitted sharing 2 blocks (5 reserved, 3 fresh), chunk 0 written:
    # A's 22 positions hold 3 blocks, B's 24 hold 3, 2 of them A's; the
    # kernel's walk reads a row's 5-block table as one chunk
    d = _step(eng)
    assert eng.stats.prefix_shared_blocks == 2 and eng.stats.cow_copies == 0
    assert d == {"decode_rows": 1, "decode_ctx_tokens": 22,
                 "decode_kv_blocks_read": 5, "decode_kv_blocks_live": 3,
                 "kv_reserved_block_steps": 4 + 3, "kv_live_block_steps": 4,
                 "host_turns": 1}
    # B's last chunk: both decode, A over 23 positions, B over 29 (4 blocks)
    d = _step(eng)
    assert d == {"decode_rows": 2, "decode_ctx_tokens": 23 + 29,
                 "decode_kv_blocks_read": 5 + 5, "decode_kv_blocks_live": 3 + 4,
                 "kv_reserved_block_steps": 7, "kv_live_block_steps": 5,
                 "host_turns": 1}
    eng.run()
    st = eng.stats
    assert 0 < st.kv_live_block_steps <= st.kv_reserved_block_steps
    assert st.decode_rows == 9 + 9          # every token after the first
    assert st.host_turns == st.decode_steps - 1 and st.host_turn_ns > 0


def test_host_turns_leave_out_the_caller_s_time(model):
    """A turn runs from a token read to the next decode dispatch, and the
    time the caller spends between two steps is not the engine's."""
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=1, s_max=40)
    eng.submit(Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=6))
    eng.step()                          # prefill, then the first decode
    assert (eng.stats.decode_steps, eng.stats.host_turns) == (1, 0)
    time.sleep(0.2)
    eng.step()
    assert eng.stats.host_turns == 1
    assert 0 < eng.stats.host_turn_ns < 0.2e9


def test_decode_dispatches_left_the_counters():
    assert "decode_dispatches" not in EngineStats.__dataclass_fields__
