"""The benchmark's command off the chip, and a whole run at a tiny size on
the CPU: sound, with the served tokens altered where they are produced, and
against its float8 control."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import time

import pytest

import bench_tiny
import harness



def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), "--workload",
         "q3x-decode", "--seed", "4294967311", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_no_tpu_means_no_result():
    p = _cli(bench_tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_mean_no_result(tmp_path):
    shutil.copy(bench_tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, seed, fault=None, cell=bench_tiny.CELL, **kw):
    return harness.run_cell(cell, seed, 1.5, False,
                            t_process=time.monotonic(), root=root,
                            require_tpu=False,
                            cfg_overrides=bench_tiny.OVERRIDES, fault=fault,
                            **kw)


def test_tiny_run_is_correct(root):
    result, checks = _run(root, 2**33 + 5)
    assert result["correct"], checks
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    assert result["attempted"] >= 5 and result["failed"] == 0
    assert result["checks"]["compared_tokens_min"]["value"] >= 32


def _alter_tokens(eng):
    """The decode program's sampled tokens, altered where they are made."""
    real = eng._paged_decode_program

    def altered(*args):
        toks, state = real(*args)
        return (toks + 1) % eng.cfg.vocab, state
    eng._paged_decode_program = altered


def test_tokens_altered_where_produced_are_not_correct(root):
    result, checks = _run(root, 2**33 + 5, fault=_alter_tokens)
    assert not result["correct"]
    assert any(c["value"] > c["limit"] for name, c in result["checks"].items()
               if name != "compared_tokens_min")


def test_backlog_in_flight_run_is_correct(root):
    """The in-flight requests decode from their built contexts, some finish
    and the backlog refills their slots inside the window."""
    result, checks = _run(root, 2**31 + 11, cell=bench_tiny.DECODE_CELL)
    assert result["correct"], checks
    run = result["run"]
    assert run["window_s"] >= 1.5 and run["steps"] >= 1
    assert run["completed"] >= 1 and run["prefill_chunks"] >= 1
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    assert len(run["longest_steps_ms"]) == min(3, run["steps"])


def test_float8_control_put_in_the_program_s_place_is_not_correct(root):
    """The control judged as a run judges: against the cell's own limits."""
    result, checks = _run(root, bench_tiny.CONTROL_SEED, control=True)
    assert not result["correct"], checks
    assert any(v > lim for name, v, lim in checks
               if name != "compared_tokens_min")


def test_float8_control_reads_far_above_the_program(root):
    """The control: the reference computed in float8 in the program's place,
    on what the tiny run served."""
    cell = harness.resolve(bench_tiny.CELL, root)
    cfg = harness.build_cfg(cell.conf, bench_tiny.OVERRIDES)
    dev = harness.local_device(root)
    seed = bench_tiny.CONTROL_SEED
    m = harness.measure(cell, cfg, seed, 1.5, dev, t_process=time.monotonic())
    got = harness.gaps(cell, seed, m.prompts, m.tokens, ("ref", "fp8"))
    for name in ("widest_gap", "mean_gap"):
        assert got["fp8"][name] > 3 * max(got["ref"][name], 1e-6), got


def test_program_peaks_grow_by_at_least_the_pool(root):
    """The pool rule's reading: each program's compiled peak grows with the
    pool by no less than the pool's own bytes."""
    import model_weights
    import traffic_gen
    from repro.core.xnor_layers import PackedLinear
    from repro.models import lm
    cell = harness.resolve(bench_tiny.DECODE_CELL, root)
    cfg = harness.build_cfg(cell.conf, bench_tiny.OVERRIDES)
    params = model_weights.make_served(
        1, lm.packed_abstract_params(cfg), cell.conf["init"],
        cell.conf["vocab_size"], PackedLinear)
    args = dict(slots=4, s_max=traffic_gen.max_context(cell.traffic), seed=1,
                prefill_chunk=cfg.prefill_chunk)
    peaks = harness.program_peaks(cfg, params, args, (5, 9))
    assert set(peaks) == {"chunk", "decode"}
    for small, large in peaks.values():
        assert large - small >= 4 * harness.kv_block_bytes(cfg, cfg.block_size)
