"""Every cell of BENCHMARK.json resolves its files by name, the file keeps
to the benchmark's shape, and a cell made only of added files resolves."""

from __future__ import annotations

import json
import re

import pytest

import bench_tiny
import harness

BENCHMARK = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert len(json.dumps(BENCHMARK)) < 64 * 1024


def test_names_units_and_bounds():
    metrics = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + [
        c["name"] for c in BENCHMARK["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
        assert all(c in CELLS for c in m.get("workloads", []))
        for c in m.get("workloads", []):
            assert c in e2e[m["moves"]].get("workloads", CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.resolve(cell, bench_tiny.REPO)
    assert c.chips == 1
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    assert set(c.settings["limits"]) <= {"widest_gap", "mean_gap"}
    assert c.settings["limits"]
    for key in ("check_tokens", "check_requests", "check_min_tokens",
                "trace_seconds"):
        assert key in c.settings
    assert c.traffic["slots"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_config_file_matches_what_the_system_runs(cell):
    c = harness.resolve(cell, bench_tiny.REPO)
    cfg = harness.build_cfg(c.conf)
    assert cfg.n_layers == c.conf["num_hidden_layers"]


def test_a_cell_added_as_files_resolves(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    # and a per-layer metric added the same way
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "tiny_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "out_tok_s", "workloads": [bench_tiny.CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "metrics" / "tiny_steps.py").write_text(
        "def read(win):\n    return win.steps or None\n")
    c = harness.resolve(bench_tiny.CELL, root)
    assert c.conf["hidden_size"] == 64
    assert c.traffic["slots"] == 4
    assert "tiny_steps" in {m["name"] for m in c.per_layer}
    assert {m["name"] for m in c.end_to_end} == {"out_tok_s", "setup_s"}
    # the real cells still resolve from the same root
    for cell in CELLS:
        harness.resolve(cell, root)


def test_a_reader_is_found_by_its_name_or_the_part_before_a_dot(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    metrics = root / "bench" / "metrics"
    assert harness.reader_path(root, "decode_ms.offline") == \
        metrics / "decode_ms.py"
    (metrics / "decode_ms.offline.py").write_text("def read(win):\n    return 1\n")
    assert harness.reader_path(root, "decode_ms.offline") == \
        metrics / "decode_ms.offline.py"
    assert harness.reader_path(root, "no_such_metric.offline") is None


def test_missing_files_are_refused(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    (root / "bench" / "traffic" / "tiny.json").unlink()
    with pytest.raises(harness.Refused):
        harness.resolve(bench_tiny.CELL, root)
    with pytest.raises(harness.Refused):
        harness.resolve("no-such-cell", root)
