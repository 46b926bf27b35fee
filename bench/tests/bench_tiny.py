"""A benchmark root at a size a CPU test can run: the real ``bench/``
readers and references, plus a tiny configuration, two traffic mixes and
two cells added as new files and entries, as a later change would add
them: an open loop (``tiny-chat``) and a backlog with requests in flight
(``tiny-decode``)."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny-chat"
TINY_CHAT_LIMIT = 0.02
CONTROL_SEED = 77
DECODE_CELL = "tiny-decode"
# the system's sizes for the tiny config (one KV head per four query heads,
# as the published model groups them)
OVERRIDES = dict(d_model=64, n_layers=2, n_heads=4, n_kv_heads=1, d_head=16,
                 d_ff=128, vocab=256, block_size=8, prefill_chunk=8)
SIZES = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=1, head_dim=16, intermediate_size=128,
             vocab_size=256)


def make_root(tmp: pathlib.Path, like_cell: str = "q3x-decode") -> pathlib.Path:
    """A root whose BENCHMARK.json holds the real entries plus one tiny
    cell made only of files added here."""
    root = tmp / "root"
    (root / "bench").mkdir(parents=True)
    for sub in ("metrics", "references", "configs", "traffic", "cells"):
        shutil.copytree(BENCH / sub, root / "bench" / sub)
    shutil.copy(BENCH / "peaks.json", root / "bench" / "peaks.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    real = {w["name"]: w for w in bench["workloads"]}[like_cell]
    conf = json.loads((REPO / real_config(bench, real)).read_text())
    conf.update(SIZES)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(conf))
    traffic = json.loads(
        (BENCH / "traffic" / f"{real['traffic']}.json").read_text())
    traffic.update(
        arrivals={"kind": "poisson", "rate_rps": 8.0}, ramp_s=1.0, slots=4,
        prompt={"dist": "lognormal", "median": 30, "sigma": 0.8, "min": 8,
                "max": 64},
        output={"dist": "lognormal", "median": 6, "sigma": 0.6, "min": 2,
                "max": 16},
        shared_prefix={"tokens": 20, "p": 0.5})
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(traffic))
    backlog = json.loads((BENCH / "traffic" / "decode.json").read_text())
    backlog.update(
        arrivals={"kind": "backlog", "requests": 12}, slots=4, in_flight=True,
        prompt={"dist": "uniform", "min": 8, "max": 24},
        output={"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
                "max": 24})
    (root / "bench" / "traffic" / "tiny_decode.json").write_text(
        json.dumps(backlog))
    settings = json.loads(
        (BENCH / "cells" / f"{like_cell}.json").read_text())
    settings["prefill_chunk"] = OVERRIDES["prefill_chunk"]
    (root / "bench" / "cells" / f"{DECODE_CELL}.json").write_text(
        json.dumps(settings))
    # at this size the float8 control parts from the program on few seeds:
    # over six seeds the program's widest gap read at most 0.0011 and the
    # control's at most 0.089 (seed 77), so the open-loop cell takes a limit
    # between the two for the test that judges the control
    settings["limits"] = {"widest_gap": TINY_CHAT_LIMIT}
    (root / "bench" / "cells" / f"{CELL}.json").write_text(
        json.dumps(settings))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": sorted(SIZES), "why": "CPU test"})
    for cell, mix in ((CELL, "tiny"), (DECODE_CELL, "tiny_decode")):
        bench["workloads"].append({"name": cell, "config": "tiny",
                                   "traffic": mix, "chips": 1,
                                   "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like_cell in m.get("workloads", []):
            m["workloads"] += [CELL, DECODE_CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def real_config(bench: dict, workload: dict) -> str:
    return {c["name"]: c for c in bench["configs"]}[workload["config"]]["file"]
