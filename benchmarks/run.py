# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (benchmarks read the paper's own artifacts: Fig. 4 functional
# verification, Fig. 5 Monte-Carlo, Table I latency, Fig. 6 XNOR-Net
# speedup, §II copy-verify/encrypt throughput, plus the beyond-paper
# roofline summary from the dry-run).
#
# ``--json PATH`` additionally writes the rows as a flat JSON record list
# (schema: benchmark, config, metric, value, commit) — the serve suites'
# records are checked in as BENCH_serve.json and re-emitted as a CI
# artifact, so serving-throughput history rides along with the code.
# ``--only tag1,tag2`` restricts the run to a subset of suites.
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import traceback

from benchmarks import (bank_scaling, fig4_functional, fig5_montecarlo,
                        fig6_xnornet, incremental_verify, paged_decode_bench,
                        roofline_bench, serve_replicated, serve_throughput,
                        serve_workloads, table1_latency, verify_throughput)
from repro.launch import compile_cache

SUITES = [
    ("fig4", fig4_functional),
    ("fig5", fig5_montecarlo),
    ("table1", table1_latency),
    ("fig6", fig6_xnornet),
    ("verify", verify_throughput),
    ("incremental", incremental_verify),
    ("banks", bank_scaling),
    ("serve", serve_throughput),
    ("workloads", serve_workloads),
    ("replicated", serve_replicated),
    ("paged_decode", paged_decode_bench),
    ("roofline", roofline_bench),
]


def _commit() -> str:
    try:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            text=True, stderr=subprocess.DEVNULL).strip()
    except Exception:
        return "unknown"


def _json_rows(tag: str, name: str, us: float, derived, commit: str) -> list:
    """One CSV row -> flat JSON records: the primary us_per_call metric
    plus every ``key=value`` pair in the derived column that parses as a
    number (free-text derived values stay CSV-only)."""
    rows = [{"benchmark": tag, "config": name, "metric": "us_per_call",
             "value": round(float(us), 1), "commit": commit}]
    for part in str(derived).split():
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            rows.append({"benchmark": tag, "config": name, "metric": k,
                         "value": float(v), "commit": commit})
        except ValueError:
            pass
    return rows


def main(argv=None) -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="",
                    help="also write records to this JSON file "
                         "(benchmark/config/metric/value/commit)")
    ap.add_argument("--only", default="",
                    help="comma-separated suite tags to run (default: all)")
    args = ap.parse_args(argv)
    suites = SUITES
    if args.only:
        want = set(args.only.split(","))
        unknown = want - {t for t, _ in SUITES}
        if unknown:
            raise SystemExit(f"unknown suite tags: {sorted(unknown)}")
        suites = [s for s in SUITES if s[0] in want]

    commit = _commit()
    records = []
    print("name,us_per_call,derived")
    failed = 0
    for tag, mod in suites:
        try:
            for name, us, derived in mod.run():
                print(f"{tag}/{name},{us:.1f},{derived}")
                records.extend(_json_rows(tag, name, us, derived, commit))
        except Exception:
            failed += 1
            print(f"{tag}/ERROR,,{traceback.format_exc(limit=2)!r}",
                  file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
            f.write("\n")
    if failed:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
