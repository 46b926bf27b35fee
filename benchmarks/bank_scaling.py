"""Bank- and device-scaling throughput: ops/cycle vs bank count and device
count (DESIGN.md §10–§11).

The paper's throughput argument is architectural: one sense cycle computes a
row-wide XOR/XNOR, and independent banks multiply that by B.  This benchmark
drives both engine views at B in {1, 8, 64}:

* circuit path — banked analog simulation (`CimEngine.simulate`): wall-clock
  per traced call and modeled ops/cycle, which must scale linearly in B;
* engine path — the packed `bulk_op` kernel over a fixed buffer: modeled
  cycle count, which must fall as 1/B.

The device axis extends the same argument across a mesh (`ShardedCimEngine`,
mesh-as-outer-bank): each D in {1, 2, 4, 8} runs in a subprocess with
`XLA_FLAGS=--xla_force_host_platform_device_count=D` (the flag must predate
jax init), reporting modeled ops/cycle and HBM bytes moved for sharded
xor / digest / stream_cipher — ops/cycle scales linearly in D while the
digest's cross-device traffic stays one 512-byte digest per reduce.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import BankGeometry, CimEngine

BANK_COUNTS = (1, 8, 64)
DEVICE_COUNTS = (1, 2, 4, 8)
PAIRS = 8            # row-pairs scheduled per bank (P sense cycles)
COLS = 128           # bank row width (bits)
BUF_WORDS = 1 << 16  # engine-path payload: 64k uint32 words = 2 Mbit

_DEVICE_CHILD = textwrap.dedent("""
    import json, sys, time
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.engine import BankGeometry, ShardedCimEngine
    from repro.launch.mesh import make_engine_mesh

    devices, buf_words, cols = (int(a) for a in sys.argv[1:4])
    mesh = make_engine_mesh(devices)
    # same row width (bits) as the bank sweep, default 8 banks: device_D1
    # matches engine_B8 ops/cycle, so the two axes compose comparably.
    eng = ShardedCimEngine(mesh, geometry=BankGeometry(cols=cols))
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 2**32, buf_words, dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, buf_words, dtype=np.uint32))
    key = jnp.array([7, 9], dtype=jnp.uint32)
    res = {"devices": devices, "bits_per_cycle": eng.geometry.bits_per_cycle}
    for name, fn, moved in (
            ("xor", lambda: eng.xor(a, b), 3 * 4 * buf_words),
            ("digest", lambda: eng.digest(a), 4 * buf_words + 512 * devices),
            ("cipher", lambda: eng.stream_cipher(a, key), 2 * 4 * buf_words)):
        jax.block_until_ready(fn())          # compile
        t0 = time.perf_counter()
        reps = 10
        for _ in range(reps):
            jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) * 1e6 / reps
        res[name] = {"us": us, "bytes_moved": moved,
                     "cycles": eng.cycles_for(buf_words * 32)}
    print(json.dumps(res))
""")


def _device_rows() -> list[tuple]:
    """Sharded-engine sweep, one subprocess per simulated device count.

    The children simulate CPU devices and are pinned to the CPU: on a chip
    host the parent already holds the accelerator, and a child reaching
    for it would fail or hang."""
    rows = []
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for d in DEVICE_COUNTS:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={d}",
                   PYTHONPATH=os.path.join(root, "src"))
        r = subprocess.run([sys.executable, "-c", _DEVICE_CHILD, str(d),
                            str(BUF_WORDS), str(COLS)],
                           capture_output=True, text=True, env=env,
                           timeout=600)
        if r.returncode != 0:
            rows.append((f"device_D{d}_ERROR", 0.0, r.stderr[-200:]))
            continue
        res = json.loads(r.stdout.splitlines()[-1])
        opc = BUF_WORDS * 32 / res["xor"]["cycles"]
        for name in ("xor", "digest", "cipher"):
            m = res[name]
            rows.append((f"device_{name}_D{d}", m["us"],
                         f"{BUF_WORDS * 32} bit-ops in {m['cycles']} cycles"
                         f" = {opc:.0f} ops/cycle;"
                         f" {m['bytes_moved']} bytes moved"))
    return rows


def run() -> list[tuple]:
    rows = []
    rng = np.random.default_rng(0)
    buf_a = jnp.asarray(rng.integers(0, 2**32, BUF_WORDS, dtype=np.uint32))
    buf_b = jnp.asarray(rng.integers(0, 2**32, BUF_WORDS, dtype=np.uint32))

    for banks in BANK_COUNTS:
        geo = BankGeometry(banks=banks, rows=2 * PAIRS, cols=COLS)
        eng = CimEngine(geo)
        n = banks * PAIRS
        a = jnp.asarray(rng.integers(0, 2, (n, COLS)))
        b = jnp.asarray(rng.integers(0, 2, (n, COLS)))

        out = eng.simulate(a, b, "xor")          # compile + correctness
        assert np.array_equal(np.asarray(out),
                              np.asarray(a ^ b).astype(bool))
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            jax.block_until_ready(eng.simulate(a, b, "xor"))
        us = (time.perf_counter() - t0) * 1e6 / reps
        rows.append((f"circuit_B{banks}", us,
                     f"{n}x{COLS}b pairs in {PAIRS} cycles = "
                     f"{geo.bits_per_cycle} ops/cycle"))

        eng2 = CimEngine(geo)
        enc = eng2.xor(buf_a, buf_b)
        jax.block_until_ready(enc)
        t0 = time.perf_counter()
        jax.block_until_ready(eng2.xor(buf_a, buf_b))
        us = (time.perf_counter() - t0) * 1e6
        cyc = eng2.cycles_for(BUF_WORDS * 32)
        rows.append((f"engine_B{banks}", us,
                     f"{BUF_WORDS * 32} bit-ops in {cyc} modeled cycles "
                     f"({eng2.stats.ops_per_cycle:.0f} ops/cycle)"))

    # linearity check across the sweep: ops/cycle ratio == bank ratio
    base = BANK_COUNTS[0]
    geo0 = BankGeometry(banks=base, rows=2 * PAIRS, cols=COLS)
    for banks in BANK_COUNTS[1:]:
        geo = BankGeometry(banks=banks, rows=2 * PAIRS, cols=COLS)
        rows.append((f"scaling_B{base}->B{banks}", 0.0,
                     f"ops/cycle x{geo.bits_per_cycle // geo0.bits_per_cycle} "
                     f"(ideal x{banks // base})"))

    rows.extend(_device_rows())
    return rows
