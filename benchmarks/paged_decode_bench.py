"""Fused vs. unfused paged-decode attention, at kernel level (DESIGN.md §18).

Two implementations of the same decode-step attention over a block-paged
KV pool:

  fused    — ``kernels/paged_attn.py``: one Pallas dispatch that walks the
             block table via scalar prefetch, streams pool blocks through
             VMEM and keeps the online-softmax accumulator in registers
             (interpreted on CPU, compiled on real TPU);
  unfused  — the gather -> QK -> mask -> softmax -> PV jnp chain
             (``kernels/ref.py::paged_decode``), which is also the
             production decode path on CPU backends and the kernel's
             bit-exact-twin reference.

Swept across block_size x slots x f32/i8 KV.  Reported per cell: wall
microseconds per call for both paths and their jaxpr dispatch counts
(``roofline/analysis.dispatch_count`` — the fused path is a single
``pallas_call`` where the chain is dozens of primitives).  On CPU the
fused timing is the *interpreter's* (orders of magnitude slower — the
win this benchmark audits is dispatches and bytes, not CPU wall time), and
the interpreter is used only when asked for with
``REPRO_KERNEL_IMPL=interpret``; on a ref backend the kernel cells refuse
to run.  The tok/s comparison under the production dispatch lives in
``serve_throughput.py``.

``--smoke`` additionally runs the engine-level gates CI pins in both
kernel modes: fused decode tokens (``REPRO_FUSED_DECODE=on``) bit-identical
to unfused (``off``) on a float32 smoke engine, and — where the decode
step contains the kernel, compiled or interpreted (one ``pallas_call``
either way) — the fused whole-decode-step dispatch count strictly below
the unfused one.  On a ref backend the fused step runs the one-shot
oracle instead, so there is no dispatch gap to pin.

Run:  REPRO_KERNEL_IMPL=interpret PYTHONPATH=src \
          python benchmarks/paged_decode_bench.py [--smoke]   # CPU
      PYTHONPATH=src python benchmarks/paged_decode_bench.py   # TPU
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np


def _time(fn, *args, iters: int = 5) -> float:
    """Median wall microseconds per call (post-compile)."""
    import jax

    jax.block_until_ready(fn(*args))            # compile / first interpret
    ts = []
    for _ in range(iters):
        t0 = time.monotonic()
        jax.block_until_ready(fn(*args))
        ts.append((time.monotonic() - t0) * 1e6)
    return float(np.median(ts))


def _make_case(bs: int, slots: int, kv_dtype: str, seed: int = 0):
    """One decode-step attention problem over a block-paged pool."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    kv, g, dh, w = 2, 2, 16, 6
    n_blocks = 1 + slots * w
    q = jnp.asarray(rng.standard_normal((slots, kv, g, dh)), jnp.float32)
    ck = rng.standard_normal((n_blocks, kv, bs, dh))
    cv = rng.standard_normal((n_blocks, kv, bs, dh))
    scale, out_scale = dh ** -0.5, 1.0
    if kv_dtype == "i8":
        i8s = 32.0
        ck = np.clip(np.round(ck * i8s), -127, 127).astype(np.int8)
        cv = np.clip(np.round(cv * i8s), -127, 127).astype(np.int8)
        scale, out_scale = scale / i8s, 1.0 / i8s
    else:
        ck, cv = ck.astype(np.float32), cv.astype(np.float32)
    table = jnp.asarray(
        rng.permutation(slots * w).reshape(slots, w) + 1, jnp.int32)
    pos = jnp.asarray(rng.integers(1, w * bs, size=(slots,)), jnp.int32)
    return (q, jnp.asarray(ck), jnp.asarray(cv), table, pos,
            float(scale), float(out_scale))


def _bench_cell(bs: int, slots: int, kv_dtype: str, quiet: bool):
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, paged_attn, ref
    from repro.roofline import analysis

    q, ck, cv, table, pos, scale, out_scale = _make_case(bs, slots, kv_dtype)
    impl = ops._resolve("auto")
    if impl == "ref":
        raise RuntimeError(
            "the fused kernel runs compiled on a TPU or, with "
            "REPRO_KERNEL_IMPL=interpret, in the Pallas interpreter; this "
            "backend resolves to the ref oracle, so there is no kernel to time")
    fused = functools.partial(paged_attn.paged_decode_attention,
                              window=0, scale=scale, out_scale=out_scale,
                              interpret=(impl == "interpret"))
    unfused = jax.jit(functools.partial(ref.paged_decode, window=0,
                                        scale=scale, out_scale=out_scale))
    out_f = np.asarray(fused(q, ck, cv, table, pos))
    out_u = np.asarray(unfused(q, ck, cv, table, pos))
    np.testing.assert_allclose(out_f, out_u, rtol=2e-5, atol=2e-5)

    disp_f = analysis.dispatch_count(
        jax.make_jaxpr(fused)(q, ck, cv, table, pos))
    disp_u = analysis.dispatch_count(
        jax.make_jaxpr(unfused)(q, ck, cv, table, pos))
    us_f = _time(fused, q, ck, cv, table, pos)
    us_u = _time(unfused, q, ck, cv, table, pos)
    name = f"bs{bs}_s{slots}_{kv_dtype}"
    derived = (f"fused_us={us_f:.1f} unfused_us={us_u:.1f} "
               f"disp_fused={disp_f} disp_unfused={disp_u}")
    if not quiet:
        print(f"{name:<16s} {us_f:>10.1f} {us_u:>11.1f} "
              f"{disp_f:>6d} {disp_u:>8d}")
    assert disp_f < disp_u, (
        f"{name}: fused kernel traces to {disp_f} dispatches, "
        f"not below the unfused chain's {disp_u}")
    return name, us_f, derived


def _bench(smoke: bool, quiet: bool = False):
    cells = ([(8, 2, "f32"), (8, 2, "i8")] if smoke else
             [(bs, s, d) for bs in (8, 16) for s in (2, 8)
              for d in ("f32", "i8")])
    if not quiet:
        print(f"{'cell':<16s} {'fused us':>10s} {'unfused us':>11s} "
              f"{'disp_f':>6s} {'disp_u':>8s}")
    return [_bench_cell(bs, s, d, quiet) for bs, s, d in cells]


def _run_smoke_engine(fused: str):
    """Tokens + decode-step audit of a small paged engine under one
    ``REPRO_FUSED_DECODE`` setting (restored afterwards)."""
    import jax
    import jax.numpy as jnp
    import repro.configs as configs
    from repro.models import lm
    from repro.serve import ServeEngine, synthetic_trace

    prev = os.environ.get("REPRO_FUSED_DECODE")
    os.environ["REPRO_FUSED_DECODE"] = fused
    try:
        cfg = configs.get("qwen3-4b").smoke(dtype=jnp.float32)
        params = lm.init_params(cfg, jax.random.PRNGKey(7))
        eng = ServeEngine(cfg, params, slots=2, s_max=24, paged=True)
        for r in synthetic_trace(4, cfg.vocab, seed=7):
            eng.submit(r)
        rep = eng.run()
        toks = {rid: rep.tokens(rid).tolist() for rid in rep.sessions}
        return toks, eng.decode_roofline()
    finally:
        if prev is None:
            os.environ.pop("REPRO_FUSED_DECODE", None)
        else:
            os.environ["REPRO_FUSED_DECODE"] = prev


def _smoke_gates() -> None:
    from repro.kernels import ops
    from repro.roofline import report

    toks_on, audit_on = _run_smoke_engine("on")
    toks_off, audit_off = _run_smoke_engine("off")
    assert toks_on == toks_off, (
        "fused decode tokens diverge from unfused on the smoke engine")
    print(report.serve_decode_header())
    print(report.serve_decode_row("decode/fused", audit_on))
    print(report.serve_decode_row("decode/unfused", audit_off))
    if ops._resolve("auto") != "ref":
        assert audit_on["dispatches"] < audit_off["dispatches"], (
            f"fused decode step dispatches ({audit_on['dispatches']}) not "
            f"below unfused ({audit_off['dispatches']})")
    print("smoke OK: fused tokens == unfused; decode-step dispatches "
          f"{audit_on['dispatches']} (fused) vs {audit_off['dispatches']} "
          "(unfused)")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    _bench(args.smoke)
    if args.smoke:
        _smoke_gates()
    return 0


def run():
    """benchmarks/run.py entry: (name, us_per_call, derived) CSV rows —
    us_per_call is the fused path's wall microseconds per call (the
    interpreter's on CPU; see module docstring)."""
    for name, us, derived in _bench(True, quiet=True):
        yield name, us, derived


if __name__ == "__main__":
    raise SystemExit(main())
