"""Pallas TPU kernel: fused (flash) causal attention — §Perf It8b follow-up.

The roofline analysis (EXPERIMENTS.md §Perf iteration 8b) shows the 32k
prefill cells are memory-bound almost entirely by the f32 score stream of
chunked attention (~2.7 TB/chip/step for qwen2-7b): scores round-trip HBM
once per chunk.  This kernel keeps the (bq, bk) score tile in VMEM and
streams K/V exactly once per query block — the same "one memory pass"
discipline as the paper's XOR engine, applied to the framework's own
hotspot.  Projected effect: prefill memory term 6.85 s → ~0.15 s
(q/k/v/out streams only), leaving the cell collective-bound at ~2.5 s.

Online-softmax (Dao et al. FA-2 schedule): per q-tile running (m, l, acc),
one pass over k-tiles, causal masking at tile granularity.

Grid: (B*H, Sq/bq, Sk/bk) with the k axis innermost ("arbitrary"); the
q-tile accumulators live in the output ref + two SMEM-side carries folded
into VMEM scratch via input_output_aliasing-free re-reads (interpret-mode
validated; ops.flash_attention is the jit wrapper, ref is _sdpa).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm

NEG_INF = osm.NEG_INF


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, scale: float, causal: bool):
    kstep = pl.program_id(2)
    qstep = pl.program_id(1)

    @pl.when(kstep == 0)
    def _init():
        osm.init(m_ref, l_ref, acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)                  # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        qpos = qstep * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kstep * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        # tiles strictly above the causal diagonal are fully masked: their
        # update is an exact no-op (p == 0, corr == 1), so skip the work.
        # The k axis walks left-to-right, so tile (q, 0) is never all-masked
        # and the online_softmax all-NEG_INF edge case cannot arise here.
        @pl.when(kstep * bk <= qstep * bq + (bq - 1))
        def _update():
            osm.update(s, v, m_ref, l_ref, acc_ref)
    else:
        osm.update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(kstep == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = osm.finish(m_ref, l_ref, acc_ref).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 256,
                    bk: int = 256, interpret: bool = False):
    """q/k/v: (BH, S, dh) -> (BH, S, dh).  S % bq == S % bk == 0.

    VMEM per step: q,k,v,o tiles + (bq, dh) acc + 2*(bq,1) carries — e.g.
    bq=bk=256, dh=128: ~0.6 MB, far under budget; bk can grow to amortize.
    """
    bh, s, dh = q.shape
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    grid = (bh, s // bq, s // bk)
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, scale=dh ** -0.5,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)


def flash_attention_ref(q, k, v, causal: bool = True):
    """Oracle: plain masked softmax attention (f32)."""
    bh, s, dh = q.shape
    sc = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * dh ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(mask[None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
