"""Pallas TPU kernel: fused block-paged decode attention (DESIGN.md §18).

The paged decode hot path (`models/attention.py::paged_attention`, C == 1)
was a chain of separate XLA ops — block-table gather -> QK matmul -> mask ->
softmax -> PV matmul — so every decode step round-tripped the gathered
(B, KV, cap, dh) K/V and the (B, KV, G, 1, cap) score tensor through HBM.
This kernel collapses the chain into a single dispatch: per (slot, kv-head)
it walks the slot's host-built int32 block table, streams each referenced
K/V pool block through VMEM exactly once, and folds it into the shared
online-softmax (m, l, acc) accumulator (:mod:`repro.kernels.online_softmax`,
the same recurrence :mod:`repro.kernels.flash_attn` uses for prefill tiles).

Block-table walk
----------------
Grid is (B, KV, W) with the table axis innermost ("arbitrary": the output
block is revisited and accumulated across w steps).  The table and the
per-slot positions ride in as *scalar-prefetched* operands
(``pltpu.PrefetchScalarGridSpec``): they are available before the kernel
body runs, so the K/V BlockSpec index_maps compute the DMA source directly
as ``table[b, w]`` — the gather never materializes, the pool block streams
HBM -> VMEM once and dies in registers.

Masking cases (bit-for-bit the unfused chain's semantics):
  * full-monotone tables — key slot ``w*bs + t`` valid iff ``<= pos[b]``;
  * window rings — ``age = (pos % cap - kslot) % cap`` valid iff
    ``age < min(window, pos + 1)`` (ring capacity ``cap = W*bs``);
  * dead slots / trash block — dead and mid-prefill slots keep table rows
    that may point at stale or trash blocks; their keys are killed by the
    position mask exactly as in the unfused path (the reserved trash block
    0 is only ever *written* through the ``valid`` scatter routing, never
    legitimately read);
  * i8 KV — the fixed-point correction folds into ``scale`` (QK side) and
    ``out_scale`` (PV side), so the int8 pool decodes in one pass too.

A block whose keys are all masked is skipped entirely (``@pl.when``):
that is both the dead-block fast path and the guard for the online-softmax
all-NEG_INF edge case (see online_softmax.update).

Numerics: the online recurrence equals one-shot masked softmax exactly in
real arithmetic but not bit-for-bit in floats (association/rounding).  The
model-level dispatch therefore routes this kernel on real TPU backends and
keeps the jnp chain — which doubles as this kernel's reference twin
(:func:`repro.kernels.ref.paged_decode` is the semantic oracle) — on
ref/interpret backends, so every cross-layout token pin (paged == dense,
prefix on == off, migration identity) stays bit-exact in both CI modes.
``REPRO_FUSED_DECODE=on`` forces the kernel everywhere (parity tests and
the microbenchmark do this explicitly).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm

NEG_INF = osm.NEG_INF


def _kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, w_total: int, window: int,
            scale: float, out_scale: float):
    b = pl.program_id(0)
    w = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(w == 0)
    def _init():
        osm.init(m_ref, l_ref, acc_ref)

    # key slot index within the gathered cap-axis of the unfused chain:
    # table column w holds tokens w*bs .. w*bs + bs - 1 of the (ring) window
    kslot = w * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    if window:
        cap = w_total * bs
        age = (pos % cap - kslot) % cap
        valid = age < jnp.minimum(window, pos + 1)
    else:
        valid = kslot <= pos

    # skip fully dead blocks: ragged table tails past pos, ring blocks that
    # fell out of the window, and dead slots' stale rows all land here
    @pl.when(jnp.any(valid))
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (bs, dh)
        v = v_ref[0, 0].astype(jnp.float32)            # (bs, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)               # (G, bs)
        osm.update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(w == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (osm.finish(m_ref, l_ref, acc_ref)
                       * out_scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "out_scale",
                                             "interpret"))
def paged_decode_attention(q, ck, cv, table, pos, *, window: int = 0,
                           scale: float = 1.0, out_scale: float = 1.0,
                           interpret: bool = False) -> jnp.ndarray:
    """One fused decode-attention dispatch over the block-paged pool.

    q       (B, KV, G, dh)  — this step's queries, compact GQA form
    ck, cv  (n_blocks, KV, bs, dh) — the shared pool (f32/bf16 or int8),
            with this step's K/V already scattered in (the scatter is a
            (B,) token write, not part of the HBM-bound gather chain)
    table   (B, W) int32    — per-slot physical block ids
    pos     (B,)  int32     — per-slot current absolute position
    window  0 for monotone tables; the local window size for block rings
    scale   QK scale (``dh**-0.5``, with the i8 fixed-point factor folded
            in for int8 pools); ``out_scale`` is the PV-side i8 correction.

    Returns (B, KV, G, dh) in q.dtype.  VMEM per step: one (bs, dh) K and V
    block + (G, dh) q/out tiles + the (G, 1)/(G, dh) accumulator — e.g.
    bs=16, dh=128, G=8: ~21 KB, so the pool never round-trips HBM.
    """
    b, kv, g, dh = q.shape
    bs = ck.shape[2]
    w_total = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv, w_total),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b, h, w, tbl, pos: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh),
                         lambda b, h, w, tbl, pos: (tbl[b, w], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh),
                         lambda b, h, w, tbl, pos: (tbl[b, w], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda b, h, w, tbl, pos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, w_total=w_total, window=window,
                          scale=scale, out_scale=out_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="paged_decode_attention",
        interpret=interpret,
    )(table, pos, q, ck, cv)
