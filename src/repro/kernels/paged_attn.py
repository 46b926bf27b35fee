"""Pallas TPU kernel: fused block-paged decode attention (DESIGN.md §18).

The paged decode hot path (`models/attention.py::paged_attention`, C == 1)
was a chain of separate XLA ops — block-table gather -> QK matmul -> mask ->
softmax -> PV matmul — so every decode step round-tripped the gathered
(B, KV, cap, dh) K/V and the (B, KV, G, 1, cap) score tensor through HBM.
This kernel collapses the chain into a single dispatch: per slot it walks
the slot's host-built int32 block table, streams each live K/V pool block
through VMEM exactly once, and folds it into the shared online-softmax
(m, l, acc) accumulator (:mod:`repro.kernels.online_softmax`, the same
recurrence :mod:`repro.kernels.flash_attn` uses for prefill tiles), one
accumulator per KV head.

Block-table walk
----------------
Grid is (B, W / P): one grid step handles a *chunk* of P consecutive table
columns for every KV head at once.  One table entry addresses a contiguous
(KV, bs, dh) slab of the (n_blocks, KV, bs, dh) pool, so a chunk is P slab
copies each of K and V, issued by hand (``pltpu.make_async_copy`` from the
``pl.ANY`` pool) into a (2, P, KV, bs, dh) VMEM scratch: double-buffered,
the copies of the next live chunk — the same row's next chunk, else the
next row's first — are in flight while this one is computed.  The table and
the per-slot positions ride in as *scalar-prefetched* operands, so the copy
sources are ``table[b, w]`` read from SMEM; the gather never materializes.
Both grid axes are "arbitrary": the prefetch chain runs across rows, and
the buffer slot in use lives in SMEM scratch.

The chunk rule (:func:`chunk_blocks`): P is the largest divisor of W whose
chunk holds at most ``CHUNK_TOKENS`` tokens and whose double-buffered K+V
scratch, 4·P·KV·bs·dh·itemsize bytes, fits ``SCRATCH_BYTES``; a W with no
such divisor but 1 walks one block per step.  qwen3-4b (W=160, bs=16, 8 KV
heads of dh 128, bf16): P=16, 256-token chunks, 512 KB per K buffer, 2 MB
of scratch, 10 grid columns per row.

Dead chunks (:func:`live_chunks`): the live chunks of a row are a prefix of
its table — chunk i is live iff ``i·P·bs <= pos[b]``.  On a monotone table
that is exact.  On a window ring (capacity ``cap = W·bs``) the same rule
reads every chunk once the ring has filled (``pos >= cap - 1``) and the
written prefix before: it may over-approximate (a chunk wholly out of the
window is still read) but never under-approximates.  A dead step issues no
copy and computes nothing: it costs only its grid overhead.

Masking within a live chunk (bit-for-bit the unfused chain's semantics):
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

A live chunk whose keys are all masked (a ring chunk out of the window) is
not folded in (``@pl.when``): the guard for the online-softmax all-NEG_INF
edge case (see online_softmax.update).

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm

NEG_INF = osm.NEG_INF
CHUNK_TOKENS = 256            # most tokens one grid step reads per row
SCRATCH_BYTES = 4 * 2**20     # most VMEM for the double-buffered K+V chunk


def chunk_blocks(w: int, bs: int, slab_bytes: int) -> int:
    """P, the table blocks per grid step, for a W-wide table of bs-token
    blocks whose one table entry addresses ``slab_bytes`` (KV·bs·dh·itemsize)
    of K: the largest divisor of W with ``P·bs <= CHUNK_TOKENS`` and
    ``4·P·slab_bytes <= SCRATCH_BYTES``, else 1."""
    return max(p for p in range(1, w + 1)
               if w % p == 0 and (p == 1 or (p * bs <= CHUNK_TOKENS and
                                             4 * p * slab_bytes
                                             <= SCRATCH_BYTES)))


def live_chunks(pos, *, p: int, bs: int, w: int):
    """Chunks of P table blocks the walk reads for a row at ``pos``: the
    first ``min(pos // (P·bs) + 1, W / P)``.  Works on a traced scalar (the
    kernel) and on ints or numpy arrays (the engine's counters) alike."""
    xp = jnp if isinstance(pos, jax.Array) else np
    return xp.minimum(pos // (p * bs) + 1, w // p)


def walk_blocks(pos, *, p: int, bs: int, w: int, window: int):
    """(read, live) table blocks of a row at ``pos``: those the walk
    streams, and those holding its attended keys (the last ``min(window,
    pos + 1)`` positions of a ring, all ``pos + 1`` of a monotone table)."""
    pos = np.asarray(pos)
    lo = np.maximum(pos - window + 1, 0) if window else 0
    live = np.minimum(pos // bs - lo // bs + 1, w)
    return live_chunks(pos, p=p, bs=bs, w=w) * p, live


def _kernel(table_ref, pos_ref, q_ref, ck_hbm, cv_hbm, o_ref, kbuf, vbuf,
            sems, slot_ref, m_ref, l_ref, acc_ref, *, bs: int, p: int,
            w_total: int, window: int, scale: float, out_scale: float):
    b, i = pl.program_id(0), pl.program_id(1)
    kv, dh = q_ref.shape[1], q_ref.shape[3]
    span = p * bs
    pos = pos_ref[b]
    n = live_chunks(pos, p=p, bs=bs, w=w_total)

    # loops, not unrolled Python, over the P blocks and the KV heads: the
    # kernel is traced anew for every pool size a program is built at, and
    # an unrolled body took seconds to trace; the head loop unrolls as it
    # is lowered (its iterations overlap, ~25% faster than a rolled one)
    def each_copy(row, chunk, slot, op):
        """``op`` on the K and V slab copy of each block of one chunk into
        buffer ``slot``."""
        def body(j, carry):
            blk = table_ref[row, chunk * p + j]
            for t, (pool, buf) in enumerate(((ck_hbm, kbuf), (cv_hbm, vbuf))):
                op(pltpu.make_async_copy(pool.at[blk], buf.at[slot, j],
                                         sems.at[t, slot]))
            return carry
        jax.lax.fori_loop(0, p, body, 0)

    def each_head(fn):
        def body(h, carry):
            fn(h)
            return carry
        jax.lax.fori_loop(0, kv, body, 0, unroll=True)

    @pl.when((b == 0) & (i == 0))
    def _first():
        slot_ref[0] = 0
        each_copy(0, 0, 0, lambda c: c.start())

    @pl.when(i < n)
    def _live():
        slot = slot_ref[0]
        last = i == n - 1

        @pl.when(jnp.logical_not(last) | (b + 1 < pl.num_programs(0)))
        def _prefetch():
            each_copy(jnp.where(last, b + 1, b), jnp.where(last, 0, i + 1),
                      1 - slot, lambda c: c.start())

        @pl.when(i == 0)
        def _init():
            osm.init(m_ref, l_ref, acc_ref)

        each_copy(b, i, slot, lambda c: c.wait())
        slot_ref[0] = 1 - slot

        # key slot index within the gathered cap-axis of the unfused chain:
        # table column w holds tokens w*bs .. w*bs + bs - 1 of the (ring)
        # window, and this chunk columns i*P .. i*P + P - 1
        kslot = i * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        if window:
            cap = w_total * bs
            age = (pos % cap - kslot) % cap
            valid = age < jnp.minimum(window, pos + 1)
        else:
            valid = kslot <= pos

        def update(h):
            q = q_ref[0, h].astype(jnp.float32)                    # (G, dh)
            k = kbuf[slot, :, h].astype(jnp.float32).reshape(span, dh)
            v = vbuf[slot, :, h].astype(jnp.float32).reshape(span, dh)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)               # (G, span)
            osm.update(s, v, m_ref.at[h], l_ref.at[h], acc_ref.at[h])

        def finish(h):
            o_ref[0, h] = (osm.finish(m_ref.at[h], l_ref.at[h], acc_ref.at[h])
                           * out_scale).astype(o_ref.dtype)

        pl.when(jnp.any(valid))(functools.partial(each_head, update))
        pl.when(last)(functools.partial(each_head, finish))


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

    Returns (B, KV, G, dh) in q.dtype.  VMEM per step: the double-buffered
    (2, P, KV, bs, dh) K and V chunk scratch (at most ``SCRATCH_BYTES``;
    2 MB for qwen3-4b's bf16 pool at P=16), the (KV, G, dh) q and out tiles
    and the (KV, G, 1)/(KV, G, dh) f32 accumulators.
    """
    b, kv, g, dh = q.shape
    bs = ck.shape[2]
    w_total = table.shape[1]
    p = chunk_blocks(w_total, bs, kv * bs * dh * ck.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w_total // p),
        in_specs=[
            pl.BlockSpec((1, kv, g, dh), lambda b, i, tbl, pos: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kv, g, dh),
                               lambda b, i, tbl, pos: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, p, kv, bs, dh), ck.dtype),
            pltpu.VMEM((2, p, kv, bs, dh), cv.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, p=p, w_total=w_total, window=window,
                          scale=scale, out_scale=out_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="paged_decode_attention",
        interpret=interpret,
    )(table, pos, q, ck, cv)
