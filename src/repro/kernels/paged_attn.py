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
columns for every KV head at once.  The pool comes layer-stacked, (L,
n_blocks, KV, bs, dh), with the layer's index: the serving scans carry the
whole stack, and a kernel handed one layer's slice would be handed a copy
of it.  One table entry addresses a contiguous (KV, bs, dh) slab of the
layer's pool, ``pool.at[layer, blk]``, so a chunk is P slab copies each of
K and V (of the one latent slab, for a latent pool: see below), issued by
hand (``pltpu.make_async_copy`` from the ``pl.ANY`` pool) into a (2, P,
KV, bs, dh) VMEM scratch: double-buffered, the copies of the next live
chunk — the same row's next chunk, else the next row's first — are in
flight while this one is computed.  The table, the per-slot positions and
the layer ride in as *scalar-prefetched* operands, so the copy sources are
``table[b, w]`` read from SMEM; the gather never materializes.
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

Latent pools (MLA, ``v_dim > 0``): one slab per token of kv_lora_rank +
qk_rope_head_dim values (576 for Moonlight), the compressed vector then
its rotary key, under a single KV "head".  The caller folds the key half
of ``kv_b`` into q, so all query heads score against the slab as it lies
(q_lat·c + q_rope·k_rope in one product), and the weighted sum is taken
over the slab's first ``v_dim`` lanes (c itself): each live slab is read
once, there is no V pool.  The value half of ``kv_b`` is applied by the
caller after the sum.  576 lanes are 4.5 tiles of 128, and a slab copy must
be tile-aligned: the pool stores the vector zero-padded to 640 lanes (the
chip lays a 576-wide array out at 640 anyway), 11% more bytes than the
yardstick counts.

Numerics: the online recurrence equals one-shot masked softmax exactly in
real arithmetic but not bit-for-bit in floats (association/rounding).  The
model-level dispatch therefore routes this kernel on real TPU backends and
keeps the jnp chain — which doubles as this kernel's reference twin
(:func:`repro.kernels.ref.paged_decode` is the semantic oracle) — on
ref/interpret backends, so every token pin (paged == the reference loop,
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


def slab_bytes(kv: int, bs: int, dk: int, itemsize: int, pools: int) -> int:
    """The ``slab_bytes`` :func:`chunk_blocks` takes for a pool of
    ``pools`` slabs per block (K and V, or one latent slab): its rule
    budgets a K and a V buffer, so one latent slab counts as half a
    pair."""
    return kv * bs * dk * itemsize * pools // 2


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


def _kernel(table_ref, pos_ref, layer_ref, q_ref, *refs, bs: int, p: int,
            w_total: int, window: int, scale: float, out_scale: float,
            v_dim: int):
    # a latent pool (v_dim > 0) is one slab per token whose first v_dim
    # lanes are the values; a GQA pool is a K slab and a V slab
    n_pools = 1 if v_dim else 2
    pools, (o_ref,) = refs[:n_pools], refs[n_pools:n_pools + 1]
    bufs = refs[n_pools + 1:2 * n_pools + 1]
    sems, slot_ref, m_ref, l_ref, acc_ref = refs[2 * n_pools + 1:]
    b, i = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    kv, dk = q_ref.shape[1], q_ref.shape[3]
    span = p * bs
    pos = pos_ref[b]
    n = live_chunks(pos, p=p, bs=bs, w=w_total)

    # loops, not unrolled Python, over the P blocks and the KV heads: the
    # kernel is traced anew for every pool size a program is built at, and
    # an unrolled body took seconds to trace; the head loop unrolls as it
    # is lowered (its iterations overlap, ~25% faster than a rolled one)
    def each_copy(row, chunk, slot, op):
        """``op`` on each pool's slab copy of each block of one chunk into
        buffer ``slot``."""
        def body(j, carry):
            blk = table_ref[row, chunk * p + j]
            for t, (pool, buf) in enumerate(zip(pools, bufs)):
                op(pltpu.make_async_copy(pool.at[layer, blk],
                                         buf.at[slot, j],
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
            q = q_ref[0, h].astype(jnp.float32)                    # (G, dk)
            k = bufs[0][slot, :, h].astype(jnp.float32).reshape(span, dk)
            if v_dim:
                v = k[:, :v_dim]
            else:
                v = bufs[1][slot, :, h].astype(jnp.float32).reshape(span, dk)
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
                                             "v_dim", "interpret"))
def paged_decode_attention(q, ck, cv, layer, table, pos, *, window: int = 0,
                           scale: float = 1.0, out_scale: float = 1.0,
                           v_dim: int = 0,
                           interpret: bool = False) -> jnp.ndarray:
    """One fused decode-attention dispatch over the block-paged pool.

    q       (B, KV, G, dk)  — this step's queries, compact GQA form
    ck, cv  (L, n_blocks, KV, bs, dk) — the layer-stacked shared pool
            (f32/bf16 or int8), read in place, with this step's K/V
            already written in (a (B,) token write, not part of the
            HBM-bound gather chain)
    layer   (1,) int32      — the layer of ``ck``/``cv`` to attend over
    table   (B, W) int32    — per-slot physical block ids
    pos     (B,)  int32     — per-slot current absolute position
    window  0 for monotone tables; the local window size for block rings
    scale   QK scale (``dk**-0.5``, with the i8 fixed-point factor folded
            in for int8 pools); ``out_scale`` is the PV-side i8 correction.
    v_dim   0, or a latent pool: ``cv`` is None, the KV "head" is one
            latent vector per token and the values are its first ``v_dim``
            lanes, so each live slab is read once for both.

    Returns (B, KV, G, dv) in q.dtype (dv = ``v_dim`` or dk).  VMEM per
    step: the double-buffered (2, P, KV, bs, dk) chunk scratch of each
    pool (at most ``SCRATCH_BYTES``; 2 MB for qwen3-4b's bf16 pool at
    P=16), the q and out tiles and the f32 accumulators.
    """
    b, kv, g, dk = q.shape
    bs = ck.shape[3]
    dv = v_dim or dk
    w_total = table.shape[1]
    pools = (ck,) if v_dim else (ck, cv)
    p = chunk_blocks(w_total, bs,
                     slab_bytes(kv, bs, dk, ck.dtype.itemsize, len(pools)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, w_total // p),
        in_specs=[
            pl.BlockSpec((1, kv, g, dk),
                         lambda b, i, tbl, pos, lyr: (b, 0, 0, 0)),
            *[pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
        ],
        out_specs=pl.BlockSpec((1, kv, g, dv),
                               lambda b, i, tbl, pos, lyr: (b, 0, 0, 0)),
        scratch_shapes=[
            *[pltpu.VMEM((2, p, kv, bs, dk), x.dtype) for x in pools],
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, p=p, w_total=w_total, window=window,
                          scale=scale, out_scale=out_scale, v_dim=v_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="paged_decode_attention",
        interpret=interpret,
    )(table, pos, layer, q, *pools)
