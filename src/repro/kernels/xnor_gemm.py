"""Pallas TPU kernel: bit-packed XNOR-popcount GEMM.

This is the TPU realization of the paper's single-cycle in-memory XNOR: each
operand word is read from HBM into VMEM exactly once, and the XOR + popcount
+ accumulate all happen in that same pass (VPU int32 lanes; the MXU is
deliberately idle — binary dot products are bitwise ops, not MACs).

Tiling
------
Grid is (M/bm, N/bn, Kw/bk) with the k-axis innermost ("arbitrary"
dimension semantics: the output block is revisited across k steps and
accumulated in place, the standard Pallas matmul pattern).  Per grid step the
VMEM working set is

    a_blk (bm, bk) u32  +  b_blk (bn, bk) u32  +  o_blk (bm, bn) i32
    + staged words (bk, bm, bn) u32 + (bk, bn) u32

e.g. (8, 128, 128) -> 4 KiB + 64 KiB + 4 KiB + 512 KiB + 64 KiB, far
under the ~16 MiB VMEM budget.  The inner loop walks the bk packed words
one (bm, bn) tile at a time, so the partial product is the only live
intermediate.

Word loop: Mosaic slices lanes only at static or 128-aligned offsets, so
the loop over packed words cannot index a lane per word.  Each step stages
both operands word-major in VMEM instead (:func:`_stage_words`): word ``w``
of every activation row broadcast across the bn output lanes, and the
weight block transposed so word ``w`` is one sublane row.  The loop body is
then a dynamic leading-axis read, a row broadcast, XOR, popcount and add.

Lane alignment: bm a multiple of 8, bn and bk multiples of 128 or the full
dimension.  `ops.xnor_matmul` pads arbitrary shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitpack import WORD
from repro.kernels.pack import pack_words


def _stage_words(a_t, a_scr):
    """Broadcast (Kw, bm) word-major activation words to (Kw, bm, bn)."""
    a_scr[...] = jnp.broadcast_to(a_t[:, :, None], a_scr.shape)


def _popcount_dots(b, a_scr, b_scr):
    """sum_w popcount(a[m, w] ^ b[n, w]) -> (bm, bn) int32."""
    kw, bm, bn = a_scr.shape
    b_scr[...] = b.T                                          # (Kw, bn)

    def body(w, acc):
        x = a_scr[w] ^ b_scr[pl.ds(w, 1), :]                  # (bm, bn)
        return acc + jax.lax.population_count(x).astype(jnp.int32)

    return jax.lax.fori_loop(0, kw, body, jnp.zeros((bm, bn), jnp.int32))


def _kernel(a_ref, b_ref, o_ref, a_scr, b_scr):
    """One (bm, bn) output block, accumulating over the k-grid axis."""
    kstep = pl.program_id(2)
    _stage_words(a_ref[...].T, a_scr)
    acc = _popcount_dots(b_ref[...], a_scr, b_scr)

    @pl.when(kstep == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(kstep != 0)
    def _accum():
        o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("valid_k", "bm", "bn", "bk", "interpret"))
def xnor_gemm(pa: jnp.ndarray, pb: jnp.ndarray, *, valid_k: int,
              bm: int = 128, bn: int = 128, bk: int = 128,
              interpret: bool = False) -> jnp.ndarray:
    """Packed binary matmul: (M, Kw) x (N, Kw) -> (M, N) int32 ±1-dot.

    Requires M % bm == N % bn == Kw % bk == 0 (use ops.xnor_matmul for
    arbitrary shapes).  ``valid_k`` is the unpacked dot length; padding bits
    must agree between operands (see ref.xnor_gemm).
    """
    m, kw = pa.shape
    n, kw2 = pb.shape
    assert kw == kw2, (kw, kw2)
    assert m % bm == 0 and n % bn == 0 and kw % bk == 0, (pa.shape, pb.shape, bm, bn, bk)

    grid = (m // bm, n // bn, kw // bk)
    popc = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bk, bm, bn), jnp.uint32),
                        pltpu.VMEM((bk, bn), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pa, pb)
    return jnp.int32(valid_k) - 2 * popc


# ---------------------------------------------------------------------------
# Fused prepacked linear: binarize + popcount GEMM + alpha/beta epilogue
# ---------------------------------------------------------------------------

def _fused_kernel(x_ref, b_ref, beta_ref, o_ref, a_scr, b_scr, alpha_scr, *,
                  valid_k: int):
    """One (bm, bn) f32 output tile of the fused packed linear.

    The real-valued activation block is read from HBM exactly once: on the
    first column step of a row block its sign bits are packed in-register
    (:func:`repro.kernels.pack.pack_words`) and staged word-major in VMEM
    with its alpha, and every column step of that row block streams its
    weight words through the XOR+popcount loop against them.  The XNOR-Net
    epilogue ``(valid_k - 2*popc) * alpha * beta`` lands in the same pass —
    no packed activation plane or int32 dot tensor ever round-trips HBM.
    """
    @pl.when(pl.program_id(1) == 0)
    def _pack():
        x = x_ref[...].astype(jnp.float32)                  # (bm, Kp)
        _stage_words(pack_words(x), a_scr)
        # 0.0 pads are |.|-neutral, so sum/valid_k is the true-row-length
        # mean; rounded through the activation dtype as the unfused layer's
        # jnp.mean rounds it, so both paths scale by the same alpha.
        alpha = jnp.sum(jnp.abs(x), axis=-1, keepdims=True) / valid_k
        alpha_scr[...] = alpha.astype(x_ref.dtype).astype(jnp.float32)

    popc = _popcount_dots(b_ref[...], a_scr, b_scr)
    # column pads of x are 0.0 -> sign bit 1, matching pb's word-tail pad
    # bits (prepacking zero-pads, 0 >= 0 -> 1): pads XOR to 0, so the
    # valid_k accounting removes their +1 dot bias exactly (ref.xnor_gemm).
    dots = (jnp.int32(valid_k) - 2 * popc).astype(jnp.float32)
    o_ref[...] = dots * alpha_scr[...] * beta_ref[...]       # beta: (1, bn)


@functools.partial(jax.jit, static_argnames=("valid_k", "bm", "bn",
                                             "interpret"))
def xnor_linear_fused(x: jnp.ndarray, pb: jnp.ndarray, beta: jnp.ndarray, *,
                      valid_k: int, bm: int = 128, bn: int = 128,
                      interpret: bool = False) -> jnp.ndarray:
    """Fused packed linear: (M, Kp) float x (N, Kw) packed -> (M, N) f32.

    Requires M % bm == N % bn == 0 and Kp == Kw * 32 (ops.xnor_linear_fused
    pads arbitrary shapes).  Grid is (M/bm, N/bn) with K unblocked — a full
    activation row must be visible in one step to compute alpha alongside
    the dot (same constraint as pack.py); the column axis is sequential
    ("arbitrary") so the packed row block staged at column 0 serves every
    column.  Per-step VMEM is the (bm, Kp) activation block, the (bn, Kw)
    weight planes and their word-major copies, and the (bm, bn) tile; the
    staged activation words, (Kw, bm, bn) uint32, dominate, so
    ``ops.xnor_linear_fused`` narrows bm as K grows.
    """
    m, kp = x.shape
    n, kw = pb.shape
    assert kp == kw * WORD and m % bm == 0 and n % bn == 0, (x.shape, pb.shape)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_fused_kernel, valid_k=valid_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, kw), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kw, bm, bn), jnp.uint32),
                        pltpu.VMEM((kw, bn), jnp.uint32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, pb, beta.astype(jnp.float32).reshape(1, -1))
