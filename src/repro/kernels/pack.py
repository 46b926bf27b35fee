"""Pallas TPU kernel: fused sign-extract + bit-pack + alpha scale.

One HBM pass over the real-valued operand produces (a) the packed sign
bit-planes and (b) the XNOR-Net scaling factor alpha = mean|x| per row —
mirroring the paper's sense amplifier producing the digital bit in the same
cycle that reads the cell.  Without fusion this costs three passes
(sign, pack, abs-mean); fused it is exactly one read of x.

The 32->1 pack (:func:`pack_words`) works word-major: the block is
transposed so K runs along sublanes, split into (Kw, 32, bm), shifted and
summed over the 32-row axis.  Bits are disjoint powers of two, so an int32
sum equals the bitwise OR (Mosaic has no unsigned reductions, and splitting
the lane axis into words is a shape cast it does not lower).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitpack import WORD


def pack_words(x: jnp.ndarray) -> jnp.ndarray:
    """(bm, K) float -> (Kw, bm) uint32 sign words, word-major.

    Word ``w`` of row ``m`` lands at ``[w, m]``; transposing gives the
    :func:`repro.core.bitpack.pack_bits` layout.
    """
    bm, k = x.shape
    bits = (x.T >= 0).astype(jnp.int32).reshape(k // WORD, WORD, bm)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, WORD, 1), 1)
    return jax.lax.bitcast_convert_type(jnp.sum(bits << shifts, axis=1),
                                        jnp.uint32)


def _kernel(x_ref, p_ref, a_ref):
    x = x_ref[...].astype(jnp.float32)           # (bm, K)
    p_ref[...] = pack_words(x).T
    a_ref[...] = jnp.mean(jnp.abs(x), axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def pack(x: jnp.ndarray, *, bm: int = 256, interpret: bool = False):
    """(M, K) float -> ((M, K/32) uint32 planes, (M,) f32 alpha).

    M % bm == 0 and K % 32 == 0 (ops.binarize pads arbitrary shapes).
    K is kept unblocked: a full row must be visible to compute alpha in the
    same pass; rows are streamed bm at a time.
    """
    m, k = x.shape
    assert m % bm == 0 and k % WORD == 0, (x.shape, bm)
    grid = (m // bm,)
    planes, alpha = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, k // WORD), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k // WORD), jnp.uint32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return planes, alpha[:, 0]
