"""Pallas TPU kernel: single-pass XOR-fold parity digest.

The paper's copy-verification XORs source row against copied row and checks
for all-zeros.  At framework scale we fold an arbitrarily large uint32 buffer
into a fixed-width digest in ONE streaming pass (digest(a) == digest(b) <=>
parity check passes for the whole buffer; any single-bit corruption flips
exactly one digest bit).  The digest block stays resident in VMEM across the
whole grid; HBM traffic is exactly one read of the buffer — the roofline for
verification is the HBM stream, the TPU analogue of "single cycle".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _xor_fold(v: jnp.ndarray) -> jnp.ndarray:
    """XOR-fold the rows of a (2^n, D) block to (1, D) by halving.

    An explicit tree of ``^``: Mosaic has no xor reduction primitive.
    """
    while v.shape[0] > 1:
        h = v.shape[0] // 2
        v = v[:h] ^ v[h:]
    return v


def _kernel(w_ref, d_ref):
    i = pl.program_id(0)
    fold = _xor_fold(w_ref[...])                         # (1, D)

    @pl.when(i == 0)
    def _init():
        d_ref[...] = fold

    @pl.when(i != 0)
    def _accum():
        d_ref[...] ^= fold


@functools.partial(jax.jit, static_argnames=("digest_width", "br", "interpret"))
def parity_digest(words: jnp.ndarray, *, digest_width: int = 128,
                  br: int = 512, interpret: bool = False) -> jnp.ndarray:
    """Fold a (R, digest_width) uint32 buffer to a (digest_width,) digest.

    R % br == 0 and br a power of two (ops.digest pads flat buffers with
    XOR-neutral zeros).
    """
    r, d = words.shape
    assert d == digest_width and r % br == 0, (words.shape, digest_width, br)
    assert br & (br - 1) == 0, br
    grid = (r // br,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.uint32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(words)
    return out[0]
