"""Pallas TPU kernel: bulk bitwise XOR/XNOR over packed uint32 tiles.

The digital-equivalent form of the paper's banked single-cycle engine
(DESIGN.md §10): each grid step is one "bank cycle" — a (br, D) tile of
packed operand words is XORed lane-parallel, br*D*32 bit-ops per step.
HBM traffic is two reads + one write of the payload; there is no reduction
and no cross-tile dependency, so the kernel streams at memory bandwidth —
the TPU analogue of every bank sensing one row-pair per cycle.

XNOR is the complementary output rail of the same datapath (paper
Fig. 2(d)): the kernel inverts in-register, still one pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, o_ref, *, invert: bool):
    x = a_ref[...] ^ b_ref[...]                        # (br, D) uint32
    o_ref[...] = ~x if invert else x


@functools.partial(jax.jit, static_argnames=("invert", "br", "interpret"))
def bulk_xor(a: jnp.ndarray, b: jnp.ndarray, *, invert: bool = False,
             br: int = 512, interpret: bool = False) -> jnp.ndarray:
    """Elementwise XOR (or XNOR with ``invert=True``) of (R, D) uint32 tiles.

    R % br == 0 (ops.bulk_op pads flat buffers; XOR pad words are sliced off
    by the caller, so the pad value never matters).
    """
    r, d = a.shape
    assert a.shape == b.shape and r % br == 0, (a.shape, b.shape, br)
    grid = (r // br,)
    return pl.pallas_call(
        functools.partial(_kernel, invert=invert),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.uint32),
        name="bulk_xor",
        interpret=interpret,
    )(a, b)
