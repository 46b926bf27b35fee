"""Pallas TPU kernel: counter-mode XOR stream cipher.

Encryption in the paper is a bulk XOR against a key row.  Counter mode makes
the pad position-dependent (no key-row reuse across rows) while staying a
pure XOR — decryption is the same kernel (involution).  The keystream is
generated *inside* the kernel from (key, word index), so the only HBM traffic
is one read + one write of the payload: the keystream never touches HBM.

Keystream = murmur3 finalizer over the global word index (shared bit-exactly
with ref.keystream_word).  Stand-in for the paper's true-random pad; external
pads are supported one level up (core/encrypt.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.ref import keystream_word


def _kernel(k_ref, w_ref, o_ref, *, cols: int):
    i = pl.program_id(0)
    chunk = w_ref[...]                                 # (br, D) uint32
    br, d = chunk.shape
    base = (i * br * d + k_ref[0, 2]).astype(jnp.uint32)
    idx = (base
           + jax.lax.broadcasted_iota(jnp.uint32, chunk.shape, 0) * np.uint32(d)
           + jax.lax.broadcasted_iota(jnp.uint32, chunk.shape, 1))
    o_ref[...] = chunk ^ keystream_word(idx, k_ref[0, 0], k_ref[0, 1])


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def xor_cipher(words: jnp.ndarray, key: jnp.ndarray, *, br: int = 512,
               interpret: bool = False) -> jnp.ndarray:
    """Encrypt/decrypt a (R, D) uint32 buffer.

    ``key`` is (3,) uint32: (key0, key1, counter_base).  R % br == 0.
    """
    r, d = words.shape
    assert r % br == 0, (words.shape, br)
    grid = (r // br,)
    return pl.pallas_call(
        functools.partial(_kernel, cols=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.uint32),
        name="xor_cipher",
        interpret=interpret,
    )(key.reshape(1, 3), words)
