"""Pure-jnp oracles for every Pallas kernel in :mod:`repro.kernels`.

Each function is the semantic ground truth the kernels are tested against
(``tests/test_kernels.py`` sweeps shapes/dtypes and asserts allclose /
bit-exact equality).  They are also the *production fallback* on non-TPU
backends: ``ops.py`` dispatches here whenever the Pallas path is unavailable,
so the whole framework (including the 512-device dry-run on CPU) runs the
same semantics everywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack

GOLDEN = np.uint32(0x9E3779B9)  # numpy scalar: folds into Pallas kernels


# ---------------------------------------------------------------------------
# XNOR-popcount GEMM
# ---------------------------------------------------------------------------

def xnor_gemm(pa: jnp.ndarray, pb: jnp.ndarray, valid_k: int) -> jnp.ndarray:
    """Binary (±1) matmul in the packed domain.

    ``pa``: (M, Kw) uint32 bit-planes, ``pb``: (N, Kw) uint32 bit-planes.
    Returns (M, N) int32 with ``out[m, n] = sum_k a[m, k] * b[n, k]`` over the
    first ``valid_k`` (unpacked, ±1) positions.  Padding bits must be equal in
    both operands (``bitpack.pad_to_word`` pads with +1): each padded slot
    XORs to 0, so ``dot_padded = K_pad - 2*popcount`` and the wrapper removes
    the pad contribution by using ``valid_k`` instead of ``K_pad``.
    """
    x = jnp.bitwise_xor(pa[:, None, :], pb[None, :, :])
    popc = jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)
    return jnp.int32(valid_k) - 2 * popc


def xnor_dot_float(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Float-domain equivalence oracle: sign(a) @ sign(b).T."""
    sa = jnp.where(a >= 0, 1.0, -1.0)
    sb = jnp.where(b >= 0, 1.0, -1.0)
    return (sa @ sb.T).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fused sign-extract + pack + alpha
# ---------------------------------------------------------------------------

def pack(x: jnp.ndarray):
    """(M, K) -> ((M, K/32) uint32, (M,) f32 alpha = mean|x|)."""
    return bitpack.pack_bits(x), jnp.mean(jnp.abs(x), axis=-1).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Fused paged-decode attention (kernels/paged_attn.py, DESIGN.md §18)
# ---------------------------------------------------------------------------

def paged_decode(q: jnp.ndarray, ck: jnp.ndarray, cv, table: jnp.ndarray,
                 pos: jnp.ndarray, *, window: int = 0, scale: float = 1.0,
                 out_scale: float = 1.0, v_dim: int = 0) -> jnp.ndarray:
    """One-shot masked-softmax oracle for the fused paged decode kernel.

    Same semantics as the C == 1 path of ``models/attention.py::
    paged_attention`` after the scatter: gather the pool through the block
    table, score, mask (monotone or window-ring), softmax, PV.  ``q`` is
    (B, KV, G, dk); ``ck``/``cv`` are the (n_blocks, KV, bs, dk) pool
    (any dtype incl. int8 — decoded to f32 here, the fixed-point factors
    arrive folded into ``scale``/``out_scale``); with ``v_dim`` the pool is
    latent, ``cv`` is None and the values are ``ck``'s first ``v_dim``
    lanes.  Returns (B, KV, G, dv) in q.dtype.
    """
    b, kv, g, dk = q.shape
    bs = ck.shape[2]
    cap = table.shape[1] * bs
    gk = jnp.moveaxis(ck[table], 1, 2).reshape(b, kv, cap, dk)
    gv = (gk[..., :v_dim] if v_dim
          else jnp.moveaxis(cv[table], 1, 2).reshape(b, kv, cap, dk))
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32),
                   gk.astype(jnp.float32)) * scale
    kslot = jnp.arange(cap, dtype=jnp.int32)
    p = pos[:, None]
    if window:
        age = (p % cap - kslot[None]) % cap
        valid = age < jnp.minimum(window, p + 1)
    else:
        valid = kslot[None] <= p
    s = jnp.where(valid[:, None, None, :], s, jnp.float32(-1e30))
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", w, gv.astype(jnp.float32))
    return (out * out_scale).astype(q.dtype)


# ---------------------------------------------------------------------------
# Grouped expert FFN (kernels/moe_ffn.py)
# ---------------------------------------------------------------------------

def moe_expert_ffn(x, w1, w3, w2, layer, tile_expert, tile_used, *,
                   tm: int) -> jnp.ndarray:
    """Oracle for the grouped expert kernel: each tile of ``tm`` rows
    through its expert's SwiGLU FFN in layer ``layer[0]`` of the stacked
    weights (f32 products, the hidden in the weights' type); rows of unused
    tiles are zero."""
    w1, w3, w2 = (w[layer[0]] for w in (w1, w3, w2))
    n, d = x.shape
    xt = x.reshape(n // tm, tm, d)
    h = (jax.nn.silu(jnp.einsum("tmd,tdf->tmf", xt, w1[tile_expert],
                                preferred_element_type=jnp.float32))
         * jnp.einsum("tmd,tdf->tmf", xt, w3[tile_expert],
                      preferred_element_type=jnp.float32))
    y = jnp.einsum("tmf,tfd->tmd", h.astype(w2.dtype), w2[tile_expert],
                   preferred_element_type=jnp.float32)
    return jnp.where(tile_used[:, None, None] == 1, y, 0.0).reshape(n, d)


# ---------------------------------------------------------------------------
# Fused prepacked XNOR linear (binarize + popcount GEMM + alpha/beta epilogue)
# ---------------------------------------------------------------------------

def xnor_linear_fused(x: jnp.ndarray, pb: jnp.ndarray, beta: jnp.ndarray,
                      valid_k: int) -> jnp.ndarray:
    """Oracle for the fused packed linear: the exact unfused chain.

    ``x``: (M, K) activations, ``pb``: (N, Kw) prepacked weight bit-planes,
    ``beta``: (N,) weight scales.  Returns (M, N) f32 =
    (valid_k - 2*popcount) * alpha * beta with alpha = mean|x| per row —
    bit-for-bit what binarize -> xnor_gemm -> scale produces unfused
    (alpha stays in x.dtype exactly as the layer computes it).
    """
    alpha = jnp.mean(jnp.abs(x), axis=-1)
    pa = bitpack.pack_bits(bitpack.pad_to_word(x))
    dots = xnor_gemm(pa, pb, valid_k).astype(jnp.float32)
    return dots * alpha[:, None] * beta[None, :]


# ---------------------------------------------------------------------------
# Bulk XOR/XNOR (the banked engine's row-pair cycle, DESIGN.md §10)
# ---------------------------------------------------------------------------

def bulk_xor(a: jnp.ndarray, b: jnp.ndarray, invert: bool = False) -> jnp.ndarray:
    """Elementwise XOR (XNOR with ``invert``) of two uint32 buffers."""
    x = jnp.bitwise_xor(a, b)
    return jnp.bitwise_not(x) if invert else x


# ---------------------------------------------------------------------------
# XOR parity digest (bulk copy-verification)
# ---------------------------------------------------------------------------

def parity_digest(words: jnp.ndarray, digest_width: int = 128) -> jnp.ndarray:
    """XOR-fold a flat uint32 buffer into a ``digest_width``-word digest.

    The digest of a buffer is invariant to where the buffer lives — comparing
    digests of source and copy is the paper's row-parity copy-verification.
    Buffer length must be a multiple of ``digest_width`` (ops.py pads with 0,
    which is XOR-neutral).
    """
    r = words.reshape(-1, digest_width)
    return jnp.bitwise_xor.reduce(r, axis=0)


# ---------------------------------------------------------------------------
# Counter-mode XOR stream cipher
# ---------------------------------------------------------------------------

def keystream_word(idx: jnp.ndarray, key0: jnp.ndarray, key1: jnp.ndarray) -> jnp.ndarray:
    """Deterministic 32-bit keystream: murmur3-finalizer counter hash.

    Not cryptographic — stands in for the paper's "true random key" XOR pad;
    the framework interface accepts externally supplied pads for real use.
    Shared verbatim by the Pallas kernel so ref and kernel are bit-identical.
    """
    h = idx.astype(jnp.uint32) * GOLDEN + key0.astype(jnp.uint32)
    h = h ^ key1.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def xor_cipher(words: jnp.ndarray, key: jnp.ndarray, counter: int | jnp.ndarray = 0) -> jnp.ndarray:
    """Encrypt/decrypt (involution) a flat uint32 buffer in counter mode."""
    idx = (jnp.arange(words.shape[0], dtype=jnp.uint32)
           + jnp.asarray(counter, jnp.uint32))
    return words ^ keystream_word(idx, key[0], key[1])
