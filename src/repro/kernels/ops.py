"""Public ops: padding, backend dispatch (Pallas on TPU / jnp ref elsewhere).

Every op has three execution paths with identical semantics:
  * ``impl="pallas"``     — the TPU kernel (real hardware),
  * ``impl="interpret"``  — the same kernel body interpreted on CPU (tests),
  * ``impl="ref"``        — the pure-jnp oracle (CPU production + dry-run).
``impl="auto"`` picks pallas on TPU backends and ref otherwise, so the same
model code lowers everywhere (the 512-device CPU dry-run included).
"""

from __future__ import annotations

import os

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.kernels import bulk as _bulk
from repro.kernels import cipher as _cipher
from repro.kernels import moe_ffn as _moe_ffn
from repro.kernels import pack as _pack
from repro.kernels import paged_attn as _paged_attn
from repro.kernels import parity as _parity
from repro.kernels import ref
from repro.kernels import xnor_gemm as _xnor_gemm

_FORCE = os.environ.get("REPRO_KERNEL_IMPL", "")  # "", "ref", "pallas", "interpret"
# staged-word scratch of the fused XNOR linear: half of the 16 MiB scoped
# VMEM a TPU v5e kernel gets by default, the rest for its blocks
_FUSED_STAGE_BYTES = 8 * 2**20


def _resolve(impl: str) -> str:
    impl = _FORCE or impl
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl not in ("ref", "pallas", "interpret"):
        raise ValueError(
            f"unknown kernel impl {impl!r} (from REPRO_KERNEL_IMPL or impl=);"
            " expected auto|ref|interpret|pallas")
    return impl


def fused_mode() -> str:
    """Resolve the fused-decode dispatch: ``"kernel"`` or ``"ref"``.

    ``"kernel"`` routes the decode hot path through the single-dispatch
    Pallas kernels (paged_attn / the fused packed linear / the grouped
    expert FFN); ``"ref"`` keeps the unfused jnp chain, which is the
    kernels' bit-exact reference twin (DESIGN.md §18).  The platform
    decides: the kernel exactly when the base dispatch resolves to
    real-TPU pallas — on ref/interpret backends the chain stays unfused so
    every token pin (paged == reference loop, prefix on == off, migration
    identity) remains bitwise across both ``REPRO_KERNEL_IMPL`` CI modes.
    The ``REPRO_FUSED_DECODE`` env var (read per call, so tests can
    monkeypatch) overrides that: ``on`` forces the kernel, ``off`` the
    chain; unset or empty leaves the platform's choice.
    """
    mode = os.environ.get("REPRO_FUSED_DECODE", "")
    if mode == "on":
        return "kernel"
    if mode == "off":
        return "ref"
    if mode:
        raise ValueError(
            f"unknown REPRO_FUSED_DECODE value {mode!r}; expected on|off")
    return "kernel" if _resolve("auto") == "pallas" else "ref"


def _pad_rows(x: jnp.ndarray, mult: int) -> jnp.ndarray:
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


def _round8(n: int) -> int:
    return -(-n // 8) * 8


def _pad_cols(x: jnp.ndarray, mult: int, value=0) -> jnp.ndarray:
    pad = (-x.shape[-1]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------


def xnor_matmul(pa: jnp.ndarray, pb: jnp.ndarray, valid_k: int,
                impl: str = "auto", **blocks) -> jnp.ndarray:
    """±1 dot in the packed domain for arbitrary (M, Kw) x (N, Kw).

    Padding rule: row pads produce garbage rows that are sliced off; column
    (word) pads are zero words in BOTH operands, XOR to zero, and are removed
    by ``valid_k`` accounting (popcount of zero is zero -> each pad word
    contributes +32 to the padded dot; using valid_k subtracts exactly that).
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.xnor_gemm(pa, pb, valid_k)
    m, n = pa.shape[0], pb.shape[0]
    # row tiles never exceed the 8-row-rounded operand (a decode batch of 8
    # is not padded to 128 rows); bk defaults to one 128-lane tile
    bm = min(blocks.get("bm", 128), _round8(m))
    bn = min(blocks.get("bn", 128), _round8(n))
    bk = blocks.get("bk", 128)
    pa2, pb2 = _pad_rows(pa, bm), _pad_rows(pb, bn)
    kw = pa2.shape[1]
    # pad kw up to a multiple of bk rather than collapsing the tile to bk=1
    # on non-divisible packed widths (e.g. kw=160 with bk=128): pad words
    # are zero in both operands and the kpad-valid_k correction below
    # removes their bias exactly, so the grid stays ceil(kw/bk) steps.  A
    # narrower operand takes bk = kw, the full (lane-legal) width.
    bk = min(bk, kw)
    pa2, pb2 = _pad_cols(pa2, bk), _pad_cols(pb2, bk)
    # pad words are 0 in both operands => popcount contribution 0; the
    # (kw_pad*32 - valid_k) correction below removes their +1 dot bias.
    kpad = pa2.shape[1] * bitpack.WORD
    out = _xnor_gemm.xnor_gemm(pa2, pb2, valid_k=kpad, bm=bm, bn=bn, bk=bk,
                               interpret=(impl == "interpret"))
    return out[:m, :n] - jnp.int32(kpad - valid_k)


def binarize(x: jnp.ndarray, impl: str = "auto", bm: int = 256):
    """(..., K) float -> ((..., Kw) uint32, (...,) f32 alpha). Fused on TPU."""
    impl = _resolve(impl)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = _pad_cols(x.reshape(-1, k), bitpack.WORD)
    if impl == "ref":
        planes = bitpack.pack_bits(x2)
        alpha = jnp.mean(jnp.abs(x2[:, :k]), axis=-1).astype(jnp.float32)
    else:
        m = x2.shape[0]
        # pad rows up to a multiple of bm rather than collapsing the tile to
        # bm=1 on non-divisible row counts (the digest/stream_cipher fix):
        # pad rows are garbage in planes/alpha and are sliced off below.
        bm = min(bm, m)
        x3 = _pad_rows(x2, bm)
        planes, alpha = _pack.pack(x3, bm=bm, interpret=(impl == "interpret"))
        planes, alpha = planes[:m], alpha[:m]
        # kernel alpha averaged over padded K; rescale to true K.
        alpha = alpha * (x2.shape[1] / k)
    return planes.reshape(*lead, -1), alpha.reshape(lead)


def xnor_linear_fused(x: jnp.ndarray, pb: jnp.ndarray, beta: jnp.ndarray,
                      valid_k: int, impl: str = "auto", bm: int = 128,
                      bn: int = 128) -> jnp.ndarray:
    """Single-dispatch packed linear: binarize + XNOR GEMM + alpha/beta.

    ``x``: (M, K) activations, ``pb``: (N, Kw) prepacked weight planes,
    ``beta``: (N,) weight scales; returns (M, N) f32.  The unfused chain
    (``binarize`` -> ``xnor_matmul`` -> scale) materializes the packed
    activation planes and the int32 dots in HBM between dispatches; here
    they live and die inside one kernel.  ``impl="ref"`` runs the pure-jnp
    oracle (bit-identical to the unfused ref chain).
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.xnor_linear_fused(x, pb, beta, valid_k)
    m, n = x.shape[0], pb.shape[0]
    # column pads are 0.0: they pack to 1-bits, matching pb's word-tail pad
    # bits, so the kernel's valid_k accounting stays exact (see _fused_kernel)
    xp = _pad_cols(x, bitpack.WORD)
    bm, bn = min(bm, _round8(m)), min(bn, _round8(n))
    # the kernel stages each row block's packed words broadcast over the
    # output lanes, (Kw, bm, bn) uint32 in VMEM: fewer rows per block keep
    # that within _FUSED_STAGE_BYTES (at K 9728 and bn 128, bm 48)
    kw = xp.shape[1] // bitpack.WORD
    bm = min(bm, max(8, _FUSED_STAGE_BYTES // (kw * bn * 4) // 8 * 8))
    xp, pb2 = _pad_rows(xp, bm), _pad_rows(pb, bn)
    beta2 = jnp.pad(beta, (0, pb2.shape[0] - n))
    out = _xnor_gemm.xnor_linear_fused(xp, pb2, beta2, valid_k=valid_k,
                                       bm=bm, bn=bn,
                                       interpret=(impl == "interpret"))
    return out[:m, :n]


def digest(buf: jnp.ndarray, digest_width: int = 128, impl: str = "auto",
           br: int = 512) -> jnp.ndarray:
    """XOR-parity digest of any array (viewed as a uint32 stream)."""
    impl = _resolve(impl)
    words = as_words(buf)
    pad = (-words.shape[0]) % digest_width
    words = jnp.pad(words, (0, pad))  # zeros are XOR-neutral
    words = words.reshape(-1, digest_width)
    if impl == "ref":
        return ref.parity_digest(words, digest_width)
    # pad rows rather than shrink the tile (zero rows are XOR-neutral for the
    # fold): shrinking to br=1 on non-divisible row counts explodes the grid
    # to one row per step.  The kernel's halving fold needs a power of two.
    br = min(br, 1 << (words.shape[0] - 1).bit_length())
    words = _pad_rows(words, br)
    return _parity.parity_digest(words, digest_width=digest_width, br=br,
                                 interpret=(impl == "interpret"))


def paged_decode(q, ck, cv, layer, table, pos, *, window: int = 0,
                 scale: float = 1.0, out_scale: float = 1.0, v_dim: int = 0,
                 impl: str = "auto") -> jnp.ndarray:
    """Fused block-paged decode attention (DESIGN.md §18) over layer
    ``layer`` of a layer-stacked pool.

    ``impl="ref"`` runs the one-shot oracle :func:`ref.paged_decode`; the
    kernel is interpreted only when ``interpret`` is asked for.  Shapes as
    in :func:`repro.kernels.paged_attn.paged_decode_attention`; a latent
    pool passes ``cv=None`` and ``v_dim``.
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.paged_decode(q, ck, cv, layer, table, pos, window=window,
                                scale=scale, out_scale=out_scale, v_dim=v_dim)
    return _paged_attn.paged_decode_attention(
        q, ck, cv, layer, table, pos, window=window, scale=scale,
        out_scale=out_scale, v_dim=v_dim, interpret=(impl == "interpret"))


def moe_expert_ffn(x, w1, w3, w2, layer, tile_expert, tile_used, *,
                   tm: int, impl: str = "auto") -> jnp.ndarray:
    """Grouped SwiGLU FFN over expert-sorted row tiles (the held experts of
    layer ``layer`` of layer-stacked weights).  Shapes as in
    :func:`repro.kernels.moe_ffn.moe_expert_ffn`; ``impl="ref"`` runs
    :func:`ref.moe_expert_ffn`."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.moe_expert_ffn(x, w1, w3, w2, layer, tile_expert,
                                  tile_used, tm=tm)
    return _moe_ffn.moe_expert_ffn(x, w1, w3, w2, layer, tile_expert,
                                   tile_used, tm=tm,
                                   interpret=(impl == "interpret"))


def bulk_op(a: jnp.ndarray, b: jnp.ndarray, op: str = "xor",
            impl: str = "auto", br: int = 512) -> jnp.ndarray:
    """Bulk bitwise XOR/XNOR of two same-shape uint32 buffers.

    The digital form of the banked engine's compute cycle (DESIGN.md §10):
    every uint32 lane carries 32 row-columns, so one call is the bulk
    row-wide Boolean op the paper computes per sense cycle, tiled over the
    whole buffer.  Restricted to uint32 like :func:`stream_cipher` so results
    are bit-exact across all three impl paths.
    """
    if op not in ("xor", "xnor"):
        raise ValueError(f"bulk_op supports xor/xnor, got {op!r}")
    if a.dtype != jnp.uint32 or b.dtype != jnp.uint32:
        raise TypeError(f"bulk_op needs uint32, got {a.dtype}/{b.dtype}")
    if a.shape != b.shape:
        raise ValueError(f"operand shapes differ: {a.shape} vs {b.shape}")
    invert = op == "xnor"
    impl = _resolve(impl)
    if impl == "ref":
        return ref.bulk_xor(a, b, invert=invert)
    words_a, words_b = a.reshape(-1), b.reshape(-1)
    n = words_a.shape[0]
    d = 128
    pad = (-n) % d
    a2 = jnp.pad(words_a, (0, pad)).reshape(-1, d)
    b2 = jnp.pad(words_b, (0, pad)).reshape(-1, d)
    # pad rows rather than shrink the tile: pad output is sliced off below
    # (no cross-tile dependency, unlike digest's fold).
    br = min(br, a2.shape[0])
    a2, b2 = _pad_rows(a2, br), _pad_rows(b2, br)
    out = _bulk.bulk_xor(a2, b2, invert=invert, br=br,
                         interpret=(impl == "interpret"))
    return out.reshape(-1)[:n].reshape(a.shape)


def stream_cipher(buf: jnp.ndarray, key: jnp.ndarray, counter: int = 0,
                  impl: str = "auto", br: int = 512) -> jnp.ndarray:
    """XOR counter-mode cipher over a uint32 buffer. Involution.

    Restricted to uint32 so decryption round-trips bit-exactly; the
    checkpoint layer views other dtypes as uint32 host-side (numpy .view).
    ``counter`` may be a python int or a traced uint32 scalar — the sharded
    engine offsets it per device by the shard's word position.
    """
    if buf.dtype != jnp.uint32:
        raise TypeError(f"stream_cipher needs uint32, got {buf.dtype}")
    impl = _resolve(impl)
    words = buf.reshape(-1)
    n = words.shape[0]
    if impl == "ref":
        return ref.xor_cipher(words, key, counter).reshape(buf.shape)
    d = 128
    pad = (-n) % d
    w2 = jnp.pad(words, (0, pad)).reshape(-1, d)
    # pad rows rather than shrink the tile: pad output is sliced off below,
    # so the keystream words the pad rows consume never reach the caller.
    br = min(br, w2.shape[0])
    w2 = _pad_rows(w2, br)
    k3 = jnp.stack([jnp.asarray(key[0], jnp.uint32),
                    jnp.asarray(key[1], jnp.uint32),
                    jnp.asarray(counter, jnp.uint32)])
    out = _cipher.xor_cipher(w2, k3, br=br, interpret=(impl == "interpret"))
    return out.reshape(-1)[:n].reshape(buf.shape)


def host_words(arr: np.ndarray, align: int = 4):
    """View a host numpy array's bytes as the canonical little-endian uint32
    stream, zero-padding the tail to ``align`` bytes.

    Returns ``(words, nbytes)``.  This is THE single definition of the host
    byte layout: :func:`repro.core.verify.np_words` delegates here and
    :func:`as_words` routes host inputs through it, so the digest/cipher
    host and device paths can never desynchronize.
    """
    raw = np.ascontiguousarray(arr).view(np.uint8).reshape(-1)
    nbytes = raw.size
    pad = (-nbytes) % align
    if pad:
        raw = np.concatenate([raw, np.zeros(pad, np.uint8)])
    return raw.view(np.uint32), nbytes


def as_words(buf: jnp.ndarray) -> jnp.ndarray:
    """Losslessly view any array as a flat uint32 stream (pads odd tails).

    Host (numpy) inputs take the :func:`host_words` byte view BEFORE any
    jax conversion: with x64 disabled ``jnp.asarray`` silently downcasts
    float64/int64 and the stream would drop half of every element's bytes.
    jax arrays bitcast on device (64-bit ones only exist with x64 on).
    """
    if not isinstance(buf, jax.Array):
        return jnp.asarray(host_words(np.asarray(buf))[0])
    flat = buf.reshape(-1)
    size = jnp.dtype(flat.dtype).itemsize
    if flat.dtype == jnp.uint32:
        return flat
    if size == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if size == 8:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)
    # narrow dtypes pack by strided slices of the flat stream: a (n, 2) or
    # (n, 4) intermediate would pad its minor axis to 128 lanes on a TPU
    if size == 2:
        u16 = jax.lax.bitcast_convert_type(flat, jnp.uint16)
        if u16.shape[0] % 2:
            u16 = jnp.pad(u16, (0, 1))
        return (u16[0::2].astype(jnp.uint32)
                | (u16[1::2].astype(jnp.uint32) << 16))
    if size == 1:
        u8 = jax.lax.bitcast_convert_type(flat, jnp.uint8)
        pad = (-u8.shape[0]) % 4
        if pad:
            u8 = jnp.pad(u8, (0, pad))
        b = [u8[i::4].astype(jnp.uint32) << (8 * i) for i in range(4)]
        return b[0] | b[1] | b[2] | b[3]
    raise ValueError(f"unsupported dtype {buf.dtype}")
