"""GQA attention: full / local-window / cross, with chunked-query streaming
for long prefills and KV-cache decode.

Layout conventions:
  activations  (B, S, d_model)
  q            (B, S, H, dh)
  k, v         (B, S, KV, dh) — expanded to (B, S, H, dh) in the batched
               (train/prefill) paths when q_per_kv > 1: repeating KV to full
               heads is mathematically identical to grouped attention and
               keeps the TP sharding on the head axis.  Sharding the packed
               GQA layout instead pads KV (4) up to the model axis (16),
               which GSPMD resolves by sharding d_head — producing multi-GiB
               score all-reduces (measured, EXPERIMENTS.md §Perf iter 1).
  KV cache     (B, KV, S_max, dh) — decode keeps the compact GQA form (the
               cache is the memory bottleneck; never expanded).
  paged cache  (n_blocks, KV, block_size, dh) — the block-paged serve form
               (DESIGN.md §14): a shared pool with no batch axis, addressed
               through per-slot int32 block tables.
Scores accumulate in f32; softmax is f32 with max subtraction.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.ctx import constrain
from repro.kernels import ops
from repro.models import layers
from repro.models.params import ParamDef

NEG_INF = -1e30


def i8_encode(cfg, x: jnp.ndarray) -> jnp.ndarray:
    """Fixed-point encode for the int8 decode cache (scale is a config
    axis: ``cfg.kv_i8_scale``, default 32 — values are RMS-normed/RoPE'd,
    |k| < ~4, so 32 gives ~2% rounding)."""
    return jnp.clip(jnp.round(x.astype(jnp.float32) * cfg.kv_i8_scale),
                    -127, 127).astype(jnp.int8)


def attn_defs(cfg, n: int, cross: bool = False) -> dict:
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    defs = {
        "wq": ParamDef((n, d, H * dh), (None, "fsdp", "tp"), cfg.dtype,
                       binarize=True),
        "wk": ParamDef((n, d, KV * dh), (None, "fsdp", "tp"), cfg.dtype,
                       binarize=True),
        "wv": ParamDef((n, d, KV * dh), (None, "fsdp", "tp"), cfg.dtype,
                       binarize=True),
        "wo": ParamDef((n, H * dh, d), (None, "tp", "fsdp"), cfg.dtype,
                       binarize=True),
    }
    if cfg.qkv_bias and not cross:
        defs |= {
            "bq": ParamDef((n, H * dh), (None, "tp"), cfg.dtype, init="zeros"),
            "bk": ParamDef((n, KV * dh), (None, "tp"), cfg.dtype, init="zeros"),
            "bv": ParamDef((n, KV * dh), (None, "tp"), cfg.dtype, init="zeros"),
        }
    if cfg.qk_norm:
        defs |= {
            "q_norm": ParamDef((n, dh), (None, None), jnp.float32, init="ones"),
            "k_norm": ParamDef((n, dh), (None, None), jnp.float32, init="ones"),
        }
    return defs


def _project_q(cfg, p, x, positions):
    """-> (B, S, H, dh)"""
    b, s, _ = x.shape
    q = layers.linear(x, p["wq"], cfg.quant, p.get("bq"))
    q = q.reshape(b, s, cfg.n_heads, cfg.d_head)
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"])
    if positions is not None:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
    return constrain(q, "batch", None, "tp", None)


def _project_kv(cfg, p, x, positions):
    """-> k, v each (B, S, KV, dh) (compact GQA form)."""
    b, s, _ = x.shape
    k = layers.linear(x, p["wk"], cfg.quant, p.get("bk"))
    v = layers.linear(x, p["wv"], cfg.quant, p.get("bv"))
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        k = layers.rms_norm(k, p["k_norm"])
    if positions is not None:
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    return k, v


def _expand_kv(cfg, k, v):
    """(B, S, KV, dh) -> (B, S, H, dh): repeat each KV head q_per_kv times."""
    if cfg.q_per_kv == 1:
        return k, v
    k = jnp.repeat(k, cfg.q_per_kv, axis=2)
    v = jnp.repeat(v, cfg.q_per_kv, axis=2)
    return (constrain(k, "batch", None, "tp", None),
            constrain(v, "batch", None, "tp", None))


def _sdpa(cfg, q, k, v, mask):
    """MHA core: q (B,Sq,H,dh), k/v (B,Sk,H,dh), mask (1|B,Sq,Sk) or None."""
    scale = cfg.d_head ** -0.5
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _causal_mask(sq, sk, q0, window: int = 0):
    """(1, sq, sk) boolean: query i (global pos q0+i) sees key j iff
    j <= q0+i and (no window or j > q0+i-window)."""
    qpos = q0 + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m[None]


def attention(cfg, p: dict, x: jnp.ndarray, *, causal: bool = True,
              window: int = 0, q_chunk: int = 0,
              positions: jnp.ndarray | None = None,
              unroll: bool = False) -> jnp.ndarray:
    """Self-attention over a full sequence (training / prefill).

    ``q_chunk > 0`` streams queries in chunks (bounds the live score tensor
    to q_chunk x S — the XLA-level flash-attention analogue, used for 32k
    prefills).  ``window > 0`` restricts keys to a trailing local window;
    the chunked path then slices K/V to the reachable 2*window band instead
    of masking the full sequence.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q = _project_q(cfg, p, x, positions)
    k, v = _expand_kv(cfg, *_project_kv(cfg, p, x, positions))

    if not q_chunk or s <= q_chunk:
        mask = _causal_mask(s, s, 0, window) if causal else None
        out = _sdpa(cfg, q, k, v, mask)
    else:
        assert s % q_chunk == 0, (s, q_chunk)
        n_chunks = s // q_chunk

        if window and window % q_chunk == 0:
            # local: each q chunk reaches keys in [(i+1)*C - W - C, (i+1)*C)
            span = window + q_chunk

            def chunk_fn(carry, i):
                q0 = i * q_chunk
                qc = jax.lax.dynamic_slice_in_dim(q, q0, q_chunk, axis=1)
                k0 = q0 + q_chunk - span
                kc = _slice_pad(k, k0, span)
                vc = _slice_pad(v, k0, span)
                mask = _band_mask(q_chunk, span, q0, k0, window)
                return carry, _sdpa(cfg, qc, kc, vc, mask)
        else:
            def chunk_fn(carry, i):
                q0 = i * q_chunk
                qc = jax.lax.dynamic_slice_in_dim(q, q0, q_chunk, axis=1)
                mask = _causal_mask(q_chunk, s, q0, window) if causal else None
                return carry, _sdpa(cfg, qc, k, v, mask)

        if unroll:
            outs = jnp.stack([chunk_fn((), jnp.int32(i))[1]
                              for i in range(n_chunks)])
        else:
            _, outs = jax.lax.scan(chunk_fn, (), jnp.arange(n_chunks))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, cfg.n_heads, cfg.d_head)

    out = out.reshape(b, s, cfg.n_heads * cfg.d_head)
    out = constrain(out, "batch", None, "tp")
    return constrain(layers.linear(out, p["wo"], cfg.quant),
                     "batch", None, None)


def _slice_pad(x, start, size):
    """dynamic_slice along axis 1 allowing negative start (clamps; the mask
    kills out-of-range positions)."""
    start = jnp.maximum(start, 0)
    return jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)


def _band_mask(sq, span, q0, k0, window):
    k0 = jnp.maximum(k0, 0)
    qpos = q0 + jnp.arange(sq)[:, None]
    kpos = k0 + jnp.arange(span)[None, :]
    m = (kpos <= qpos) & (kpos > qpos - window)
    return m[None]


# --- cross-attention ----------------------------------------------------------

def cross_attention(cfg, p: dict, x: jnp.ndarray, ctx_kv) -> jnp.ndarray:
    """ctx_kv: (k, v) each (B, T_ctx, KV, dh) — precomputed from the context
    (vision patches / encoder output) once per sequence."""
    b, s, _ = x.shape
    q = _project_q(cfg, p, x, None)        # no RoPE across modalities
    k, v = _expand_kv(cfg, *ctx_kv)
    out = _sdpa(cfg, q, k, v, None)
    out = out.reshape(b, s, cfg.n_heads * cfg.d_head)
    return constrain(layers.linear(out, p["wo"], cfg.quant),
                     "batch", None, None)


def make_ctx_kv(cfg, p: dict, ctx: jnp.ndarray):
    return _project_kv(cfg, p, ctx, None)


# --- KV-cache decode ----------------------------------------------------------

class KVCache(NamedTuple):
    k: jnp.ndarray   # (B, KV, S_max, dh)
    v: jnp.ndarray   # (B, KV, S_max, dh)

    @classmethod
    def zeros(cls, cfg, batch: int, s_max: int, dtype=None):
        shp = (batch, cfg.n_kv_heads, s_max, cfg.d_head)
        dt = dtype or cfg.dtype
        return cls(jnp.zeros(shp, dt), jnp.zeros(shp, dt))

    @classmethod
    def abstract(cls, cfg, batch: int, s_max: int, dtype=None):
        shp = (batch, cfg.n_kv_heads, s_max, cfg.d_head)
        dt = dtype or cfg.dtype
        return cls(jax.ShapeDtypeStruct(shp, dt),
                   jax.ShapeDtypeStruct(shp, dt))


def decode_attention(cfg, p: dict, x: jnp.ndarray, cache: KVCache,
                     pos: jnp.ndarray, window: int = 0):
    """One-token attention against a resident cache (compact GQA form).

    x: (B, 1, d). pos: int32 — current position (cache holds pos valid
    entries before this call).  Either a scalar (homogeneous batch: one
    slice-update covers all rows) or a (B,) vector (continuous-batching
    serve: each slot advances independently, writes scatter per row).
    Returns (out (B, 1, d), new cache).  For local layers the cache is a
    rolling buffer of size window and the write position wraps
    (pos % window).
    """
    b = x.shape[0]
    s_max = cache.k.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    pos_b = jnp.broadcast_to(pos, (b,))
    positions = pos_b[:, None]
    q = _project_q(cfg, p, x, positions)          # (B, 1, H, dh)
    q = q.reshape(b, 1, cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head)
    k, v = _project_kv(cfg, p, x, positions)

    slot_b = pos_b % s_max if window else pos_b
    knew = jnp.moveaxis(k, 1, 2)   # (B, KV, 1, dh)
    vnew = jnp.moveaxis(v, 1, 2)
    i8 = cache.k.dtype == jnp.int8
    if i8:  # fixed-point low-bit cache (paper-domain: quantized residency)
        knew, vnew = i8_encode(cfg, knew), i8_encode(cfg, vnew)
    if per_slot:
        upd = jax.vmap(lambda c, new, s:
                       jax.lax.dynamic_update_slice_in_dim(c, new, s, axis=1))
        ck = upd(cache.k, knew.astype(cache.k.dtype), slot_b)
        cv = upd(cache.v, vnew.astype(cache.v.dtype), slot_b)
    else:
        slot = pos % s_max if window else pos
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache.k, knew.astype(cache.k.dtype), slot, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache.v, vnew.astype(cache.v.dtype), slot, axis=2)

    scale = cfg.d_head ** -0.5
    if i8:
        scale = scale / cfg.kv_i8_scale
    scores = jnp.einsum("bqkgd,bksd->bkgqs", q, ck.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(s_max)
    if window:
        # rolling buffer: slot s holds absolute position
        # (pos - ((slot - s) mod s_max)); valid iff within window and <= pos
        age = (slot_b[:, None] - kpos[None, :]) % s_max          # (B, s_max)
        valid = age < jnp.minimum(window, pos_b[:, None] + 1)
    else:
        valid = kpos[None, :] <= pos_b[:, None]                  # (B, s_max)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bqkgd", probs.astype(q.dtype),
                     cv.astype(q.dtype),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    if i8:
        out = out / cfg.kv_i8_scale
    out = out.reshape(b, 1, cfg.n_heads * cfg.d_head)
    return layers.linear(out, p["wo"], cfg.quant), KVCache(ck, cv)


def decode_cross_attention(cfg, p: dict, x: jnp.ndarray, ctx_kv):
    return cross_attention(cfg, p, x, ctx_kv)


# --- block-paged KV cache (DESIGN.md §14) -------------------------------------

class PagedKVCache(NamedTuple):
    """Shared block pool: ``n_blocks`` blocks of ``block_size`` token slots
    each, in the compact GQA form.  Unlike :class:`KVCache` there is no
    batch axis — slots address blocks through per-slot int32 block tables
    (host-owned device data), so resident memory is proportional to tokens
    actually cached, not ``slots x s_max``.  Physical block 0 is reserved
    as the trash block: writes from inactive slots / padding tokens are
    routed there and never read back."""

    k: jnp.ndarray   # (n_blocks, KV, block_size, dh)
    v: jnp.ndarray   # (n_blocks, KV, block_size, dh)

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @classmethod
    def zeros(cls, cfg, n_blocks: int, block_size: int, dtype=None):
        shp = (n_blocks, cfg.n_kv_heads, block_size, cfg.d_head)
        dt = dtype or cfg.dtype
        return cls(jnp.zeros(shp, dt), jnp.zeros(shp, dt))

    @classmethod
    def abstract(cls, cfg, n_blocks: int, block_size: int, dtype=None):
        shp = (n_blocks, cfg.n_kv_heads, block_size, cfg.d_head)
        dt = dtype or cfg.dtype
        return cls(jax.ShapeDtypeStruct(shp, dt),
                   jax.ShapeDtypeStruct(shp, dt))

    def copy_block(self, src, dst) -> "PagedKVCache":
        """Physical block copy ``dst := src`` in both pools — the device
        side of copy-on-write prefix sharing (DESIGN.md §15): a request
        whose next scatter would land in a block it shares read-only first
        duplicates that block into a private one and repoints its table row.
        Accepts the bare ``(n_blocks, ...)`` pool or the layer-stacked
        ``(n_layers, n_blocks, ...)`` resident form (block axis = ndim-4);
        ``src``/``dst`` are device scalars, so one jitted copy program
        serves every (donor, recipient) pair without retracing."""
        axis = self.k.ndim - 4

        def cp(a):
            row = jax.lax.dynamic_index_in_dim(a, src, axis, keepdims=True)
            return jax.lax.dynamic_update_index_in_dim(a, row, dst, axis)
        return PagedKVCache(cp(self.k), cp(self.v))


@jax.named_scope("attn.core")
def paged_attention(cfg, p: dict, x: jnp.ndarray, cache: PagedKVCache,
                    table: jnp.ndarray, pos: jnp.ndarray, *, window: int = 0,
                    valid: jnp.ndarray | None = None):
    """Attention through a per-slot block table: scatter the new tokens into
    the pool, gather K/V back through the table, and mask by position.

    One function covers both serve regimes:
      decode          — x (B, 1, d), per-slot ``pos`` (B,), B = slots;
      chunked prefill — x (1, C, d), scalar-ish ``pos`` (1,) = chunk start.

    ``table`` (B, W) holds physical block ids; token at absolute position q
    lives at block ``table[b, (q // bs) % W]``, offset ``q % bs``.  For full
    (non-window) tables ``W * bs >= s_max`` so the ring modulus is the
    identity; for local layers the table is a block ring of capacity
    ``W * bs >= window + C - 1`` (older blocks are recycled — blocks that
    fall out of the window never stay resident).  ``valid`` (B, C) routes
    padding / dead-slot writes to the reserved trash block 0.
    Returns (out (B, C, d), new cache).
    """
    b, c, _ = x.shape
    bs = cache.k.shape[2]
    w = table.shape[1]
    cap = w * bs
    pos = jnp.asarray(pos, jnp.int32)
    qpos = jnp.broadcast_to(pos, (b,))[:, None] + jnp.arange(c)[None, :]
    q = _project_q(cfg, p, x, qpos)                  # (B, C, H, dh)
    q = q.reshape(b, c, cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head)
    k, v = _project_kv(cfg, p, x, qpos)              # (B, C, KV, dh)

    i8 = cache.k.dtype == jnp.int8
    if i8:
        k, v = i8_encode(cfg, k), i8_encode(cfg, v)
    with jax.named_scope("attn.kv_write"):
        phys = jnp.take_along_axis(table, (qpos // bs) % w, axis=1)  # (B, C)
        if valid is not None:
            phys = jnp.where(valid, phys, 0)         # trash block
        off = qpos % bs
        # advanced indices (phys, off) broadcast to (B, C); the KV slice
        # stays: scatter target shape (B, C, KV, dh).  Distinct live tokens
        # always hit distinct (block, offset) pairs (BlockPool uniqueness +
        # ring sizing); only trash-block writes may collide, never read.
        ck = cache.k.at[phys, :, off].set(k.astype(cache.k.dtype))
        cv = cache.v.at[phys, :, off].set(v.astype(cache.v.dtype))

    scale = cfg.d_head ** -0.5
    if i8:
        scale = scale / cfg.kv_i8_scale
    if c == 1 and ops.fused_mode(cfg.fused_decode) == "kernel":
        # single-dispatch decode: the Pallas kernel walks the block table via
        # scalar prefetch and streams pool blocks through VMEM (DESIGN.md
        # §18) — the gather/mask/softmax/PV chain below is its reference
        # twin (exact in real arithmetic, allclose in floats), kept as the
        # production path on ref/interpret backends so cross-layout token
        # pins stay bitwise.  Forced on a ref backend, ops.paged_decode runs
        # the one-shot oracle; the kernel is interpreted only under an
        # explicit interpret impl.  Chunked prefill (c > 1) always takes the
        # unfused path.
        out = ops.paged_decode(
            q[:, 0], ck, cv, table, qpos[:, 0], window=window,
            scale=float(scale),
            out_scale=float(1.0 / cfg.kv_i8_scale) if i8 else 1.0)
        out = out.reshape(b, 1, cfg.n_heads * cfg.d_head).astype(x.dtype)
        return layers.linear(out, p["wo"], cfg.quant), PagedKVCache(ck, cv)

    gk = jnp.moveaxis(ck[table], 1, 2).reshape(b, cfg.n_kv_heads, cap,
                                               cfg.d_head)
    gv = jnp.moveaxis(cv[table], 1, 2).reshape(b, cfg.n_kv_heads, cap,
                                               cfg.d_head)
    scores = jnp.einsum("bqkgd,bksd->bkgqs", q, gk.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    kslot = jnp.arange(cap)[None, None, :]
    if window:
        # ring: slot s holds the latest position p with p % cap == s; the
        # ring capacity >= window + C - 1 guarantees every in-window key of
        # every chunk query is still resident (DESIGN.md §14).
        age = (qpos[:, :, None] % cap - kslot) % cap           # (B, C, cap)
        valid_k = age < jnp.minimum(window, qpos[:, :, None] + 1)
    else:
        valid_k = kslot <= qpos[:, :, None]                    # (B, C, cap)
    scores = jnp.where(valid_k[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bqkgd", probs.astype(q.dtype),
                     gv.astype(q.dtype),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    if i8:
        out = out / cfg.kv_i8_scale
    out = out.reshape(b, c, cfg.n_heads * cfg.d_head)
    return layers.linear(out, p["wo"], cfg.quant), PagedKVCache(ck, cv)
