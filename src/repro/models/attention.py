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
               through per-slot int32 block tables; the resident state
               stacks it per layer, and the serving scans hand each layer
               the whole stack with its index (``LayerOf``).
Latent attention (MLA, ``cfg.kv_lora_rank > 0``, DeepSeek-V2/V3 without q
compression): q is projected per head (nope and rotary parts); ``wkv_a``
gives a kv_lora_rank vector c, normalised by its own RMSNorm, and one
rotary key shared by the heads; ``wkv_b`` expands c to per-head nope keys
and values.  Training and prefill run the expanded form.  Caches hold the
latent alone: one (kv_lora_rank + qk_rope_head_dim) vector per token under
a single cache head (``cfg.cache_heads``), with a zero-width V; the
vector is stored zero-padded to ``cfg.cache_k_dim`` lanes, whole tiles of
128 (576 -> 640: the chip's layout pads a 576-wide pool to 640 anyway, and
the kernel's slab copies must be tile-aligned).  Attention
over a cache folds ``wkv_b``'s key half into q (scores q_lat·c +
q_rope·k_rope) and applies its value half after the weighted sum of c.
Rotary embeddings follow the published modelling code: the rotary lanes
are de-interleaved (even lanes, then odd) before the rotate-half form.
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
from repro.models.params import LayerOf, ParamDef

NEG_INF = -1e30


def i8_encode(cfg, x: jnp.ndarray) -> jnp.ndarray:
    """Fixed-point encode for the int8 decode cache (scale is a config
    axis: ``cfg.kv_i8_scale``, default 32 — values are RMS-normed/RoPE'd,
    |k| < ~4, so 32 gives ~2% rounding)."""
    return jnp.clip(jnp.round(x.astype(jnp.float32) * cfg.kv_i8_scale),
                    -127, 127).astype(jnp.int8)


def attn_defs(cfg, n: int, cross: bool = False) -> dict:
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    if cfg.latent:
        r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        rope, dv = cfg.qk_rope_head_dim, cfg.v_head_dim
        return {
            "wq": ParamDef((n, d, H * (nope + rope)), (None, "fsdp", "tp"),
                           cfg.dtype, binarize=True),
            "wkv_a": ParamDef((n, d, r + rope), (None, "fsdp", None),
                              cfg.dtype, binarize=True),
            "kv_norm": ParamDef((n, r), (None, None), jnp.float32,
                                init="ones"),
            "wkv_b": ParamDef((n, r, H * (nope + dv)), (None, None, "tp"),
                              cfg.dtype, binarize=True),
            "wo": ParamDef((n, H * dv, d), (None, "tp", "fsdp"), cfg.dtype,
                           binarize=True),
        }
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
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
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
        k = layers.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if positions is not None:
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    return k, v


def _rope_deinterleaved(x, positions, theta):
    """Rotary embedding of MLA's rotary lanes, as the published modelling
    code applies it: even lanes, then odd, then the rotate-half form."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return layers.apply_rope(x, positions, theta)


def _mla_q(cfg, p, x, positions):
    """-> q_nope (B, S, H, nope), q_rope (B, S, H, rope), the latter roped."""
    b, s, _ = x.shape
    q = layers.linear(x, p["wq"], cfg.quant).reshape(
        b, s, cfg.n_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    return q_nope, _rope_deinterleaved(q_rope, positions, cfg.rope_theta)


def mla_latent(cfg, p, x, positions):
    """-> (B, S, kv_lora_rank + rope): the normed latent c, then the roped
    shared key — what a latent cache holds per token."""
    kv = layers.linear(x, p["wkv_a"], cfg.quant)
    c, kr = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    c = layers.rms_norm(c, p["kv_norm"], cfg.norm_eps)
    kr = _rope_deinterleaved(kr[:, :, None], positions, cfg.rope_theta)
    return jnp.concatenate([c, kr[:, :, 0].astype(c.dtype)], axis=-1)


def _pad_lanes(cfg, x):
    """The last axis zero-padded to ``cfg.cache_k_dim`` (a latent cache's
    stored width)."""
    pad = cfg.cache_k_dim - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _mla_expand(cfg, p, lat):
    """Latents (B, S, r + rope) -> k (B, S, H, nope + rope), v (B, S, H, dv)."""
    b, s, _ = lat.shape
    c, kr = jnp.split(lat, [cfg.kv_lora_rank], axis=-1)
    kv = layers.linear(c, p["wkv_b"], cfg.quant).reshape(
        b, s, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
    kr = jnp.broadcast_to(kr[:, :, None], k_nope.shape[:3] + kr.shape[-1:])
    return jnp.concatenate([k_nope, kr.astype(k_nope.dtype)], axis=-1), v


def _mla_kv_b(cfg, p):
    """``wkv_b`` as (r, H, nope + dv): float only (folding it into q
    needs the weights, which a binarized layer does not apply linearly)."""
    w = p["wkv_b"]
    if cfg.quant != "none" or not isinstance(w, jax.Array):
        raise NotImplementedError(
            "latent attention over a cache folds wkv_b into q: float "
            "weights only (quant='none')")
    return w.reshape(cfg.kv_lora_rank, cfg.n_heads,
                     cfg.qk_nope_head_dim + cfg.v_head_dim)


def _mla_absorb_q(cfg, p, x, positions):
    """-> (B, S, H, cache_k_dim): q with ``wkv_b``'s key half folded in, to
    score against cached latents as they lie (zeros on the pad lanes)."""
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    w_uk = _mla_kv_b(cfg, p)[:, :, :cfg.qk_nope_head_dim]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    return _pad_lanes(cfg, jnp.concatenate([q_lat.astype(q_rope.dtype),
                                            q_rope], axis=-1))


def _mla_out(cfg, p, o_lat):
    """Weighted sums of latents (B, S, H, r) -> the layer's output (B, S, d):
    ``wkv_b``'s value half, then ``wo``."""
    b, s = o_lat.shape[:2]
    w_uv = _mla_kv_b(cfg, p)[:, :, cfg.qk_nope_head_dim:]
    o = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv,
                   preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return layers.linear(o.reshape(b, s, cfg.n_heads * cfg.v_head_dim),
                         p["wo"], cfg.quant)


def mla_scale(cfg) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def project_cache(cfg, p, x, positions):
    """-> (k, v) each (B, S, cache_heads, width) as a cache holds them: the
    compact GQA K and V, or the latent vector and a zero-width V."""
    if cfg.latent:
        lat = _pad_lanes(cfg, mla_latent(cfg, p, x, positions))[:, :, None]
        return lat, jnp.zeros(lat.shape[:3] + (0,), lat.dtype)
    return _project_kv(cfg, p, x, positions)


def _expand_kv(cfg, k, v):
    """(B, S, KV, dh) -> (B, S, H, dh): repeat each KV head q_per_kv times."""
    if cfg.q_per_kv == 1:
        return k, v
    k = jnp.repeat(k, cfg.q_per_kv, axis=2)
    v = jnp.repeat(v, cfg.q_per_kv, axis=2)
    return (constrain(k, "batch", None, "tp", None),
            constrain(v, "batch", None, "tp", None))


def _sdpa(cfg, q, k, v, mask):
    """MHA core: q/k (B,Sq|Sk,H,dk), v (B,Sk,H,dv), mask (1|B,Sq,Sk) or
    None; scaled by dk**-0.5."""
    scale = q.shape[-1] ** -0.5
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
    if cfg.latent:
        q = jnp.concatenate(_mla_q(cfg, p, x, positions), axis=-1)
        k, v = _mla_expand(cfg, p, mla_latent(cfg, p, x, positions))
    else:
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
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, cfg.n_heads,
                                               v.shape[-1])

    out = out.reshape(b, s, cfg.n_heads * v.shape[-1])
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
        dt = dtype or cfg.dtype
        return cls(*(jnp.zeros(s, dt) for s in _cache_shapes(cfg, batch,
                                                            s_max)))

    @classmethod
    def abstract(cls, cfg, batch: int, s_max: int, dtype=None):
        dt = dtype or cfg.dtype
        return cls(*(jax.ShapeDtypeStruct(s, dt)
                     for s in _cache_shapes(cfg, batch, s_max)))


def _cache_shapes(cfg, lead: int, tokens: int):
    """K and V shapes of a cache: (lead, cache_heads, tokens, width); a
    latent cache's V is zero-width (the latent serves as both)."""
    if cfg.latent and cfg.kv_cache_dtype == "i8":
        raise NotImplementedError("a latent cache is bf16/f32 only")
    base = (lead, cfg.cache_heads, tokens)
    return base + (cfg.cache_k_dim,), base + (cfg.cache_v_dim,)


def _latent_attend(cfg, p, q, ck, kv_valid, dtype):
    """Attention of absorbed queries q (B, C, H, L) over latents ck
    (B, cap, L), L = ``cfg.cache_k_dim``, under ``kv_valid`` (B, C, cap) -> output (B, C, d)."""
    scores = jnp.einsum("bchd,bsd->bhcs", q, ck.astype(q.dtype),
                        preferred_element_type=jnp.float32) * mla_scale(cfg)
    scores = jnp.where(kv_valid[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # the weighted sum in f32, as the decode kernel takes it (the CPU has
    # no bf16 x bf16 -> f32 product of a single query row)
    o_lat = jnp.einsum("bhcs,bsr->bchr", probs,
                       ck[..., :cfg.kv_lora_rank].astype(jnp.float32))
    return _mla_out(cfg, p, o_lat.astype(dtype))


def decode_attention(cfg, p: dict, x: jnp.ndarray, cache: KVCache,
                     pos: jnp.ndarray, window: int = 0):
    """One-token attention against a resident cache (compact GQA form).

    x: (B, 1, d). pos: int32 scalar — the batch's current position (cache
    holds pos valid entries before this call; one slice-update covers all
    rows).  Returns (out (B, 1, d), new cache).  For local layers the cache
    is a rolling buffer of size window and the write position wraps
    (pos % window).
    """
    b = x.shape[0]
    s_max = cache.k.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = jnp.broadcast_to(pos, (b,))
    positions = pos_b[:, None]
    if cfg.latent:
        lat = _pad_lanes(cfg, mla_latent(cfg, p, x, positions))[:, :, None]
        ck = jax.vmap(lambda c, new, s: jax.lax.dynamic_update_slice_in_dim(
            c, new, s, axis=1))(cache.k, jnp.moveaxis(lat, 1, 2).astype(
                cache.k.dtype), pos_b)
        valid = jnp.arange(s_max)[None, None, :] <= pos_b[:, None, None]
        y = _latent_attend(cfg, p, _mla_absorb_q(cfg, p, x, positions),
                           ck[:, 0], valid, x.dtype)
        return y, KVCache(ck, cache.v)
    q = _project_q(cfg, p, x, positions)          # (B, 1, H, dh)
    q = q.reshape(b, 1, cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head)
    k, v = _project_kv(cfg, p, x, positions)

    slot_b = pos_b % s_max if window else pos_b
    knew = jnp.moveaxis(k, 1, 2)   # (B, KV, 1, dh)
    vnew = jnp.moveaxis(v, 1, 2)
    i8 = cache.k.dtype == jnp.int8
    if i8:  # fixed-point low-bit cache (paper-domain: quantized residency)
        knew, vnew = i8_encode(cfg, knew), i8_encode(cfg, vnew)
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
        dt = dtype or cfg.dtype
        return cls(*(jnp.zeros(s, dt) for s in _cache_shapes(
            cfg, n_blocks, block_size)))

    @classmethod
    def abstract(cls, cfg, n_blocks: int, block_size: int, dtype=None):
        dt = dtype or cfg.dtype
        return cls(*(jax.ShapeDtypeStruct(s, dt) for s in _cache_shapes(
            cfg, n_blocks, block_size)))

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
def paged_attention(cfg, p: dict, x: jnp.ndarray, cache: LayerOf,
                    table: jnp.ndarray, pos: jnp.ndarray, *, window: int = 0,
                    valid: jnp.ndarray | None = None):
    """Attention through a per-slot block table: write the new tokens into
    the pool, read K/V back through the table, and mask by position.

    One function covers both serve regimes:
      decode          — x (B, 1, d), per-slot ``pos`` (B,), B = slots;
      chunked prefill — x (1, C, d), scalar-ish ``pos`` (1,) = chunk start.

    ``cache`` is one layer of the layer-stacked pool, handed over whole:
    ``LayerOf(PagedKVCache((L, n_blocks, KV, bs, dh) ...), layer)``.  The
    tokens are written into ``[layer, block, :, offset]`` of the stack and
    the stack is returned, so a scan that carries the pool never slices,
    relays out or restacks it.  ``table`` (B, W) holds physical block ids;
    token at absolute position q lives at block ``table[b, (q // bs) %
    W]``, offset ``q % bs``.  For full (non-window) tables ``W * bs >=
    s_max`` so the ring modulus is the identity; for local layers the table
    is a block ring of capacity ``W * bs >= window + C - 1`` (older blocks
    are recycled — blocks that fall out of the window never stay
    resident).  ``valid`` (B, C) routes padding / dead-slot writes to the
    reserved trash block 0.
    Returns (out (B, C, d), the new ``LayerOf`` of the stack).
    """
    b, c, _ = x.shape
    pool, layer = cache
    bs = pool.k.shape[3]
    w = table.shape[1]
    cap = w * bs
    pos = jnp.asarray(pos, jnp.int32)
    qpos = jnp.broadcast_to(pos, (b,))[:, None] + jnp.arange(c)[None, :]
    if cfg.latent:
        return _paged_latent(cfg, p, x, cache, table, qpos, valid)
    q = _project_q(cfg, p, x, qpos)                  # (B, C, H, dh)
    q = q.reshape(b, c, cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head)
    k, v = _project_kv(cfg, p, x, qpos)              # (B, C, KV, dh)

    i8 = pool.k.dtype == jnp.int8
    if i8:
        k, v = i8_encode(cfg, k), i8_encode(cfg, v)
    with jax.named_scope("attn.kv_write"):
        phys = _write_blocks(table, qpos, bs, valid)
        ck = _write_tokens(pool.k, layer, phys, qpos % bs, k)
        cv = _write_tokens(pool.v, layer, phys, qpos % bs, v)
    new = LayerOf(PagedKVCache(ck, cv), layer)

    scale = cfg.d_head ** -0.5
    if i8:
        scale = scale / cfg.kv_i8_scale
    if c == 1 and ops.fused_mode() == "kernel":
        # single-dispatch decode: the Pallas kernel walks the block table via
        # scalar prefetch and streams the layer's pool blocks through VMEM
        # from the stack in place (DESIGN.md §18) — the gather/mask/
        # softmax/PV chain below is its reference twin (exact in real
        # arithmetic, allclose in floats), kept as the production path on
        # ref/interpret backends so cross-layout token pins stay bitwise.
        # Forced on a ref backend, ops.paged_decode runs the one-shot
        # oracle; the kernel is interpreted only under an explicit
        # interpret impl.  Chunked prefill (c > 1) always takes the unfused
        # path.
        out = ops.paged_decode(
            q[:, 0], ck, cv, jnp.reshape(layer, (1,)), table, qpos[:, 0],
            window=window, scale=float(scale),
            out_scale=float(1.0 / cfg.kv_i8_scale) if i8 else 1.0)
        out = out.reshape(b, 1, cfg.n_heads * cfg.d_head).astype(x.dtype)
        return layers.linear(out, p["wo"], cfg.quant), new

    gk = jnp.moveaxis(ck[layer, table], 1, 2).reshape(b, cfg.n_kv_heads, cap,
                                                      cfg.d_head)
    gv = jnp.moveaxis(cv[layer, table], 1, 2).reshape(b, cfg.n_kv_heads, cap,
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
    return layers.linear(out, p["wo"], cfg.quant), new


def _write_blocks(table, qpos, bs, valid):
    """(B, C) physical blocks the tokens at ``qpos`` are written to: their
    table entries, or the trash block 0 where not ``valid``."""
    phys = jnp.take_along_axis(table, (qpos // bs) % table.shape[1], axis=1)
    return phys if valid is None else jnp.where(valid, phys, 0)


def _write_tokens(pool, layer, phys, off, new):
    """``pool`` (L, n_blocks, heads, bs, width) with the tokens ``new`` (B,
    C, heads, width) written into ``[layer, phys, :, off]``, in place.

    The write goes through the pool viewed as rows, (L, n_blocks, heads·bs,
    width), a row per head and token: a write of whole (heads, width)
    slabs into the 5-d pool makes the TPU compiler lay the whole pool out
    with heads minor for the scatter, and copy it back for the attention
    kernel, every layer of every step.  Distinct live tokens always hit
    distinct (block, offset) pairs (BlockPool uniqueness + ring sizing);
    only trash-block writes may collide, never read."""
    n_layers, n_blocks, heads, bs, width = pool.shape
    rows = jnp.arange(heads) * bs + off[..., None]             # (B, C, heads)
    flat = pool.reshape(n_layers, n_blocks, heads * bs, width)
    flat = flat.at[layer, phys[..., None], rows].set(new.astype(pool.dtype))
    return flat.reshape(pool.shape)


def _paged_latent(cfg, p, x, cache: LayerOf, table, qpos, valid):
    """:func:`paged_attention` over a latent pool (monotone tables): the
    token's latent is written into its slot of the layer-stacked pool, then
    the absorbed queries attend over the table's latents — through the
    Pallas kernel at decode where the kernels are on, else the gather
    chain, its twin."""
    b, c, _ = x.shape
    pool, layer = cache
    bs, w = pool.k.shape[3], table.shape[1]
    q = _mla_absorb_q(cfg, p, x, qpos)               # (B, C, H, L)
    lat = _pad_lanes(cfg, mla_latent(cfg, p, x, qpos))   # (B, C, L)
    with jax.named_scope("attn.kv_write"):
        phys = _write_blocks(table, qpos, bs, valid)
        ck = _write_tokens(pool.k, layer, phys, qpos % bs, lat[:, :, None])
    new = LayerOf(PagedKVCache(ck, pool.v), layer)
    if c == 1 and ops.fused_mode() == "kernel":
        o_lat = ops.paged_decode(q[:, 0][:, None], ck, None,
                                 jnp.reshape(layer, (1,)), table, qpos[:, 0],
                                 scale=mla_scale(cfg), v_dim=cfg.kv_lora_rank)
        y = _mla_out(cfg, p, o_lat.reshape(b, 1, cfg.n_heads,
                                           cfg.kv_lora_rank).astype(x.dtype))
        return y, new
    gk = ck[layer, table][:, :, 0].reshape(b, w * bs, cfg.cache_k_dim)
    kv_valid = jnp.arange(w * bs)[None, None, :] <= qpos[:, :, None]
    return _latent_attend(cfg, p, q, gk, kv_valid, x.dtype), new
