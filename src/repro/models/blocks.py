"""Decoder blocks (one per kind) + the scanned-segment machinery.

A model's depth plan is a list of (kind, count) segments
(``ArchConfig.segments()``).  Within a segment all layers share a kind, so
their parameters are stacked on a leading axis and the segment is applied
with ``jax.lax.scan`` — keeping the HLO size O(#segments), not O(#layers),
which is what makes 512-device lower+compile tractable for 48-layer models.

Block kinds:
  attn   — self-attention + SwiGLU FFN (dense transformers)
  local  — sliding-window GQA + FFN (RecurrentGemma attention layers)
  moe    — self-attention + dropless top-k MoE FFN
  cross  — cross-attention to modality context + FFN (Llama-3.2-Vision)
  enc    — bidirectional self-attention + FFN (Whisper encoder)
  dec    — self-attn + cross-attn + FFN (Whisper decoder)
  rglru  — Griffin recurrent block (conv1d + RG-LRU) + FFN
  mlstm  — xLSTM mLSTM block (own up/down projections, no FFN)
  slstm  — xLSTM sLSTM block (post-up GLU projection)

The attention of attn and moe follows the config: GQA, or latent (MLA)
where ``cfg.kv_lora_rank`` is set — the same block kinds either way.

Every kind implements:
  defs(cfg, n)                          stacked ParamDefs
  fwd(cfg, p, x, ctx, opts)             -> (x, aux, state|None)
  decode(cfg, p, x, state, pos, ctx)    -> (x, state), and a kind with
                                           routed experts (x, state, counts)
  state_spec(cfg, batch, s_max, abstract) decode-state pytree per layer

Paged serving (DESIGN.md §14) adds a parallel surface:
  paged_state_spec(...)                 per-layer state with KV caches
                                        replaced by shared PagedKVCache pools
  paged_split / paged_merge             separate the pool (shared, no batch
                                        axis) from dense per-slot leaves
  decode(..., table=)                   gather/scatter through a block table
  chunk(...)                           one chunked-prefill piece (B=1, S=C)

Each kind declares a :class:`BlockContract` (DESIGN.md §16) naming its
state layout, table class, and prefix-shareability; the paged surface is
*generated* from that contract by :class:`PagedLayout`, and every
consumer — the segment machinery below, ``lm.py``'s builders, the serve
scheduler's gates — reads contracts instead of matching kind strings.
New kinds register through ``repro.models.registry`` and plug into all
of it without edits here.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.ctx import constrain
from repro.kernels import ops
from repro.models import attention as attn_mod
from repro.models import layers, moe, registry, ssm
from repro.models.attention import KVCache, PagedKVCache
from repro.models.params import LayerOf, ParamDef
from repro.models.registry import BlockContract, register


class FwdOpts(NamedTuple):
    q_chunk: int = 0          # stream queries in chunks of this size
    want_state: bool = False  # prefill: return decode state
    s_max: int = 0            # cache capacity when want_state
    unroll: bool = False      # unroll inner chunk loops (exact HLO costs)


def _norm_def(cfg, n):
    return ParamDef((n, cfg.d_model), (None, None), jnp.float32, init="ones")


# ---------------------------------------------------------------------------
# attention-family blocks
# ---------------------------------------------------------------------------

def _attn_ffn_defs(cfg, n, window=False, moe_ffn_=False, cross=False,
                   encdec=False):
    defs = {"ln1": _norm_def(cfg, n), "ln2": _norm_def(cfg, n)}
    defs |= {f"attn_{k}": v for k, v in attn_mod.attn_defs(cfg, n, cross=cross).items()}
    if encdec:
        defs["lnx"] = _norm_def(cfg, n)
        defs |= {f"xattn_{k}": v
                 for k, v in attn_mod.attn_defs(cfg, n, cross=True).items()}
    if moe_ffn_:
        defs |= {f"moe_{k}": v for k, v in moe.moe_defs(cfg, n).items()}
    else:
        defs |= {f"ffn_{k}": v for k, v in layers.ffn_defs(cfg, n).items()}
    return defs


def _sub(p: dict, prefix: str) -> dict:
    cut = len(prefix)
    return {k[cut:]: v for k, v in p.items() if k.startswith(prefix)}


def _kv_from_seq(cfg, k, v, s_max, rolling: bool = False):
    """(B, S, KV, dh) k/v -> KVCache of capacity s_max.

    ``rolling=True`` (local windows): keep the trailing s_max tokens laid out
    so that slot == position % s_max, matching decode_attention's rolling
    write (token at position p lands in slot p % s_max).
    """
    s = k.shape[1]
    if rolling and s > s_max:
        k, v = k[:, -s_max:], v[:, -s_max:]
        shift = s % s_max
        k = jnp.roll(k, shift, axis=1)
        v = jnp.roll(v, shift, axis=1)
    ck = jnp.moveaxis(k, 1, 2)
    cv = jnp.moveaxis(v, 1, 2)
    pad = s_max - ck.shape[2]
    if pad > 0:
        ck = jnp.pad(ck, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cv = jnp.pad(cv, ((0, 0), (0, 0), (0, pad), (0, 0)))
    if cfg.kv_cache_dtype == "i8":
        return KVCache(attn_mod.i8_encode(cfg, ck), attn_mod.i8_encode(cfg, cv))
    return KVCache(ck.astype(cfg.dtype), cv.astype(cfg.dtype))


def _scan_leaves(block, p, n):
    """(leaves a serving scan slices per layer, leaves it hands whole with
    the layer's index — the block's ``whole`` — and the layer indices)."""
    names = getattr(block, "whole", ())
    whole = {k: v for k, v in p.items() if k in names}
    return ({k: v for k, v in p.items() if k not in whole}, whole,
            jnp.arange(n))


def _layer_params(pl, whole, i):
    return dict(pl, **{k: LayerOf(v, i) for k, v in whole.items()})


class PagedLayout:
    """Contract-driven paged-serving surface.

    The whole ``paged_state_spec``/``paged_split``/``paged_merge`` triple
    is derived from the kind's declared :class:`BlockContract` instead of
    hand-copied per class:

      ``paged_kv`` only         state IS the shared pool (KV caches become
                                PagedKVCache pools, no batch axis)
      ``per_slot_state`` only   paged layout == dense layout — documented
                                exception in DESIGN.md §14 (recurrent state
                                is O(1) per slot; nothing block-granular to
                                page)
      both                      state is a (pool, per_slot) pair (Whisper
                                decoder: self-attn pool + ctx_kv)
      neither                   stateless; None flows through everything

    Kinds with ``paged_kv`` may override :meth:`pool_spec` (default: one
    PagedKVCache pool honoring ``kv_cache_dtype``); kinds with
    ``per_slot_state`` may override :meth:`slot_spec` (default: the dense
    ``state_spec``, correct whenever the dense state is entirely per-slot).
    """

    @classmethod
    def pool_spec(cls, cfg, n_blocks, block_size, abstract):
        mk = PagedKVCache.abstract if abstract else PagedKVCache.zeros
        dt = jnp.int8 if cfg.kv_cache_dtype == "i8" else cfg.dtype
        return mk(cfg, n_blocks, block_size, dtype=dt)

    @classmethod
    def slot_spec(cls, cfg, batch, s_max, abstract):
        return cls.state_spec(cfg, batch, s_max, abstract)

    @classmethod
    def paged_state_spec(cls, cfg, batch, s_max, n_blocks, block_size,
                         abstract):
        c = cls.contract
        if c.paged_kv and c.per_slot_state:
            return (cls.pool_spec(cfg, n_blocks, block_size, abstract),
                    cls.slot_spec(cfg, batch, s_max, abstract))
        if c.paged_kv:
            return cls.pool_spec(cfg, n_blocks, block_size, abstract)
        if c.per_slot_state:
            return cls.slot_spec(cfg, batch, s_max, abstract)
        return None

    @classmethod
    def paged_split(cls, state):
        """-> (shared pool leaves, per-slot leaves)."""
        c = cls.contract
        if c.paged_kv and c.per_slot_state:
            return state[0], state[1]
        if c.paged_kv:
            return state, None
        return None, state

    @classmethod
    def paged_merge(cls, shared, per_slot):
        c = cls.contract
        if c.paged_kv and c.per_slot_state:
            return (shared, per_slot)
        if c.paged_kv:
            return shared
        return per_slot

    # -- slot extraction / injection (DESIGN.md §17: session migration) ------

    @classmethod
    def export_slot(cls, state, slot, ids):
        """Lift one slot's complete state out of the stacked paged layout.

        ``state`` is the segment-stacked paged state (pool leaves
        ``(n, n_blocks, ...)``, per-slot leaves ``(n, batch, ...)``);
        ``slot`` is a device scalar; ``ids`` is this kind's full (W,)
        block-table row (physical block ids; unused entries point at the
        trash block 0, whose gathered garbage is carried along and never
        read).  Returns ``(shared, per_slot)`` payloads — pool blocks in
        table-row order (which is exactly what preserves position->block
        addressing on re-import, including window *rings*, whose
        ``(pos // bs) % W`` mapping is a function of row order alone) and
        the slot's dense leaves at batch width 1.  Contract-generic: no
        kind overrides this."""
        shared, per_slot = cls.paged_split(state)
        sh = None if shared is None else jax.tree.map(
            lambda l: jnp.take(l, ids, axis=1), shared)
        ps = None if per_slot is None else jax.tree.map(
            lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
            per_slot)
        return (sh, ps)

    @classmethod
    def import_slot(cls, state, slot, ids, payload):
        """Inverse of :meth:`export_slot`: scatter a payload into ``slot``
        and the blocks named by ``ids`` (the *destination* table row — same
        width, freshly allocated ids).  Unused row entries are 0, so the
        payload's trash-gathered garbage lands back in the trash block —
        harmless by the §14 never-read invariant."""
        sh_p, ps_p = payload
        shared, per_slot = cls.paged_split(state)
        if shared is not None:
            shared = jax.tree.map(
                lambda l, q: l.at[:, ids].set(q.astype(l.dtype)),
                shared, sh_p)
        if per_slot is not None:
            per_slot = jax.tree.map(
                lambda l, q: jax.lax.dynamic_update_slice_in_dim(
                    l, q.astype(l.dtype), slot, axis=1),
                per_slot, ps_p)
        return cls.paged_merge(shared, per_slot)


@register
class AttnBlock(PagedLayout):
    contract = BlockContract("attn", paged_kv=True, table_class="full",
                             prefix_shareable=True)
    causal = True
    window = 0

    @classmethod
    def defs(cls, cfg, n):
        return _attn_ffn_defs(cfg, n)

    @classmethod
    def _ffn(cls, cfg, p, x, valid=None, serve=False):
        """-> (x, aux loss, routed-expert counts or None)."""
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + layers.ffn(cfg, _sub(p, "ffn_"), h), jnp.float32(0.0), None

    @classmethod
    def fwd(cls, cfg, p, x, ctx, opts: FwdOpts):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        ap = _sub(p, "attn_")
        win = cfg.local_window if cls.window else 0
        state = None
        if opts.want_state:
            s = h.shape[1]
            positions = jnp.arange(s)
            k, v = attn_mod.project_cache(cfg, ap, h, positions)
            cap = min(opts.s_max, win) if win else opts.s_max
            state = _kv_from_seq(cfg, k, v, cap, rolling=bool(win))
        y = attn_mod.attention(cfg, ap, h, causal=cls.causal, window=win,
                               q_chunk=opts.q_chunk, unroll=opts.unroll)
        x = x + y
        x, aux, _ = cls._ffn(cfg, p, x)
        return x, aux, state

    @classmethod
    def _attend(cls, cfg, p, x, state, pos, table, valid):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        win = cfg.local_window if cls.window else 0
        if table is not None:
            y, state = attn_mod.paged_attention(cfg, _sub(p, "attn_"), h,
                                                state, table, pos, window=win,
                                                valid=valid)
        else:
            y, state = attn_mod.decode_attention(cfg, _sub(p, "attn_"), h,
                                                 state, pos, window=win)
        return x + y, state

    @classmethod
    def decode(cls, cfg, p, x, state, pos, ctx, table=None, valid=None):
        x, state = cls._attend(cfg, p, x, state, pos, table, valid)
        x, _, _ = cls._ffn(cfg, p, x, valid=valid, serve=True)
        return x, state

    @classmethod
    def chunk(cls, cfg, p, x, state, pos0, valid, n_valid, ctx, table):
        """One chunked-prefill piece: x (1, C, d) at positions
        pos0..pos0+C-1, of which the first ``n_valid`` are real tokens."""
        x, state = cls._attend(cfg, p, x, state, pos0, table, valid)
        x, _, _ = cls._ffn(cfg, p, x, valid=valid, serve=True)
        return x, state

    @classmethod
    def state_spec(cls, cfg, batch, s_max, abstract):
        cap = min(s_max, cfg.local_window) if cls.window else s_max
        mk = KVCache.abstract if abstract else KVCache.zeros
        dt = jnp.int8 if cfg.kv_cache_dtype == "i8" else cfg.dtype
        return mk(cfg, batch, cap, dtype=dt)

    @classmethod
    def state_pspec(cls, cfg, ba, kv_shard: str = "heads", tp_size: int = 16):
        """ba = batch mesh axes; kv_shard: "heads" (TP over KV heads) or
        "seq" (sequence-parallel cache — the softmax reduces over shards,
        XLA inserts the partial-max/sum all-reduces)."""
        if kv_shard == "seq":
            spec = P(ba, None, "model", None)
        else:
            spec = P(ba, "model", None, None)
        return KVCache(spec, spec)


@register
class LocalBlock(AttnBlock):
    # a window ring recycles physical blocks in place — never shareable
    contract = BlockContract("local", paged_kv=True, table_class="win",
                             window=True)
    window = 1


@register
class EncBlock(AttnBlock):
    # encoder-only: runs inside lm.encode, never in the decode path
    contract = BlockContract("enc", paged_kv=True, table_class="full",
                             decodes=False)
    causal = False


@register
class MoeBlock(AttnBlock):
    contract = BlockContract("moe", paged_kv=True, table_class="full",
                             prefix_shareable=True, routed_experts=True)
    # the serving scans hand the expert weights over whole (``LayerOf``):
    # the grouped kernel reads only the experts a step touches
    whole = ("moe_w1", "moe_w3", "moe_w2")

    @classmethod
    def defs(cls, cfg, n):
        return _attn_ffn_defs(cfg, n, moe_ffn_=True)

    @classmethod
    def _ffn(cls, cfg, p, x, valid=None, serve=False):
        # the serving paths take the grouped expert kernel where the
        # kernels are on (DESIGN.md §18); training keeps the dense twin
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        grouped = serve and ops.fused_mode() == "kernel"
        y, aux, counts = moe.moe_ffn(cfg, _sub(p, "moe_"), h, valid=valid,
                                     grouped=grouped)
        return x + y, aux, counts

    @classmethod
    def decode(cls, cfg, p, x, state, pos, ctx, table=None, valid=None):
        """-> (x, state, counts): :func:`moe.held_counts` of the rows that
        decode."""
        x, state = cls._attend(cfg, p, x, state, pos, table, valid)
        x, _, counts = cls._ffn(cfg, p, x, valid=valid, serve=True)
        return x, state, counts


@register
class CrossBlock(PagedLayout):
    # ctx_kv is a pure function of the request's context — rebuilding a
    # shared prefix cannot go stale, so sharing is safe (DESIGN.md §15)
    contract = BlockContract("cross", per_slot_state=True,
                             prefix_shareable=True)

    @classmethod
    def defs(cls, cfg, n):
        return _attn_ffn_defs(cfg, n, cross=True)

    @classmethod
    def fwd(cls, cfg, p, x, ctx, opts: FwdOpts):
        ap = _sub(p, "attn_")
        ctx_kv = attn_mod.make_ctx_kv(cfg, ap, ctx)
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        x = x + attn_mod.cross_attention(cfg, ap, h, ctx_kv)
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.ffn(cfg, _sub(p, "ffn_"), h)
        state = ctx_kv if opts.want_state else None
        return x, jnp.float32(0.0), state

    @classmethod
    def decode(cls, cfg, p, x, state, pos, ctx, table=None, valid=None):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        x = x + attn_mod.decode_cross_attention(cfg, _sub(p, "attn_"), h, state)
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.ffn(cfg, _sub(p, "ffn_"), h)
        return x, state

    @classmethod
    def chunk(cls, cfg, p, x, state, pos0, valid, n_valid, ctx, table):
        # ctx_kv is recomputed from the per-request context each chunk (the
        # dense fwd recomputes it per forward too) and stored as the slot's
        # state so decode can read it without the raw ctx staying resident.
        ap = _sub(p, "attn_")
        ctx_kv = attn_mod.make_ctx_kv(cfg, ap, ctx)
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        x = x + attn_mod.cross_attention(cfg, ap, h, ctx_kv)
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.ffn(cfg, _sub(p, "ffn_"), h)
        return x, ctx_kv

    @classmethod
    def state_spec(cls, cfg, batch, s_max, abstract):
        shp = (batch, cfg.n_ctx_tokens, cfg.n_kv_heads, cfg.d_head)
        if abstract:
            return (jax.ShapeDtypeStruct(shp, cfg.dtype),) * 2
        return (jnp.zeros(shp, cfg.dtype), jnp.zeros(shp, cfg.dtype))

    @classmethod
    def state_pspec(cls, cfg, ba, kv_shard: str = "heads", tp_size: int = 16):
        # ctx_kv (B, T_ctx, KV, dh): shard the ctx-token axis when divisible
        # (KV heads rarely divide 16-way TP), else replicate (small).
        if cfg.n_ctx_tokens % tp_size == 0:
            return (P(ba, "model", None, None),) * 2
        return (P(ba, None, None, None),) * 2


@register
class DecBlock(PagedLayout):
    """Whisper decoder block: self-attn + cross-attn(encoder) + FFN."""
    contract = BlockContract("dec", paged_kv=True, per_slot_state=True,
                             table_class="full", prefix_shareable=True)

    @classmethod
    def defs(cls, cfg, n):
        return _attn_ffn_defs(cfg, n, encdec=True)

    @classmethod
    def fwd(cls, cfg, p, x, ctx, opts: FwdOpts):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        ap = _sub(p, "attn_")
        self_state = None
        if opts.want_state:
            s = h.shape[1]
            k, v = attn_mod._project_kv(cfg, ap, h, jnp.arange(s))
            self_state = _kv_from_seq(cfg, k, v, opts.s_max)
        x = x + attn_mod.attention(cfg, ap, h, causal=True,
                                   q_chunk=opts.q_chunk, unroll=opts.unroll)
        xp = _sub(p, "xattn_")
        ctx_kv = attn_mod.make_ctx_kv(cfg, xp, ctx)
        h = layers.rms_norm(x, p["lnx"], cfg.norm_eps)
        x = x + attn_mod.cross_attention(cfg, xp, h, ctx_kv)
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.ffn(cfg, _sub(p, "ffn_"), h)
        state = (self_state, ctx_kv) if opts.want_state else None
        return x, jnp.float32(0.0), state

    @classmethod
    def decode(cls, cfg, p, x, state, pos, ctx, table=None, valid=None):
        self_cache, ctx_kv = state
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        if table is not None:
            y, self_cache = attn_mod.paged_attention(cfg, _sub(p, "attn_"),
                                                     h, self_cache, table,
                                                     pos, valid=valid)
        else:
            y, self_cache = attn_mod.decode_attention(cfg, _sub(p, "attn_"),
                                                      h, self_cache, pos)
        x = x + y
        h = layers.rms_norm(x, p["lnx"], cfg.norm_eps)
        x = x + attn_mod.decode_cross_attention(cfg, _sub(p, "xattn_"), h, ctx_kv)
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.ffn(cfg, _sub(p, "ffn_"), h)
        return x, (self_cache, ctx_kv)

    @classmethod
    def chunk(cls, cfg, p, x, state, pos0, valid, n_valid, ctx, table):
        """ctx here is the *encoded* encoder output (1, T, d) — encoded once
        at admission, not once per chunk."""
        self_cache, _ = state
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        y, self_cache = attn_mod.paged_attention(cfg, _sub(p, "attn_"), h,
                                                 self_cache, table, pos0,
                                                 valid=valid)
        x = x + y
        xp = _sub(p, "xattn_")
        ctx_kv = attn_mod.make_ctx_kv(cfg, xp, ctx)
        h = layers.rms_norm(x, p["lnx"], cfg.norm_eps)
        x = x + attn_mod.cross_attention(cfg, xp, h, ctx_kv)
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.ffn(cfg, _sub(p, "ffn_"), h)
        return x, (self_cache, ctx_kv)

    @classmethod
    def slot_spec(cls, cfg, batch, s_max, abstract):
        # the per-slot half is just ctx_kv; the self-cache pages
        shp = (batch, cfg.n_ctx_tokens, cfg.n_kv_heads, cfg.d_head)
        if abstract:
            return (jax.ShapeDtypeStruct(shp, cfg.dtype),) * 2
        return (jnp.zeros(shp, cfg.dtype), jnp.zeros(shp, cfg.dtype))

    @classmethod
    def state_spec(cls, cfg, batch, s_max, abstract):
        mk = KVCache.abstract if abstract else KVCache.zeros
        # the self-cache honors kv_cache_dtype like AttnBlock's (the i8
        # words _kv_from_seq produces must land in an i8 resident cache or
        # decode_attention skips the fixed-point correction)
        dt = jnp.int8 if cfg.kv_cache_dtype == "i8" else cfg.dtype
        return (mk(cfg, batch, s_max, dtype=dt),
                cls.slot_spec(cfg, batch, s_max, abstract))

    @classmethod
    def state_pspec(cls, cfg, ba, kv_shard: str = "heads", tp_size: int = 16):
        self_spec = AttnBlock.state_pspec(cfg, ba, kv_shard, tp_size)
        return (self_spec, CrossBlock.state_pspec(cfg, ba, kv_shard, tp_size))


# ---------------------------------------------------------------------------
# recurrent blocks
# ---------------------------------------------------------------------------

@register
class RglruBlock(PagedLayout):
    contract = BlockContract("rglru", per_slot_state=True)

    @classmethod
    def defs(cls, cfg, n):
        d = cfg.d_model
        return {
            "ln1": _norm_def(cfg, n), "ln2": _norm_def(cfg, n),
            "w_gate": ParamDef((n, d, d), (None, "fsdp", "tp"), cfg.dtype,
                               binarize=True),
            "w_x": ParamDef((n, d, d), (None, "fsdp", "tp"), cfg.dtype,
                            binarize=True),
            "conv_k": ParamDef((n, cfg.conv_width, d), (None, None, "tp"),
                               jnp.float32, scale=0.5),
            "w_r": ParamDef((n, d, d), (None, "fsdp", "tp"), cfg.dtype,
                            binarize=True),
            "w_i": ParamDef((n, d, d), (None, "fsdp", "tp"), cfg.dtype,
                            binarize=True),
            "lam": ParamDef((n, d), (None, "tp"), jnp.float32, init="ones"),
            "w_out": ParamDef((n, d, d), (None, "tp", "fsdp"), cfg.dtype,
                              binarize=True),
        } | {f"ffn_{k}": v for k, v in layers.ffn_defs(cfg, n).items()}

    @classmethod
    def _mix(cls, cfg, p, h):
        g = jax.nn.gelu(layers.linear(h, p["w_gate"], cfg.quant))
        u = layers.linear(h, p["w_x"], cfg.quant)
        return (constrain(g, "batch", None, "tp"),
                constrain(u, "batch", None, "tp"))

    @classmethod
    def fwd(cls, cfg, p, x, ctx, opts: FwdOpts):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        g, u = cls._mix(cfg, p, h)
        uc = ssm.conv1d(u, p["conv_k"])
        r = layers.linear(uc, p["w_r"], cfg.quant)
        i = layers.linear(uc, p["w_i"], cfg.quant)
        st0 = ssm.RGLRUState.zeros(x.shape[0], cfg.d_model)
        y, st = ssm.rglru(uc, r, i, p["lam"], cfg.rglru_c, st0)
        x = x + layers.linear(g * y, p["w_out"], cfg.quant)
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.ffn(cfg, _sub(p, "ffn_"), h)
        state = None
        if opts.want_state:
            w = cfg.conv_width
            buf = u[:, -(w - 1):].astype(cfg.dtype)
            state = (st, buf)
        return x, jnp.float32(0.0), state

    @classmethod
    def decode(cls, cfg, p, x, state, pos, ctx, table=None, valid=None):
        st, buf = state
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        g, u = cls._mix(cfg, p, h)
        uc, buf = ssm.conv1d_step(buf, u, p["conv_k"])
        r = layers.linear(uc, p["w_r"], cfg.quant)
        i = layers.linear(uc, p["w_i"], cfg.quant)
        y, st = ssm.rglru_step(uc, r, i, p["lam"], cfg.rglru_c, st)
        x = x + layers.linear(g * y, p["w_out"], cfg.quant)
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.ffn(cfg, _sub(p, "ffn_"), h)
        return x, (st, buf.astype(cfg.dtype))

    @classmethod
    def chunk(cls, cfg, p, x, state, pos0, valid, n_valid, ctx, table):
        st, buf = state
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        g, u = cls._mix(cfg, p, h)
        uc = ssm.conv1d_carry(buf, u, p["conv_k"])
        r = layers.linear(uc, p["w_r"], cfg.quant)
        i = layers.linear(uc, p["w_i"], cfg.quant)
        y, st = ssm.rglru(uc, r, i, p["lam"], cfg.rglru_c, st, valid=valid)
        x = x + layers.linear(g * y, p["w_out"], cfg.quant)
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + layers.ffn(cfg, _sub(p, "ffn_"), h)
        buf = ssm.conv1d_carry_out(buf, u, n_valid).astype(cfg.dtype)
        return x, (st, buf)

    @classmethod
    def state_spec(cls, cfg, batch, s_max, abstract):
        w = cfg.conv_width
        if abstract:
            return (ssm.RGLRUState.abstract(batch, cfg.d_model),
                    jax.ShapeDtypeStruct((batch, w - 1, cfg.d_model), cfg.dtype))
        return (ssm.RGLRUState.zeros(batch, cfg.d_model),
                jnp.zeros((batch, w - 1, cfg.d_model), cfg.dtype))

    @classmethod
    def state_pspec(cls, cfg, ba, kv_shard: str = "heads", tp_size: int = 16):
        return (ssm.RGLRUState(P(ba, "model")), P(ba, None, "model"))


@register
class MlstmBlock(PagedLayout):
    contract = BlockContract("mlstm", per_slot_state=True)

    @classmethod
    def _di(cls, cfg):
        return int(cfg.proj_factor * cfg.d_model)

    @classmethod
    def defs(cls, cfg, n):
        d, di, nh = cfg.d_model, cls._di(cfg), cfg.n_heads
        return {
            "ln1": _norm_def(cfg, n),
            "w_up": ParamDef((n, d, di), (None, "fsdp", "tp"), cfg.dtype,
                             binarize=True),
            "w_gate": ParamDef((n, d, di), (None, "fsdp", "tp"), cfg.dtype,
                               binarize=True),
            "conv_k": ParamDef((n, cfg.conv_width, di), (None, None, "tp"),
                               jnp.float32, scale=0.5),
            "wq": ParamDef((n, di, di), (None, "fsdp", "tp"), cfg.dtype,
                           binarize=True),
            "wk": ParamDef((n, di, di), (None, "fsdp", "tp"), cfg.dtype,
                           binarize=True),
            "wv": ParamDef((n, di, di), (None, "fsdp", "tp"), cfg.dtype,
                           binarize=True),
            "w_if": ParamDef((n, di, 2 * nh), (None, "fsdp", None), jnp.float32),
            "b_if": ParamDef((n, 2 * nh), (None, None), jnp.float32, init="zeros"),
            "out_norm": ParamDef((n, di), (None, "tp"), jnp.float32, init="ones"),
            "w_down": ParamDef((n, di, d), (None, "tp", "fsdp"), cfg.dtype,
                               binarize=True),
        }

    @classmethod
    def _qkvif(cls, cfg, p, u, uc):
        nh = cfg.n_heads
        di = cls._di(cfg)
        dh = di // nh
        b, s = u.shape[:2]
        q = layers.linear(uc, p["wq"], cfg.quant).reshape(b, s, nh, dh)
        k = layers.linear(uc, p["wk"], cfg.quant).reshape(b, s, nh, dh) * (dh ** -0.5)
        v = layers.linear(u, p["wv"], cfg.quant).reshape(b, s, nh, dh)
        gif = jnp.einsum("bsd,dg->bsg", uc.astype(jnp.float32),
                         p["w_if"]) + p["b_if"]
        return q, k, v, gif[..., :nh], gif[..., nh:]

    @classmethod
    def fwd(cls, cfg, p, x, ctx, opts: FwdOpts):
        b, s, d = x.shape
        di, nh = cls._di(cfg), cfg.n_heads
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        u = constrain(layers.linear(h, p["w_up"], cfg.quant),
                      "batch", None, "tp")
        z = constrain(layers.linear(h, p["w_gate"], cfg.quant),
                      "batch", None, "tp")
        uc = jax.nn.silu(ssm.conv1d(u, p["conv_k"]))
        q, k, v, ig, fg = cls._qkvif(cfg, p, u, uc)
        st0 = ssm.MLSTMState.zeros(b, nh, di // nh)
        # chunk loop stays scanned even in unrolled-roofline runs: the
        # intra-chunk D-matrix is O(L^2) and unrolling ncs x layers bodies
        # explodes compile; the resulting HLO-flop undercount is documented
        # analytically in EXPERIMENTS.md SSM note.
        hseq, st = ssm.mlstm_chunkwise(q, k, v, ig, fg, st0,
                                       min(cfg.mlstm_chunk, s))
        hseq = hseq.reshape(b, s, di).astype(x.dtype)
        hseq = layers.rms_norm(hseq, p["out_norm"], cfg.norm_eps) * jax.nn.silu(z)
        x = x + layers.linear(hseq, p["w_down"], cfg.quant)
        state = None
        if opts.want_state:
            w = cfg.conv_width
            state = (st, u[:, -(w - 1):].astype(cfg.dtype))
        return x, jnp.float32(0.0), state

    @classmethod
    def decode(cls, cfg, p, x, state, pos, ctx, table=None, valid=None):
        st, buf = state
        b = x.shape[0]
        di, nh = cls._di(cfg), cfg.n_heads
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        u = layers.linear(h, p["w_up"], cfg.quant)
        z = layers.linear(h, p["w_gate"], cfg.quant)
        uc_lin, buf = ssm.conv1d_step(buf, u, p["conv_k"])
        uc = jax.nn.silu(uc_lin)
        q, k, v, ig, fg = cls._qkvif(cfg, p, u, uc)
        hstep, st = ssm.mlstm_step(st, q[:, 0], k[:, 0], v[:, 0],
                                   ig[:, 0], fg[:, 0])
        hstep = hstep.reshape(b, 1, di).astype(x.dtype)
        hstep = layers.rms_norm(hstep, p["out_norm"], cfg.norm_eps) * jax.nn.silu(z)
        x = x + layers.linear(hstep, p["w_down"], cfg.quant)
        return x, (st, buf.astype(cfg.dtype))

    @classmethod
    def chunk(cls, cfg, p, x, state, pos0, valid, n_valid, ctx, table):
        st, buf = state
        b, c, _ = x.shape
        di = cls._di(cfg)
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        u = layers.linear(h, p["w_up"], cfg.quant)
        z = layers.linear(h, p["w_gate"], cfg.quant)
        uc = jax.nn.silu(ssm.conv1d_carry(buf, u, p["conv_k"]))
        q, k, v, ig, fg = cls._qkvif(cfg, p, u, uc)
        # state-neutral gates at padding positions (i = -inf: nothing
        # inserted; f = +inf: no decay) — the same trick mlstm_chunkwise
        # uses for its own ragged tails, so the boundary state is exact.
        ig = jnp.where(valid[..., None], ig, -1e30)
        fg = jnp.where(valid[..., None], fg, 1e30)
        hseq, st = ssm.mlstm_chunkwise(q, k, v, ig, fg, st,
                                       min(cfg.mlstm_chunk, c))
        hseq = hseq.reshape(b, c, di).astype(x.dtype)
        hseq = layers.rms_norm(hseq, p["out_norm"], cfg.norm_eps) * jax.nn.silu(z)
        x = x + layers.linear(hseq, p["w_down"], cfg.quant)
        buf = ssm.conv1d_carry_out(buf, u, n_valid).astype(cfg.dtype)
        return x, (st, buf)

    @classmethod
    def state_spec(cls, cfg, batch, s_max, abstract):
        di, nh = cls._di(cfg), cfg.n_heads
        w = cfg.conv_width
        if abstract:
            return (ssm.MLSTMState.abstract(batch, nh, di // nh),
                    jax.ShapeDtypeStruct((batch, w - 1, di), cfg.dtype))
        return (ssm.MLSTMState.zeros(batch, nh, di // nh),
                jnp.zeros((batch, w - 1, di), cfg.dtype))

    @classmethod
    def state_pspec(cls, cfg, ba, kv_shard: str = "heads", tp_size: int = 16):
        # mLSTM matrix memory: NH (4) won't divide 16-way TP; shard the
        # first dh axis instead (dh = proj_factor*d/NH = 512, divisible).
        return (ssm.MLSTMState(P(ba, None, "model", None),
                               P(ba, None, "model"), P(ba, None)),
                P(ba, None, "model"))


@register
class SlstmBlock(PagedLayout):
    contract = BlockContract("slstm", per_slot_state=True)

    @classmethod
    def defs(cls, cfg, n):
        d, nh = cfg.d_model, cfg.n_heads
        dh = d // nh
        dff = int(4 * d / 3 / 64) * 64 * 2  # GLU up width (xLSTM 4/3 factor)
        return {
            "ln1": _norm_def(cfg, n),
            "w_gates": ParamDef((n, d, 4 * d), (None, "fsdp", "tp"), cfg.dtype,
                                binarize=True),
            # r_kernel is tiny and nh (4) won't divide 16-way TP: replicate
            "r_kernel": ParamDef((n, 4, nh, dh, dh),
                                 (None, None, None, None, None),
                                 jnp.float32, scale=0.05),
            "ln2": _norm_def(cfg, n),
            "w_up": ParamDef((n, d, dff), (None, "fsdp", "tp"), cfg.dtype,
                             binarize=True),
            "w_down": ParamDef((n, dff // 2, d), (None, "tp", "fsdp"), cfg.dtype,
                               binarize=True),
        }

    @classmethod
    def _post_ffn(cls, cfg, p, x):
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        up = layers.linear(h, p["w_up"], cfg.quant)
        a, g = jnp.split(up, 2, axis=-1)
        return x + layers.linear(a * jax.nn.gelu(g), p["w_down"], cfg.quant)

    @classmethod
    def fwd(cls, cfg, p, x, ctx, opts: FwdOpts):
        b = x.shape[0]
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        gates = constrain(layers.linear(h, p["w_gates"], cfg.quant),
                          "batch", None, "tp")
        st0 = ssm.SLSTMState.zeros(b, cfg.d_model)
        y, st = ssm.slstm_sequence(gates, p["r_kernel"], st0, cfg.n_heads)
        x = x + y.astype(x.dtype)
        x = cls._post_ffn(cfg, p, x)
        return x, jnp.float32(0.0), (st if opts.want_state else None)

    @classmethod
    def decode(cls, cfg, p, x, state, pos, ctx, table=None, valid=None):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        gates = layers.linear(h, p["w_gates"], cfg.quant)
        state, y = ssm.slstm_step(state, gates[:, 0], p["r_kernel"], cfg.n_heads)
        x = x + y[:, None].astype(x.dtype)
        x = cls._post_ffn(cfg, p, x)
        return x, state

    @classmethod
    def chunk(cls, cfg, p, x, state, pos0, valid, n_valid, ctx, table):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        gates = layers.linear(h, p["w_gates"], cfg.quant)
        y, state = ssm.slstm_sequence(gates, p["r_kernel"], state,
                                      cfg.n_heads, valid=valid)
        x = x + y.astype(x.dtype)
        x = cls._post_ffn(cfg, p, x)
        return x, state

    @classmethod
    def state_spec(cls, cfg, batch, s_max, abstract):
        mk = ssm.SLSTMState.abstract if abstract else ssm.SLSTMState.zeros
        return mk(batch, cfg.d_model)

    @classmethod
    def state_pspec(cls, cfg, ba, kv_shard: str = "heads", tp_size: int = 16):
        return ssm.SLSTMState(*(P(ba, "model"),) * 4)


# Live view of the registry (satellite kinds registered after import — e.g.
# bcnn's "bindense" — appear here too).  Kept for back-compat; new code
# should go through ``registry.get`` / ``registry.contract``.
KINDS: dict[str, Any] = registry.view()


# ---------------------------------------------------------------------------
# scanned segments
# ---------------------------------------------------------------------------

def segment_defs(cfg, segments=None) -> list:
    return [(kind, n, registry.get(kind).defs(cfg, n))
            for kind, n in (segments or cfg.segments())]


def segment_fwd(cfg, seg_params: list, x, ctx=None,
                opts: FwdOpts = FwdOpts(), remat: bool = False,
                unroll: bool = False):
    """Apply all segments. Returns (x, aux_total, states per segment).

    ``unroll=True`` replaces lax.scan with a Python loop: identical math and
    memory behavior (per-layer remat preserved), but every layer appears in
    the HLO — required for exact cost/collective analysis, since XLA's
    cost_analysis counts a while-loop body once regardless of trip count.
    """
    aux_total = jnp.float32(0.0)
    states = []
    for (kind, n), p in seg_params:
        block = registry.get(kind)

        def body(carry, pl, _block=block):
            xc, aux = carry
            xn, a, st = _block.fwd(cfg, pl, xc, ctx, opts)
            return (xn, aux + a), st

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        if unroll:
            sts = []
            carry = (x, aux_total)
            for i in range(n):
                pl = jax.tree.map(lambda a: a[i], p)
                carry, st_i = body(carry, pl)
                sts.append(st_i)
            (x, aux_total) = carry
            st = (jax.tree.map(lambda *ls: jnp.stack(ls), *sts)
                  if sts[0] is not None else None)
        else:
            (x, aux_total), st = jax.lax.scan(body, (x, aux_total), p)
        states.append(st)
    return x, aux_total, states


def _block_table(block, tables):
    """The block's (B, W) table under paged serving, else None — resolved
    through the kind's declared table class, not its name."""
    if tables is None or not block.contract.paged_kv:
        return None
    return tables[block.contract.table_class]


def _freeze_inactive(block, old, new, active):
    """Keep inactive slots' per-slot state frozen across a decode step.

    Needed once chunked prefill interleaves with decode: a mid-prefill
    slot is in the batch with ``active=False`` and its recurrent carry
    must not advance on the garbage token it is fed.  Shared pool leaves
    pass through (their writes are trash-routed via ``valid``); dense-mode
    KVCache leaves are classified shared too, which is correct — dead rows
    there are inert by overwrite, the historical §13 behavior."""
    shared, ps_new = block.paged_split(new)
    if ps_new is None:
        return new
    _, ps_old = block.paged_split(old)
    sel = lambda nw, ol: jnp.where(
        active.reshape((1, -1) + (1,) * (nw.ndim - 2)), nw, ol)
    return block.paged_merge(shared, jax.tree.map(sel, ps_new, ps_old))


def _carried(block, st, table):
    """(the pool a serving scan carries, the leaves it scans): a paged
    kind's layer-stacked pool rides in the scan's carry, handed to each
    layer whole with its index (``LayerOf``) and written in place, since a
    scan that took it as xs and ys would slice each layer out, relay it
    out for the token write and stack it again on every step.  Dense
    states (no table) and per-slot leaves stay xs and ys."""
    if table is None:
        return None, st
    return block.paged_split(st)


def _layer_state(block, pool, per_slot, i):
    return (per_slot if pool is None
            else block.paged_merge(LayerOf(pool, i), per_slot))


def _layer_out(block, pool, st):
    """(the carried pool, the per-layer leaves) of a layer's new state."""
    if pool is None:
        return None, st
    shared, per_slot = block.paged_split(st)
    return shared.stack, per_slot


def _merge_carried(block, pool, per_slot):
    return per_slot if pool is None else block.paged_merge(pool, per_slot)


def segment_decode(cfg, seg_params: list, x, states: list, pos, ctx=None,
                   unroll: bool = False, tables: dict | None = None,
                   active=None):
    """``tables`` switches attn-family blocks to the paged gather/scatter
    path: {"full": (B, W), "win": (B, W)} per-slot block tables (DESIGN.md
    §14); their states are then shared PagedKVCache pools, carried through
    the layer scan.  ``active`` (B,) bool additionally freezes inactive
    slots' per-slot state and trash-routes their KV writes (mid-prefill
    slots share the decode batch).  Returns (x, new states, counts): the
    routed-expert counts (:func:`moe.held_counts`) summed over the layers
    of kinds with routed experts, int32 (3,), or None where no layer
    routes experts."""
    valid = None if active is None else active[:, None]
    new_states = []
    counts = (jnp.zeros((3,), jnp.int32)
              if any(registry.get(kind).contract.routed_experts
                     for (kind, _), _ in seg_params) else None)
    for ((kind, n), p), st in zip(seg_params, states):
        block = registry.get(kind)
        table = _block_table(block, tables)
        routed = block.contract.routed_experts

        sliced, whole, layer = _scan_leaves(block, p, n)
        pool, scanned = _carried(block, st, table)

        def body(carry, pst, _block=block, _table=table, _routed=routed,
                 _whole=whole):
            xc, cnt, pool_c = carry
            pl, stl, i = pst
            out = _block.decode(cfg, _layer_params(pl, _whole, i), xc,
                                _layer_state(_block, pool_c, stl, i), pos,
                                ctx, table=_table, valid=valid)
            if _routed:
                xn, stn, c = out
                cnt = cnt + c
            else:
                xn, stn = out
            pool_c, stn = _layer_out(_block, pool_c, stn)
            return (xn, cnt, pool_c), stn

        if unroll:
            outs = []
            carry = (x, counts, pool)
            for i in range(n):
                pst = jax.tree.map(lambda a: a[i], (sliced, scanned))
                carry, stn_i = body(carry, (*pst, jnp.int32(i)))
                outs.append(stn_i)
            x, counts, pool = carry
            stn = jax.tree.map(lambda *ls: jnp.stack(ls), *outs)
        else:
            (x, counts, pool), stn = jax.lax.scan(body, (x, counts, pool),
                                                  (sliced, scanned, layer))
        stn = _merge_carried(block, pool, stn)
        if active is not None:
            stn = _freeze_inactive(block, st, stn, active)
        new_states.append(stn)
    return x, new_states, counts


def segment_chunk(cfg, seg_params: list, x, states: list, slot, pos0,
                  valid, n_valid, ctx=None, tables: dict | None = None,
                  fresh=None):
    """One chunked-prefill piece through every segment (B=1, S=C).

    Per-slot dense leaves (recurrent state, ctx_kv) are sliced out for
    ``slot``, optionally reset to their initial values when ``fresh`` (the
    request's first chunk overwrites whatever the previous tenant left),
    run through the chunk, and scattered back; shared PagedKVCache pools
    ride whole in the layer scan's carry (the block table confines writes
    to this slot's blocks).  ``tables`` rows here are (1, W) — just this
    slot's row.
    """
    new_states = []
    for ((kind, n), p), st in zip(seg_params, states):
        block = registry.get(kind)
        table = _block_table(block, tables)
        pool, per_slot = _carried(block, st, table)
        ps_slot = None
        if per_slot is not None:
            ps_slot = jax.tree.map(
                lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
                per_slot)
            if fresh is not None:
                one = block.paged_state_spec(cfg, 1, 0, 0, 0, False)
                _, init = block.paged_split(one)
                init = jax.tree.map(
                    lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), init)
                ps_slot = jax.tree.map(
                    lambda i, l: jnp.where(fresh, i.astype(l.dtype), l),
                    init, ps_slot)

        sliced, whole, layer = _scan_leaves(block, p, n)

        def body(carry, pst, _block=block, _table=table, _whole=whole):
            xc, pool_c = carry
            pl, ps_l, i = pst
            xn, st_n = _block.chunk(cfg, _layer_params(pl, _whole, i), xc,
                                    _layer_state(_block, pool_c, ps_l, i),
                                    pos0, valid, n_valid, ctx, _table)
            pool_c, ps_n = _layer_out(_block, pool_c, st_n)
            return (xn, pool_c), ps_n

        (x, pool), ps_new = jax.lax.scan(body, (x, pool),
                                         (sliced, ps_slot, layer))
        if per_slot is not None:
            ps_new = jax.tree.map(
                lambda full_l, new_l: jax.lax.dynamic_update_slice_in_dim(
                    full_l, new_l.astype(full_l.dtype), slot, axis=1),
                per_slot, ps_new)
        new_states.append(_merge_carried(block, pool, ps_new))
    return x, new_states


def segment_copy_block(cfg, states: list, src, dst):
    """Copy physical block ``src`` -> ``dst`` in every shared pool leaf
    (the device half of copy-on-write prefix sharing, DESIGN.md §15).

    Per-slot leaves (recurrent carries, ctx_kv) pass through untouched —
    prefix sharing is only enabled for archs whose sequential state lives
    entirely in the paged pools, so there is nothing per-slot to duplicate.
    Block ids are unique across table classes and requests, which makes the
    copy safe to apply to *every* pool: at most one class maps ``src``.
    """
    out = []
    for (kind, _), st in zip(cfg.segments(), states):
        block = registry.get(kind)
        shared, per_slot = block.paged_split(st)
        if shared is not None:
            shared = shared.copy_block(src, dst)
        out.append(block.paged_merge(shared, per_slot))
    return out


def segment_export_slot(cfg, states: list, slot, ids: dict):
    """Extract one slot's state from every segment (DESIGN.md §17).

    ``ids`` maps table class -> this slot's full (W,) block-table row;
    each kind resolves its row through its contract's ``table_class`` —
    the same dispatch :func:`_block_table` uses on the forward path, so
    a kind can never be exported through the wrong table.  Returns a
    tuple of per-segment ``(shared, per_slot)`` payloads.
    """
    out = []
    for (kind, _), st in zip(cfg.segments(), states):
        block = registry.get(kind)
        c = block.contract
        row = ids[c.table_class] if c.paged_kv else None
        out.append(block.export_slot(st, slot, row))
    return tuple(out)


def segment_import_slot(cfg, states: list, slot, ids: dict, payloads):
    """Inverse of :func:`segment_export_slot`: scatter per-segment payloads
    into ``slot`` and the destination table rows ``ids``."""
    out = []
    for (kind, _), st, pl in zip(cfg.segments(), states, payloads):
        block = registry.get(kind)
        c = block.contract
        row = ids[c.table_class] if c.paged_kv else None
        out.append(block.import_slot(st, slot, row, pl))
    return out


def segment_gather_block(cfg, states: list, bid):
    """Read physical block ``bid`` out of every shared pool leaf (the
    integrity scrubber's view of an idle cached block, DESIGN.md §17).
    Returns a per-segment tuple of shared-pool slices (None for segments
    with no paged pool); per-slot state is never block-granular and is
    not part of a block's identity."""
    out = []
    for (kind, _), st in zip(cfg.segments(), states):
        block = registry.get(kind)
        shared, _ = block.paged_split(st)
        out.append(None if shared is None
                   else jax.tree.map(lambda l: l[:, bid], shared))
    return tuple(out)


def segment_states(cfg, segments, batch, s_max, abstract: bool):
    """Stacked decode states per segment (leading axis = layers in segment)."""
    out = []
    for kind, n in segments:
        block = registry.get(kind)
        one = block.state_spec(cfg, batch, s_max, abstract)
        if abstract:
            stacked = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype), one)
        else:
            stacked = jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), one)
        out.append(stacked)
    return out


def segment_paged_states(cfg, segments, batch, s_max, n_blocks: int,
                         block_size: int, abstract: bool):
    """Paged decode states per segment: attn-family KV caches become shared
    ``(n, n_blocks, KV, block_size, dh)`` pools (stacked per layer, no batch
    axis); recurrent / ctx_kv leaves keep the dense ``(n, batch, ...)``
    layout (DESIGN.md §14)."""
    out = []
    for kind, n in segments:
        block = registry.get(kind)
        one = block.paged_state_spec(cfg, batch, s_max, n_blocks, block_size,
                                     abstract)
        if abstract:
            stacked = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype), one)
        else:
            stacked = jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), one)
        out.append(stacked)
    return out


def segment_state_pspecs(cfg, segments, ba, kv_shard: str = "heads",
                         tp_size: int = 16):
    """PartitionSpecs matching segment_states (stack axis unsharded)."""
    out = []
    for kind, n in segments:
        one = registry.get(kind).state_pspec(cfg, ba, kv_shard, tp_size)
        out.append(jax.tree.map(lambda s: P(None, *s), one,
                                is_leaf=lambda x: isinstance(x, P)))
    return out
