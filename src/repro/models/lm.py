"""Language-model assembly: embeddings + scanned segments + head, for all
ten assigned architectures (decoder-only, VLM cross-attn, and enc-dec).

Public surface (all pure functions of (cfg, params, ...)):
  param_defs / abstract_params / param_pspecs / init_params
  forward(cfg, params, tokens, ctx)          -> (logits, aux)
  loss_fn(cfg, params, batch)                -> (loss, metrics)
  prefill(cfg, params, tokens, ctx, s_max)   -> (last_logits, DecodeState)
  decode_step(cfg, params, token, state)     -> (logits, DecodeState)

``ctx`` is the stubbed modality context: precomputed patch embeddings (vlm)
or encoder frames (audio); None for text-only archs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import blocks, layers
from repro.models import params as pdefs
from repro.models import registry
from repro.models.blocks import FwdOpts


# ---------------------------------------------------------------------------
# parameter declaration
# ---------------------------------------------------------------------------

def param_defs(cfg) -> dict:
    defs: dict[str, Any] = {"embed": layers.embed_defs(cfg)}
    for i, (kind, n, d) in enumerate(blocks.segment_defs(cfg)):
        defs[f"seg{i}_{kind}"] = d
    if cfg.is_encdec():
        enc_segs = cfg.encoder_segments()
        for i, (kind, n, d) in enumerate(blocks.segment_defs(cfg, enc_segs)):
            defs[f"enc{i}_{kind}"] = d
        defs["enc_norm"] = pdefs.ParamDef((cfg.d_model,), (None,),
                                          jnp.float32, init="ones")
    return defs


def abstract_params(cfg):
    return pdefs.abstract(param_defs(cfg))


def param_pspecs(cfg, rules: dict):
    return pdefs.pspecs(param_defs(cfg), rules)


def init_params(cfg, key: jax.Array):
    return pdefs.init(param_defs(cfg), key)


def pack_params(cfg, params, impl: str = "auto"):
    """Serve-resident form of ``params``: every binarizable linear is packed
    once to ``PackedLinear`` sign-planes + beta (the float weight leaves the
    tree — packed residency, DESIGN.md §13).  Identity for quant="none"
    archs: there is nothing binary to pack."""
    if cfg.quant != "xnor":
        return params
    return pdefs.pack(param_defs(cfg), params, impl=impl)


def packed_abstract_params(cfg):
    """Abstract tree matching :func:`pack_params` output."""
    if cfg.quant != "xnor":
        return abstract_params(cfg)
    return pdefs.pack_abstract(param_defs(cfg))


def param_count(cfg) -> int:
    return pdefs.count(param_defs(cfg))


def active_param_count(cfg) -> int:
    """Params touched per token: excludes the embedding table gather and
    the held experts a token is not routed to — of the top_k it chooses
    among the router's experts, the held share on average (MODEL_FLOPS
    accounting, DESIGN.md §9)."""
    total = pdefs.count(param_defs(cfg))
    inactive = cfg.vocab * cfg.d_model          # embedding table
    if cfg.n_experts:
        per_expert = 3 * cfg.d_model * cfg.d_ff_expert
        n_routed = sum(1 for k in cfg.layer_kinds()
                       if registry.contract(k).routed_experts)
        active = cfg.top_k * cfg.n_experts / cfg.n_router_experts
        inactive += int(n_routed * (cfg.n_experts - active) * per_expert)
    return total - inactive


def model_flops(cfg, kind: str, tokens: int) -> float:
    """6·N_active·D for training, 2·N_active·D for inference forward."""
    n = active_param_count(cfg)
    return (6.0 if kind == "train" else 2.0) * n * tokens


def _seg_params(cfg, params, enc: bool = False):
    """[( (kind, n), stacked-params ), ...] in depth order."""
    segs = cfg.encoder_segments() if enc else cfg.segments()
    prefix = "enc" if enc else "seg"
    out = []
    for i, (kind, n) in enumerate(segs):
        out.append(((kind, n), params[f"{prefix}{i}_{kind}"]))
    return out


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def encode(cfg, params, frames: jnp.ndarray, q_chunk: int = 0) -> jnp.ndarray:
    """Whisper-style encoder over stubbed frame embeddings (B, T, d)."""
    x, _, _ = blocks.segment_fwd(cfg, _seg_params(cfg, params, enc=True),
                                 frames.astype(cfg.dtype), None,
                                 FwdOpts(q_chunk=q_chunk))
    return layers.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(cfg, params, tokens: jnp.ndarray, ctx: jnp.ndarray | None = None,
            q_chunk: int = 0, remat: bool = False, unroll: bool = False):
    """tokens (B, S) -> (logits (B, S, V), aux)."""
    x = layers.embed(params["embed"], tokens).astype(cfg.dtype)
    if cfg.is_encdec():
        assert ctx is not None, "enc-dec arch needs encoder frames"
        ctx = encode(cfg, params, ctx, q_chunk)
    elif ctx is not None:
        ctx = ctx.astype(cfg.dtype)
    x, aux, _ = blocks.segment_fwd(cfg, _seg_params(cfg, params), x, ctx,
                                   FwdOpts(q_chunk=q_chunk, unroll=unroll),
                                   remat=remat, unroll=unroll)
    return layers.logits(cfg, params["embed"], x), aux


def loss_fn(cfg, params, batch: dict, q_chunk: int = 0, remat: bool = True,
            unroll: bool = False):
    """Next-token CE (labels pre-shifted by the data pipeline; -1 = pad).

    The picked-logit term is a one-hot contraction, NOT take_along_axis:
    gathering along the vocab axis defeats the vocab (TP) sharding — GSPMD
    replicates the full (tokens, vocab) f32 logits on every chip (hundreds
    of GiB at production shapes).  The iota==label formulation partitions
    cleanly (local compare/multiply + a reduction over the sharded axis).
    """
    logits, aux = forward(cfg, params, batch["tokens"], batch.get("ctx"),
                          q_chunk=q_chunk, remat=remat, unroll=unroll)
    labels = batch["labels"]
    valid = labels >= 0
    labels = jnp.maximum(labels, 0)
    logits32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)
    onehot = (labels[..., None]
              == jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1))
    picked = jnp.sum(logits32 * onehot, axis=-1)
    ll = picked - lse
    denom = jnp.maximum(jnp.sum(valid), 1)
    ce = -jnp.sum(ll * valid) / denom
    loss = ce + cfg.router_aux_weight * aux
    return loss, {"ce": ce, "aux": aux,
                  "tokens": jnp.sum(valid).astype(jnp.int32)}


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    pos: jnp.ndarray          # int32 tokens consumed: scalar (homogeneous
                              # batch) or (B,) per-slot (continuous batching)
    seg_states: tuple         # per-segment stacked block states
    ctx: Any = None           # encoded modality context (or None)


def decode_state_spec(cfg, batch: int, s_max: int, abstract: bool = True):
    """The slot-dense decode state for (arch, batch, cache length), one
    position for the whole batch: what :func:`prefill` builds and
    :func:`decode_step` advances (the dry-run lowers it).  The serve engine
    keeps :func:`paged_decode_state_spec` resident instead."""
    seg_states = blocks.segment_states(cfg, cfg.segments(), batch, s_max,
                                       abstract)
    ctx = None
    if cfg.n_ctx_tokens and not cfg.is_encdec():
        shp = (batch, cfg.n_ctx_tokens, cfg.d_model)
        ctx = (jax.ShapeDtypeStruct(shp, cfg.dtype) if abstract
               else jnp.zeros(shp, cfg.dtype))
    pos = (jax.ShapeDtypeStruct((), jnp.int32) if abstract
           else jnp.zeros((), jnp.int32))
    return DecodeState(pos, tuple(seg_states), ctx)


def paged_table_widths(cfg, s_max: int, block_size: int,
                       prefill_chunk: int) -> dict:
    """Block-table widths per cache class for the paged serve layout.

    Each decoder kind with a paged pool declares (via its BlockContract)
    which table class addresses it and whether that table is a window
    *ring*.  Monotone classes get ``ceil(s_max / bs)`` blocks; ring
    classes get capacity ``W * bs >= window + C - 1``, which guarantees
    that scatter-then-attend chunked prefill (chunk size C) never
    overwrites an in-window key.  Kinds sharing a class take the max
    width.  Archs with no KV cache at all (pure recurrent) return {}.
    """
    bs = block_size
    widths: dict[str, int] = {}
    for kind, _ in cfg.segments():
        c = registry.contract(kind)
        if not c.paged_kv:
            continue
        if c.window:
            cap = min(s_max, cfg.local_window + max(prefill_chunk, 1) - 1)
        else:
            cap = s_max
        w = -(-cap // bs)
        widths[c.table_class] = max(widths.get(c.table_class, 0), w)
    return widths


def paged_table_windows(cfg) -> dict:
    """The attention window per block-table class: ``cfg.local_window``
    for a ring class, 0 for a monotone one (the ``window`` the paged
    decode kernel walks that class's tables with)."""
    return {c.table_class: cfg.local_window if c.window else 0
            for c in (registry.contract(kind) for kind, _ in cfg.segments())
            if c.paged_kv}


def paged_decode_layer_classes(cfg) -> dict:
    """Paged decoder layers per block-table class.

    The roofline floor for a decode step streams each paged layer's live
    K/V once (``analysis.decode_roofline_bytes``); this is the layer-count
    side of that accounting, derived from the same BlockContract registry
    as :func:`paged_table_widths` so the two can never disagree about
    which layers are paged.
    """
    counts: dict[str, int] = {}
    for kind, n in cfg.segments():
        c = registry.contract(kind)
        if c.paged_kv:
            counts[c.table_class] = counts.get(c.table_class, 0) + n
    return counts


def paged_decode_state_spec(cfg, batch: int, s_max: int, *, n_blocks: int,
                            block_size: int, abstract: bool = True):
    """The block-paged resident serving state (DESIGN.md §14).

    Attn-family KV caches are shared ``(n_blocks, KV, block_size, dh)``
    pools addressed through host-owned per-slot block tables; ``pos`` is
    per-slot; recurrent per-slot state stays dense (it is O(1) per slot).
    ``ctx`` is never kept resident — chunked prefill receives the modality
    context as a program input and stores the derived ctx_kv per slot.
    """
    seg_states = blocks.segment_paged_states(cfg, cfg.segments(), batch,
                                             s_max, n_blocks, block_size,
                                             abstract)
    pos = (jax.ShapeDtypeStruct((batch,), jnp.int32) if abstract
           else jnp.zeros((batch,), jnp.int32))
    return DecodeState(pos, tuple(seg_states), None)


def decode_state_pspecs(cfg, ba, kv_shard: str = "heads", tp_size: int = 16):
    """PartitionSpecs mirroring decode_state_spec (ba = batch mesh axes)."""
    from jax.sharding import PartitionSpec as P
    seg = blocks.segment_state_pspecs(cfg, cfg.segments(), ba, kv_shard,
                                      tp_size)
    ctx = None
    if cfg.n_ctx_tokens and not cfg.is_encdec():
        ctx = P(ba, None, None)
    return DecodeState(P(), tuple(seg), ctx)


def prefill(cfg, params, tokens: jnp.ndarray, ctx: jnp.ndarray | None,
            s_max: int, q_chunk: int = 0, unroll: bool = False):
    x = layers.embed(params["embed"], tokens).astype(cfg.dtype)
    if cfg.is_encdec():
        ctx = encode(cfg, params, ctx, q_chunk)
    elif ctx is not None:
        ctx = ctx.astype(cfg.dtype)
    opts = FwdOpts(q_chunk=q_chunk, want_state=True, s_max=s_max,
                   unroll=unroll)
    x, _, states = blocks.segment_fwd(cfg, _seg_params(cfg, params), x, ctx,
                                      opts, unroll=unroll)
    logits = layers.logits(cfg, params["embed"], x[:, -1:])
    pos = jnp.asarray(tokens.shape[1], jnp.int32)
    keep_ctx = ctx if (cfg.is_encdec() or cfg.n_ctx_tokens) else None
    return logits, DecodeState(pos, tuple(states), keep_ctx)


def decode_step(cfg, params, token: jnp.ndarray, state: DecodeState,
                unroll: bool = False):
    """token (B, 1) int32 -> (logits (B, 1, V), new state): one token for
    every row of a slot-dense state (:func:`prefill`'s), all at
    ``state.pos``."""
    x = layers.embed(params["embed"], token).astype(cfg.dtype)
    x, new_states, _ = blocks.segment_decode(cfg, _seg_params(cfg, params),
                                             x, list(state.seg_states),
                                             state.pos, state.ctx,
                                             unroll=unroll)
    logits = layers.logits(cfg, params["embed"], x)
    return logits, DecodeState(state.pos + 1, tuple(new_states), state.ctx)


def paged_decode_step(cfg, params, token: jnp.ndarray, state: DecodeState,
                      tables: dict, active: jnp.ndarray | None = None):
    """One token for every slot against the block-paged resident state:
    (logits, new state, counts), the last the routed-expert counts of the
    rows that decode (``blocks.segment_decode``): int32 (3,), None for a
    model without routed experts.

    ``tables`` {"full"/"win": (B, W) int32} are host-owned device data —
    they change as blocks are allocated and freed without ever retracing.
    ``active`` additionally gates the paged batch's inactive rows: their
    per-slot recurrent state freezes and their KV writes are trash-routed,
    so a mid-prefill slot (chunked prefill interleaves with decode) rides
    along inertly; dead slots' table rows are zeroed by the host as well.
    """
    with jax.named_scope("embed"):
        x = layers.embed(params["embed"], token).astype(cfg.dtype)
    x, new_states, counts = blocks.segment_decode(
        cfg, _seg_params(cfg, params), x, list(state.seg_states), state.pos,
        state.ctx, tables=tables, active=active)
    with jax.named_scope("head"):
        logits = layers.logits(cfg, params["embed"], x)
    inc = 1 if active is None else active.astype(jnp.int32)
    return (logits, DecodeState(state.pos + inc, tuple(new_states), state.ctx),
            counts)


def truncate(cfg, params, state: DecodeState, depth: int):
    """The model cut to its first ``depth`` decoder layers: ``(cfg, params,
    state)`` with the same weights and cached state for those layers, and
    the embedding, head and any encoder kept — e.g. to compare two numerics
    before depth compounds their differences."""
    if not 0 < depth <= cfg.n_layers:
        raise ValueError(f"depth {depth} outside 1..{cfg.n_layers}")
    params, seg_states, left = dict(params), [], depth
    for i, (kind, n) in enumerate(cfg.segments()):
        keep, left = min(n, left), left - min(n, left)
        name = f"seg{i}_{kind}"
        if not keep:
            del params[name]
            continue
        params[name] = jax.tree.map(lambda x: x[:keep], params[name])
        seg_states.append(jax.tree.map(lambda x: x[:keep],
                                       state.seg_states[i]))
    return (dataclasses.replace(cfg, n_layers=depth), params,
            state._replace(seg_states=tuple(seg_states)))


def prefill_chunk_step(cfg, params, tokens: jnp.ndarray, state: DecodeState,
                       slot, n_valid, tables: dict,
                       ctx: jnp.ndarray | None = None, fresh=None, start=0):
    """One chunked-prefill piece for resident slot ``slot``.

    tokens (1, C) — positions ``pos0 .. pos0+C-1`` of the prompt with
    ``pos0 = state.pos[slot]`` when continuing (``fresh`` false) and
    ``start`` when the slot was just admitted; only the first ``n_valid``
    tokens are real, the rest are padding (every prompt runs through this
    one program in fixed-C pieces — one trace for the whole mixed-length
    workload).  ``start`` is 0 for a cold prompt and the skip point under
    prefix caching (DESIGN.md §15): positions 0..start-1 are already held
    in shared cache blocks mapped by this slot's table, so the resumed
    chunk scatters and attends from ``start`` as if it had computed the
    prefix itself.  ``ctx`` is the request's modality context: *encoded*
    frames for enc-dec archs (:func:`encode` runs once at admission), raw
    patch embeddings for vlm.  ``tables`` rows are this slot's (1, W)
    block-table rows.
    Returns (logits of the last valid position (1, 1, V), new state).
    """
    c = tokens.shape[1]
    pos0 = jnp.where(jnp.asarray(fresh if fresh is not None else False),
                     jnp.asarray(start, jnp.int32),
                     state.pos[slot]).astype(jnp.int32)
    valid = (jnp.arange(c) < n_valid)[None]                    # (1, C)
    with jax.named_scope("embed"):
        x = layers.embed(params["embed"], tokens).astype(cfg.dtype)
    if ctx is not None:
        ctx = ctx.astype(cfg.dtype)
    x, new_states = blocks.segment_chunk(cfg, _seg_params(cfg, params), x,
                                         list(state.seg_states), slot, pos0,
                                         valid, n_valid, ctx, tables,
                                         fresh=fresh)
    with jax.named_scope("head"):
        xlast = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = layers.logits(cfg, params["embed"], xlast)
    pos = state.pos.at[slot].set(pos0 + n_valid)
    return logits, DecodeState(pos, tuple(new_states), state.ctx)


def prefix_cache_eligible(cfg) -> bool:
    """Whether prefix sharing over the paged pools is sound for this arch.

    Sharing reconstructs a request's entire sequential state from cached
    blocks, so every decoder kind must declare ``prefix_shareable`` in its
    BlockContract — **fail-closed**: a kind that says nothing is
    ineligible, and one such kind anywhere in the stack disables sharing
    for the arch.  The built-in kinds that (correctly) don't declare it
    (DESIGN.md §15):

    * recurrent kinds (rglru/mlstm/slstm) carry dense per-slot state that
      is not block-granular — a skipped prefix would leave the carry cold;
    * local sliding-window layers use block *rings* whose physical blocks
      are recycled in place, so their contents are never stable enough to
      register, and a resumed chunk could not rebuild the in-window keys
      (the registry rejects a window+shareable contract outright).

    MoE declares it: its KV is ordinary paged attention state, and its
    dropless routing makes a token's output its own.
    """
    kinds = {k for k, _ in cfg.segments()}
    return bool(kinds) and all(registry.contract(k).prefix_shareable
                               for k in kinds)


def prefix_table_class(cfg) -> str | None:
    """The block-table class shared prefixes are registered under.

    Prefix sharing maps *stable* cached blocks between requests, so the
    share class is the table class addressed by the arch's shareable paged
    kinds.  Returns None (sharing off) when the arch has no such class or
    its shareable pools span several classes — the registration protocol
    hashes one table row per request, so a single class must cover every
    pool being rebuilt.
    """
    classes = set()
    for kind, _ in cfg.segments():
        c = registry.contract(kind)
        if c.paged_kv and c.prefix_shareable:
            classes.add(c.table_class)
    return classes.pop() if len(classes) == 1 else None


def paged_copy_block(cfg, state: DecodeState, src, dst) -> DecodeState:
    """Copy-on-write block duplication ``dst := src`` across every shared
    pool (DESIGN.md §15).  ``src``/``dst`` are device scalars — the serve
    engine jits this once per engine and calls it for any pair."""
    seg = blocks.segment_copy_block(cfg, list(state.seg_states), src, dst)
    return DecodeState(state.pos, tuple(seg), state.ctx)


# ---------------------------------------------------------------------------
# session migration: slot extraction / injection (DESIGN.md §17)
# ---------------------------------------------------------------------------


def export_slot(cfg, state: DecodeState, slot, ids: dict):
    """One slot's complete sequential state as a self-contained tree.

    ``ids`` maps table class -> the slot's full (W,) block-table row (host
    tables are device data here).  The payload is ``{"pos": (1,) int32,
    "segs": per-segment (shared, per_slot) pairs}`` — paged KV blocks in
    table-row order plus dense per-slot carries at batch width 1.  Pure
    gather: jitted once per engine, the exporting slot is untouched.
    Defined for the paged layout only (``paged_decode_state_spec``); the
    dense layout classifies whole KV caches as shared pools, which this
    row-gather addressing cannot represent.
    """
    pos = jax.lax.dynamic_slice_in_dim(state.pos, slot, 1, axis=0)
    segs = blocks.segment_export_slot(cfg, list(state.seg_states), slot, ids)
    return {"pos": pos, "segs": segs}


def import_slot(cfg, state: DecodeState, slot, ids: dict,
                payload) -> DecodeState:
    """Inverse of :func:`export_slot`: seat a payload into resident slot
    ``slot`` with ``ids`` the *destination* table rows (same widths,
    freshly allocated block ids).  Re-import is content-faithful even when
    the source blocks were shared/COW prefix blocks — blocks travel by
    value, so the destination holds a private content-identical copy and
    re-registers with its own prefix index."""
    pos = jax.lax.dynamic_update_slice_in_dim(
        state.pos, payload["pos"].astype(jnp.int32), slot, axis=0)
    seg = blocks.segment_import_slot(cfg, list(state.seg_states), slot, ids,
                                     payload["segs"])
    return DecodeState(pos, tuple(seg), state.ctx)


def export_slot_spec(cfg, state_like, slot_ids_widths: dict):
    """Shape/dtype tree of :func:`export_slot`'s payload for this engine
    geometry — the ``like`` tree a migration checkpoint restores against
    (:func:`repro.checkpoint.ckpt.restore` needs exact shapes/dtypes to
    address and decrypt leaves).  ``state_like`` is the engine's resident
    state (or its abstract spec); ``slot_ids_widths`` maps table class ->
    table width W."""
    ids = {c: jax.ShapeDtypeStruct((w,), jnp.int32)
           for c, w in slot_ids_widths.items()}
    return jax.eval_shape(
        lambda st, rows: export_slot(cfg, st, jnp.int32(0), rows),
        state_like, ids)


def gather_block(cfg, state: DecodeState, bid):
    """Physical block ``bid``'s contents across every shared pool — the
    integrity scrubber's unit of verification for idle cached blocks
    (DESIGN.md §17)."""
    return blocks.segment_gather_block(cfg, list(state.seg_states), bid)
