"""Mixture-of-Experts FFN: dropless top-k routing over the router's experts,
of which this layer holds a contiguous share, plus optional shared experts.

Routing (``cfg.router_score``):
  softmax   gates are the softmax of the router's logits at the top_k;
  sigmoid   scores are sigmoid(logits); the top_k are chosen on score plus
            a per-expert bias (``router_bias``: DeepSeek-V3's ``noaux_tc``
            correction, used only to choose) and the gates are their scores.
Either way the gates are normalised over the k chosen and scaled by
``cfg.routed_scale``.

The router chooses over all ``cfg.n_router_experts``; the layer holds
experts ``first .. first + cfg.n_experts - 1`` (the chip's share of an
expert-parallel deployment, model-configs §4) and computes their part of
the result for exactly the (token, choice) pairs routed to them: no
capacity, nothing dropped, so a token's output depends on that token
alone.  What absent experts would add is left out; on one chip the layer
runs without its exchange.  ``cfg.n_shared_experts`` run on every token as
one SwiGLU FFN of that many times ``d_ff_expert``.

Two forms of the routed part, equal in real arithmetic:
  dense     every held expert over every row, weighted by the gates (zero
            where not chosen) — the plain twin, for CPU and training;
  grouped   the chosen pairs sorted by expert into tiles of ``tm`` rows, one
            expert per tile, through ``ops.moe_expert_ffn`` (the Pallas
            grouped-matmul kernel on TPU): each touched expert's weights are
            read once, untouched experts not at all.  The serving paths
            take it where ``ops.fused_mode`` picks the kernels, and hand it
            the layer-stacked expert weights whole (``LayerOf``), so that
            no copy of a layer's experts precedes it.

Routers and experts stay full-precision under quant="xnor" (binary routers
collapse; XNOR-Net also exempts the network's decision layers — DESIGN.md
§5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.params import LayerOf, ParamDef

F32 = jnp.float32


def _ffn_defs(cfg, n: int, lead: tuple, ff: int) -> dict:
    d = cfg.d_model
    ep = ("ep",) if lead else ()
    return {
        "w1": ParamDef((n, *lead, d, ff), (None, *ep, "fsdp", None), cfg.dtype),
        "w3": ParamDef((n, *lead, d, ff), (None, *ep, "fsdp", None), cfg.dtype),
        "w2": ParamDef((n, *lead, ff, d), (None, *ep, None, "fsdp"), cfg.dtype),
    }


def moe_defs(cfg, n: int) -> dict:
    d, er = cfg.d_model, cfg.n_router_experts
    defs = {"router": ParamDef((n, d, er), (None, "fsdp", None), F32)}
    if cfg.router_score == "sigmoid":
        defs["router_bias"] = ParamDef((n, er), (None, None), F32,
                                       init="zeros")
    defs |= _ffn_defs(cfg, n, (cfg.n_experts,), cfg.d_ff_expert)
    if cfg.n_shared_experts:
        defs |= {f"shared_{k}": v for k, v in _ffn_defs(
            cfg, n, (), cfg.n_shared_experts * cfg.d_ff_expert).items()}
    return defs


def route(cfg, p: dict, x: jnp.ndarray):
    """x (T, d) -> (gates (T, k) f32, chosen experts (T, k) int32 over the
    router's width, the per-expert scores (T, E_router) the balancing loss
    reads)."""
    logits = jnp.einsum("td,de->te", x.astype(F32), p["router"])
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * cfg.routed_scale
    return gates, idx, probs


def _swiglu(x, w1, w3, w2):
    h = (jax.nn.silu(jnp.einsum("...d,df->...f", x, w1,
                                preferred_element_type=F32))
         * jnp.einsum("...d,df->...f", x, w3, preferred_element_type=F32))
    return jnp.einsum("...f,fd->...d", h.astype(x.dtype), w2,
                      preferred_element_type=F32)


def _layer(w):
    """An expert leaf (E, ...) of one layer, from itself or a ``LayerOf``."""
    return w.get() if isinstance(w, LayerOf) else w


def _stack(w):
    """(layer-stacked expert leaf, layer index): a ``LayerOf`` as it is, a
    single layer's leaf as a stack of one."""
    if isinstance(w, LayerOf):
        return w.stack, jnp.asarray(w.index, jnp.int32)
    return w[None], jnp.int32(0)


def routed_dense(p: dict, x, local, gates):
    """The held experts' part, each held expert over every row: x (T, d),
    ``local`` (T, k) the chosen experts' index among the held (outside
    0..E-1 where not held or not counted), ``gates`` (T, k)."""
    w1, w3, w2 = (_layer(p[n]) for n in ("w1", "w3", "w2"))
    e = w1.shape[0]
    wts = jnp.sum(jnp.where(local[..., None] == jnp.arange(e), gates[..., None],
                            0.0), axis=1)                             # (T, E)
    h = (jax.nn.silu(jnp.einsum("td,edf->etf", x, w1,
                                preferred_element_type=F32))
         * jnp.einsum("td,edf->etf", x, w3, preferred_element_type=F32))
    y = jnp.einsum("etf,efd->etd", h.astype(x.dtype), w2,
                   preferred_element_type=F32)
    return jnp.einsum("te,etd->td", wts, y)


def tile_rows(pairs: int, router_experts: int) -> int:
    """Rows per tile of the grouped form: the rows one expert expects under
    even routing, rounded up to a power of two, between 8 and 128."""
    want = -(-pairs // router_experts)
    return min(128, max(8, 1 << (want - 1).bit_length()))


def routed_grouped(p: dict, x, local, gates, *, tm: int):
    """The held experts' part through the grouped kernel: the (token,
    choice) pairs on held experts sorted by expert, each expert's run
    padded to whole tiles of ``tm`` rows."""
    t, k = local.shape
    (w1, layer), (w3, _), (w2, _) = (_stack(p[n]) for n in ("w1", "w3", "w2"))
    e = w1.shape[1]
    n = t * k
    expert = jnp.where((local >= 0) & (local < e), local, e).reshape(n)
    counts = jnp.bincount(expert, length=e + 1)[:e]
    padded = -(-counts // tm) * tm
    start = jnp.cumsum(padded) - padded                  # each run's first row
    first = jnp.cumsum(counts) - counts
    order = jnp.argsort(expert, stable=True)
    se = expert[order]
    held = se < e
    rank = jnp.arange(n) - first[jnp.minimum(se, e - 1)]
    n_tiles = -(-(n + e * (tm - 1)) // tm)
    dest = jnp.where(held, start[jnp.minimum(se, e - 1)] + rank,
                     n_tiles * tm)                       # off the end: dropped
    rows = order // k
    xs = jnp.zeros((n_tiles * tm, x.shape[1]), x.dtype).at[dest].set(
        x[rows], mode="drop")
    used = jnp.sum(padded) // tm
    ends = jnp.cumsum(padded) // tm
    tile = jnp.arange(n_tiles)
    tile_expert = jnp.minimum(jnp.searchsorted(ends, jnp.minimum(tile, used - 1),
                                               side="right"), e - 1)
    ys = ops.moe_expert_ffn(xs, w1, w3, w2, layer[None],
                            tile_expert.astype(jnp.int32),
                            (tile < used).astype(jnp.int32), tm=tm)
    g = gates.reshape(n)[order]
    contrib = jnp.where(held[:, None], ys[jnp.minimum(dest, n_tiles * tm - 1)]
                        * g[:, None], 0.0)
    return jnp.zeros((t, x.shape[1]), F32).at[rows].add(contrib)


def held_counts(local, e: int, valid) -> jnp.ndarray:
    """(choices routed, choices on held experts, held experts with at least
    one row) over the valid rows, int32 (3,)."""
    v = valid[:, None]
    held = (local >= 0) & (local < e) & v
    touched = jnp.any((local[..., None] == jnp.arange(e)) & held[..., None],
                      axis=(0, 1))
    return jnp.stack([jnp.sum(v) * local.shape[1], jnp.sum(held),
                      jnp.sum(touched)]).astype(jnp.int32)


@jax.named_scope("mlp")
def moe_ffn(cfg, p: dict, x: jnp.ndarray, valid: jnp.ndarray | None = None,
            *, first: int = 0, grouped: bool = False):
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar, counts int32 (3,)).

    ``valid`` (B, S) bool excludes padding tokens (chunked-prefill ragged
    tails, rows riding along inert in a decode batch) from the routed part,
    the balancing loss and the counts (:func:`held_counts`).  ``first`` is
    the first held expert; ``grouped`` takes the grouped form.
    """
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    gates, idx, probs = route(cfg, p, xt)
    v = (jnp.ones((b * s,), bool) if valid is None
         else valid.reshape(b * s))
    e = cfg.n_experts
    local = jnp.where(v[:, None], idx - first, -1)
    if grouped:
        y = routed_grouped(p, xt, local, gates,
                           tm=tile_rows(b * s * cfg.top_k,
                                        cfg.n_router_experts))
    else:
        y = routed_dense(p, xt, local, gates)
    if cfg.n_shared_experts:
        y = y + _swiglu(xt, p["shared_w1"], p["shared_w3"], p["shared_w2"])

    # Switch/GShard load-balancing loss over the router's experts: E * sum_e
    # f_e * P_e (f_e the top-1 share, P_e the mean normalised score), means
    # over valid tokens only
    vf = v.astype(F32)[:, None]
    denom = jnp.maximum(jnp.sum(vf), 1.0)
    f_e = jnp.sum(jax.nn.one_hot(idx[:, 0], probs.shape[-1]) * vf, 0) / denom
    p_e = jnp.sum(probs * vf, 0) / denom
    aux = probs.shape[-1] * jnp.sum(f_e * p_e)
    return (y.reshape(b, s, d).astype(x.dtype), aux,
            held_counts(local, e, v))
