"""Mixture-of-Experts FFN: top-k routing with grouped GShard-style dispatch.

Tokens are processed in groups (one dispatch problem per group) so the
dispatch/combine one-hots stay O(group_len^2 * k) regardless of expert
count; experts are sharded over the "ep" logical axis (-> mesh "model"), so
the dispatch einsum lowers to the canonical all-to-all pattern.

Capacity: C = ceil(group_len * top_k / E * capacity_factor); overflow tokens
are dropped (their combine weight is zero — the residual path carries them),
standard GShard/Switch behavior.

The router stays full-precision even under quant="xnor" (binary routers
collapse; XNOR-Net also exempts the network's decision layers — DESIGN.md §5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.ctx import constrain
from repro.models.params import ParamDef

F32 = jnp.float32


def moe_defs(cfg, n: int) -> dict:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    return {
        "router": ParamDef((n, d, e), (None, "fsdp", None), F32),
        "w1": ParamDef((n, e, d, ff), (None, "ep", "fsdp", None), cfg.dtype),
        "w3": ParamDef((n, e, d, ff), (None, "ep", "fsdp", None), cfg.dtype),
        "w2": ParamDef((n, e, ff, d), (None, "ep", None, "fsdp"), cfg.dtype),
    }


def group_len(cfg) -> int:
    """Dispatch-tensor budget: size ~ group_len^2 * k * cf (dtype bytes)."""
    return 512 if cfg.top_k > 2 else 1024


def capacity(cfg, tg: int) -> int:
    return max(1, int(tg * cfg.top_k / cfg.n_experts * cfg.capacity_factor))


@jax.named_scope("mlp")
def moe_ffn(cfg, p: dict, x: jnp.ndarray, valid: jnp.ndarray | None = None):
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar).

    ``valid`` (B, S) bool excludes padding tokens (chunked-prefill ragged
    tails) from routing entirely: they occupy no expert capacity, their
    combine weight is zero, and the aux loss ignores them.
    """
    b, s, d = x.shape
    tg = min(group_len(cfg), s)
    assert (b * s) % tg == 0, (b, s, tg)
    g = (b * s) // tg
    xg = x.reshape(g, tg, d)

    xg = constrain(xg, "batch", None, None)
    logits = jnp.einsum("gtd,de->gte", xg.astype(F32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (G, T, E)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)                 # (G, T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    e, k = cfg.n_experts, cfg.top_k
    c = capacity(cfg, tg)
    mask = jax.nn.one_hot(idx, e, dtype=F32)                     # (G, T, k, E)
    if valid is not None:
        mask = mask * valid.reshape(g, tg)[:, :, None, None].astype(F32)
    # position of each (token, choice) within its expert queue; choices of
    # earlier tokens and earlier k-slots go first (choice-major priority).
    prio = jnp.moveaxis(mask, 2, 1).reshape(g, k * tg, e)
    pos = jnp.cumsum(prio, axis=1) - prio
    pos = jnp.moveaxis(pos.reshape(g, k, tg, e), 1, 2)           # (G, T, k, E)
    keep = (pos < c).astype(F32) * mask
    pos_sel = jnp.sum(pos * keep, axis=-1)                       # (G, T, k)
    gate_kept = gates * jnp.sum(keep, axis=-1)                   # (G, T, k)
    pos_oh = jax.nn.one_hot(pos_sel, c, dtype=F32)               # (G, T, k, C)
    combine = jnp.einsum("gtk,gtke,gtkc->gtec", gate_kept, keep, pos_oh)
    dispatch = (combine > 0).astype(x.dtype)                     # (G, T, E, C)

    xe = jnp.einsum("gtec,gtd->egcd", dispatch, xg)              # (E, G, C, d)
    xe = constrain(xe, "ep", "batch", None, None)   # the all-to-all boundary
    h = (jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe, p["w1"]))
         * jnp.einsum("egcd,edf->egcf", xe, p["w3"]))
    h = constrain(h, "ep", "batch", None, None)
    ye = jnp.einsum("egcf,efd->egcd", h, p["w2"])                # (E, G, C, d)
    ye = constrain(ye, "ep", "batch", None, None)
    y = jnp.einsum("gtec,egcd->gtd", combine.astype(ye.dtype), ye)
    y = constrain(y, "batch", None, None)

    # Switch/GShard load-balancing loss: E * sum_e f_e * P_e (means over
    # valid tokens only — padding must pollute neither factor)
    if valid is not None:
        v = valid.reshape(g, tg, 1).astype(F32)
        denom = jnp.maximum(jnp.sum(v), 1.0)
        f_e = jnp.sum(mask[:, :, 0, :], axis=(0, 1)) / denom
        p_e = jnp.sum(probs * v, axis=(0, 1)) / denom
    else:
        f_e = jnp.mean(mask[:, :, 0, :], axis=(0, 1))            # top-1 frac
        p_e = jnp.mean(probs, axis=(0, 1))
    aux = cfg.n_experts * jnp.sum(f_e * p_e)
    return y.reshape(b, s, d).astype(x.dtype), aux
