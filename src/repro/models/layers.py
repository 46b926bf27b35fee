"""Shared neural layers: norms, RoPE, projections (with optional XNOR
quantization — the paper's technique as a first-class config axis), SwiGLU
FFN, embeddings.

All functions are pure; parameters are declared via :mod:`repro.models.params`
ParamDefs with logical sharding axes ("fsdp" -> data, "tp"/"ep" -> model).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import xnor_layers
from repro.distributed.ctx import constrain
from repro.models.params import ParamDef


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def linear(x: jnp.ndarray, w, quant: str = "none",
           bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """x (..., K) @ w (K, N). ``quant="xnor"`` routes through the binary
    XNOR-Net path (STE in float domain at train time).

    ``w`` may also be a :class:`repro.core.xnor_layers.PackedLinear` — the
    packed-residency serve form produced by ``lm.pack_params`` — in which
    case the float weight no longer exists and the XNOR-popcount GEMM runs
    over the resident bit-planes (bit-exact with the float sign path).
    """
    if isinstance(w, xnor_layers.PackedLinear):
        y = xnor_layers.xnor_linear_prepacked(x, w.pb, w.beta, valid_k=w.k)
    elif quant == "xnor":
        y = xnor_layers.xnor_linear(x, w.T)
    else:
        y = jnp.einsum("...k,kn->...n", x, w)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


# --- RoPE -------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, dh); positions: (B, S) or (S,). NeoX-style halves."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (dh/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, dh/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --- SwiGLU FFN ---------------------------------------------------------------

def ffn_defs(cfg, n: int, d_ff: int | None = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w1": ParamDef((n, d, ff), (None, "fsdp", "tp"), cfg.dtype,
                       binarize=True),
        "w3": ParamDef((n, d, ff), (None, "fsdp", "tp"), cfg.dtype,
                       binarize=True),
        "w2": ParamDef((n, ff, d), (None, "tp", "fsdp"), cfg.dtype,
                       binarize=True),
    }


@jax.named_scope("mlp")
def ffn(cfg, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(linear(x, p["w1"], cfg.quant)) * linear(x, p["w3"], cfg.quant)
    h = constrain(h, "batch", None, "tp")
    return constrain(linear(h, p["w2"], cfg.quant), "batch", None, None)


# --- embedding / unembedding --------------------------------------------------

def embed_defs(cfg) -> dict:
    v = cfg.padded_vocab
    return {
        "tokens": ParamDef((v, cfg.d_model), ("tp", "fsdp"),
                           cfg.dtype, init="embed"),
        "final_norm": ParamDef((cfg.d_model,), (None,), jnp.float32, init="ones"),
        # lm_head d-axis unsharded: fsdp on the contraction dim makes GSPMD
        # all-gather the (tokens, vocab) f32 logits over the data axis
        # (37 GiB/step measured) instead of this 68 MB/chip weight.
        "lm_head": ParamDef((cfg.d_model, v), (None, "tp"), cfg.dtype),
    }


def embed(p: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return constrain(jnp.take(p["tokens"], tokens, axis=0),
                     "batch", None, None)


def logits(cfg, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, p["final_norm"])
    # lm_head stays full precision even under quant="xnor" (XNOR-Net keeps
    # first/last layers full precision; DESIGN.md §5).
    return constrain(jnp.einsum("...d,dv->...v", x, p["lm_head"]),
                     "batch", None, "tp")
