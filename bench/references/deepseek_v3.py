"""Plain reference of the DeepSeek-V3 decoder (Moonlight-16B-A3B), for
deciding ``correct``.

Straight ``jax.numpy`` over one whole sequence, layer by layer, with no
cache, no paging, no batching and no kernel, following the published
modelling code (``modeling_deepseek.py`` beside the model's
``config.json``, ``model_type`` deepseek_v3):

  * pre-norm RMSNorm blocks (``rms_norm_eps``), a final RMSNorm and an
    untied LM head;
  * latent attention (MLA) in its expanded form, without q compression
    (``q_lora_rank`` null): q = x Wq per head, split into nope and rotary
    parts; [c, k_rope] = x Wkv_a; c normalised by its own RMSNorm;
    [k_nope, v] = c Wkv_b per head; the rotary key shared by the heads;
    rotary embeddings on the rotary parts after the published
    de-interleave (even lanes, then odd) in the rotate-half form, base
    ``rope_theta``, no scaling; causal softmax at (nope + rope)^-1/2;
  * layers before ``first_k_dense_replace`` a SwiGLU FFN of
    ``intermediate_size``; the rest MoE: sigmoid scores of a router over
    ``router_experts`` experts, the top ``num_experts_per_tok`` chosen on
    score plus the per-expert correction bias (``noaux_tc`` with one
    group), gates their scores normalised over the chosen and times
    ``routed_scaling_factor``; ``n_shared_experts`` as one SwiGLU FFN of
    that many times ``moe_intermediate_size`` on every token.

Departures, each the configuration's own cut or a rounding no run can see:

  * the expert share: of the router's experts the layer holds the first
    ``n_routed_experts`` (the configuration's chip share) and adds only
    their part of the routed output — the program's cut, given to the
    reference alike (model-configs §4); the router still chooses over all;
  * the gate normaliser's 1e-20 guard is left out (a sum of sigmoids);
  * the embedding and weights are the benchmark's seeded draws
    (:mod:`model_weights`), one layer at a time, as the program holds them
    (bfloat16 values, computed here in float32).

Two precisions, as in ``qwen3.py``:

  ``ref``      float32 throughout, matrix products at ``highest``.
  ``fp8``      the control: every stored tensor (residual stream, norm
               outputs, projections, q/k/v, the latent, attention weights,
               FFN hiddens, router scores) and every matrix operand rounded
               to float8 e4m3, scaled per row by its largest magnitude, with
               float32 accumulation: what the served model would compute
               one precision step below its bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import numpy as np

import jax
import jax.numpy as jnp

import model_weights as mw

HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_BLOCK = 512                   # attention query rows per block
SEQ_BUCKET = 2048               # sequences are padded to a multiple of this
DENSE, MOE = "seg0_attn/", "seg1_moe/"


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference reads from a configuration file (hashable,
    so that each compiled step is reused across layers and calls)."""

    d: int
    heads: int
    nope: int
    rope: int
    dv: int
    rank: int
    ff: int
    ffe: int
    held: int
    router: int
    top_k: int
    shared: int
    scale: float
    dense: int
    layers: int
    vocab: int
    eps: float
    theta: float
    init_json: str

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        return cls(conf["hidden_size"], conf["num_attention_heads"],
                   conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                   conf["v_head_dim"], conf["kv_lora_rank"],
                   conf["intermediate_size"], conf["moe_intermediate_size"],
                   conf["n_routed_experts"], conf["router_experts"],
                   conf["num_experts_per_tok"], conf["n_shared_experts"],
                   conf["routed_scaling_factor"],
                   conf["first_k_dense_replace"], conf["num_hidden_layers"],
                   conf["vocab_size"], conf["rms_norm_eps"],
                   conf["rope_theta"], json.dumps(conf["init"],
                                                  sort_keys=True))

    @property
    def init(self) -> dict:
        return json.loads(self.init_json)


def _round8(x):
    """float8 e4m3 round trip, scaled per row (last axis) by its max |x|."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _rnd(x, mode: str):
    return _round8(x) if mode == "fp8" else x


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, heads, dr) at positions 0..S-1: the published de-interleave,
    then the rotate-half form."""
    s, _, dr = x.shape
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # (S, dr/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(x, w, mode: str):
    """x @ w with both operands rounded as ``mode`` says (w per output
    channel), then the product."""
    wt = _rnd(w.T, mode).T
    return _rnd(jnp.matmul(_rnd(x, mode), wt, precision=HI), mode)


def _draw(key, name, layer, shape, init, index=None):
    return mw.dense_leaf(key, name, layer, shape, jnp.bfloat16, init,
                         index).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _draw_layer(dims: Dims, key, layer, moe: bool):
    """One decoder layer's weights (``layer`` counts within its segment)."""
    seg, init = (MOE if moe else DENSE), dims.init
    d, h = dims.d, dims.heads
    w = {
        "wq": _draw(key, seg + "attn_wq", layer,
                    (d, h * (dims.nope + dims.rope)), init),
        "wkv_a": _draw(key, seg + "attn_wkv_a", layer,
                       (d, dims.rank + dims.rope), init),
        "wkv_b": _draw(key, seg + "attn_wkv_b", layer,
                       (dims.rank, h * (dims.nope + dims.dv)), init),
        "wo": _draw(key, seg + "attn_wo", layer, (h * dims.dv, d), init),
    }
    for n, shape in (("ln1", (d,)), ("ln2", (d,)),
                     ("attn_kv_norm", (dims.rank,))):
        w[n] = mw.dense_leaf(key, seg + n, layer, shape, jnp.float32, init)
    if not moe:
        for n, shape in (("ffn_w1", (d, dims.ff)), ("ffn_w3", (d, dims.ff)),
                         ("ffn_w2", (dims.ff, d))):
            w[n] = _draw(key, seg + n, layer, shape, init)
        return w
    w["router"] = mw.dense_leaf(key, seg + "moe_router", layer,
                                (d, dims.router), jnp.float32, init)
    w["bias"] = mw.dense_leaf(key, seg + "moe_router_bias", layer,
                              (dims.router,), jnp.float32, init)
    sh = dims.shared * dims.ffe
    for n, shape in (("moe_w1", (dims.held, d, dims.ffe)),
                     ("moe_w3", (dims.held, d, dims.ffe)),
                     ("moe_w2", (dims.held, dims.ffe, d)),
                     ("moe_shared_w1", (d, sh)), ("moe_shared_w3", (d, sh)),
                     ("moe_shared_w2", (sh, d))):
        w[n] = _draw(key, seg + n, layer, shape, init)
    return w


def _attention(dims: Dims, h, w, mode: str):
    """Latent attention, expanded, over the whole sequence h (S, d)."""
    s, nh = h.shape[0], dims.heads
    q = _mm(h, w["wq"], mode).reshape(s, nh, dims.nope + dims.rope)
    kv = _mm(h, w["wkv_a"], mode)
    c = _rnd(_rms_norm(kv[:, :dims.rank], w["attn_kv_norm"], dims.eps), mode)
    k_rope = _rope(kv[:, None, dims.rank:], dims.theta)           # (S, 1, dr)
    kvb = _mm(c, w["wkv_b"], mode).reshape(s, nh, dims.nope + dims.dv)
    q = _rnd(jnp.concatenate([q[..., :dims.nope],
                              _rope(q[..., dims.nope:], dims.theta)], -1),
             mode)
    k = _rnd(jnp.concatenate([kvb[..., :dims.nope],
                              jnp.broadcast_to(k_rope, (s, nh, dims.rope))],
                             -1), mode)
    v = kvb[..., dims.nope:]
    scale = (dims.nope + dims.rope) ** -0.5
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = jnp.einsum("qhd,shd->hqs", qb, k, precision=HI) * scale
        causal = (jnp.arange(s)[None, :]
                  <= q0 + jnp.arange(qb.shape[0])[:, None])
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = _rnd(jax.nn.softmax(sc, axis=-1), mode)
        outs.append(jnp.einsum("hqs,shd->qhd", pr, v, precision=HI))
    att = _rnd(jnp.concatenate(outs).reshape(s, nh * dims.dv), mode)
    return _mm(att, w["wo"], mode)


def _swiglu(h, w1, w3, w2, mode: str):
    m = _rnd(jax.nn.silu(_mm(h, w1, mode)) * _mm(h, w3, mode), mode)
    return _mm(m, w2, mode)


def _moe(dims: Dims, h, w, mode: str):
    """The held experts' part of the routed output, plus the shared ones."""
    scores = _rnd(jax.nn.sigmoid(jnp.matmul(h, w["router"], precision=HI)),
                  mode)
    _, idx = jax.lax.top_k(scores + w["bias"], dims.top_k)
    gates = jnp.take_along_axis(scores, idx, -1)
    gates = gates / jnp.sum(gates, -1, keepdims=True) * dims.scale
    weight = jnp.sum(jax.nn.one_hot(idx, dims.held) * gates[..., None], 1)
    routed = 0.0
    for e in range(dims.held):
        out = _swiglu(h, w["moe_w1"][e], w["moe_w3"][e], w["moe_w2"][e], mode)
        routed = routed + weight[:, e:e + 1] * out
    return routed + _swiglu(h, w["moe_shared_w1"], w["moe_shared_w3"],
                            w["moe_shared_w2"], mode)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer(dims: Dims, x, w, moe: bool, mode: str):
    """One decoder layer over the whole padded sequence x (S, d)."""
    h = _rnd(_rms_norm(x, w["ln1"], dims.eps), mode)
    x = _rnd(x + _attention(dims, h, w, mode), mode)
    h = _rnd(_rms_norm(x, w["ln2"], dims.eps), mode)
    if moe:
        y = _moe(dims, h, w, mode)
    else:
        y = _swiglu(h, w["ffn_w1"], w["ffn_w3"], w["ffn_w2"], mode)
    return _rnd(x + y, mode)


@functools.partial(jax.jit, static_argnums=(0,))
def _draw_globals(dims: Dims, key):
    emb = mw.dense_leaf(key, "embed/tokens", 0, (dims.vocab, dims.d),
                        jnp.bfloat16, dims.init)
    norm = mw.dense_leaf(key, "embed/final_norm", 0, (dims.d,), jnp.float32,
                         dims.init)
    head = mw.dense_leaf(key, "embed/lm_head", 0, (dims.d, dims.vocab),
                         jnp.bfloat16, dims.init)
    return emb, norm, head


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(dims: Dims, x, norm, head, mode: str):
    h = _rnd(_rms_norm(x, norm, dims.eps), mode)
    return jnp.matmul(h, _rnd(head.astype(jnp.float32).T, mode).T,
                      precision=HI)


def logits_at(conf: dict, seed: int, sequences, read, modes=("ref",)):
    """Logits of the reference at chosen positions of whole sequences.

    ``sequences``: list of 1-D int token arrays; ``read``: for each, the
    positions whose next-token logits are wanted.  Returns, per mode, a
    list of (len(read[i]), vocab) float32 numpy arrays.  Each sequence runs
    alone, padded at its end to a multiple of ``SEQ_BUCKET`` (causal
    attention keeps the pads out of every real position; a pad's routing
    touches only the pad's own row).
    """
    dims = Dims.of(conf)
    key = mw.root_key(seed)
    out = {m: [] for m in modes}
    with jax.default_matmul_precision("highest"):
        emb, norm, head = _draw_globals(dims, key)
        xs = {}
        for m in modes:
            for i, seq in enumerate(sequences):
                s_pad = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
                toks = np.zeros((s_pad,), np.int32)
                toks[:len(seq)] = seq
                xs[m, i] = _rnd(emb[jnp.asarray(toks)].astype(jnp.float32), m)
        for layer in range(dims.layers):
            moe = layer >= dims.dense
            w = _draw_layer(dims, key,
                            jnp.int32(layer - dims.dense if moe else layer),
                            moe)
            for (m, i), x in xs.items():
                xs[m, i] = _layer(dims, x, w, moe, m)
            del w
        for (m, i), x in sorted(xs.items(), key=lambda kv: kv[0]):
            rows = x[jnp.asarray(np.asarray(read[i], np.int32))]
            out[m].append(np.asarray(_head(dims, rows, norm, head, m)))
    return out
