"""Plain reference of the Qwen3 decoder, for deciding ``correct``.

Straight ``jax.numpy`` over one whole sequence, layer by layer, with no
cache, no paging, no batching and no kernel, following the published
description (Qwen3 technical report; the ``Qwen3ForCausalLM`` modelling
code): pre-norm RMSNorm blocks, grouped-query attention with a per-head
RMSNorm on q and k before rotary embeddings (rotate-half form), causal
softmax attention, a SwiGLU FFN, a final RMSNorm and an untied LM head.

With ``binarized`` set in the configuration, every projection of every
layer is an XNOR-Net linear (Rastegari et al., ECCV 2016):
``y = (sign(x) . sign(W)) * mean|x| * beta``, with ``sign(0) = +1``; the
embedding, the norms and the LM head stay in floating point.

Two precisions:

  ``ref``      float32 throughout, matrix products at ``highest``
               precision; the sign products of binarized layers are exact.
  ``fp8``      the control: every stored tensor (residual stream, norm
               outputs, projections, q/k/v, attention weights, FFN
               hidden) and every matrix operand rounded to float8 e4m3,
               scaled per row by its largest magnitude, with float32
               accumulation.  This is what the served model would compute
               one precision step below its bfloat16.

Weights come from the benchmark's own seeded draws
(:mod:`model_weights`), one layer at a time, so the float32 model never
has to fit on the chip at once.
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
LAYER = "seg0_attn/"
PROJ = {"q": "attn_wq", "k": "attn_wk", "v": "attn_wv", "o": "attn_wo",
        "gate": "ffn_w1", "up": "ffn_w3", "down": "ffn_w2"}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference reads from a configuration file (hashable,
    so that each compiled step is reused across layers and calls)."""

    d: int
    heads: int
    kv: int
    dh: int
    ff: int
    layers: int
    vocab: int
    eps: float
    theta: float
    binarized: bool
    init_json: str

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        return cls(conf["hidden_size"], conf["num_attention_heads"],
                   conf["num_key_value_heads"], conf["head_dim"],
                   conf["intermediate_size"], conf["num_hidden_layers"],
                   conf["vocab_size"], conf["rms_norm_eps"],
                   conf["rope_theta"], bool(conf["binarized"]),
                   json.dumps(conf["init"], sort_keys=True))

    @property
    def init(self) -> dict:
        return json.loads(self.init_json)

    def proj_shape(self, p: str) -> tuple[int, int]:
        """(fan_in K, fan_out N) of projection ``p``."""
        d, q, kv, ff = self.d, self.heads * self.dh, self.kv * self.dh, self.ff
        return {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d),
                "gate": (d, ff), "up": (d, ff), "down": (ff, d)}[p]


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
    """x (S, heads, dh) at positions 0..S-1, rotate-half form."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _unpack_signs(planes, k: int):
    """(N, K/32) uint32, LSB-first, bit 1 = +1 -> (N, K) of +-1 (bf16, exact)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (planes[..., None] >> shifts) & jnp.uint32(1)
    return jnp.where(bits.reshape(planes.shape[0], k) == 1, 1.0, -1.0
                     ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(0,))
def _draw_layer(dims: Dims, key, layer):
    """One layer's weights from the benchmark's seeded draws."""
    w = {}
    for p, name in PROJ.items():
        k_in, n_out = dims.proj_shape(p)
        if dims.binarized:
            planes, beta = mw.packed_leaf(key, LAYER + name, layer, k_in,
                                          n_out, dims.init)
            w[p] = (_unpack_signs(planes, k_in), beta)
        else:
            w[p] = mw.dense_leaf(key, LAYER + name, layer, (k_in, n_out),
                                 jnp.bfloat16, dims.init).astype(jnp.float32)
    for n, shape in (("ln1", (dims.d,)), ("ln2", (dims.d,)),
                     ("attn_q_norm", (dims.dh,)), ("attn_k_norm", (dims.dh,))):
        w[n] = mw.dense_leaf(key, LAYER + n, layer, shape, jnp.float32,
                             dims.init)
    return w


def _linear(dims: Dims, x, w, mode: str):
    if dims.binarized:
        signs, beta = w
        sx = jnp.where(x >= 0, 1.0, -1.0).astype(jnp.bfloat16)
        dots = jnp.einsum("sk,nk->sn", sx, signs,
                          preferred_element_type=jnp.float32)
        alpha = jnp.mean(jnp.abs(x), -1)
        if mode == "fp8":
            alpha, beta = _round8(alpha), _round8(beta)
        y = dots * alpha[:, None] * beta[None, :]
    else:
        wt = _rnd(w.T, mode).T                  # per output channel
        y = jnp.matmul(_rnd(x, mode), wt, precision=HI)
    return _rnd(y, mode)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(dims: Dims, x, w, mode: str):
    """One decoder layer over the whole padded sequence x (S, d)."""
    s = x.shape[0]
    g = dims.heads // dims.kv
    h = _rnd(_rms_norm(x, w["ln1"], dims.eps), mode)
    q = _linear(dims, h, w["q"], mode).reshape(s, dims.heads, dims.dh)
    k = _linear(dims, h, w["k"], mode).reshape(s, dims.kv, dims.dh)
    v = _linear(dims, h, w["v"], mode).reshape(s, dims.kv, dims.dh)
    q = _rnd(_rope(_rms_norm(q, w["attn_q_norm"], dims.eps), dims.theta), mode)
    k = _rnd(_rope(_rms_norm(k, w["attn_k_norm"], dims.eps), dims.theta), mode)
    q = q.reshape(s, dims.kv, g, dims.dh)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) * dims.dh ** -0.5
        causal = (jnp.arange(s)[None, :]
                  <= q0 + jnp.arange(qb.shape[0])[:, None])
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = _rnd(jax.nn.softmax(sc, axis=-1), mode)
        outs.append(jnp.einsum("kgqs,skd->qkgd", pr, v, precision=HI))
    att = _rnd(jnp.concatenate(outs).reshape(s, dims.heads * dims.dh), mode)
    x = _rnd(x + _linear(dims, att, w["o"], mode), mode)
    h = _rnd(_rms_norm(x, w["ln2"], dims.eps), mode)
    m = _rnd(jax.nn.silu(_linear(dims, h, w["gate"], mode))
             * _linear(dims, h, w["up"], mode), mode)
    return _rnd(x + _linear(dims, m, w["down"], mode), mode)


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
    attention keeps the pads out of every real position).
    """
    dims = Dims.of(conf)
    key = mw.root_key(seed)
    emb, norm, head = _draw_globals(dims, key)
    out = {m: [] for m in modes}
    xs = {}
    for m in modes:
        for i, seq in enumerate(sequences):
            s_pad = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
            toks = np.zeros((s_pad,), np.int32)
            toks[:len(seq)] = seq
            xs[m, i] = _rnd(emb[jnp.asarray(toks)].astype(jnp.float32), m)
    for layer in range(dims.layers):
        w = _draw_layer(dims, key, jnp.int32(layer))
        for (m, i), x in xs.items():
            xs[m, i] = _layer(dims, x, w, m)
        del w
    for (m, i), x in sorted(xs.items(), key=lambda kv: (kv[0][0], kv[0][1])):
        rows = x[jnp.asarray(np.asarray(read[i], np.int32))]
        out[m].append(np.asarray(_head(dims, rows, norm, head, m)))
    return out
