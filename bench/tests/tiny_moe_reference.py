"""A plain reference of the system's ``moe`` decoder, for the CPU tests of
the harness only: it shows that a configuration whose leaves the file maps
in ``init.roles`` is drawn through ``model_weights`` by a reference that
imports nothing of the system.

Layers ``0 .. first_k_dense_replace - 1`` are dense (the qwen3 reference's
block without q/k norms), the rest route each token by a softmax over the
router's logits to its ``num_experts_per_tok`` best experts, gates
renormalised over them, with no capacity (no token dropped).  Binarized
configurations binarize the attention projections and the dense FFN; the
router and the experts stay in float.  Only the ``ref`` precision.
"""

from __future__ import annotations

import pathlib

import numpy as np

import jax
import jax.numpy as jnp

import harness
import model_weights as mw

q3 = harness.load_module(pathlib.Path(__file__).with_name("qwen3.py"),
                         "bench_reference_qwen3")
HI = q3.HI
BUCKET = 64


def _sizes(conf: dict) -> dict:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return dict(d=d, heads=h, kv=conf["num_key_value_heads"],
                dh=conf["head_dim"], ff=conf["intermediate_size"],
                ffe=conf["moe_intermediate_size"],
                experts=conf["n_routed_experts"],
                top_k=conf["num_experts_per_tok"],
                dense=conf["first_k_dense_replace"])


def _proj(conf, key, name, layer, k_in, n_out):
    """x -> x @ W for one projection, binarized as the config says."""
    init = conf["init"]
    if conf["binarized"]:
        planes, beta = mw.packed_leaf(key, name, layer, k_in, n_out, init)
        signs = q3._unpack_signs(planes, k_in).astype(jnp.float32)

        def lin(x):
            sx = jnp.where(x >= 0, 1.0, -1.0)
            dots = jnp.matmul(sx, signs.T, precision=HI)
            return dots * jnp.mean(jnp.abs(x), -1)[:, None] * beta[None, :]
        return lin
    w = mw.dense_leaf(key, name, layer, (k_in, n_out), jnp.bfloat16,
                      init).astype(jnp.float32)
    return lambda x: jnp.matmul(x, w, precision=HI)


def _attention(conf, sz, key, seg, layer, h):
    s = h.shape[0]
    g = sz["heads"] // sz["kv"]
    shapes = {"wq": sz["heads"] * sz["dh"], "wk": sz["kv"] * sz["dh"],
              "wv": sz["kv"] * sz["dh"]}
    q, k, v = (_proj(conf, key, f"{seg}/attn_{n}", layer, sz["d"], w)(h)
               for n, w in shapes.items())
    q = q3._rope(q.reshape(s, sz["heads"], sz["dh"]), conf["rope_theta"])
    k = q3._rope(k.reshape(s, sz["kv"], sz["dh"]), conf["rope_theta"])
    v = v.reshape(s, sz["kv"], sz["dh"])
    q = q.reshape(s, sz["kv"], g, sz["dh"])
    sc = jnp.einsum("qkgd,skd->kgqs", q, k, precision=HI) * sz["dh"] ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    att = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(sc, -1), v,
                     precision=HI).reshape(s, sz["heads"] * sz["dh"])
    return _proj(conf, key, f"{seg}/attn_wo", layer, sz["heads"] * sz["dh"],
                 sz["d"])(att)


def _moe(conf, sz, key, seg, layer, h):
    init = conf["init"]
    e, d, ffe = sz["experts"], sz["d"], sz["ffe"]
    router = mw.dense_leaf(key, f"{seg}/moe_router", layer, (d, e),
                           jnp.float32, init)
    w = {n: mw.dense_leaf(key, f"{seg}/moe_{n}", layer, shape, jnp.bfloat16,
                          init).astype(jnp.float32)
         for n, shape in (("w1", (e, d, ffe)), ("w3", (e, d, ffe)),
                          ("w2", (e, ffe, d)))}
    probs = jax.nn.softmax(jnp.matmul(h, router, precision=HI), -1)
    gates, idx = jax.lax.top_k(probs, sz["top_k"])
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, e) * gates[..., None], 1)  # (S, E)
    hid = (jax.nn.silu(jnp.einsum("sd,edf->esf", h, w["w1"], precision=HI))
           * jnp.einsum("sd,edf->esf", h, w["w3"], precision=HI))
    out = jnp.einsum("esf,efd->esd", hid, w["w2"], precision=HI)
    return jnp.einsum("se,esd->sd", weight, out, precision=HI)


def _ffn(conf, sz, key, seg, layer, h):
    gate = _proj(conf, key, f"{seg}/ffn_w1", layer, sz["d"], sz["ff"])(h)
    up = _proj(conf, key, f"{seg}/ffn_w3", layer, sz["d"], sz["ff"])(h)
    return _proj(conf, key, f"{seg}/ffn_w2", layer, sz["ff"], sz["d"])(
        jax.nn.silu(gate) * up)


def logits_at(conf: dict, seed: int, sequences, read, modes=("ref",)):
    """As ``qwen3.logits_at``, in the ``ref`` precision alone; each sequence
    is padded at its end to a multiple of ``BUCKET``, which causal
    attention keeps out of every real position."""
    assert tuple(modes) == ("ref",), modes
    sz = _sizes(conf)
    eps, init = conf["rms_norm_eps"], conf["init"]

    @jax.jit
    def forward(key, toks):
        emb = mw.dense_leaf(key, "embed/tokens", 0,
                            (conf["vocab_size"], sz["d"]), jnp.bfloat16, init)
        x = emb[toks].astype(jnp.float32)
        for i in range(conf["num_hidden_layers"]):
            dense = i < sz["dense"]
            seg, layer = (("seg0_attn", i) if dense
                          else ("seg1_moe", i - sz["dense"]))
            ln1, ln2 = (mw.dense_leaf(key, f"{seg}/{n}", layer, (sz["d"],),
                                      jnp.float32, init)
                        for n in ("ln1", "ln2"))
            x = x + _attention(conf, sz, key, seg, layer,
                               q3._rms_norm(x, ln1, eps))
            h = q3._rms_norm(x, ln2, eps)
            x = x + (_ffn if dense else _moe)(conf, sz, key, seg, layer, h)
        norm = mw.dense_leaf(key, "embed/final_norm", 0, (sz["d"],),
                             jnp.float32, init)
        head = mw.dense_leaf(key, "embed/lm_head", 0,
                             (sz["d"], conf["vocab_size"]), jnp.bfloat16, init)
        return jnp.matmul(q3._rms_norm(x, norm, eps),
                          head.astype(jnp.float32), precision=HI)

    key = mw.root_key(seed)
    out = []
    for seq, pos in zip(sequences, read):
        toks = np.zeros(-(-len(seq) // BUCKET) * BUCKET, np.int32)
        toks[:len(seq)] = seq
        logits = forward(key, jnp.asarray(toks))
        out.append(np.asarray(logits[np.asarray(pos, np.int32)]))
    return {"ref": out}
