"""Seeded random weights, made on the device in the type they are served in.

Every value is a function of ``(seed, leaf name, layer index)`` alone, and
of the slice's index where a leaf stacks slices along further axes (an
expert axis), so the whole served tree comes out of one compiled program,
and the plain reference can draw any single layer, or any single expert of
a layer, again on its own, in the same values, without anything the system
under test made.

Leaves are named by their path in the system's served tree
(``embed/tokens``, ``seg0_attn/attn_wq``, ``seg1_moe/moe_w2``, ...), and
each takes one rule of the config file's ``init`` section by its base name
(the part after the last ``/``).  The file's ``init.roles`` maps base names
to the name of one of its rules, or to ``"ones"``; a name it does not map
takes the default below, and a name in neither is refused, naming the leaf.
The defaults, for the qwen3 family's served tree:

  embed       ``tokens``: token embedding; ``sign``: every entry
              +-scale, or ``normal`` with std ``scale``
  lm_head     ``lm_head``: ``normal`` with std ``scale / sqrt(fan_in)``
  linear_in   ``attn_wq/wk/wv``, ``ffn_w1/w3``: std ``scale / sqrt(fan_in)``
  attn_out    ``attn_wo``, which writes attention into the residual
  ffn_out     ``ffn_w2``, which writes the FFN into the residual
  ones        ``final_norm``, ``ln1``, ``ln2``, ``attn_q_norm``,
              ``attn_k_norm``

Fan-in is a slice's first axis (of its two).  Axes of a leaf before its last
two (besides the layer axis of a stacked segment) are drawn one index at a
time, each slice keyed by ``(name, layer, index)``, with the index running
over those axes in row-major order: a tree that holds fewer experts holds
the same values in the experts it keeps.

A binarized (``+xnor``) projection is drawn directly in its packed form
``(..., N, K/32)``: random sign planes, LSB-first along K (bit 1 = +1, the
layout the packed serve path documents), and per-output-channel ``beta``,
the mean |w| of the Gaussian it stands for, times a factor in [0.8, 1.2];
leading axes are drawn one index at a time as above.
"""

from __future__ import annotations

import math
import zlib

import numpy as np

import jax
import jax.numpy as jnp

WORD = 32
_ROLES = {
    "tokens": "embed", "lm_head": "lm_head",
    "attn_wq": "linear_in", "attn_wk": "linear_in", "attn_wv": "linear_in",
    "ffn_w1": "linear_in", "ffn_w3": "linear_in",
    "attn_wo": "attn_out", "ffn_w2": "ffn_out",
}
_ONES = ("final_norm", "ln1", "ln2", "attn_q_norm", "attn_k_norm")


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (64 bits and more)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def _leaf_key(key, name: str, layer, index=None):
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    return k if index is None else jax.random.fold_in(k, index)


def role(name: str, init: dict) -> str:
    """The rule leaf ``name`` is drawn by: ``"ones"`` or a key of ``init``."""
    base = name.split("/")[-1]
    mapped = init.get("roles", {})
    if base in mapped:
        r = mapped[base]
    elif base in _ONES:
        r = "ones"
    elif base in _ROLES:
        r = _ROLES[base]
    else:
        raise KeyError(f"no init rule for weight {name!r}")
    if r != "ones" and (r == "roles" or r not in init):
        raise KeyError(f"weight {name!r}: init has no rule {r!r}")
    return r


def _per_index(draw, lead: tuple):
    """``draw(i)`` for every index of the axes ``lead``, row-major, stacked
    along those axes."""
    out = jax.vmap(draw)(jnp.arange(math.prod(lead)))
    return jax.tree.map(lambda a: a.reshape(*lead, *a.shape[1:]), out)


def dense_leaf(key, name: str, layer, shape, dtype, init: dict, index=None):
    """One layer's slice of a float leaf; axes before the last two are
    drawn one index at a time (``index`` draws that one slice alone)."""
    r = role(name, init)
    if r == "ones":
        return jnp.ones(shape, dtype)
    if len(shape) > 2:
        return _per_index(lambda i: dense_leaf(key, name, layer, shape[-2:],
                                               dtype, init, i), shape[:-2])
    rule = init[r]
    k = _leaf_key(key, name, layer, index)
    if rule["dist"] == "sign":
        s = jnp.where(jax.random.bernoulli(k, 0.5, shape), 1.0, -1.0)
        return (s * rule["scale"]).astype(dtype)
    std = rule["scale"] / (1.0 if r == "embed" else math.sqrt(shape[0]))
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def packed_leaf(key, name: str, layer, k_in: int, n_out: int, init: dict,
                index=None):
    """One layer's (or with ``index``, one slice's) packed projection:
    ((N, K/32) uint32 planes, (N,) beta)."""
    rule = init[role(name, init)]
    lk = _leaf_key(key, name, layer, index)
    planes = jax.random.bits(jax.random.fold_in(lk, 0), (n_out, k_in // WORD),
                             jnp.uint32)
    spread = jax.random.uniform(jax.random.fold_in(lk, 1), (n_out,),
                                jnp.float32, 0.8, 1.2)
    beta = rule["scale"] * math.sqrt(2.0 / math.pi) / math.sqrt(k_in) * spread
    return planes, beta


def _name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def logical_shape(name: str, shape, vocab: int) -> tuple:
    """The drawn shape of a leaf: the served tree pads the vocabulary axis
    (rows of the embedding, columns of the head) with rows never read, and
    those pads are zeros, not draws."""
    base = name.split("/")[-1]
    if base == "tokens":
        return (vocab,) + tuple(shape[1:])
    if base == "lm_head":
        return tuple(shape[:-1]) + (vocab,)
    return tuple(shape)


def make_served(seed: int, abstract, init: dict, vocab: int,
                packed_cls=None):
    """The served tree shaped like ``abstract``, drawn in one compiled call.

    ``abstract`` is the system's abstract served tree (ShapeDtypeStructs,
    and ``packed_cls`` nodes for packed projections); leaves under ``seg*``
    carry a leading layer axis.
    """
    def is_packed(x):
        return packed_cls is not None and isinstance(x, packed_cls)

    def build(key):
        def one(path, leaf):
            name = _name(path)
            stacked = name.startswith("seg")
            if is_packed(leaf):
                n_out, k_in = leaf.pb.shape[-2], leaf.k
                if k_in % WORD:
                    raise ValueError(f"{name}: K={k_in} is not whole words")
                lead = leaf.pb.shape[1 if stacked else 0:-2]

                def fn(l):
                    if not lead:
                        return packed_leaf(key, name, l, k_in, n_out, init)
                    return _per_index(lambda i: packed_leaf(
                        key, name, l, k_in, n_out, init, i), lead)
                if stacked:
                    pb, beta = jax.vmap(fn)(jnp.arange(leaf.pb.shape[0]))
                else:
                    pb, beta = fn(0)
                return packed_cls(pb, beta, k_in)
            shape = leaf.shape[1:] if stacked else leaf.shape
            drawn = logical_shape(name, shape, vocab)
            fn = lambda l: dense_leaf(key, name, l, drawn, leaf.dtype, init)
            if stacked:
                return jax.vmap(fn)(jnp.arange(leaf.shape[0]))
            out = fn(0)
            return jnp.pad(out, [(0, a - b) for a, b in zip(shape, drawn)])
        return jax.tree_util.tree_map_with_path(one, abstract,
                                                is_leaf=is_packed)
    return jax.jit(build)(root_key(seed))
