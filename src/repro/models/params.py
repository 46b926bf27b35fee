"""Parameter definition machinery.

Every model declares its parameters once as a pytree of :class:`ParamDef`
(shape + dtype + logical sharding spec + init scale).  Three products derive
from that single declaration:

* ``abstract(defs)``   -> pytree of ShapeDtypeStruct (dry-run lowering —
                          no allocation, the 512-device path),
* ``pspecs(defs)``     -> pytree of jax.sharding.PartitionSpec,
* ``init(defs, key)``  -> real arrays (CPU-scale smoke tests / examples).

Logical axes used in specs (mapped to mesh axes in distributed/sharding.py):
  "fsdp"   — parameter shards over the data axis (ZeRO-3 style)
  "tp"     — tensor-parallel over the model axis (heads / d_ff / vocab)
  "ep"     — expert-parallel over the model axis
  None     — replicated
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class ParamDef(NamedTuple):
    shape: tuple
    spec: tuple             # logical axis per dim ("fsdp"/"tp"/"ep"/None)
    dtype: Any = jnp.float32
    init: str = "normal"    # "normal" | "zeros" | "ones" | "embed"
    scale: float | None = None  # None => 1/sqrt(fan_in)
    binarize: bool = False  # binarizable linear under quant="xnor": packed to
                            # sign-planes for serving (routers/norms/embeddings
                            # /lm-head stay full precision — DESIGN.md §5)


class LayerOf(NamedTuple):
    """One layer's slice of a layer-stacked leaf, handed over whole:
    ``stack[index]``.  A scanned segment gives a kernel that reads only
    parts of a large leaf (the held experts a step touches) the stack and
    the index, since a scan that sliced the leaf would copy the whole
    layer's slice before the kernel ran."""

    stack: Any
    index: Any

    def get(self):
        return self.stack[self.index]


def abstract(defs):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs,
        is_leaf=lambda x: isinstance(x, ParamDef))


def logical_specs(defs):
    return jax.tree.map(lambda d: d.spec, defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def pspecs(defs, rules: dict[str, Any]):
    """Map logical axes to mesh axes per ``rules`` (e.g. {"tp": "model"})."""
    def one(d):
        return P(*(rules.get(a) if a is not None else None for a in d.spec))
    return jax.tree.map(one, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def init(defs, key: jax.Array):
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    out = []
    for d, k in zip(leaves, keys):
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, d.dtype))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, d.dtype))
        else:
            if d.scale is not None:
                s = d.scale
            elif d.init == "embed" or len(d.shape) < 2:
                s = 1.0
            else:
                # stacked-layer weights: fan_in is the second-to-last dim
                s = 1.0 / math.sqrt(d.shape[-2])
            out.append((s * jax.random.normal(k, d.shape)).astype(d.dtype))
    return jax.tree.unflatten(treedef, out)


def count(defs) -> int:
    return sum(math.prod(d.shape) for d in jax.tree.leaves(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)))


# ---------------------------------------------------------------------------
# packed-weight residency (serve form of binarizable linears)
# ---------------------------------------------------------------------------


def pack(defs, tree, impl: str = "auto"):
    """Replace every ``binarize``-marked float leaf with its packed form.

    The returned tree holds :class:`repro.core.xnor_layers.PackedLinear`
    nodes (uint32 sign planes + f32 beta) where the defs mark binarizable
    linears — the float weights for those leaves are *absent* from the
    result, which is the packed-residency contract: at serve time the
    binary filters only exist as bit-planes (a 16x footprint cut vs bf16).
    All other leaves pass through unchanged.  Idempotent: leaves that are
    already ``PackedLinear`` pass through too, so a tree loaded via
    ``ckpt.restore_packed`` can be handed to consumers that pack by default
    (``ServeEngine``) without double-packing.
    """
    from repro.core import xnor_layers

    def one(d, w):
        if d.binarize and not isinstance(w, xnor_layers.PackedLinear):
            return xnor_layers.pack_linear(w, impl=impl)
        return w
    return jax.tree.map(one, defs, tree,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def pack_abstract(defs):
    """ShapeDtypeStruct tree of :func:`pack` output (restore-`like` trees)."""
    from repro.core import bitpack, xnor_layers

    def one(d):
        if not d.binarize:
            return jax.ShapeDtypeStruct(d.shape, d.dtype)
        *lead, k, n = d.shape
        kw = bitpack.packed_width(k)
        return xnor_layers.PackedLinear(
            jax.ShapeDtypeStruct((*lead, n, kw), jnp.uint32),
            jax.ShapeDtypeStruct((*lead, n), jnp.float32), k=k)
    return jax.tree.map(one, defs, is_leaf=lambda x: isinstance(x, ParamDef))
