"""Logical-axis -> mesh-axis rules and sharding helpers.

Mesh axes (launch/mesh.py):
  pod    — inter-pod (DCN-class links); pure data parallelism, and the axis
           the 1-bit gradient compression targets.
  data   — intra-pod batch + FSDP (ZeRO-3 param/optimizer sharding).
  model  — tensor parallel (heads / d_ff / vocab) and expert parallel.

Rules are per-call overridable — the §Perf hillclimbs swap them without
touching model code.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

DEFAULT_RULES = {"fsdp": "data", "tp": "model", "ep": "model"}


def shard_map(f, mesh: Mesh, in_specs, out_specs, manual_axes: set[str]):
    """``jax.shard_map`` manual over ``manual_axes`` only (the other mesh
    axes stay automatic)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(manual_axes),
                         check_vma=False)


def pxor(x: jax.Array, axis_name: str) -> jax.Array:
    """Bitwise-XOR all-reduce over a named mesh axis (inside shard_map).

    XOR has no built-in collective, so all-gather the per-device values and
    fold them locally, pairwise (log2-depth arithmetic — the *communication*
    is the one all-gather, ``devices`` copies of ``x`` per device).  The
    sharded engine only reduces ``digest_width``-word digests (512 bytes
    each at the default width), so digests are the entire cross-device
    payload of a sharded digest — the buffer itself never moves.
    """
    g = jax.lax.all_gather(x, axis_name, axis=0)      # (devices, ...)
    while g.shape[0] > 1:
        half = g.shape[0] // 2
        folded = g[:half] ^ g[half:2 * half]
        g = (folded if g.shape[0] % 2 == 0
             else jnp.concatenate([folded, g[2 * half:]], axis=0))
    return g[0]


def batch_axes(mesh: Mesh, global_batch: int):
    """Largest prefix of (pod, data) that divides the batch."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    chosen = []
    div = 1
    for a in axes:
        n = mesh.shape[a]
        if global_batch % (div * n) == 0:
            chosen.append(a)
            div *= n
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def data_specs(mesh: Mesh, global_batch: int, has_ctx: bool = False):
    """PartitionSpecs for a train/prefill batch dict."""
    ba = batch_axes(mesh, global_batch)
    specs = {"tokens": P(ba, None), "labels": P(ba, None)}
    if has_ctx:
        specs["ctx"] = P(ba, None, None)
    return specs
