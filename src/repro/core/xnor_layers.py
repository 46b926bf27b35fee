"""XNOR-Net style binary layers (the paper's §VI application, as framework
first-class quantization).

Semantics follow XNOR-Net (Rastegari et al., ECCV'16, [34] in the paper):

  y = ( sign(x) . sign(W)^T ) * alpha_x * beta_w
      alpha_x = mean(|x|)  per input row (the paper's K map, collapsed to
                per-token for LM linears),
      beta_w  = mean(|W|)  per output channel.

Two execution modes:

* ``packed=False`` (training): float-domain straight-through-estimator —
  differentiable, used inside ``train_step``.  sign() forward, clipped
  identity backward (grads flow through alpha/beta exactly as in XNOR-Net).
* ``packed=True`` (inference): bit-plane domain — packs both operands and
  runs the XNOR-popcount GEMM kernel.  Bit-exact with the sign semantics of
  the float path.

Router/norm/embedding/lm-head layers are never binarized (XNOR-Net keeps
first/last layers full precision); `models/` enforces that policy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.kernels import ops


@jax.tree_util.register_pytree_node_class
class PackedLinear:
    """A binarized linear's resident serve form: sign bit-planes + scale.

    The float weight matrix is gone — only the packed planes (one bit per
    weight, the CiM array storing binary filters) and the per-output-channel
    XNOR-Net scale survive.  Leading axes are free (models stack per-layer
    weights on a leading axis and ``lax.scan`` slices it off).

      pb    (..., N, Kw) uint32 — sign planes of w.T, packed along K
      beta  (..., N)     f32    — mean(|w|) per output channel
      k     int                 — the true (unpacked) K, kept as static
                                  pytree aux data: the packed planes round K
                                  up to whole words, so shape alone cannot
                                  validate the activation width — dispatch
                                  checks ``x.shape[-1] == k`` instead of
                                  silently mis-correcting the popcount.
    """

    __slots__ = ("pb", "beta", "k")

    def __init__(self, pb, beta, k: int):
        self.pb, self.beta, self.k = pb, beta, k

    def tree_flatten(self):
        return (self.pb, self.beta), self.k

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    def __repr__(self):
        return (f"PackedLinear(pb={self.pb!r}, beta={self.beta!r}, "
                f"k={self.k})")


def xnor_linear(x: jnp.ndarray, w: jnp.ndarray, *, packed: bool = False,
                impl: str = "auto") -> jnp.ndarray:
    """Binary linear: x (..., K) @ w (N, K)^T -> (..., N).

    ``w`` is stored transposed relative to jnp.dot convention (rows are
    output channels) so both operands pack along their last axis.
    """
    n, k = w.shape
    beta = jnp.mean(jnp.abs(w), axis=-1)                      # (N,)
    if packed:
        lead = x.shape[:-1]
        x2 = x.reshape(-1, k)
        alpha = jnp.mean(jnp.abs(x2), axis=-1)                # (M,)
        pa, _ = ops.binarize(x2, impl=impl)
        pb, _ = ops.binarize(w, impl=impl)
        dots = ops.xnor_matmul(pa, pb, valid_k=k, impl=impl)  # (M, N) int32
        y = dots.astype(jnp.float32) * alpha[:, None] * beta[None, :]
        return y.reshape(*lead, n).astype(x.dtype)
    alpha = jnp.mean(jnp.abs(x), axis=-1, keepdims=True)      # (..., 1)
    bx = bitpack.binarize_ste(x)
    bw = bitpack.binarize_ste(w)
    y = jnp.einsum("...k,nk->...n", bx, bw,
                   preferred_element_type=jnp.float32)
    return (y * alpha * beta).astype(x.dtype)


def xnor_linear_prepacked(x: jnp.ndarray, pb: jnp.ndarray, beta: jnp.ndarray,
                          valid_k: int, *, impl: str = "auto") -> jnp.ndarray:
    """Inference with weights already packed offline.

    ``pb``: (N, Kw) uint32, ``beta``: (N,) f32.  The weight matrix never
    exists in float form at serve time — a 16x memory-footprint reduction vs
    bf16 (the CiM array storing binary filters in the paper).

    :func:`ops.fused_mode` selects between the fused single-dispatch kernel
    (binarize + popcount GEMM + alpha/beta epilogue in one pass, DESIGN.md
    §18) and the unfused three-dispatch chain below — the fused path's
    bit-exact-twin reference on ref/interpret backends.
    """
    lead, k = x.shape[:-1], x.shape[-1]
    if k != valid_k:
        # a raise, not an assert: python -O would strip the assert and the
        # popcount correction below would silently be wrong whenever the
        # mismatched widths round to the same packed word count
        raise ValueError(
            f"activation width {k} != packed weight's true K {valid_k}")
    x2 = x.reshape(-1, k)
    if ops.fused_mode() == "kernel":
        y = ops.xnor_linear_fused(x2, pb, beta, valid_k, impl=impl)
        return y.reshape(*lead, pb.shape[0]).astype(x.dtype)
    alpha = jnp.mean(jnp.abs(x2), axis=-1)
    pa, _ = ops.binarize(x2, impl=impl)
    dots = ops.xnor_matmul(pa, pb, valid_k=valid_k, impl=impl)
    y = dots.astype(jnp.float32) * alpha[:, None] * beta[None, :]
    return y.reshape(*lead, pb.shape[0]).astype(x.dtype)


def pack_weights(w: jnp.ndarray, impl: str = "auto"):
    """Offline weight packing: (N, K) float -> ((N, Kw) uint32, (N,) beta)."""
    pb, _ = ops.binarize(w, impl=impl)
    return pb, jnp.mean(jnp.abs(w), axis=-1).astype(jnp.float32)


def pack_linear(w: jnp.ndarray, impl: str = "auto") -> PackedLinear:
    """Pack a model-layout linear weight (possibly layer-stacked).

    ``w``: (..., K, N) in the ``jnp.dot`` convention used by
    :func:`repro.models.layers.linear` (columns are output channels); any
    leading axes are mapped over, so a scanned segment's stacked
    (n_layers, K, N) weight packs to ``PackedLinear((n, N, Kw), (n, N))``.
    """
    if w.ndim < 2:
        raise ValueError(f"pack_linear needs a (..., K, N) matrix, got {w.shape}")
    k = w.shape[-2]
    fn = lambda wi: PackedLinear(*pack_weights(wi.T, impl=impl), k=k)
    for _ in range(w.ndim - 2):
        fn = jax.vmap(fn)
    return fn(w)
