"""Binary-dense classifier blocks: the paper's XNOR-CNN as a registered
block kind (Fig. 1(c) / §VI, Fig. 6 workload).

``bindense`` is the first block kind registered *outside* ``blocks.py`` —
the registry's proof of composability (DESIGN.md §16).  It is an XNOR-Net
residual MLP block conditioned on an image context:

  g  = W_ctx · mean(ctx)          full precision (XNOR-Net first-layer rule)
  u  = XNOR(W_up  · (norm(x)+g))  binary weights+activations — the popcount
  y  = XNOR(W_down· relu(u))      GEMM the paper's CiM array executes
  x' = x + y

Its decode state is the third layout the contracts name: *ctx-derived* —
a pure function of the request's context, held dense per slot (like
cross-attn ctx_kv) so decode never needs the raw image resident.  No
sequential state at all, so fwd/decode/chunk agree token-for-token and
the kind is trivially chunk-exact.

The module also provides the classifier-as-generation plumbing used by
:class:`repro.serve.workloads.ClassifierService`: synthetic stripe images
(the task from ``examples/xnor_cnn_classifier.py``), image -> ctx-patch
embedding, and end-to-end training of the LM-shaped model so a class id
is literally the argmax token (class ids are vocab ids; one query token
prompts the prediction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.ctx import constrain
from repro.models import layers
from repro.models.blocks import PagedLayout
from repro.models.params import ParamDef
from repro.models.registry import BlockContract, register

# vocab layout of the classifier head: class ids are token ids, and the
# one-token prompt is a reserved query token (never a valid class)
N_CLASSES = 2
QUERY_TOKEN = N_CLASSES
VOCAB = N_CLASSES + 2  # classes + query + one spare


def _norm_def(cfg, n):
    return ParamDef((n, cfg.d_model), (None, None), jnp.float32, init="ones")


@register
class BinDenseBlock(PagedLayout):
    """Stateless-in-sequence binary MLP block gated by pooled image ctx."""

    contract = BlockContract("bindense", per_slot_state=True,
                             prefix_shareable=True)

    @classmethod
    def defs(cls, cfg, n):
        d, ff = cfg.d_model, cfg.d_ff
        return {
            "ln1": _norm_def(cfg, n),
            # ctx projection stays full precision: the image enters the
            # network here (XNOR-Net keeps first/last layers fp)
            "w_ctx": ParamDef((n, d, d), (None, "fsdp", "tp"), cfg.dtype),
            "w_up": ParamDef((n, d, ff), (None, "fsdp", "tp"), cfg.dtype,
                             binarize=True),
            "w_down": ParamDef((n, ff, d), (None, "tp", "fsdp"), cfg.dtype,
                               binarize=True),
        }

    @classmethod
    def _gate(cls, cfg, p, ctx, batch):
        """(B, 1, d) ctx-derived gate — the block's whole decode state."""
        if ctx is None:
            return jnp.zeros((batch, 1, cfg.d_model), cfg.dtype)
        pooled = jnp.mean(ctx.astype(jnp.float32), axis=1, keepdims=True)
        return layers.linear(pooled.astype(cfg.dtype),
                             p["w_ctx"]).astype(cfg.dtype)

    @classmethod
    def _mlp(cls, cfg, p, x, g):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        u = layers.linear(h + g, p["w_up"], cfg.quant)
        u = constrain(u, "batch", None, "tp")
        y = layers.linear(jax.nn.relu(u), p["w_down"], cfg.quant)
        return x + constrain(y, "batch", None, None)

    @classmethod
    def fwd(cls, cfg, p, x, ctx, opts):
        g = cls._gate(cfg, p, ctx, x.shape[0])
        x = cls._mlp(cfg, p, x, g)
        return x, jnp.float32(0.0), (g if opts.want_state else None)

    @classmethod
    def decode(cls, cfg, p, x, state, pos, ctx, table=None, valid=None):
        return cls._mlp(cfg, p, x, state), state

    @classmethod
    def chunk(cls, cfg, p, x, state, pos0, valid, n_valid, ctx, table):
        # no cross-token flow: padded positions only produce unread rows,
        # and the ctx-derived state is position-independent — chunk-exact
        g = cls._gate(cfg, p, ctx, x.shape[0])
        return cls._mlp(cfg, p, x, g), g

    @classmethod
    def state_spec(cls, cfg, batch, s_max, abstract):
        shp = (batch, 1, cfg.d_model)
        if abstract:
            return jax.ShapeDtypeStruct(shp, cfg.dtype)
        return jnp.zeros(shp, cfg.dtype)

    @classmethod
    def state_pspec(cls, cfg, ba, kv_shard: str = "heads", tp_size: int = 16):
        from jax.sharding import PartitionSpec as P
        return P(ba, None, "model")


# ---------------------------------------------------------------------------
# classifier-as-generation plumbing
# ---------------------------------------------------------------------------

def synthetic_images(key, n: int, side: int = 16):
    """Two-class stripe task from examples/xnor_cnn_classifier.py:
    vertical vs horizontal stripes + noise -> ((n, side, side), (n,))."""
    k1, k2 = jax.random.split(key)
    y = jax.random.bernoulli(k1, 0.5, (n,)).astype(jnp.int32)
    xs = jnp.linspace(-1, 1, side)
    vert = jnp.sign(jnp.sin(8 * xs))[None, :].repeat(side, 0)
    horz = vert.T
    base = jnp.where(y[:, None, None] == 1, vert[None], horz[None])
    x = base + 0.8 * jax.random.normal(k2, (n, side, side))
    return x, y


def image_ctx(cfg, images) -> np.ndarray:
    """(N, H, W) images -> (N, n_ctx_tokens, d_model) patch embeddings:
    contiguous pixel bands, no learned patchifier (the fp w_ctx projection
    inside each block is the learned part)."""
    imgs = np.asarray(images, np.float32)
    n = imgs.shape[0]
    flat = imgs.reshape(n, -1)
    want = cfg.n_ctx_tokens * cfg.d_model
    if flat.shape[1] != want:
        raise ValueError(
            f"image has {flat.shape[1]} pixels; arch {cfg.name} expects "
            f"n_ctx_tokens*d_model = {cfg.n_ctx_tokens}*{cfg.d_model} = {want}")
    return flat.reshape(n, cfg.n_ctx_tokens, cfg.d_model)


def train_classifier(cfg, *, steps: int = 150, lr: float = 0.1,
                     n_train: int = 512, seed: int = 0):
    """Train the LM-shaped classifier end-to-end (STE through the binary
    layers) on the stripe task.  Returns (params, train_accuracy).

    The model is queried exactly the way it is served: one QUERY_TOKEN
    prompt, image as ctx, class = argmax over the full vocab at the last
    position — so training also suppresses the non-class token ids and
    greedy serve-time sampling emits a class id.
    """
    from repro.models import lm  # deferred: lm imports the block registry

    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    imgs, y = synthetic_images(jax.random.PRNGKey(seed + 1), n_train)
    ctx = jnp.asarray(image_ctx(cfg, imgs))
    tokens = jnp.full((n_train, 1), QUERY_TOKEN, jnp.int32)

    def loss_fn(p):
        logits, _ = lm.forward(cfg, p, tokens, ctx)
        logp = jax.nn.log_softmax(logits[:, -1, :cfg.vocab]
                                  .astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree.map(lambda w, gw: w - lr * gw, p, g), l

    for _ in range(steps):
        params, _ = step(params)

    logits, _ = lm.forward(cfg, params, tokens, ctx)
    acc = float(jnp.mean(
        jnp.argmax(logits[:, -1, :cfg.vocab], -1) == y))
    return params, acc
