"""Pallas TPU kernel: grouped SwiGLU FFN over the held experts of a MoE layer.

The rows are the (token, choice) pairs routed to the experts this layer
holds, sorted by expert, each expert's run padded to whole tiles of ``tm``
rows (``models/moe.py::routed_grouped`` builds the layout), so every tile
belongs to one expert.  The grid walks the tiles; the tile's expert, read
from a scalar-prefetched table, picks the weight blocks:

  y[tile] = (silu(x[tile] @ w1[e]) * (x[tile] @ w3[e])) @ w2[e]

with both products accumulated in f32 and the hidden cast back to the
weights' type before the down projection.  The weights come layer-stacked,
(L, E, d, ff), with the layer's index scalar-prefetched too: a scan over
layers that sliced them would copy the whole layer's experts before the
kernel ran.  Each weight block is a whole expert (d x ff) of that layer, so
consecutive tiles of one expert keep the same block index and the pipeline
fetches that expert's weights once, from HBM, inside the kernel; a tile
past the last used one repeats the last expert and computes nothing.
Experts that no pair chose are never read.  That is the layer's floor: the
touched experts' weights moved once.

VMEM: the three weight blocks, double-buffered (6·d·ff·itemsize: 34.6 MB
at Moonlight's d 2048, ff 1408 in bf16), the x and y tiles and the f32
hidden; the kernel asks for that much scoped VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _kernel(layer_ref, tile_expert_ref, tile_used_ref, x_ref, w1_ref,
            w3_ref, w2_ref, y_ref):
    t = pl.program_id(0)

    @pl.when(tile_used_ref[t] == 1)
    def _compute():
        x = x_ref[...]
        h = (jax.nn.silu(jnp.dot(x, w1_ref[0, 0], preferred_element_type=F32))
             * jnp.dot(x, w3_ref[0, 0], preferred_element_type=F32))
        y_ref[...] = jnp.dot(h.astype(w2_ref.dtype), w2_ref[0, 0],
                             preferred_element_type=F32)


def vmem_bytes(tm: int, d: int, ff: int, itemsize: int) -> int:
    """Scoped VMEM the kernel asks for: double-buffered weight blocks and
    x/y tiles, the f32 hidden, and 25% room."""
    need = (2 * 3 * d * ff * itemsize + 2 * tm * d * (itemsize + 4)
            + 2 * tm * ff * 4)
    return int(need * 1.25)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_expert_ffn(x, w1, w3, w2, layer, tile_expert, tile_used, *, tm: int,
                   interpret: bool = False) -> jnp.ndarray:
    """x (n_tiles·tm, d) rows sorted by expert; w1, w3 (L, E, d, ff), w2
    (L, E, ff, d) layer-stacked; layer (1,) int32; tile_expert, tile_used
    (n_tiles,) int32.  Returns (n_tiles·tm, d) f32; rows of unused tiles
    are left unwritten."""
    n, d = x.shape
    ff = w1.shape[-1]

    def expert(t, lyr, te, tu):
        return (lyr[0], te[t], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tm,),
        in_specs=[
            pl.BlockSpec((tm, d), lambda t, lyr, te, tu: (t, 0)),
            pl.BlockSpec((1, 1, d, ff), expert),
            pl.BlockSpec((1, 1, d, ff), expert),
            pl.BlockSpec((1, 1, ff, d), expert),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda t, lyr, te, tu: (t, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(tm, d, ff, w1.dtype.itemsize)),
        name="moe_expert_ffn",
        interpret=interpret,
    )(layer, tile_expert, tile_used, x, w1, w3, w2)
