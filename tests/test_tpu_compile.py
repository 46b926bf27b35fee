"""Compile rehearsal of the main-path Pallas kernels for a TPU v5e chip.

The TPU compiler that ships with jax compiles for a chip that is described
(``v5e:2x2``, device 0) and not attached, and refuses what the chip would
refuse: Mosaic lowerings that do not exist, block shapes that break the
(8, 128) tiling rule, VMEM overruns.  Nothing runs, so these tests say
nothing about results (the interpret-mode tests pin those) or speed.
Shapes are qwen3-4b's: d 2560, ff 9728, 8 KV heads of 4 query heads,
dh 128, with the serve geometry of ``chip_smoke.py`` (8 slots, 16-token
blocks, 50-block tables).  Each test asserts that the kernel is in the
compiled program (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D, FF = 2560, 9728
KV, G, DH = 8, 4, 128
SLOTS, BS, W = 8, 16, 50
N_BLOCKS = 1 + SLOTS * W
STREAM_WORDS = 64 * 2**20           # a 256 MiB uint32 buffer
KW_D, KW_FF = D // 32, FF // 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_text(one_chip, monkeypatch):
    """Compile ``fn`` for the described chip; returns the compiled text.

    The persistent compilation cache is off around these compiles (an entry
    written for a described chip cannot be read back without one), and the
    kernel dispatch is held to compiled Pallas whatever REPRO_KERNEL_IMPL
    the run was started with.
    """
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ops, "_FORCE", "")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("pool", [jnp.bfloat16, jnp.int8], ids=["bf16", "i8"])
def test_paged_decode_attention_compiles(compile_text, pool):
    def fn(q, ck, cv, table, pos):
        return ops.paged_decode(q, ck, cv, table, pos, scale=DH**-0.5,
                                impl="pallas")
    text = compile_text(fn, ((SLOTS, KV, G, DH), jnp.bfloat16),
                        ((N_BLOCKS, KV, BS, DH), pool),
                        ((N_BLOCKS, KV, BS, DH), pool),
                        ((SLOTS, W), jnp.int32), ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", [(D, FF), (FF, D)], ids=["up", "down"])
@pytest.mark.parametrize("m", [SLOTS, 32, 128],
                         ids=["decode", "prefill_chunk", "rows_128"])
def test_xnor_linear_fused_compiles(compile_text, m, k, n):
    def fn(x, pb, beta):
        return ops.xnor_linear_fused(x, pb, beta, k, impl="pallas")
    text = compile_text(fn, ((m, k), jnp.bfloat16), ((n, k // 32), jnp.uint32),
                        ((n,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(SLOTS, D), (FF, D), (D, FF)],
                         ids=["activations", "w_up", "w_down"])
def test_binarize_compiles(compile_text, shape):
    text = compile_text(lambda x: ops.binarize(x, impl="pallas"),
                        (shape, jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kw,n", [(KW_D, FF), (KW_FF, D)], ids=["up", "down"])
def test_xnor_matmul_compiles(compile_text, kw, n):
    text = compile_text(
        lambda a, b: ops.xnor_matmul(a, b, kw * 32, impl="pallas"),
        ((SLOTS, kw), jnp.uint32), ((n, kw), jnp.uint32))
    assert "tpu_custom_call" in text


def test_digest_compiles(compile_text):
    text = compile_text(lambda w: ops.digest(w, impl="pallas"),
                        ((STREAM_WORDS,), jnp.uint32))
    assert "tpu_custom_call" in text


def test_stream_cipher_compiles(compile_text):
    def fn(w, key):
        return ops.stream_cipher(w, key, counter=7, impl="pallas")
    text = compile_text(fn, ((STREAM_WORDS,), jnp.uint32),
                        ((2,), jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["xor", "xnor"])
def test_bulk_op_compiles(compile_text, op):
    text = compile_text(lambda a, b: ops.bulk_op(a, b, op, impl="pallas"),
                        ((STREAM_WORDS,), jnp.uint32),
                        ((STREAM_WORDS,), jnp.uint32))
    assert "tpu_custom_call" in text



def test_decode_step_names_its_kernels_and_scopes(compile_text, one_chip,
                                                  monkeypatch):
    """The served decode step at qwen3-4b+xnor widths (two layers): each
    kernel in it is named by its ``pallas_call`` name, which for the two it
    holds is the name the benchmark's readers match, and its ops carry the
    model scopes in their metadata."""
    import dataclasses
    import re

    from repro import configs
    from repro.models import lm
    from repro.serve import engine

    monkeypatch.setattr(ops, "_FORCE", "pallas")
    cfg = dataclasses.replace(configs.get("qwen3-4b+xnor"), n_layers=2)
    s_max = W * BS

    def fn(params, tokens, state, tables, active, key, seeds):
        logits, state = lm.paged_decode_step(cfg, params, tokens, state,
                                             tables, active=active)
        return engine._sample_tokens(cfg, logits, key, seeds, 0.0), state

    specs = (
        lm.packed_abstract_params(cfg),
        jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
        lm.paged_decode_state_spec(cfg, SLOTS, s_max, n_blocks=N_BLOCKS,
                                   block_size=BS),
        {c: jax.ShapeDtypeStruct((SLOTS, w), jnp.int32) for c, w in
         lm.paged_table_widths(cfg, s_max, BS, cfg.prefill_chunk).items()},
        jax.ShapeDtypeStruct((SLOTS,), jnp.bool_),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), specs)
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = set(re.findall(r"%([a-z_]+)[.\d]* = \S+ custom-call\(", text))
    assert kernels == {"paged_decode_attention", "xnor_linear_fused"}
    scopes = set(re.findall(r'op_name="[^"]*/(embed|attn\.kv_write|attn\.core'
                            r'|mlp|head|sample)/', text))
    assert scopes == {"embed", "attn.kv_write", "attn.core", "mlp", "head",
                      "sample"}
