"""Compile rehearsal of the main-path Pallas kernels for a TPU v5e chip.

The TPU compiler that ships with jax compiles for a chip that is described
(``v5e:2x2``, device 0) and not attached, and refuses what the chip would
refuse: Mosaic lowerings that do not exist, block shapes that break the
(8, 128) tiling rule, VMEM overruns.  Nothing runs, so these tests say
nothing about results (the interpret-mode tests pin those) or speed.
Shapes are qwen3-4b's: d 2560, ff 9728, 8 KV heads of 4 query heads,
dh 128, with the serve geometry of ``chip_smoke.py`` (8 slots, 16-token
blocks, 50-block tables); and Moonlight-16B-A3B's latent attention (16
heads over one latent of 576 values stored at 640 lanes, 352-block tables, 32 slots) and held
experts (8 of d 2048, ff 1408).  Each test asserts that the kernel is in the
compiled program (``tpu_custom_call``); the served programs' tests also pin
what the compiled step holds of the layer-stacked KV pool.

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
ML_D, ML_FF, ML_E, ML_H, ML_LAT, ML_R = 2048, 1408, 8, 16, 640, 512
ML_SLOTS, ML_W = 32, 352
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
    def fn(q, ck, cv, layer, table, pos):
        return ops.paged_decode(q, ck, cv, layer, table, pos,
                                scale=DH**-0.5, impl="pallas")
    text = compile_text(fn, ((SLOTS, KV, G, DH), jnp.bfloat16),
                        ((36, N_BLOCKS, KV, BS, DH), pool),
                        ((36, N_BLOCKS, KV, BS, DH), pool),
                        ((1,), jnp.int32), ((SLOTS, W), jnp.int32),
                        ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


def test_latent_paged_decode_attention_compiles(compile_text):
    def fn(q, ck, layer, table, pos):
        return ops.paged_decode(q, ck, None, layer, table, pos,
                                scale=192**-0.5, v_dim=ML_R, impl="pallas")
    text = compile_text(fn, ((ML_SLOTS, 1, ML_H, ML_LAT), jnp.bfloat16),
                        ((27, 1024, 1, BS, ML_LAT), jnp.bfloat16),
                        ((1,), jnp.int32), ((ML_SLOTS, ML_W), jnp.int32),
                        ((ML_SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,tm", [(ML_SLOTS * 6, 8), (256 * 6, 32)],
                         ids=["decode", "prefill_chunk"])
def test_moe_expert_ffn_compiles(compile_text, rows, tm):
    n_tiles = -(-(rows + ML_E * (tm - 1)) // tm)

    def fn(x, w1, w3, w2, layer, te, tu):
        return ops.moe_expert_ffn(x, w1, w3, w2, layer, te, tu, tm=tm,
                                  impl="pallas")
    text = compile_text(fn, ((n_tiles * tm, ML_D), jnp.bfloat16),
                        ((26, ML_E, ML_D, ML_FF), jnp.bfloat16),
                        ((26, ML_E, ML_D, ML_FF), jnp.bfloat16),
                        ((26, ML_E, ML_FF, ML_D), jnp.bfloat16),
                        ((1,), jnp.int32), ((n_tiles,), jnp.int32),
                        ((n_tiles,), jnp.int32))
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



@pytest.mark.parametrize("arch,layers,slots,w,kernels", [
    ("qwen3-4b+xnor", 2, SLOTS, W,
     {"paged_decode_attention", "xnor_linear_fused"}),
    ("moonlight-16b-a3b", 2, ML_SLOTS, ML_W,
     {"paged_decode_attention", "moe_expert_ffn"})],
    ids=["qwen3-4b-xnor", "moonlight-16b-a3b"])
def test_decode_step_names_its_kernels_and_scopes(compile_text, one_chip,
                                                  monkeypatch, arch, layers,
                                                  slots, w, kernels):
    """The served decode step at published widths (two layers; Moonlight's
    dense first layer and one MoE layer): each kernel in it is named by its
    ``pallas_call`` name, which for those it holds is the name the
    benchmark's readers match, and its ops carry the model scopes in their
    metadata."""
    import dataclasses
    import re

    from repro import configs
    from repro.models import lm
    from repro.serve import engine

    monkeypatch.setattr(ops, "_FORCE", "pallas")
    cfg = dataclasses.replace(configs.get(arch), n_layers=layers)
    s_max = w * BS
    def fn(params, tokens, state, tables, active, key, seeds):
        logits, state, counts = lm.paged_decode_step(cfg, params, tokens,
                                                     state, tables,
                                                     active=active)
        return (engine._sample_tokens(cfg, logits, key, seeds, 0.0), state,
                counts)

    specs = (
        lm.packed_abstract_params(cfg),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32),
        lm.paged_decode_state_spec(cfg, slots, s_max, n_blocks=N_BLOCKS,
                                   block_size=BS),
        {c: jax.ShapeDtypeStruct((slots, w), jnp.int32) for c, w in
         lm.paged_table_widths(cfg, s_max, BS, cfg.prefill_chunk).items()},
        jax.ShapeDtypeStruct((slots,), jnp.bool_),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((slots,), jnp.int32))
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), specs)
    text = jax.jit(fn).lower(*args).compile().as_text()
    found = set(re.findall(r"%([a-z_]+)[.\d]* = \S+ custom-call\(", text))
    assert found == kernels
    scopes = set(re.findall(r'op_name="[^"]*/(embed|attn\.kv_write|attn\.core'
                            r'|mlp|head|sample)/', text))
    assert scopes == {"embed", "attn.kv_write", "attn.core", "mlp", "head",
                      "sample"}


def _serve_fn(cfg, program):
    """The engine's decode or chunk program body (``serve/engine.py``):
    the model step, then greedy sampling."""
    from repro.models import lm
    from repro.serve import engine

    def decode(params, tokens, state, tables, active, key, seeds):
        logits, state, counts = lm.paged_decode_step(cfg, params, tokens,
                                                     state, tables,
                                                     active=active)
        return (engine._sample_tokens(cfg, logits, key, seeds, 0.0), state,
                counts)

    def chunk(params, tokens, state, slot, n_valid, tables, fresh, start,
              key, seeds):
        logits, state = lm.prefill_chunk_step(cfg, params, tokens, state,
                                              slot, n_valid, tables,
                                              fresh=fresh, start=start)
        return engine._sample_tokens(cfg, logits, key, seeds, 0.0), state
    return decode if program == "decode" else chunk


def _serve_specs(cfg, program, slots, w, n_blocks):
    """Abstract arguments of :func:`_serve_fn`'s program at a pool of
    ``n_blocks`` blocks."""
    from repro.models import lm

    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    s_max = w * BS
    rows = slots if program == "decode" else 1
    tables = {c: jax.ShapeDtypeStruct((rows, tw), jnp.int32) for c, tw in
              lm.paged_table_widths(cfg, s_max, BS, cfg.prefill_chunk).items()}
    state = lm.paged_decode_state_spec(cfg, slots, s_max, n_blocks=n_blocks,
                                       block_size=BS)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if program == "decode":
        return (lm.packed_abstract_params(cfg),
                jax.ShapeDtypeStruct((slots, 1), jnp.int32), state, tables,
                jax.ShapeDtypeStruct((slots,), jnp.bool_), key,
                jax.ShapeDtypeStruct((slots,), jnp.int32))
    return (lm.packed_abstract_params(cfg),
            jax.ShapeDtypeStruct((1, cfg.prefill_chunk), jnp.int32), state,
            i32, i32, tables, jax.ShapeDtypeStruct((), jnp.bool_), i32, key,
            jax.ShapeDtypeStruct((1,), jnp.int32))


def _compile_serve(one_chip, cfg, program, slots, w, n_blocks):
    """(compiled program, its state spec), the state donated as the engine
    donates it."""
    specs = _serve_specs(cfg, program, slots, w, n_blocks)
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), specs)
    compiled = jax.jit(_serve_fn(cfg, program),
                       donate_argnums=(2,)).lower(*args).compile()
    return compiled, specs[2]


def _pool_shapes(state):
    """Every shape a layer-stacked pool leaf (L, n_blocks, heads, bs,
    width) or one layer of it takes, also as rows (heads·bs, width)."""
    shapes = set()
    for leaf in jax.tree.leaves(state):
        if leaf.ndim != 5 or not leaf.size:
            continue
        n, blocks, heads, bs, width = leaf.shape
        for rows in ((heads, bs), (heads * bs,)):
            shapes |= {(n, blocks, *rows, width), (blocks, *rows, width)}
    return shapes


def _pool_sized_ops(text, shapes):
    """Instructions of a compiled program whose result has a pool's shape
    and which are not a view of it (parameter, tuple element, bitcast) or
    the in-place token write (a scatter, alone or as a fusion's root)."""
    import re

    inst = re.compile(r"\s*(ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
    roots, ops_, comp = {}, [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%\S+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = inst.match(line)
        if not m:
            continue
        root, dims, op = m.groups()
        if root:
            roots[comp] = op
        calls = re.search(r"calls=(%[\w.\-]+)", line)
        ops_.append((tuple(int(d) for d in dims.split(",") if d), op,
                     calls and calls.group(1), line.strip()[:160]))
    return [line for dims, op, calls, line in ops_ if dims in shapes and not (
        op in ("parameter", "get-tuple-element", "bitcast", "scatter")
        or op == "fusion" and roots.get(calls) == "scatter")]


POOL_BLOCKS = (3000, 6000)


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("arch,layers,slots,w", [
    ("qwen3-4b+xnor", 2, SLOTS, W),
    ("moonlight-16b-a3b", 3, ML_SLOTS, ML_W)],
    ids=["qwen3-4b-xnor", "moonlight-16b-a3b"])
def test_serve_programs_hold_the_pool_once(one_chip, monkeypatch, arch,
                                           layers, slots, w, program):
    """The served decode and chunk programs carry the layer-stacked KV pool
    through their layer scans and write each token in place: compiled at
    two pool sizes, their temporary memory does not grow with the pool,
    and no instruction makes a pool-sized or layer-sized copy, slice or
    restack of it; only the token write has its shape.  Moonlight takes
    three layers so that its MoE segment scans two."""
    import dataclasses

    from repro import configs

    monkeypatch.setattr(ops, "_FORCE", "pallas")
    cfg = dataclasses.replace(configs.get(arch), n_layers=layers)
    temp, pool = {}, {}
    for n_blocks in POOL_BLOCKS:
        compiled, state = _compile_serve(one_chip, cfg, program, slots, w,
                                         n_blocks)
        temp[n_blocks] = compiled.memory_analysis().temp_size_in_bytes
        pool[n_blocks] = sum(l.size * l.dtype.itemsize
                             for l in jax.tree.leaves(state) if l.ndim == 5)
        assert _pool_sized_ops(compiled.as_text(), _pool_shapes(state)) == []
    small, large = POOL_BLOCKS
    assert temp[large] - temp[small] < 0.02 * (pool[large] - pool[small])

