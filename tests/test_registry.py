"""Block-contract registry conformance (DESIGN.md §16).

Every registered kind — present and future — gets the same contract
coverage for free: state_spec abstract/concrete round-trip, the
contract-generated paged split/merge inverse, fwd-vs-decode parity, and
chunk ragged-tail exactness (paged == dense through the real engine).
Registration-time validation and the fail-closed prefix gate (a kind that
doesn't declare ``prefix_shareable`` disables sharing for any arch that
contains it) are pinned here too.

The ``_OVER`` table below gives each kind the config knobs its *model*
needs (ctx tokens, encoder stack, expert counts).  That is test-harness
knowledge — the consumers under test never switch on kind strings.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.configs.base import ArchConfig
from repro.models import blocks, lm, registry
from repro.models.registry import BlockContract
from repro.serve import ServeEngine, synthetic_trace

KINDS = registry.kinds()   # configs import above registers satellite kinds

_OVER = {
    "local": dict(local_window=8),
    "cross": dict(n_ctx_tokens=16, family="vlm"),
    "dec": dict(n_ctx_tokens=16, encoder_layers=2, family="audio"),
    "bindense": dict(n_ctx_tokens=4, vocab=4, quant="xnor", family="vlm"),
    "moe": dict(n_experts=4, top_k=2, d_ff_expert=32, family="moe"),
}


def _cfg(kind, **extra):
    base = dict(name=f"conformance-{kind}", family="dense", n_layers=2,
                d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                pattern=(kind,), local_window=32, mlstm_chunk=8,
                block_size=8, prefill_chunk=8, dtype=jnp.float32)
    base.update(_OVER.get(kind, {}))
    base.update(extra)
    return ArchConfig(**base)


def _key(kind):
    return jax.random.PRNGKey(zlib.crc32(kind.encode()) % 2**31)


def _model(kind):
    cfg = _cfg(kind)
    params = lm.init_params(cfg, _key(kind))
    return cfg, params


# ---------------------------------------------------------------------------
# per-kind conformance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_state_spec_abstract_concrete_roundtrip(kind):
    """Abstract and concrete specs agree on structure, shape and dtype —
    for both the dense and the contract-generated paged layouts."""
    cls = registry.get(kind)
    cfg = _cfg(kind)
    for mk in (lambda a: cls.state_spec(cfg, 2, 16, a),
               lambda a: cls.paged_state_spec(cfg, 2, 16, 4,
                                              cfg.block_size, a)):
        abs_t, con_t = mk(True), mk(False)
        assert (jax.tree.structure(abs_t) == jax.tree.structure(con_t))
        for la, lc in zip(jax.tree.leaves(abs_t), jax.tree.leaves(con_t)):
            assert la.shape == lc.shape, (kind, la.shape, lc.shape)
            assert la.dtype == lc.dtype, (kind, la.dtype, lc.dtype)


@pytest.mark.parametrize("kind", KINDS)
def test_paged_split_merge_inverse(kind):
    """split's halves track the declared contract flags, and merge(split)
    is the identity on the paged state tree."""
    cls = registry.get(kind)
    c = cls.contract
    cfg = _cfg(kind)
    state = cls.paged_state_spec(cfg, 2, 16, 4, cfg.block_size, False)
    shared, per_slot = cls.paged_split(state)
    assert (shared is not None) == c.paged_kv
    assert (per_slot is not None) == c.per_slot_state
    merged = cls.paged_merge(shared, per_slot)
    assert jax.tree.structure(merged) == jax.tree.structure(state)
    for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", KINDS)
def test_fwd_decode_parity(kind):
    """Single-kind model: prefill + step-by-step decode reproduces the
    full-sequence forward (the §13 serve-path equivalence, per kind)."""
    if not registry.contract(kind).decodes:
        pytest.skip("encoder-only kind never runs the decode path")
    B, S, s0 = 2, 12, 8
    cfg, params = _model(kind)
    key = _key(kind)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    ctx = None
    if cfg.n_ctx_tokens:
        ctx = jax.random.normal(key, (B, cfg.n_ctx_tokens, cfg.d_model),
                                jnp.float32) * 0.1
    full, _ = lm.forward(cfg, params, tokens, ctx)
    lg, st = lm.prefill(cfg, params, tokens[:, :s0], ctx, s_max=S + 2)
    outs = [lg]
    for t in range(s0, S):
        lg, st = lm.decode_step(cfg, params, tokens[:, t:t + 1], st)
        outs.append(lg)
    dec = np.asarray(jnp.concatenate(outs, 1), np.float32)
    want = np.asarray(full[:, s0 - 1:], np.float32)
    rel = np.abs(dec - want).max() / max(np.abs(want).max(), 1e-6)
    assert rel < 1e-3, (kind, rel)


@pytest.mark.parametrize("kind", KINDS)
def test_chunked_paged_matches_dense(kind):
    """Chunk ragged-tail exactness through the real engine: prompt lengths
    straddling the C=8 chunk size (5, 8, 13) serve token-identically on
    the paged and dense layouts."""
    c = registry.contract(kind)
    if not c.decodes:
        pytest.skip("encoder-only kind never runs the decode path")
    cfg, params = _model(kind)
    trace = synthetic_trace(4, cfg.vocab, seed=3, prompt_lens=(5, 8, 13),
                            new_tokens=(3, 5),
                            n_ctx_tokens=cfg.n_ctx_tokens,
                            d_model=cfg.d_model)
    outs = []
    for paged in (False, True):
        eng = ServeEngine(cfg, params, slots=2, s_max=24, paged=paged)
        for r in trace:
            eng.submit(r)
        report = eng.run()
        outs.append({rid: report.tokens(rid).tolist()
                     for rid in report.sessions})
    assert outs[0] == outs[1], kind


@pytest.mark.parametrize("kind", KINDS)
def test_table_widths_follow_contract(kind):
    """paged_table_widths is generic: exactly the kinds declaring a paged
    pool produce a table class, sized >= 1 block."""
    c = registry.contract(kind)
    cfg = _cfg(kind)
    widths = lm.paged_table_widths(cfg, 32, cfg.block_size,
                                   cfg.prefill_chunk)
    if c.paged_kv:
        assert list(widths) == [c.table_class] and widths[c.table_class] >= 1
    else:
        assert widths == {}


def test_every_arch_kind_is_registered():
    """Every kind any shipped arch names (decoder and encoder stacks)
    resolves to a registered contract."""
    for cfg in configs.ALL.values():
        for kind, _ in cfg.segments() + cfg.encoder_segments():
            assert isinstance(registry.contract(kind), BlockContract)


# ---------------------------------------------------------------------------
# registration-time validation
# ---------------------------------------------------------------------------


def test_contract_validation():
    with pytest.raises(ValueError):       # pool without a table class
        BlockContract("p", paged_kv=True)
    with pytest.raises(ValueError):       # ring without a table class
        BlockContract("w", window=True)
    with pytest.raises(ValueError):       # rings are never stable (§15)
        BlockContract("ws", window=True, table_class="win",
                      prefix_shareable=True)
    with pytest.raises(ValueError):
        BlockContract("")


def test_register_rejects_duplicates_and_malformed():
    with pytest.raises(ValueError):       # "attn" already registered
        registry.register(blocks.AttnBlock)

    class NoContract:
        pass

    with pytest.raises(TypeError):
        registry.register(NoContract)

    class NoSurface:
        contract = BlockContract("hollow")

    with pytest.raises(TypeError):        # lacks defs/fwd/state_spec
        registry.register(NoSurface)
    assert "hollow" not in registry.kinds()


def test_unknown_kind_error_names_registered_kinds():
    with pytest.raises(KeyError, match="attn"):
        registry.get("no-such-kind")


# ---------------------------------------------------------------------------
# fail-closed prefix gate (satellite regression)
# ---------------------------------------------------------------------------


class _OpaqueAttn(blocks.AttnBlock):
    """Physically identical to attn, but its contract says nothing about
    prefix sharing — the gate must fail closed."""
    contract = BlockContract("opaque_attn", paged_kv=True,
                             table_class="full")


def test_prefix_gate_fails_closed_for_undeclared_kind():
    with registry.temporary(_OpaqueAttn):
        cfg = _cfg("opaque_attn")
        assert lm.prefix_cache_eligible(cfg) is False
        assert lm.prefix_table_class(cfg) is None
        # one undeclared kind anywhere in the stack disables the arch,
        # even when every other kind declares shareability
        mixed = _cfg("opaque_attn", pattern=("attn", "opaque_attn"))
        assert lm.prefix_cache_eligible(mixed) is False
        # the engine honors the gate (prefix_cache=True requested) and the
        # kind still serves through the generic machinery
        params = lm.init_params(cfg, _key("opaque_attn"))
        eng = ServeEngine(cfg, params, slots=2, s_max=24, prefix_cache=True)
        assert eng.prefix_caching is False
        trace = synthetic_trace(3, cfg.vocab, seed=1, prompt_lens=(4, 9),
                                new_tokens=(3,))
        for r in trace:
            eng.submit(r)
        report = eng.run()
        assert all(len(s.tokens) > 0 for s in report.sessions.values())
    # the temporary registration is gone afterwards
    with pytest.raises(KeyError):
        registry.get("opaque_attn")


def test_declared_kinds_keep_eligibility():
    """The contract flag reproduces the historical allowlist on the
    shipped archs (no eligibility regressions from the refactor)."""
    want = {"qwen3-4b": True, "whisper-tiny": True,
            "llama-3.2-vision-11b": True, "recurrentgemma-2b": False,
            "xlstm-350m": False, "xnor-cnn": True}
    for name, eligible in want.items():
        assert lm.prefix_cache_eligible(configs.get(name)) is eligible, name
