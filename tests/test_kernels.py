"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle vs
float-domain semantics, swept over shapes/dtypes.  Hypothesis properties on
the bit-domain invariants live in test_kernels_properties.py (importorskip-
guarded so this file collects without hypothesis installed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitpack
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(m, k, dtype=np.float32):
    return RNG.standard_normal((m, k)).astype(dtype)


# ---------------------------------------------------------------------------
# bitpack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 32), (4, 64), (3, 5, 96)])
def test_pack_unpack_roundtrip(shape):
    x = _rand(int(np.prod(shape[:-1])), shape[-1]).reshape(shape)
    p = bitpack.pack_bits(jnp.asarray(x))
    u = bitpack.unpack_bits(p)
    assert np.array_equal(np.asarray(u), np.where(x >= 0, 1.0, -1.0))


def test_binarize_alpha():
    x = _rand(5, 50)
    _, alpha = bitpack.binarize(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(alpha), np.abs(x).mean(-1), rtol=1e-6)


# ---------------------------------------------------------------------------
# xnor gemm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k", [(8, 16, 64), (130, 70, 100), (1, 1, 32),
                                   (33, 5, 31), (256, 128, 512)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_xnor_gemm_matches_float_oracle(m, n, k, dtype):
    a, b = _rand(m, k, dtype), _rand(n, k, dtype)
    pa = bitpack.pack_bits(bitpack.pad_to_word(jnp.asarray(a)))
    pb = bitpack.pack_bits(bitpack.pad_to_word(jnp.asarray(b)))
    want = ref.xnor_dot_float(jnp.asarray(a), jnp.asarray(b))
    got_ref = ops.xnor_matmul(pa, pb, k, impl="ref")
    got_pl = ops.xnor_matmul(pa, pb, k, impl="interpret", bm=8, bn=8, bk=2)
    assert np.array_equal(np.asarray(want), np.asarray(got_ref))
    assert np.array_equal(np.asarray(want), np.asarray(got_pl))


@pytest.mark.parametrize("blocks", [dict(bm=8, bn=8, bk=1),
                                    dict(bm=16, bn=32, bk=4),
                                    dict(bm=128, bn=128, bk=8)])
def test_xnor_gemm_block_shapes(blocks):
    a, b = _rand(64, 256), _rand(48, 256)
    pa = bitpack.pack_bits(jnp.asarray(a))
    pb = bitpack.pack_bits(jnp.asarray(b))
    want = ops.xnor_matmul(pa, pb, 256, impl="ref")
    got = ops.xnor_matmul(pa, pb, 256, impl="interpret", **blocks)
    assert np.array_equal(np.asarray(want), np.asarray(got))


# ---------------------------------------------------------------------------
# fused pack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k", [(4, 64), (7, 50), (256, 1024), (1, 32)])
def test_fused_pack(m, k):
    x = jnp.asarray(_rand(m, k))
    p1, a1 = ops.binarize(x, impl="ref")
    p2, a2 = ops.binarize(x, impl="interpret", bm=4)
    assert np.array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=1e-6)


# ---------------------------------------------------------------------------
# parity digest
# ---------------------------------------------------------------------------

def test_digest_interpret_matches_ref():
    buf = jnp.asarray(RNG.integers(0, 2**32, 5000, dtype=np.uint32))
    assert np.array_equal(np.asarray(ops.digest(buf, impl="ref")),
                          np.asarray(ops.digest(buf, impl="interpret")))


def test_digest_single_bit_flip_flips_one_digest_bit():
    buf = jnp.asarray(RNG.integers(0, 2**32, 5000, dtype=np.uint32))
    d0 = np.asarray(ops.digest(buf, impl="ref"))
    for pos, bit in [(0, 0), (1234, 17), (4999, 31)]:
        flipped = buf.at[pos].set(buf[pos] ^ np.uint32(1 << bit))
        d1 = np.asarray(ops.digest(flipped, impl="ref"))
        # XOR linearity: exactly one digest bit differs
        diff = d0 ^ d1
        assert sum(int(x).bit_count() for x in diff) == 1


def test_digest_order_sensitivity_is_columnwise():
    """Digest folds rows; swapping two words in the same column is invisible
    (XOR commutes) — documented property, not a defect of parity checking
    (the paper's check is positional row-vs-row, ours is stream parity)."""
    buf = jnp.arange(512, dtype=jnp.uint32)
    swapped = buf.at[0].set(buf[128]).at[128].set(buf[0])
    assert np.array_equal(np.asarray(ops.digest(buf)), np.asarray(ops.digest(swapped)))


# ---------------------------------------------------------------------------
# cipher
# ---------------------------------------------------------------------------

def test_cipher_involution():
    for n, ctr in [(1, 0), (37, 5), (3000, 2**32 - 7)]:
        buf = jnp.asarray(RNG.integers(0, 2**32, n, dtype=np.uint32))
        key = jnp.asarray(RNG.integers(0, 2**32, 2, dtype=np.uint32))
        enc = ops.stream_cipher(buf, key, counter=ctr, impl="ref")
        dec = ops.stream_cipher(enc, key, counter=ctr, impl="ref")
        assert np.array_equal(np.asarray(dec), np.asarray(buf))


def test_cipher_interpret_matches_ref_and_scrambles():
    buf = jnp.asarray(RNG.integers(0, 2**32, 4096, dtype=np.uint32))
    key = jnp.array([123, 456], dtype=jnp.uint32)
    c1 = ops.stream_cipher(buf, key, counter=7, impl="ref")
    c2 = ops.stream_cipher(buf, key, counter=7, impl="interpret")
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
    assert not np.array_equal(np.asarray(c1), np.asarray(buf))
    # different key/counter -> different stream
    c3 = ops.stream_cipher(buf, key, counter=8, impl="ref")
    assert not np.array_equal(np.asarray(c1), np.asarray(c3))


def test_cipher_traced_counter_matches_python_int():
    """The sharded engine offsets the counter per device as a traced uint32;
    the dispatch must accept it and hash identically to the int path."""
    buf = jnp.asarray(RNG.integers(0, 2**32, 300, dtype=np.uint32))
    key = jnp.array([5, 6], dtype=jnp.uint32)
    want = ops.stream_cipher(buf, key, counter=41, impl="ref")
    got = jax.jit(lambda c: ops.stream_cipher(buf, key, counter=c,
                                              impl="ref"))(jnp.uint32(41))
    assert np.array_equal(np.asarray(want), np.asarray(got))


def test_cipher_rejects_non_uint32():
    with pytest.raises(TypeError):
        ops.stream_cipher(jnp.zeros(4, jnp.float32), jnp.zeros(2, jnp.uint32))


# ---------------------------------------------------------------------------
# grid regression: non-divisible shapes must pad up to the tile, never shrink
# the tile to 1 (which explodes the Pallas grid to one row/word per step)
# ---------------------------------------------------------------------------

N_ODD = 513 * 128  # 513 tile rows of 128 words: 513 % 512 != 0


def _spy(monkeypatch, module, name):
    """Record the first operand's shape and the kwargs of a kernel call."""
    seen = {}
    real = getattr(module, name)

    def wrapper(x, *args, **kw):
        seen["rows"], seen["shape"] = x.shape[0], x.shape
        seen.update(kw)
        return real(x, *args, **kw)

    monkeypatch.setattr(module, name, wrapper)
    return seen


def test_digest_grid_never_degenerates_to_one_row(monkeypatch):
    seen = _spy(monkeypatch, ops._parity, "parity_digest")
    buf = jnp.asarray(RNG.integers(0, 2**32, N_ODD, dtype=np.uint32))
    got = ops.digest(buf, impl="interpret")
    assert seen["br"] == 512, seen            # full tile, not br=1
    assert seen["rows"] % seen["br"] == 0
    assert seen["rows"] // seen["br"] == 2    # grid of 2 steps, not 513
    assert np.array_equal(np.asarray(got),
                          np.asarray(ops.digest(buf, impl="ref")))


def test_cipher_grid_never_degenerates_to_one_row(monkeypatch):
    seen = _spy(monkeypatch, ops._cipher, "xor_cipher")
    buf = jnp.asarray(RNG.integers(0, 2**32, N_ODD, dtype=np.uint32))
    key = jnp.array([3, 4], dtype=jnp.uint32)
    got = ops.stream_cipher(buf, key, counter=5, impl="interpret")
    assert seen["br"] == 512, seen
    assert seen["rows"] % seen["br"] == 0
    assert seen["rows"] // seen["br"] == 2
    assert np.array_equal(
        np.asarray(got),
        np.asarray(ops.stream_cipher(buf, key, counter=5, impl="ref")))


def test_binarize_grid_never_degenerates_to_one_row(monkeypatch):
    """300 rows with bm=256 must pad to 512 (grid of 2), not shrink to bm=1
    (grid of 300) — the digest/stream_cipher fix applied to the fused pack."""
    seen = _spy(monkeypatch, ops._pack, "pack")
    x = jnp.asarray(_rand(300, 64))
    p, a = ops.binarize(x, impl="interpret")
    assert seen["bm"] == 256, seen
    assert seen["rows"] % seen["bm"] == 0
    assert seen["rows"] // seen["bm"] == 2    # grid of 2 steps, not 300
    p_ref, a_ref = ops.binarize(x, impl="ref")
    assert p.shape == p_ref.shape and a.shape == a_ref.shape
    assert np.array_equal(np.asarray(p), np.asarray(p_ref))
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref), rtol=1e-6)


def test_xnor_matmul_tile_never_degenerates_to_bk_one(monkeypatch):
    """kw=160 packed words with the lane-legal default bk=128 must pad kw
    to 256 (k-grid of 2), not shrink to bk=1 (k-grid of 160); valid_k
    keeps the result exact."""
    seen = _spy(monkeypatch, ops._xnor_gemm, "xnor_gemm")
    k = 160 * 32                              # kw = 160 words
    a, b = _rand(16, k), _rand(8, k)
    pa = bitpack.pack_bits(jnp.asarray(a))
    pb = bitpack.pack_bits(jnp.asarray(b))
    got = ops.xnor_matmul(pa, pb, k, impl="interpret")   # default bk=128
    assert seen["bk"] == 128, seen
    assert seen["shape"][1] % seen["bk"] == 0
    assert seen["shape"][1] // seen["bk"] == 2           # k-grid of 2, not 160
    want = ref.xnor_dot_float(jnp.asarray(a), jnp.asarray(b))
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# bulk XOR/XNOR (the banked engine's compute tile, DESIGN.md §10)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 37, 4096, 70000])
@pytest.mark.parametrize("op", ["xor", "xnor"])
def test_bulk_op_matches_numpy_all_impls(n, op):
    a = RNG.integers(0, 2**32, n, dtype=np.uint32)
    b = RNG.integers(0, 2**32, n, dtype=np.uint32)
    want = ~(a ^ b) if op == "xnor" else a ^ b
    got_ref = ops.bulk_op(jnp.asarray(a), jnp.asarray(b), op, impl="ref")
    got_pl = ops.bulk_op(jnp.asarray(a), jnp.asarray(b), op, impl="interpret")
    assert np.array_equal(np.asarray(got_ref), want)
    assert np.array_equal(np.asarray(got_pl), want)


def test_bulk_op_preserves_shape():
    a = jnp.asarray(RNG.integers(0, 2**32, (13, 17), dtype=np.uint32))
    b = jnp.asarray(RNG.integers(0, 2**32, (13, 17), dtype=np.uint32))
    out = ops.bulk_op(a, b, "xnor", impl="interpret")
    assert out.shape == a.shape and out.dtype == jnp.uint32


def test_as_words_is_byte_true_for_host_64bit_arrays():
    """numpy float64/int64 inputs must stream their true bytes — with x64
    off, a jnp.asarray-first path would silently drop half of every
    element."""
    x = np.arange(10, dtype=np.float64) * 0.5
    w = np.asarray(ops.as_words(x))
    assert w.size == 20 and w.tobytes() == x.tobytes()
    i = np.arange(10, dtype=np.int64) << 40     # live bits above bit 31
    assert np.asarray(ops.as_words(i)).tobytes() == i.tobytes()
    # jax-array and numpy paths agree for 32-bit dtypes
    f = RNG.standard_normal(33).astype(np.float32)
    assert np.array_equal(np.asarray(ops.as_words(f)),
                          np.asarray(ops.as_words(jnp.asarray(f))))


def test_bulk_op_rejects_bad_inputs():
    a = jnp.zeros(8, jnp.uint32)
    with pytest.raises(ValueError):
        ops.bulk_op(a, a, "and")
    with pytest.raises(TypeError):
        ops.bulk_op(a.astype(jnp.float32), a, "xor")
    with pytest.raises(ValueError):
        ops.bulk_op(a, jnp.zeros(9, jnp.uint32), "xor")
