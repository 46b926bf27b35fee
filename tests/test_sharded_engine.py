"""Sharded engine (DESIGN.md §11): ShardedCimEngine must be bit-identical to
the single-device CimEngine on whatever device grid the host exposes (1 in
the plain suite; the interpret+8-device CI job and the subprocess sweep in
test_distributed.py exercise real multi-device meshes), plus the streaming
mode and the device tier of the cycle model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import verify
from repro.core.engine import BankGeometry, CimEngine, ShardedCimEngine
from repro.launch import mesh as mesh_mod

RNG = np.random.default_rng(0)


@pytest.fixture(scope="module")
def sharded():
    return ShardedCimEngine(mesh_mod.make_engine_mesh(), impl="ref")


@pytest.fixture
def single():
    return CimEngine(impl="ref")


@pytest.mark.parametrize("n", [1, 37, 4096, 70001])
@pytest.mark.parametrize("op", ["xor", "xnor"])
def test_sharded_bulk_matches_single_device(sharded, single, n, op):
    a = jnp.asarray(RNG.integers(0, 2**32, n, dtype=np.uint32))
    b = jnp.asarray(RNG.integers(0, 2**32, n, dtype=np.uint32))
    got = getattr(sharded, op)(a, b)
    want = getattr(single, op)(a, b)
    assert got.shape == a.shape and got.dtype == jnp.uint32
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,width", [(1, 128), (5000, 128), (70001, 128),
                                     (5000, 96), (333, 32)])
def test_sharded_digest_matches_single_device(sharded, single, n, width):
    buf = jnp.asarray(RNG.integers(0, 2**32, n, dtype=np.uint32))
    assert np.array_equal(np.asarray(sharded.digest(buf, width)),
                          np.asarray(single.digest(buf, width)))


@pytest.mark.parametrize("n,ctr", [(1, 0), (4096, 11), (70001, 2**32 - 7)])
def test_sharded_cipher_matches_single_device_and_involutes(sharded, single,
                                                            n, ctr):
    buf = jnp.asarray(RNG.integers(0, 2**32, n, dtype=np.uint32))
    key = jnp.asarray(RNG.integers(0, 2**32, 2, dtype=np.uint32))
    enc = sharded.stream_cipher(buf, key, counter=ctr)
    assert np.array_equal(np.asarray(enc),
                          np.asarray(single.stream_cipher(buf, key,
                                                          counter=ctr)))
    dec = sharded.stream_cipher(enc, key, counter=ctr)
    assert np.array_equal(np.asarray(dec), np.asarray(buf))


def test_sharded_digest_of_float_tree_matches_host(sharded):
    tree = {"w": jnp.asarray(RNG.standard_normal((64, 33)), jnp.float32),
            "b": jnp.asarray(RNG.standard_normal((129,)), jnp.float32)}
    dig = verify.tree_digest(tree, engine=sharded)
    for k, v in tree.items():
        assert np.array_equal(np.asarray(dig[k]),
                              verify.np_digest(np.asarray(v))), k
    ok, _ = verify.verify_trees(tree, tree, engine=sharded)
    assert bool(ok)


def test_sharded_engine_streams_in_chunks(sharded, single):
    buf = jnp.asarray(RNG.integers(0, 2**32, 100001, dtype=np.uint32))
    b2 = jnp.asarray(RNG.integers(0, 2**32, 100001, dtype=np.uint32))
    for chunk in (999, 1 << 14):
        assert np.array_equal(np.asarray(sharded.xor_stream(buf, b2, chunk)),
                              np.asarray(single.xor(buf, b2)))
        assert np.array_equal(
            np.asarray(sharded.digest_stream(buf, chunk_words=chunk)),
            np.asarray(single.digest(buf)))


def test_device_tier_of_cycle_model(sharded):
    """devices x banks x cols bits/cycle: the mesh multiplies throughput."""
    d = sharded.geometry.devices
    assert d == len(sharded.mesh.devices)
    base = BankGeometry()
    assert sharded.geometry.bits_per_cycle == d * base.bits_per_cycle
    nbits = 1 << 24
    assert sharded.cycles_for(nbits) == -(-nbits
                                          // (d * base.banks * base.cols))


def test_sharded_engine_accounts_stats():
    eng = ShardedCimEngine(mesh_mod.make_engine_mesh(), impl="ref")
    buf = jnp.asarray(RNG.integers(0, 2**32, 256, dtype=np.uint32))
    eng.xor(buf, buf)
    eng.digest(buf)
    assert eng.stats.calls == 2
    assert eng.stats.bit_ops == 2 * 256 * 32
    assert eng.stats.cycles == 2 * eng.cycles_for(256 * 32)


def test_sharded_verify_copy_accepts_non_uint32(sharded):
    """verify_copy must route non-uint32 buffers through as_words on the
    sharded engine too (the bulk path is uint32-only)."""
    x = jnp.asarray(RNG.standard_normal((65, 7)), jnp.float32)
    assert bool(sharded.verify_copy(x, jnp.array(x)))
    assert not bool(sharded.verify_copy(x, x.at[64, 6].set(x[64, 6] + 1)))
    with pytest.raises(ValueError, match="shape/dtype"):
        sharded.verify_copy(x, x.astype(jnp.int32))


def test_sharded_digest_chunks_matches_single_device(sharded, single):
    buf = jnp.asarray(RNG.integers(0, 2**32, 5 * 384 + 100, dtype=np.uint32))
    got = np.asarray(sharded.digest_chunks(buf, 384))
    want = np.asarray(single.digest_chunks(buf, 384))
    assert got.shape == (6, 128)
    assert np.array_equal(got, want)


def test_sharded_engine_rejects_bad_inputs(sharded):
    a = jnp.zeros(8, jnp.uint32)
    with pytest.raises(TypeError):
        sharded.xor(a.astype(jnp.float32), a)
    with pytest.raises(ValueError):
        sharded.xor(a, jnp.zeros(9, jnp.uint32))
    with pytest.raises(TypeError):
        sharded.stream_cipher(jnp.zeros(4, jnp.float32), jnp.zeros(2,
                                                                   jnp.uint32))
    with pytest.raises(ValueError):
        ShardedCimEngine(mesh_mod.make_engine_mesh(), axis="nope")


def test_engine_mesh_axis_is_bank():
    mesh = mesh_mod.make_engine_mesh()
    assert mesh.axis_names == ("bank",)
    with pytest.raises(ValueError):
        mesh_mod.make_engine_mesh(len(jax.devices()) + 1)


def test_sharded_digest_of_committed_buffer(sharded, single):
    """A buffer committed to one device (a serve replica's resident params)
    digests on the mesh without an implicit cross-device move."""
    buf = jax.device_put(
        jnp.asarray(RNG.integers(0, 2**32, 5000, dtype=np.uint32)),
        jax.devices()[0])
    assert np.array_equal(np.asarray(sharded.digest(buf)),
                          np.asarray(single.digest(buf)))
