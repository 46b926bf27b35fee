"""Banked batched CiM engine — the scheduler over the single-cycle primitive.

The paper's array computes ONE row-pair XOR/XNOR per sense cycle, but a
deployment tiles many independent arrays (banks) behind one controller:
every cycle, every bank senses one row-pair across its full row width, so
throughput is ``banks * cols`` bit-ops/cycle (DESIGN.md §10; the same
array-level parallelism X-SRAM and the in-DRAM X(N)OR designs lean on).

:class:`CimEngine` is that controller at framework scale.  It exposes two
coupled views of the same machine:

* **engine path** — bit-packed uint32 buffers (:mod:`repro.core.bitpack`
  layout) dispatched through the three-path kernel layer
  (:func:`repro.kernels.ops.bulk_op` / ``digest`` / ``stream_cipher``),
  with *cycle accounting* under the bank model: production throughput.
* **circuit path** (:meth:`simulate`) — the same schedule mapped onto a
  banked :class:`repro.core.cim.ArrayState` and computed through the analog
  SL-current model, one traced call for banks x pairs x cols bit-ops:
  the faithful cross-check the tests pin the engine against.

:class:`ShardedCimEngine` extends the controller across a device mesh
(DESIGN.md §11): the mesh axis is the outermost bank dimension, buffers are
partitioned on their leading word axis, and throughput becomes
``devices * banks * cols`` bit-ops/cycle.  Results are bit-identical to the
single-device engine path; for digests the per-device 512-byte partial
digests are the only cross-device traffic — the buffer never moves.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax._src import core as jax_core
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bitpack, cim
from repro.kernels import ops


def _under_trace() -> bool:
    """True when the caller is being traced (jit/vmap/...)."""
    return not jax_core.trace_state_clean()


class BankGeometry(NamedTuple):
    """Geometry of the bank stack: ``banks`` arrays of rows x cols cells.

    ``devices`` is the outermost tier — the number of mesh devices the stack
    is replicated across (1 for the single-device engine; the sharded engine
    sets it from the mesh axis size, DESIGN.md §11).
    """
    banks: int = 8
    rows: int = 512       # paper §V: 512 rows supported at nominal HRS/LRS
    cols: int = 4096      # bits per row (= 128 uint32 words)
    devices: int = 1      # mesh devices (outer bank tier)

    @property
    def words_per_row(self) -> int:
        return bitpack.packed_width(self.cols)

    @property
    def bits_per_cycle(self) -> int:
        """One row-wide op per bank per device per cycle."""
        return self.devices * self.banks * self.cols

    @property
    def pass_words(self) -> int:
        """uint32 words one full pass over every row of every bank senses."""
        return self.devices * self.banks * self.rows * self.words_per_row


@dataclasses.dataclass
class EngineStats:
    """Cycle/op counters accumulated across engine calls.

    ``by_op`` breaks the same totals down per op kind ("xor", "digest",
    "cipher", ...) so consumers like the incremental verifier can assert
    *which* traffic a code path generated; :meth:`snapshot` captures the
    counters so a later ``stats.cycles - snap.cycles`` measures exactly one
    region (the incremental tests pin O(dirty-chunks) dispatch this way).
    """
    cycles: int = 0
    bit_ops: int = 0
    calls: int = 0
    by_op: dict = dataclasses.field(default_factory=dict)

    def account(self, cycles: int, bit_ops: int, op: str = "bulk") -> None:
        self.cycles += cycles
        self.bit_ops += bit_ops
        self.calls += 1
        per = self.by_op.setdefault(op, [0, 0, 0])
        per[0] += cycles
        per[1] += bit_ops
        per[2] += 1

    def snapshot(self) -> "EngineStats":
        """Frozen copy of the counters (deep-copies ``by_op``)."""
        return dataclasses.replace(
            self, by_op={k: list(v) for k, v in self.by_op.items()})

    @property
    def ops_per_cycle(self) -> float:
        return self.bit_ops / self.cycles if self.cycles else 0.0


class CimEngine:
    """Schedules arbitrarily large packed buffers onto the bank stack.

    ``impl`` selects the kernel path (ref/interpret/pallas/auto) for every
    dispatched op, same semantics as :mod:`repro.kernels.ops`.
    """

    def __init__(self, geometry: BankGeometry = BankGeometry(),
                 impl: str = "auto"):
        self.geometry = geometry
        self.impl = impl
        self.stats = EngineStats()

    # -- schedule model ------------------------------------------------------

    def cycles_for(self, nbits: int) -> int:
        """Sense cycles to stream ``nbits`` bit-ops through the bank stack."""
        return -(-nbits // self.geometry.bits_per_cycle)

    def _account_raw(self, cycles: int, bit_ops: int,
                     op: str = "bulk") -> None:
        """Record stats exactly once per *execution*, not per trace.

        Cycle/op counts derive from static shapes, so they are known at
        trace time — but mutating ``self.stats`` inside a traced function
        would record once per trace instead of once per call.  Under a
        trace, stage a host callback that fires on every execution of the
        compiled function instead (call :func:`jax.effects_barrier` before
        reading stats that jitted calls produced).
        """
        if _under_trace():
            jax.debug.callback(
                lambda: self.stats.account(cycles, bit_ops, op))
        else:
            self.stats.account(cycles, bit_ops, op)

    def _account(self, *buffers: jnp.ndarray, op: str = "bulk") -> None:
        nbits = max(b.size * b.dtype.itemsize * 8 for b in buffers)
        self._account_raw(self.cycles_for(nbits), nbits, op=op)

    # -- engine path: packed uint32 buffers ----------------------------------

    def xor(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Bulk XOR of two same-shape uint32 buffers (one pass)."""
        out = ops.bulk_op(a, b, "xor", impl=self.impl)
        self._account(a, op="xor")  # after dispatch: failures don't skew stats
        return out

    def xnor(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Bulk XNOR — complementary rail, same cycle count."""
        out = ops.bulk_op(a, b, "xnor", impl=self.impl)
        self._account(a, op="xnor")
        return out

    def digest(self, buf: jnp.ndarray, digest_width: int = 128) -> jnp.ndarray:
        """XOR-parity digest routed through the bank stack.

        Folding is XOR of successive row-groups, so the cycle model is the
        same one-op-per-bit stream as :meth:`xor`.
        """
        out = ops.digest(buf, digest_width, impl=self.impl)
        self._account(buf, op="digest")
        return out

    def verify_copy(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Paper Fig. 1(a): XOR source against copy, all-zero means intact.

        Accepts any same-shape/dtype buffer pair — operands are viewed as
        the canonical uint32 word stream (:func:`repro.kernels.ops.as_words`)
        before the bulk XOR, which is uint32-only.  Host numpy operands are
        inspected before any jax conversion, so 64-bit buffers compare
        byte-true even with x64 off (``jnp.asarray`` would downcast them
        and a corruption in the dropped bytes would read as intact).
        """
        if not isinstance(a, jax.Array):
            a = np.asarray(a)
        if not isinstance(b, jax.Array):
            b = np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(
                "verify_copy compares a buffer against its copy: operands "
                f"must share shape/dtype, got {a.shape}/{a.dtype} vs "
                f"{b.shape}/{b.dtype}")
        return jnp.logical_not(jnp.any(self.xor(ops.as_words(a),
                                                ops.as_words(b))))

    def stream_cipher(self, buf: jnp.ndarray, key: jnp.ndarray,
                      counter: int = 0) -> jnp.ndarray:
        """Paper Fig. 1(b): counter-mode XOR pad over the bank stack."""
        out = ops.stream_cipher(buf, key, counter=counter, impl=self.impl)
        self._account(buf, op="cipher")
        return out

    # -- chunked streaming: buffers larger than one bank pass -----------------

    def _chunk_words(self, chunk_words: int | None, align: int) -> int:
        """Resolve the streaming chunk: default one bank pass, ``align``ed up."""
        chunk = chunk_words if chunk_words else self.geometry.pass_words
        return -(-chunk // align) * align

    def xor_stream(self, a: jnp.ndarray, b: jnp.ndarray,
                   chunk_words: int | None = None) -> jnp.ndarray:
        """:meth:`xor`, iterated over fixed-size chunks of the word stream.

        Bit-identical to one-shot :meth:`xor` for any chunk size (XOR is
        elementwise); the default chunk is one bank pass
        (``geometry.pass_words``), bounding peak kernel footprint.
        """
        return self._bulk_stream(a, b, "xor", chunk_words)

    def xnor_stream(self, a: jnp.ndarray, b: jnp.ndarray,
                    chunk_words: int | None = None) -> jnp.ndarray:
        """Chunked :meth:`xnor` — complementary rail of :meth:`xor_stream`."""
        return self._bulk_stream(a, b, "xnor", chunk_words)

    def _bulk_stream(self, a, b, op, chunk_words):
        if a.shape != b.shape:
            raise ValueError(f"operand shapes differ: {a.shape} vs {b.shape}")
        bulk = self.xor if op == "xor" else self.xnor
        chunk = self._chunk_words(chunk_words, 128)
        wa, wb = a.reshape(-1), b.reshape(-1)
        n = wa.shape[0]
        if n <= chunk:
            return bulk(a, b)
        outs = [bulk(wa[i:i + chunk], wb[i:i + chunk])
                for i in range(0, n, chunk)]
        return jnp.concatenate(outs).reshape(a.shape)

    def digest_stream(self, buf: jnp.ndarray, digest_width: int = 128,
                      chunk_words: int | None = None) -> jnp.ndarray:
        """Chunked :meth:`digest`, bit-identical to the one-shot digest.

        The chunk is aligned up to a multiple of ``digest_width`` so every
        chunk covers whole digest rows; XOR-folding the per-chunk digests
        then equals the digest of the whole stream (the tail chunk's zero
        padding is XOR-neutral).
        """
        words = ops.as_words(buf)
        chunk = self._chunk_words(chunk_words, digest_width)
        n = words.shape[0]
        if n <= chunk:
            return self.digest(buf if buf.dtype == jnp.uint32 else words,
                               digest_width)
        dig = self.digest(words[:chunk], digest_width)
        for i in range(chunk, n, chunk):
            dig = dig ^ self.digest(words[i:i + chunk], digest_width)
        return dig

    def digest_chunks(self, buf: jnp.ndarray, chunk_words: int | None = None,
                      digest_width: int = 128) -> jnp.ndarray:
        """Chunk-level digest export: one digest row per ``chunk_words`` slab.

        Returns a ``(n_chunks, digest_width)`` uint32 matrix — row ``i``
        equals :meth:`digest` of words ``[i*chunk, (i+1)*chunk)`` of the
        stream (bit-exactly; XOR is exact in uint32).  Chunks are aligned
        to whole digest rows (same rule as :meth:`digest_stream`), so
        XOR-folding the matrix rows equals the one-shot digest of the
        whole buffer.  The full matrix is ONE fused device fold (priming a
        :class:`repro.core.incremental.DigestCache` over thousands of
        chunks must not pay per-chunk dispatch overhead); the incremental
        verifier's dirty-chunk *re*-digests go through :meth:`digest` per
        chunk, which is what makes its traffic O(dirty).  Cycle accounting
        is the same one-op-per-bit stream either way.
        """
        words = ops.as_words(buf)
        chunk = self._chunk_words(chunk_words, digest_width)
        n = words.shape[0]
        n_chunks = max(1, -(-n // chunk))
        if n_chunks == 1:
            return jnp.stack([self.digest(words, digest_width)])
        w2 = jnp.pad(words, (0, n_chunks * chunk - n))  # zeros: XOR-neutral
        m = w2.reshape(n_chunks, chunk // digest_width, digest_width)
        out = jax.lax.reduce(m, jnp.uint32(0), jax.lax.bitwise_xor, (1,))
        self._account(words, op="digest")
        return out

    # -- circuit path: the analog model, banked ------------------------------

    def simulate(self, bits_a: jnp.ndarray, bits_b: jnp.ndarray,
                 op: str = "xor") -> jnp.ndarray:
        """Run N row-pairs through the *analog* banked array model.

        ``bits_a``/``bits_b``: (N, C) 0/1 operand rows, C <= geometry.cols.
        Pair ``j`` is programmed into bank ``j // P`` (P = ceil(N/banks))
        as rows (2p, 2p+1); one banked :func:`repro.core.cim.compute` call
        then senses all banks x P pairs — P sense cycles on real hardware,
        one traced call here.  Returns (N, C) bool.
        """
        bits_a, bits_b = jnp.asarray(bits_a), jnp.asarray(bits_b)
        n, c = bits_a.shape
        if bits_b.shape != (n, c):
            raise ValueError(f"operand shapes differ: {bits_a.shape} vs "
                             f"{bits_b.shape}")
        if c > self.geometry.cols:
            raise ValueError(f"{c} cols exceed bank width {self.geometry.cols}")
        banks = self.geometry.banks
        pairs = -(-n // banks)
        if 2 * pairs > self.geometry.rows:
            raise ValueError(f"{n} pairs need {2 * pairs} rows/bank, "
                             f"bank has {self.geometry.rows}")
        pad = banks * pairs - n
        bits_a = jnp.pad(bits_a, ((0, pad), (0, 0)))
        bits_b = jnp.pad(bits_b, ((0, pad), (0, 0)))
        # (banks, pairs, 2, C) -> interleave operand rows -> (banks, 2P, C)
        stacked = jnp.stack([bits_a, bits_b], axis=1)      # (B*P, 2, C)
        cells = stacked.reshape(banks, pairs, 2, c).reshape(banks, 2 * pairs, c)
        state = cim.make_array(cells)
        row_a = 2 * jnp.arange(pairs)
        out = cim.compute(state, row_a, row_a + 1, op)     # (banks, P, C)
        self._account_raw(pairs, n * c, op="simulate")
        return out.reshape(banks * pairs, c)[:n]


class ShardedCimEngine(CimEngine):
    """The bank stack sharded across a device mesh (DESIGN.md §11).

    The mesh axis is the *outermost bank dimension*: a buffer's flat word
    stream is split into ``devices`` contiguous chunks, each chunk scheduled
    onto that device's local bank stack, so throughput scales to
    ``devices * banks * cols`` bit-ops/cycle.

    * :meth:`xor`/:meth:`xnor`/:meth:`stream_cipher` stay fully partitioned
      (the output keeps the input's leading-axis sharding; zero cross-device
      traffic — the cipher regenerates its keystream locally from the
      device's global word offset);
    * :meth:`digest` XOR-reduces the per-device partial digests (all-gather
      + local pairwise fold), so the ``digest_width``-word digests (512
      bytes each at the default width) are the only collective payload —
      the whole point of digesting before comparing;
    * every result is bit-identical to the single-device
      :class:`CimEngine` path (pinned by ``tests/test_sharded_engine.py``
      and the 8-way property sweep in ``tests/test_distributed.py``).

    ``axis`` defaults to the mesh's first axis; pass any axis of a larger
    (pod, data, model) production mesh to dedicate it to engine traffic.
    """

    def __init__(self, mesh: Mesh, axis: str | None = None,
                 geometry: BankGeometry = BankGeometry(), impl: str = "auto"):
        axis = axis if axis is not None else mesh.axis_names[0]
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
        super().__init__(geometry._replace(devices=int(mesh.shape[axis])),
                         impl)
        self.mesh = mesh
        self.axis = axis
        self._fns: dict = {}

    # -- sharded dispatch -----------------------------------------------------

    def _shard_words(self, words: jnp.ndarray, align: int = 128):
        """Pad the flat word stream and fold it to (devices, per_device).

        ``per_device`` is aligned to ``align`` words (the kernel tile width,
        and the digest width for digests) so per-device row blocks line up
        with the unsharded layout; chunks are contiguous, so device ``d``
        holds global words ``[d*per, (d+1)*per)`` — the slice the cipher's
        counter offset and the output un-pad below rely on.  The fold is
        placed on the mesh explicitly: a buffer committed to one device
        (a serve replica's resident params) is not moved implicitly.
        """
        n = words.shape[0]
        dev = self.geometry.devices
        per = -(-max(n, 1) // (dev * align)) * align
        w2 = jnp.pad(words, (0, dev * per - n)).reshape(dev, per)
        return jax.device_put(w2, NamedSharding(self.mesh, P(self.axis))), n

    def _sharded(self, key, build):
        """Cache shard_map-wrapped jitted callables per (op, static args)."""
        if key not in self._fns:
            self._fns[key] = jax.jit(build())
        return self._fns[key]

    def _build_bulk(self, op):
        from repro.distributed import sharding
        ax, impl = self.axis, self.impl

        def f(x, y):
            return ops.bulk_op(x, y, op, impl=impl)

        return sharding.shard_map(f, self.mesh, in_specs=(P(ax), P(ax)),
                                  out_specs=P(ax), manual_axes={ax})

    def _build_digest(self, digest_width):
        from repro.distributed import sharding
        ax, impl = self.axis, self.impl

        def f(x):  # x: (1, per) — this device's contiguous word chunk
            part = ops.digest(x, digest_width, impl=impl)
            return sharding.pxor(part, ax)  # 512B digest = all the traffic

        return sharding.shard_map(f, self.mesh, in_specs=(P(ax),),
                                  out_specs=P(), manual_axes={ax})

    def _build_cipher(self):
        from repro.distributed import sharding
        ax, impl = self.axis, self.impl

        def f(x, k3):  # x: (1, per); keystream index = global word position
            per = jnp.uint32(x.size)
            ctr = k3[2] + jax.lax.axis_index(ax).astype(jnp.uint32) * per
            out = ops.stream_cipher(x.reshape(-1), k3[:2], counter=ctr,
                                    impl=impl)
            return out.reshape(x.shape)

        return sharding.shard_map(f, self.mesh, in_specs=(P(ax), P()),
                                  out_specs=P(ax), manual_axes={ax})

    # -- engine path, sharded -------------------------------------------------

    def _bulk(self, a, b, op):
        if a.dtype != jnp.uint32 or b.dtype != jnp.uint32:
            raise TypeError(f"bulk {op} needs uint32, got {a.dtype}/{b.dtype}")
        if a.shape != b.shape:
            raise ValueError(f"operand shapes differ: {a.shape} vs {b.shape}")
        wa, n = self._shard_words(a.reshape(-1))
        wb, _ = self._shard_words(b.reshape(-1))
        out = self._sharded(op, lambda: self._build_bulk(op))(wa, wb)
        self._account(a, op=op)
        return out.reshape(-1)[:n].reshape(a.shape)

    def xor(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return self._bulk(a, b, "xor")

    def xnor(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return self._bulk(a, b, "xnor")

    def digest(self, buf: jnp.ndarray, digest_width: int = 128) -> jnp.ndarray:
        words = ops.as_words(buf)
        # align per-device chunks to whole digest rows AND the kernel tile
        # width, so the global row partition matches the unsharded fold.
        w2, _ = self._shard_words(words, math.lcm(128, digest_width))
        out = self._sharded(("digest", digest_width),
                            lambda: self._build_digest(digest_width))(w2)
        self._account(buf, op="digest")
        return out

    def digest_chunks(self, buf: jnp.ndarray, chunk_words: int | None = None,
                      digest_width: int = 128) -> jnp.ndarray:
        """Per-chunk *sharded* dispatch: each row folds across the mesh, so
        only 512-byte partials cross devices — the single-device fused fold
        would pull the whole buffer onto one device instead."""
        words = ops.as_words(buf)
        chunk = self._chunk_words(chunk_words, digest_width)
        n = words.shape[0]
        rows = [self.digest(words[i:i + chunk], digest_width)
                for i in range(0, max(n, 1), chunk)]
        return jnp.stack(rows)

    def stream_cipher(self, buf: jnp.ndarray, key: jnp.ndarray,
                      counter: int = 0) -> jnp.ndarray:
        if buf.dtype != jnp.uint32:
            raise TypeError(f"stream_cipher needs uint32, got {buf.dtype}")
        w2, n = self._shard_words(buf.reshape(-1))
        k3 = jnp.stack([jnp.asarray(key[0], jnp.uint32),
                        jnp.asarray(key[1], jnp.uint32),
                        jnp.asarray(counter, jnp.uint32)])
        out = self._sharded("cipher", self._build_cipher)(w2, k3)
        self._account(buf, op="cipher")
        return out.reshape(-1)[:n].reshape(buf.shape)
