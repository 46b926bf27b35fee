"""Continuous-batching decode engine (DESIGN.md §13–§14).

The paper's application regime — binary filters resident in the CiM array,
XNOR-popcount as the serve-time inner loop — needs a *request-level* engine
on top of the token-level serve path.  This module provides it:

* a FIFO request queue and a fixed pool of batch **slots** over one resident
  :class:`repro.models.lm.DecodeState` (per-slot position vector);
* a **block-paged KV cache** (DESIGN.md §14): attention state lives in a
  shared block pool addressed through host-owned per-slot block tables
  (:class:`repro.serve.pools.BlockPool` allocates; tables are device
  *data*), so cache memory is proportional to tokens actually held, not
  ``slots x s_max``;
* **admission**: a freed slot is immediately refilled.  The request's
  worst-case blocks are reserved (OOM backpressure holds the FIFO head
  otherwise) and the prompt is consumed by **chunked prefill** — fixed
  ``prefill_chunk``-sized pieces through ONE jitted program, so prefill
  compiles once for any prompt-length mix and long prompts interleave
  with decode in bounded slices;
* **eviction** on EOS or max-token budget: the slot is marked free and its
  blocks return to the pool; dead rows are inert (position frozen via the
  active mask, table rows zeroed so frozen re-writes land in the reserved
  trash block);
* **one jitted decode program** for the whole run: position vector, active
  mask, block tables, sampling seeds are device *data*, never trace
  constants, so slots joining/leaving and blocks moving never retrace;
* **session export/import** (DESIGN.md §17): a live slot's complete state —
  paged KV blocks gathered through its table rows, per-slot recurrent /
  window carries, position, generated tokens, chunked-prefill progress —
  lifts out as a flat array tree plus host metadata and re-admits into any
  engine with the same (cfg, geometry), token-identically under the
  schedule-independent (rid, step) seed-folding contract.  The replicated
  tier (:mod:`repro.serve.router`) moves it between replicas as an
  encrypted delta checkpoint.

With ``pack=True`` (default) and a ``quant="xnor"`` arch the resident
params are the packed form (:func:`repro.models.lm.pack_params`): binary
filter planes + beta, float weights absent — packed-weight residency.

Scheduling bookkeeping lives in :mod:`repro.serve.pools` (pure host logic,
unit-testable without a model), the content-addressed prefix index in
:mod:`repro.serve.prefix`, and counters/reports in
:mod:`repro.serve.stats`; this module owns the jitted programs and the
engine loop that drives them.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.kernels import paged_attn
from repro.models import lm
from repro.serve.pools import BlockPool, SlotPool
from repro.serve.prefix import PrefixIndex
from repro.serve.session import Request, Session
from repro.serve.stats import EngineStats, ServeReport

# ---------------------------------------------------------------------------
# jitted programs (module level: one trace cache per (cfg, shapes))
# ---------------------------------------------------------------------------


@jax.named_scope("sample")
def _sample_tokens(cfg, logits, key, seeds, temperature: float):
    """Last-position sampling, sliced to the true vocab (pad ids never
    sampled).  Per-row keys fold the host-computed (rid, step) seed into the
    engine key, so draws depend only on the request and its token index —
    never on slot assignment or batch composition (determinism under a
    fixed seed, whatever the schedule)."""
    lg = logits[:, -1, :cfg.vocab].astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]

    def one(row, seed):
        g = jax.random.gumbel(jax.random.fold_in(key, seed), row.shape,
                              jnp.float32)
        return jnp.argmax(row / temperature + g, axis=-1).astype(jnp.int32)
    return jax.vmap(one)(lg, seeds)[:, None]


@dataclasses.dataclass
class _PrefillProgress:
    """Host bookkeeping for one slot's in-flight chunked prefill."""

    session: Session
    padded: np.ndarray          # prompt suffix zero-padded to n_chunks * C
    p_len: int                  # suffix length = prompt length - skip
    n_chunks: int
    next_chunk: int
    ctx: Any                    # encoded (enc-dec) / raw (vlm) ctx, or None
    seeds: Any                  # (1,) device seeds for the prefill sample
    rows: dict                  # this slot's (1, W) block-table rows
    skip: int = 0               # positions served from shared prefix blocks
    chain: list = dataclasses.field(default_factory=list)
                                # full prompt's (key, parent, tokens) chain
    registered: int = 0         # prompt blocks registered so far


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """Continuous-batching serve engine over one resident decode state.

    Args:
      cfg: ArchConfig. ``quant="xnor"`` archs serve from packed weights
        unless ``pack=False``.
      params: float param tree (as from ``lm.init_params`` / ``ckpt``);
        packed at construction when applicable — the float copies of
        binarized linears are not retained by the engine.
      slots: resident batch width (concurrent requests).
      s_max: per-slot cache capacity; every request needs
        ``len(prompt) + max_new_tokens - 1 <= s_max``.
      eos_id: token id that terminates a request early (None: budget only).
      temperature: 0 = greedy (deterministic); > 0 = gumbel sampling with
        schedule-independent per-(request, step) keys.
      seed: engine sampling seed.
      pack: keep binarizable linears packed-resident (xnor archs only).
      prefix_cache: content-addressed prefix sharing over the paged pool
        (DESIGN.md §15).  Auto-disabled for archs whose state cannot be
        rebuilt from cached blocks (recurrent carries, local window rings)
        — ``engine.prefix_caching`` reports the effective setting.
      device: the device that holds the resident params and decode state
        (None: leave them where JAX puts them).  Every program then runs
        there, since host inputs follow committed arrays.
    """

    def __init__(self, cfg, params, *, slots: int, s_max: int,
                 eos_id: int | None = None, temperature: float = 0.0,
                 seed: int = 0, pack: bool = True, block_size: int = 0,
                 prefill_chunk: int = 0, n_blocks: int = 0,
                 prefix_cache: bool = True, device=None):
        if cfg.latent and cfg.quant != "none":
            raise ValueError(
                f"{cfg.name}: latent attention is served from float weights "
                "only (decoding over cached latents folds wkv_b into q, "
                "which a binarized wkv_b does not allow)")
        self.cfg = cfg
        self.slots = slots
        self.s_max = s_max
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.params = lm.pack_params(cfg, params) if pack else params
        if device is not None:
            self.params = jax.device_put(self.params, device)
        self.pool = SlotPool(slots)
        self.sessions: dict[int, Session] = {}
        self._key = jax.random.PRNGKey(seed)
        self.stats = EngineStats()
        self._step_idx = 0                 # engine steps since construction
        # the engine's host time since the last decode token read (None
        # before the first), and when the current stretch of it began
        self._turn_ns: int | None = None
        self._t_mark = 0
        self.block_size = block_size or cfg.block_size
        self.prefill_chunk = prefill_chunk or cfg.prefill_chunk
        self._widths = lm.paged_table_widths(cfg, s_max, self.block_size,
                                             self.prefill_chunk)
        per_slot_worst = sum(self._widths.values())
        if n_blocks <= 0:
            # default: enough for every slot at full table width
            # (callers shrink n_blocks to oversubscribe slots)
            n_blocks = 1 + slots * max(per_slot_worst, 1)
        self.n_blocks = n_blocks
        self.blocks = BlockPool(n_blocks) if self._widths else None
        self.stats.blocks_total = n_blocks - 1 if self.blocks else 0
        # prefix caching (DESIGN.md §15): only for archs whose whole
        # sequential state is reconstructible from the paged pools —
        # prefix_cache_eligible is fail-closed over each kind's declared
        # prefix_shareable contract flag (recurrent carries and local
        # window *rings* don't declare it).  The table class shared
        # prefixes register under comes from the same contracts.
        self._share_cls = lm.prefix_table_class(cfg)
        self._prefix = (PrefixIndex(self.block_size)
                        if prefix_cache and self.blocks is not None
                        and self._share_cls is not None
                        and lm.prefix_cache_eligible(cfg) else None)
        # host-owned block tables, mirrored to device on change
        self._tables = {c: np.zeros((slots, w), np.int32)
                        for c, w in self._widths.items()}
        self._dev_tables = None
        # the decode attention kernel's walk of each table class:
        # (W, P table blocks per grid step, window), for the counters
        kv_item = (1 if cfg.kv_cache_dtype == "i8"
                   else jnp.dtype(cfg.dtype).itemsize)
        slab = paged_attn.slab_bytes(cfg.cache_heads, self.block_size,
                                     cfg.cache_k_dim, kv_item,
                                     1 if cfg.latent else 2)
        windows = lm.paged_table_windows(cfg)
        self._walks = {
            c: (w, paged_attn.chunk_blocks(w, self.block_size, slab),
                windows[c])
            for c, w in self._widths.items()}
        self._state = lm.paged_decode_state_spec(
            cfg, slots, s_max, n_blocks=n_blocks,
            block_size=self.block_size, abstract=False)
        self._build_paged_programs()
        if device is not None:
            self._state = jax.device_put(self._state, device)
        # host-side mirrors of the device batch (tiny, moved every step)
        self._tokens = np.zeros((slots, 1), np.int32)
        self._active = np.zeros((slots,), bool)
        # slots mid-chunked-prefill: slot -> _PrefillProgress
        self._prefilling: dict[int, _PrefillProgress] = {}
        # memoized FIFO-head prefix plan: ((rid, index generation), plan)
        self._plan_cache: tuple[tuple[int, int], tuple] | None = None

    def _build_paged_programs(self):
        """Per-engine jits so trace counts are observable: the python side
        effect on ``stats`` runs at trace time only, so ``prefill_traces``
        counts compilations — the chunked-prefill contract pins it to 1."""
        cfg, temperature = self.cfg, self.temperature

        def chunk_fn(params, tokens, state, slot, n_valid, tables, ctx,
                     fresh, start, key, seeds):
            self.stats.prefill_traces += 1
            logits, state = lm.prefill_chunk_step(cfg, params, tokens, state,
                                                  slot, n_valid, tables, ctx,
                                                  fresh=fresh, start=start)
            return (_sample_tokens(cfg, logits, key, seeds, temperature),
                    state)

        def decode_fn(params, tokens, state, tables, active, key, seeds):
            """-> (tokens, state), and a MoE model's expert counts."""
            self.stats.decode_traces += 1
            logits, state, counts = lm.paged_decode_step(
                cfg, params, tokens, state, tables, active=active)
            toks = _sample_tokens(cfg, logits, key, seeds, temperature)
            return (toks, state) if counts is None else (toks, state, counts)

        self._chunk_program = jax.jit(chunk_fn, donate_argnums=(2,))
        self._paged_decode_program = jax.jit(decode_fn, donate_argnums=(2,))
        # copy-on-write block duplication: src/dst are device scalars, so
        # one program covers every (donor, recipient) pair without retracing
        self._cow_program = jax.jit(
            lambda state, src, dst: lm.paged_copy_block(cfg, state, src, dst),
            donate_argnums=(0,))
        self._encode_program = None
        if cfg.is_encdec():
            self._encode_program = jax.jit(
                lambda params, frames: lm.encode(cfg, params, frames))
        # session migration (§17): slot/ids are device data, payload shapes
        # are fixed by (cfg, geometry) — one trace each for the whole run
        self._export_program = jax.jit(
            lambda state, slot, rows: lm.export_slot(cfg, state, slot, rows))
        self._import_program = jax.jit(
            lambda state, slot, rows, payload: lm.import_slot(
                cfg, state, slot, rows, payload),
            donate_argnums=(0,))
        self._gather_block_program = jax.jit(
            lambda state, bid: lm.gather_block(cfg, state, bid))

    def _blocks_per_class(self, prompt_len: int,
                          max_new_tokens: int) -> dict[str, int]:
        """Worst-case block reservation per table class for one request:
        positions 0..P+G-2 are cached, window classes cap at their ring
        width.  Single source for both the admission gate and the actual
        allocation — they must never drift apart."""
        nb = -(-(prompt_len + max_new_tokens - 1) // self.block_size)
        return {c: min(nb, w) for c, w in self._widths.items()}

    def _blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        return sum(self._blocks_per_class(prompt_len,
                                          max_new_tokens).values())

    # -- request lifecycle ---------------------------------------------------

    def submit(self, request: Request) -> Session:
        if request.rid in self.sessions:
            raise ValueError(f"duplicate request id {request.rid}")
        need = request.prompt.shape[0] + request.max_new_tokens - 1
        if need > self.s_max:
            raise ValueError(
                f"request {request.rid} needs {need} cache positions, "
                f"engine capacity is s_max={self.s_max}")
        if self.blocks is not None:
            nb = self._blocks_needed(request.prompt.shape[0],
                                     request.max_new_tokens)
            if nb > self.blocks.capacity:
                raise ValueError(
                    f"request {request.rid} needs {nb} blocks, pool "
                    f"capacity is {self.blocks.capacity} "
                    f"(n_blocks={self.n_blocks} incl. trash block)")
        session = Session(request, t_submit=time.monotonic())
        self.sessions[request.rid] = session
        self.pool.submit(session)
        return session

    def _seed_for(self, rid: int, step: int) -> int:
        return (rid * 1_000_003 + step) % (2**31 - 1)

    def _finish(self, session: Session, reason: str) -> None:
        session.finish_reason = reason
        session.t_done = time.monotonic()
        if session.slot is not None and session.slot in self.pool.active:
            slot = session.slot
            self.pool.evict(slot)
            self._active[slot] = False
            self._tokens[slot] = 0   # dead slots feed a constant token id
            # eviction returns every block the request held; the zeroed
            # table row routes the dead slot's frozen re-writes to the
            # trash block so reallocated blocks are never corrupted.
            # Cached blocks (registered below / during prefill) park in
            # the pool's idle tier instead of freeing.
            if self.blocks is not None:
                if self._prefix is not None:
                    self._register_finished(session, slot)
                self.blocks.free(session.request.rid)
            for t in self._tables.values():
                t[slot, :] = 0
            self._dev_tables = None

    def _register_finished(self, session: Session, slot: int) -> None:
        """Register the request's full written blocks on release — prompt
        *and* generated region: positions 0..P+G-2 are written (the last
        sampled token never is), so every full block's contents are final
        and a later prompt extending this one past its prompt shares the
        decode region too."""
        req = session.request
        written = req.prompt.shape[0] + len(session.tokens) - 1
        seq = req.prompt
        if len(session.tokens) > 1:
            seq = np.concatenate(
                [seq, np.asarray(session.tokens[:-1], np.int32)])
        row = self._tables[self._share_cls][slot]
        chain = self._prefix.chain(seq[:written], req.ctx)
        for i, (key, parent, toks) in enumerate(chain):
            bid = int(row[i])
            if self._prefix.register(key, parent, bid, toks):
                self.blocks.set_cached(bid)
        self.stats.prefix_cached_blocks = len(self._prefix)

    def _ctx_for(self, req: Request):
        if req.ctx is not None:
            ctx = jnp.asarray(np.asarray(req.ctx)[None])
            if self.cfg.is_encdec():
                # encode once at admission; chunks consume the frames
                ctx = self._encode_program(self.params, ctx)
            return ctx
        if self.cfg.n_ctx_tokens:
            raise ValueError(
                f"arch {self.cfg.name} needs per-request ctx; request "
                f"{req.rid} has none")
        return None

    def _post_prefill(self, session: Session, slot: int, tok) -> None:
        """Record the prefill-sampled token; the request joins the decode
        batch unless that token already finishes it."""
        with TraceAnnotation("serve.prefill.sync", rid=session.request.rid):
            t = int(np.asarray(tok)[0, 0])
        session.tokens.append(t)
        session.t_first = time.monotonic()
        session.step_first = self._step_idx
        if self.eos_id is not None and t == self.eos_id:
            self._finish(session, "eos")
        elif session.request.max_new_tokens == 1:
            self._finish(session, "length")
        else:
            self._tokens[slot, 0] = t
            self._active[slot] = True

    def resident_devices(self) -> set:
        """Devices holding any leaf of the resident params or decode state."""
        return {d for leaf in jax.tree.leaves((self.params, self._state))
                for d in leaf.devices()}

    @property
    def prefix_caching(self) -> bool:
        """Whether prefix sharing is effectively on for this engine."""
        return self._prefix is not None

    # -- session migration (DESIGN.md §17) -----------------------------------

    def export_session(self, rid: int) -> dict:
        """Lift a live admitted session out of the engine as a flat wire
        tree: paged KV blocks gathered through the slot's table rows,
        per-slot carries, position, generated tokens, chunked-prefill
        progress and timing — everything the destination needs beyond the
        :class:`Request` itself.  Pure read: the slot keeps running until
        :meth:`release_migrated`.  Every leaf shape is a function of
        (cfg, engine geometry, request) only, so the destination can derive
        the restore spec via :meth:`export_spec` without trusting the wire.
        """
        session = self.sessions[rid]
        slot = session.slot
        if slot is None or slot not in self.pool.active:
            raise RuntimeError(
                f"request {rid} is not admitted; queued sessions migrate by "
                "resubmission, finished ones by their tokens")
        prog = self._prefilling.get(slot)
        rows = {c: jnp.asarray(t[slot]) for c, t in self._tables.items()}
        payload = self._export_program(self._state, jnp.int32(slot), rows)
        req = session.request
        toks = np.zeros((req.max_new_tokens,), np.int32)
        toks[:len(session.tokens)] = session.tokens
        meta = np.array([
            req.rid, req.max_new_tokens, len(session.tokens),
            int(self._active[slot]), int(prog is not None),
            prog.next_chunk if prog is not None else 0,
            prog.skip if prog is not None else 0,
            int(self._tokens[slot, 0]),
        ], np.int64)

        def _t(v):
            return np.nan if v is None else float(v)
        times = np.array([session.t_submit, _t(session.t_admit),
                          _t(session.t_first), _t(session.step_first),
                          _t(session.t_done)], np.float64)
        wire = {"meta": meta, "times": times, "tokens": toks,
                "prompt": np.asarray(req.prompt, np.int32),
                "state": jax.tree.map(np.asarray, payload)}
        if req.ctx is not None:
            wire["ctx"] = np.asarray(req.ctx)
        return wire

    def export_spec(self, request: Request) -> dict:
        """Shape/dtype tree of :meth:`export_session`'s wire for this
        engine's geometry — the ``like`` tree the migration checkpoint is
        restored against (shapes come from (cfg, geometry, request), never
        from the stored file)."""
        spec = {
            "meta": jax.ShapeDtypeStruct((8,), np.int64),
            "times": jax.ShapeDtypeStruct((5,), np.float64),
            "tokens": jax.ShapeDtypeStruct((request.max_new_tokens,),
                                           np.int32),
            "prompt": jax.ShapeDtypeStruct(request.prompt.shape, np.int32),
            "state": lm.export_slot_spec(self.cfg, self._state, self._widths),
        }
        if request.ctx is not None:
            c = np.asarray(request.ctx)
            spec["ctx"] = jax.ShapeDtypeStruct(c.shape, c.dtype)
        return spec

    def release_migrated(self, rid: int) -> None:
        """Drop a session whose state has been exported elsewhere: free the
        slot and its blocks without the finish-path side effects (no
        finish_reason, no t_done, no prefix registration — the request is
        still in flight, just not here).  Blocks this request's prefill
        already registered as a donor stay cached: their pool contents are
        untouched by release, so the index's content promise still holds."""
        session = self.sessions.pop(rid)
        slot = session.slot
        self._prefilling.pop(slot, None)
        self.pool.evict(slot)
        self._active[slot] = False
        self._tokens[slot] = 0
        if self.blocks is not None:
            self.blocks.free(rid)
        for t in self._tables.values():
            t[slot, :] = 0
        self._dev_tables = None
        self.stats.migrations_out += 1

    def import_session(self, request: Request, wire: dict) -> Session:
        """Re-admit an exported session token-identically: seat it in a
        free slot, allocate fresh private blocks at this engine's table
        widths, scatter the wire payload, and rebuild host bookkeeping —
        including mid-flight chunked-prefill progress.  Shared/COW prefix
        blocks arrive by value and re-register against *this* engine's
        prefix index as the prefill advances.  Token identity needs the
        same (cfg, s_max, block_size, prefill_chunk, temperature, seed) as
        the source; geometry that differs only in slots/n_blocks is fine
        (the schedule-independent (rid, step) seed contract)."""
        rid = request.rid
        if rid in self.sessions:
            raise ValueError(f"duplicate request id {rid}")
        meta = np.asarray(wire["meta"])
        if int(meta[0]) != rid:
            raise ValueError(
                f"wire is for request {int(meta[0])}, not {rid}")
        if not np.array_equal(np.asarray(wire["prompt"]),
                              np.asarray(request.prompt)):
            raise ValueError(f"request {rid}: wire prompt differs from the "
                             "submitted prompt")
        p_len = request.prompt.shape[0]
        if p_len + request.max_new_tokens - 1 > self.s_max:
            raise ValueError(f"request {rid} does not fit s_max={self.s_max}")
        if not self.pool.free_slots:
            raise RuntimeError("import_session: no free slot")
        per = self._blocks_per_class(p_len, request.max_new_tokens)
        if self.blocks is not None:
            if sum(per.values()) > self.blocks.reclaimable:
                raise RuntimeError("import_session: not enough free blocks")
        n_tok = int(meta[2])
        session = Session(request, t_submit=float(wire["times"][0]))
        session.tokens = [int(t) for t in np.asarray(wire["tokens"])[:n_tok]]

        def _t(v):
            return None if np.isnan(v) else float(v)
        times = np.asarray(wire["times"])
        session.t_admit = _t(times[1])
        session.t_first = _t(times[2])
        session.step_first = (None if np.isnan(times[3])
                              else int(times[3]))
        slot = self.pool.free_slots[0]
        self.pool.place(session, slot)
        self.sessions[rid] = session
        if self.blocks is not None:
            fresh = {c: self._alloc_blocks(rid, n) for c, n in per.items()}
            for c, ids in fresh.items():
                row = self._tables[c][slot]
                row[:] = 0
                row[:len(ids)] = ids
            self.stats.fresh_blocks += sum(len(v) for v in fresh.values())
            self.stats.observe_blocks(self.blocks.in_use)
        self._dev_tables = None
        rows = {c: jnp.asarray(t[slot]) for c, t in self._tables.items()}
        payload = jax.tree.map(jnp.asarray, wire["state"])
        self._state = self._import_program(self._state, jnp.int32(slot),
                                           rows, payload)
        self._tokens[slot, 0] = int(meta[7])
        self._active[slot] = bool(meta[3])
        if bool(meta[4]):           # mid-chunked-prefill: rebuild progress
            skip, next_chunk = int(meta[6]), int(meta[5])
            c = self.prefill_chunk
            n_suffix = p_len - skip
            n_chunks = -(-n_suffix // c)
            padded = np.zeros((n_chunks * c,), np.int32)
            padded[:n_suffix] = request.prompt[skip:]
            chain = ([] if self._prefix is None
                     else self._prefix.chain(request.prompt, request.ctx))
            self._prefilling[slot] = _PrefillProgress(
                session=session, padded=padded, p_len=n_suffix,
                n_chunks=n_chunks, next_chunk=next_chunk,
                ctx=self._ctx_for(request),
                seeds=jnp.asarray([self._seed_for(rid, 0)], jnp.int32),
                rows=self._slot_table_rows(slot), skip=skip, chain=chain)
        self.stats.migrations_in += 1
        return session

    def gather_block(self, bid: int):
        """Host copy of physical block ``bid`` across every shared pool —
        the scrubber's unit of verification for idle cached blocks."""
        out = self._gather_block_program(self._state, jnp.int32(bid))
        return jax.tree.map(np.asarray, out)

    def _prefix_plan(self, req: Request) -> tuple[list[int], int, int | None]:
        """``(shared, skip, cow_src)`` for one request: which cached blocks
        it can map read-only, how many prompt positions that skips, and the
        shared block its first write would land in (the copy-on-write
        source), if any.  Pure lookup — residency changes at admission.

        The divergence block (the registered block extending the matched
        chain, matching ``d >= 0`` further tokens) is mapped whenever at
        least one full block matched or ``d > 0`` — the uniform rule that
        makes "exactly one COW per divergence" hold at block boundaries
        too; a request that matches nothing takes the wholly-fresh path.
        ``skip`` is capped at P-1: the prefill always recomputes at least
        the last prompt position, because it must emit that logit row —
        which also means a full-prompt hit COWs the block holding position
        P-1 rather than writing a donor's block."""
        p_len = req.prompt.shape[0]
        if self._prefix is None:
            return [], 0, None
        ids, n_full, child = self._prefix.lookup(req.prompt, req.ctx)
        shared = list(ids)
        skip = n_full * self.block_size
        if child is not None and (n_full > 0 or child[1] > 0):
            shared.append(child[0])
            skip += child[1]
        skip = min(skip, p_len - 1)
        if skip <= 0:
            return [], 0, None
        w0 = skip // self.block_size
        cow = shared[w0] if w0 < len(shared) else None
        return shared, skip, cow

    def _fresh_needed(self, req: Request,
                      plan: tuple[list[int], int, int | None]) -> dict:
        """Fresh-block need per table class given a prefix plan: shared
        blocks cost nothing, the COW target costs one extra."""
        shared, _, cow = plan
        per = self._blocks_per_class(req.prompt.shape[0], req.max_new_tokens)
        if shared:
            per = dict(per)
            per[self._share_cls] -= len(shared) - (1 if cow is not None else 0)
        return per

    def _alloc_blocks(self, rid: int, n: int) -> list[int]:
        """Alloc with eviction: when the free list runs short, reclaim the
        LRU idle cached blocks and drop their index entries (the admission
        gate already checked free + idle covers the need)."""
        short = n - self.blocks.available
        if short > 0:
            for bid in self.blocks.evict_idle(short):
                self._prefix.drop_block(bid)
                self.stats.prefix_evictions += 1
            self.stats.prefix_cached_blocks = len(self._prefix)
        return self.blocks.alloc(rid, n)

    def _head_plan(self, req: Request) -> tuple[list[int], int, int | None]:
        """The FIFO head's prefix plan, memoized on (rid, index
        generation): a head blocked on blocks or slots is re-polled every
        engine step, and the plan — an O(P) chain hash plus child scans —
        only changes when the index does (revival/idling of blocks moves
        residency tiers, never index contents)."""
        if self._prefix is None:
            return self._prefix_plan(req)
        tag = (req.rid, self._prefix.generation)
        if self._plan_cache is None or self._plan_cache[0] != tag:
            self._plan_cache = (tag, self._prefix_plan(req))
        return self._plan_cache[1]

    def _admissible(self) -> tuple | None:
        """The FIFO head's prefix plan when it can be admitted, else None.
        OOM backpressure gates on *fresh* blocks needed (shared blocks are
        free) against free + evictable-idle — the head waits, no skipping
        (determinism and no starvation).

        Idle blocks the plan itself shares don't count as evictable: admit
        revives them (refcount 1) before allocating, so they can't also
        cover the fresh need.  When that deficit is the only thing blocking
        the head and nothing is in flight — no active request will ever
        free another block, so waiting would deadlock — the head degrades
        to a wholly-fresh plan, which :meth:`submit`'s capacity check
        guarantees fits once the idle tier is evicted."""
        head = self.pool.peek()
        if head is None or not self.pool.free_slots:
            return None
        plan = self._head_plan(head.request)
        if self.blocks is None:
            return plan
        need = sum(self._fresh_needed(head.request, plan).values())
        revived = sum(1 for b in plan[0] if self.blocks.is_idle(b))
        if need <= self.blocks.reclaimable - revived:
            return plan
        if plan[0] and not self.pool.active:
            fresh = ([], 0, None)
            n = sum(self._fresh_needed(head.request, fresh).values())
            if n <= self.blocks.reclaimable:
                return fresh
        return None

    def _slot_table_rows(self, slot: int) -> dict:
        return {c: jnp.asarray(t[slot:slot + 1])
                for c, t in self._tables.items()}

    def _admit(self) -> None:
        """Fill free slots from the queue: map the request's shared
        prefix blocks read-only, reserve fresh blocks for the remainder
        (evicting idle cached blocks LRU-first under pressure), COW the
        divergence block if the first write would land in shared cache, and
        queue the chunked prefill of the unshared suffix.  The chunks
        themselves are dispatched by :meth:`_prefill_step` — ONE per engine
        step per admitting slot — so a long prompt interleaves with the
        decode batch in bounded ``prefill_chunk``-sized slices instead of
        blocking it head-of-line."""
        while (head := self.pool.peek()) is not None and self.pool.free_slots:
            with TraceAnnotation("serve.admit", rid=head.request.rid):
                plan = self._admissible()
                if plan is None:
                    return
                self._admit_head(plan)

    def _admit_head(self, plan: tuple) -> None:
        """Seat the FIFO head under its prefix ``plan``."""
        session, slot = self.pool.admit()
        req = session.request
        session.t_admit = time.monotonic()
        p_len = req.prompt.shape[0]
        shared, skip, cow_src = plan
        if self.blocks is not None:
            if shared:
                self.blocks.share(req.rid, shared)
            fresh = {cls_name: self._alloc_blocks(req.rid, n)
                     for cls_name, n in
                     self._fresh_needed(req, plan).items()}
            for cls_name, ids in fresh.items():
                row = self._tables[cls_name][slot]
                row[:] = 0
                if cls_name == self._share_cls and shared:
                    row[:len(shared)] = shared
                    tail = ids
                    if cow_src is not None:
                        # repoint the first-write block at a private
                        # copy; the device copy below runs before any
                        # subsequently dispatched program can write it
                        row[skip // self.block_size] = ids[0]
                        tail = ids[1:]
                    row[len(shared):len(shared) + len(tail)] = tail
                else:
                    row[:len(ids)] = ids
            self._dev_tables = None
            if cow_src is not None:
                self._state = self._cow_program(
                    self._state, jnp.int32(cow_src),
                    jnp.int32(fresh[self._share_cls][0]))
                self.blocks.drop(req.rid, cow_src)
                self.stats.cow_copies += 1
            self.stats.fresh_blocks += sum(len(v) for v in fresh.values())
            self.stats.observe_blocks(self.blocks.in_use)
        self.stats.prompt_tokens += p_len
        if shared:
            self.stats.prefix_hits += 1
            self.stats.prefix_shared_blocks += len(shared)
            self.stats.prefix_tokens += skip
        c = self.prefill_chunk
        n_suffix = p_len - skip
        n_chunks = -(-n_suffix // c)
        padded = np.zeros((n_chunks * c,), np.int32)
        padded[:n_suffix] = req.prompt[skip:]
        chain = ([] if self._prefix is None
                 else self._prefix.chain(req.prompt, req.ctx))
        self._prefilling[slot] = _PrefillProgress(
            session=session, padded=padded, p_len=n_suffix,
            n_chunks=n_chunks, next_chunk=0, ctx=self._ctx_for(req),
            seeds=jnp.asarray([self._seed_for(req.rid, 0)], jnp.int32),
            rows=self._slot_table_rows(slot), skip=skip, chain=chain)
        self.stats.prefills += 1

    def _register_upto(self, prog: _PrefillProgress, slot: int,
                       n_done: int) -> None:
        """Register the prompt's first ``n_done`` full blocks (those wholly
        covered by dispatched chunks) in the prefix index.  Device programs
        execute in dispatch order, so by the time any later-admitted
        sharer's gather runs, the content the key promises is in place —
        this is what lets a request share with a *still-prefilling* donor
        (the mid-prefill divergence case).  Already-registered keys (the
        blocks this request itself shares) no-op via keep-first."""
        row = self._tables[self._share_cls][slot]
        n = min(n_done, len(prog.chain))
        while prog.registered < n:
            key, parent, toks = prog.chain[prog.registered]
            bid = int(row[prog.registered])
            if self._prefix.register(key, parent, bid, toks):
                self.blocks.set_cached(bid)
            prog.registered += 1
        self.stats.prefix_cached_blocks = len(self._prefix)

    def _prefill_step(self) -> None:
        """Advance every in-flight chunked prefill by exactly one chunk;
        a prompt that finishes joins the decode batch this same step."""
        for slot in sorted(self._prefilling):
            prog = self._prefilling[slot]
            c = self.prefill_chunk
            j = prog.next_chunk
            with TraceAnnotation("serve.prefill.dispatch",
                                 rid=prog.session.request.rid, chunk=j):
                piece = jnp.asarray(prog.padded[None, j * c:(j + 1) * c])
                n_valid = min(c, prog.p_len - j * c)
                tok, self._state = self._chunk_program(
                    self.params, piece, self._state, jnp.int32(slot),
                    jnp.int32(n_valid), prog.rows, prog.ctx,
                    jnp.asarray(j == 0), jnp.int32(prog.skip), self._key,
                    prog.seeds)
            self.stats.prefill_chunks += 1
            prog.next_chunk += 1
            if self._prefix is not None:
                done = prog.skip + min((j + 1) * c, prog.p_len)
                self._register_upto(prog, slot, done // self.block_size)
            if prog.next_chunk == prog.n_chunks:
                del self._prefilling[slot]
                self._post_prefill(prog.session, slot, tok)

    def _device_tables(self) -> dict:
        if self._dev_tables is None:
            self._dev_tables = {c: jnp.asarray(t)
                                for c, t in self._tables.items()}
        return self._dev_tables

    def _decode_args(self) -> tuple:
        """The paged decode program's arguments for the live batch."""
        return (self.params, jnp.asarray(self._tokens), self._state,
                self._device_tables(), jnp.asarray(self._active), self._key,
                jnp.zeros((self.slots,), jnp.int32))

    def decode_roofline(self) -> dict:
        """AOT roofline audit of this engine's decode step (nothing runs).

        Re-traces the paged decode step side-effect-free on abstract avals
        (so ``decode_traces``, which pins real program compilations, is
        untouched), compiles it ahead-of-time, and returns the
        ``analysis.roofline`` dict augmented with the analytic per-step
        byte floor (``roofline_bytes``), ``achieved_bytes`` and the jaxpr
        ``dispatches`` count.  The serve benchmarks render this via
        ``report.serve_decode_row``; the fused/unfused comparison is the
        same engine audited under each ``REPRO_FUSED_DECODE`` setting.
        """
        from repro.roofline import analysis
        cfg, temperature = self.cfg, self.temperature

        def fn(params, tokens, state, tables, active, key, seeds):
            logits, state, _ = lm.paged_decode_step(cfg, params, tokens,
                                                    state, tables,
                                                    active=active)
            return (_sample_tokens(cfg, logits, key, seeds, temperature),
                    state)

        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self._decode_args())
        dispatches = analysis.dispatch_count(jax.make_jaxpr(fn)(*args))
        r = analysis.roofline(jax.jit(fn).lower(*args).compile())
        param_bytes = sum(x.size * jnp.dtype(x.dtype).itemsize
                          for x in jax.tree_util.tree_leaves(self.params))
        kv_itemsize = (1 if cfg.kv_cache_dtype == "i8"
                       else jnp.dtype(cfg.dtype).itemsize)
        r["roofline_bytes"] = analysis.decode_roofline_bytes(
            param_bytes=param_bytes, widths=self._widths,
            layers_per_class=lm.paged_decode_layer_classes(cfg),
            slots=self.slots, block_size=self.block_size,
            n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            kv_itemsize=kv_itemsize)
        r["achieved_bytes"] = r["hlo_bytes_per_chip"]
        r["dispatches"] = dispatches
        return r

    def _count_decode(self, rows: dict) -> None:
        """One decode step's counters: the rows that decode a token and
        the context each attends, the table blocks the attention kernel's
        walk reads for them and those holding their attended keys (once
        per table class, as each layer of a class repeats its walk); the
        KV blocks live requests hold, and those among them that hold a
        written token (a block several rows share counts once)."""
        st = self.stats
        ctx = np.array([s.request.prompt.shape[0] + len(s.tokens)
                        for s in rows.values()], np.int64)
        st.decode_rows += len(rows)
        st.decode_ctx_tokens += int(ctx.sum())
        if self.blocks is None:
            return
        for w, p, window in self._walks.values():
            read, live = paged_attn.walk_blocks(
                ctx - 1, p=p, bs=self.block_size, w=w, window=window)
            st.decode_kv_blocks_read += int(np.sum(read))
            st.decode_kv_blocks_live += int(np.sum(live))
        st.observe_blocks(self.blocks.in_use)
        st.kv_reserved_block_steps += self.blocks.in_use
        written = np.zeros((self.slots, 1), np.int64)
        for slot, sess in self.pool.active.items():
            prog = self._prefilling.get(slot)
            written[slot] = (
                sess.request.prompt.shape[0] + len(sess.tokens) if prog is None
                else prog.skip + min(prog.next_chunk * self.prefill_chunk,
                                     prog.p_len))
        n = -(-written // self.block_size)
        ids = np.unique(np.concatenate(
            [t[np.arange(t.shape[1]) < n] for t in self._tables.values()]))
        st.kv_live_block_steps += int(np.count_nonzero(ids))

    def _decode_once(self) -> None:
        """One batched decode step; append/evict per active slot (slots
        still mid-prefill ride along inertly and are skipped here)."""
        with TraceAnnotation("serve.decode.prepare"):
            active_sessions = {s: sess for s, sess in self.pool.active.items()
                               if s not in self._prefilling}
            seeds = np.zeros((self.slots,), np.int32)
            for slot, sess in active_sessions.items():
                seeds[slot] = self._seed_for(sess.request.rid,
                                             len(sess.tokens))
            args = (*self._decode_args()[:-1], jnp.asarray(seeds))
        if self._turn_ns is not None:
            self.stats.host_turns += 1
            self.stats.host_turn_ns += (self._turn_ns + time.perf_counter_ns()
                                        - self._t_mark)
        with TraceAnnotation("serve.decode.dispatch"):
            toks, self._state, *counts = self._paged_decode_program(*args)
        self.stats.decode_steps += 1
        self._count_decode(active_sessions)       # while the device runs
        with TraceAnnotation("serve.decode.sync"):
            toks = np.asarray(toks)                 # the per-step sync point
            if counts:
                self.stats.count_experts(np.asarray(counts[0]))
        self._turn_ns, self._t_mark = 0, time.perf_counter_ns()
        with TraceAnnotation("serve.decode.commit"):
            for slot, sess in active_sessions.items():
                t = int(toks[slot, 0])
                sess.tokens.append(t)
                self._tokens[slot, 0] = t
                if self.eos_id is not None and t == self.eos_id:
                    self._finish(sess, "eos")
                elif len(sess.tokens) >= sess.request.max_new_tokens:
                    self._finish(sess, "length")

    def step(self) -> bool:
        """Admit, advance in-flight prefills by one chunk each, then decode
        once; returns False when fully drained."""
        self._t_mark = time.perf_counter_ns()
        self._step_idx += 1
        with StepTraceAnnotation("serve.step", step_num=self._step_idx):
            self._admit()
            if self._prefilling:
                self._prefill_step()
            if any(s not in self._prefilling for s in self.pool.active):
                self._decode_once()
            more = not self.pool.idle()
        if self._turn_ns is not None:     # the caller's time is not ours
            self._turn_ns += time.perf_counter_ns() - self._t_mark
        return more

    def run(self) -> ServeReport:
        """Drain queue + slots; returns the per-request report."""
        t0 = time.monotonic()
        while self.step():
            pass
        return ServeReport(sessions=dict(self.sessions),
                           wall=time.monotonic() - t0,
                           decode_steps=self.stats.decode_steps,
                           prefills=self.stats.prefills,
                           stats=self.stats)
