"""Replicated serving tier with verified live session migration
(DESIGN.md §17).

:class:`Router` fronts N :class:`repro.serve.engine.ServeEngine` replicas,
each pinned to its own ``launch.mesh`` sub-mesh (one device slice per
replica — genuinely side-by-side under the multi-device CI mode) and
watched by a :class:`repro.distributed.fault.StragglerPolicy` fed the
replica's per-step wall time:

* **admission** routes each request to the least-loaded alive replica
  (in-flight + queued; ties to the lowest index — deterministic);
* **migration** moves a *live* session between replicas through an
  encrypted checkpoint: the source engine's :meth:`export_session` wire
  tree is written with :func:`repro.checkpoint.ckpt.save` (first hop) or
  :func:`~repro.checkpoint.ckpt.save_delta` (later hops — unchanged
  leaves such as the prompt, modality ctx and any still-identical KV
  prefix resolve through the chain instead of being re-stored), and the
  destination restores against a spec derived from (cfg, geometry,
  request) — never from the file — then :meth:`import_session` re-admits
  it token-identically under the schedule-independent (rid, step)
  seed-folding contract;
* **kill drill**: :meth:`kill` marks a replica dead, resubmits its queued
  sessions, and drains every admitted session onto surviving replicas via
  migration checkpoints, stepping the survivors when they are momentarily
  full — every in-flight request finishes with zero token divergence;
* a background **integrity scrubber** (:class:`IntegrityScrubber`) walks
  each replica every router epoch: an incremental
  :class:`repro.core.incremental.DigestCache` pass over the resident
  packed weights (identity tier: zero dispatch while nothing changed) and
  over idle cached KV blocks (baselined per (block, idle-stamp) so a
  legitimately recycled block is re-baselined, not flagged), surfacing
  mismatches in ``EngineStats.scrub_corruptions``.
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np
import jax

from repro.checkpoint import ckpt
from repro.core.incremental import DigestCache
from repro.core.verify import leaf_key
from repro.distributed.fault import StragglerPolicy
from repro.launch.mesh import make_replica_meshes
from repro.models import lm
from repro.serve.engine import ServeEngine
from repro.serve.session import Request, Session
from repro.serve.stats import EngineStats, ServeReport


class IntegrityScrubber:
    """Background digest verification of one replica's resident state.

    Weights: the first pass records a per-leaf digest baseline of the
    engine's (packed) params through a :class:`DigestCache`; later passes
    re-digest — the cache's identity tier makes an unchanged pass free —
    and any digest that moved against the baseline is a corruption (the
    params of a serving engine are immutable by contract).

    Idle cached KV blocks: each idle block's pool contents are digested
    and baselined per ``(bid, idle_stamp)``; while the block stays in the
    idle tier its bytes must not move (nothing may write a cached block —
    DESIGN.md §15), so a moved digest is a corruption.  A block that was
    revived, rewritten by a new holder and re-idled carries a new stamp
    and is re-baselined instead of flagged.
    """

    def __init__(self, engine: ServeEngine, cache: DigestCache | None = None):
        self.engine = engine
        self.cache = cache if cache is not None else DigestCache()
        self._weight_baseline: dict[str, bytes] | None = None
        # bid -> (idle stamp, {leaf key: digest bytes})
        self._block_baseline: dict[int, tuple[int, dict[str, bytes]]] = {}

    @staticmethod
    def _flat_digests(tree) -> dict[str, bytes]:
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {leaf_key(p): np.asarray(d).tobytes() for p, d in flat}

    def scrub(self) -> int:
        """One pass; returns mismatches found (also accumulated into the
        engine's ``scrub_*`` counters)."""
        eng, st = self.engine, self.engine.stats
        bad = 0
        digs = self._flat_digests(self.cache.digests(eng.params))
        if self._weight_baseline is None:
            self._weight_baseline = digs
        else:
            bad += sum(1 for k, v in digs.items()
                       if v != self._weight_baseline[k])
        st.scrub_weight_leaves += len(digs)
        if eng.paged and eng.blocks is not None:
            idle = set(eng.blocks.idle_blocks)
            for bid in list(self._block_baseline):
                if bid not in idle:
                    del self._block_baseline[bid]
            for bid in sorted(idle):
                stamp = eng.blocks.idle_stamp(bid)
                digs = self._flat_digests(
                    self.cache.digests({f"idle_block/{bid}":
                                        eng.gather_block(bid)}))
                base = self._block_baseline.get(bid)
                if base is not None and base[0] == stamp:
                    bad += sum(1 for k, v in digs.items() if v != base[1][k])
                else:
                    self._block_baseline[bid] = (stamp, digs)
            st.scrub_idle_blocks += len(idle)
        st.scrub_passes += 1
        st.scrub_corruptions += bad
        return bad


@dataclasses.dataclass
class ReplicaHandle:
    """One replica: engine + sub-mesh + its fault-detection state."""

    index: int
    engine: ServeEngine
    mesh: object                       # this replica's launch.mesh sub-mesh
    device: object                     # mesh.devices.flat[0]: where it runs
    policy: StragglerPolicy
    scrubber: IntegrityScrubber
    alive: bool = True

    @property
    def load(self) -> int:
        """In-flight + queued — the admission routing metric."""
        return len(self.engine.pool.active) + self.engine.pool.queued

    def can_accept(self, request: Request) -> bool:
        """Whether an import/submit of ``request`` fits right now (free
        slot, and a wholly-fresh block reservation fits the pool)."""
        eng = self.engine
        if not eng.pool.free_slots:
            return False
        if eng.blocks is None:
            return True
        need = sum(eng._blocks_per_class(request.prompt.shape[0],
                                         request.max_new_tokens).values())
        return need <= eng.blocks.reclaimable


@dataclasses.dataclass
class RouterReport:
    """Outcome of one :meth:`Router.run`: the merged session map plus
    per-replica stats and the migration/fault event log."""

    sessions: dict[int, Session]
    wall: float
    replicas: list[EngineStats]
    migrations: list[tuple[int, int, int, int]]   # (rid, src, dst, ckpt step)
    straggler_events: list[tuple[int, int, str]]  # (router step, replica, verdict)
    killed: list[int]

    @property
    def generated(self) -> int:
        return sum(len(s.tokens) for s in self.sessions.values())

    @property
    def tok_per_s(self) -> float:
        return self.generated / max(self.wall, 1e-9)

    def tokens(self, rid: int) -> np.ndarray:
        return np.asarray(self.sessions[rid].tokens, np.int32)

    @property
    def scrub_passes(self) -> int:
        return sum(r.scrub_passes for r in self.replicas)

    @property
    def scrub_corruptions(self) -> int:
        return sum(r.scrub_corruptions for r in self.replicas)

    def serve_report(self) -> ServeReport:
        """The merged sessions as a :class:`ServeReport` so the quantile
        helpers (latency / ttft / queue-wait) apply across replicas."""
        agg = EngineStats()
        for r in self.replicas:
            for f in ("decode_steps", "prefills", "prefill_chunks",
                      "migrations_out", "migrations_in", "scrub_passes",
                      "scrub_weight_leaves", "scrub_idle_blocks",
                      "scrub_corruptions", "prefix_hits", "prefix_tokens",
                      "prompt_tokens", "fresh_blocks", "cow_copies"):
                setattr(agg, f, getattr(agg, f) + getattr(r, f))
        return ServeReport(sessions=dict(self.sessions), wall=self.wall,
                           decode_steps=agg.decode_steps,
                           prefills=agg.prefills, stats=agg)


class Router:
    """N-replica serving tier with live migration (DESIGN.md §17).

    Every replica is a full :class:`ServeEngine` over the *same* (cfg,
    params, s_max, block_size, prefill_chunk, temperature, seed) — the
    migration token-identity contract — pinned to its own sub-mesh
    device.  ``slots`` / ``n_blocks`` are per-replica and may differ from
    the source at import time without affecting tokens (the seed contract
    is schedule-independent).

    ``ckpt_dir`` is where migration wires land, one directory per request
    (``rid_<rid>/``), encrypted under ``root_key``; successive migrations
    of the same request extend a delta chain.  ``epoch_steps`` sets the
    scrubber cadence in router steps (0 disables).
    """

    def __init__(self, cfg, params, n_replicas: int, *, slots: int,
                 s_max: int, ckpt_dir: str, root_key: str = "serve-mig",
                 epoch_steps: int = 8, eos_id: int | None = None,
                 temperature: float = 0.0, seed: int = 0, pack: bool = True,
                 block_size: int = 0, prefill_chunk: int = 0,
                 n_blocks: int = 0, prefix_cache: bool = True,
                 straggler_factor: float = 2.0):
        if n_replicas < 1:
            raise ValueError(f"need at least one replica, got {n_replicas}")
        self.ckpt_dir = ckpt_dir
        self.root_key = root_key
        self.epoch_steps = int(epoch_steps)
        self.replicas: list[ReplicaHandle] = []
        meshes = make_replica_meshes(n_replicas)
        # pack once, then copy the packed tree to each replica's device: a
        # committed array ignores jax.default_device, so only an explicit
        # device_put moves params and resident state off the first device
        resident = lm.pack_params(cfg, params) if pack else params
        for i, mesh in enumerate(meshes):
            dev = mesh.devices.flat[0]
            eng = ServeEngine(cfg, resident, slots=slots, s_max=s_max,
                              eos_id=eos_id, temperature=temperature,
                              seed=seed, pack=pack, paged=True,
                              block_size=block_size,
                              prefill_chunk=prefill_chunk,
                              n_blocks=n_blocks,
                              prefix_cache=prefix_cache, device=dev)
            self.replicas.append(ReplicaHandle(
                index=i, engine=eng, mesh=mesh, device=dev,
                policy=StragglerPolicy(straggler_factor=straggler_factor),
                scrubber=IntegrityScrubber(eng)))
        self._requests: dict[int, Request] = {}
        self._where: dict[int, int] = {}          # rid -> replica index
        self._mig_step: dict[int, int] = {}       # rid -> last ckpt step
        self._mig_cache: dict[int, DigestCache] = {}
        self._step = 0
        self.migrations: list[tuple[int, int, int, int]] = []
        self.straggler_events: list[tuple[int, int, str]] = []
        self.killed: list[int] = []

    # -- admission -----------------------------------------------------------

    def _alive(self) -> list[ReplicaHandle]:
        return [h for h in self.replicas if h.alive]

    def submit(self, request: Request) -> Session:
        """Route to the least-loaded alive replica (ties: lowest index)."""
        alive = self._alive()
        if not alive:
            raise RuntimeError("no alive replica")
        h = min(alive, key=lambda h: (h.load, h.index))
        session = h.engine.submit(request)
        self._requests[request.rid] = request
        self._where[request.rid] = h.index
        return session

    # -- the step loop -------------------------------------------------------

    def step(self) -> bool:
        """Advance every alive replica one engine step, feed each step's
        wall time to its straggler policy, and scrub on epoch boundaries.
        Returns False once every alive replica is drained."""
        self._step += 1
        busy = False
        for h in self._alive():
            if h.engine.pool.idle():
                continue
            t0 = time.monotonic()
            with jax.default_device(h.device):
                busy |= h.engine.step()
            verdict = h.policy.observe(self._step, time.monotonic() - t0)
            if verdict != "ok":
                self.straggler_events.append((self._step, h.index, verdict))
        if self.epoch_steps and self._step % self.epoch_steps == 0:
            self.scrub()
        return busy

    def scrub(self) -> int:
        """One scrubber pass over every alive replica; returns mismatches."""
        bad = 0
        for h in self._alive():
            with jax.default_device(h.device):
                bad += h.scrubber.scrub()
        return bad

    # -- migration -----------------------------------------------------------

    def _wire_dir(self, rid: int) -> str:
        return os.path.join(self.ckpt_dir, f"rid_{rid}")

    def migrate(self, rid: int, src: int, dst: int) -> Session:
        """Move live session ``rid`` from replica ``src`` to ``dst``
        through an encrypted (delta) checkpoint.  The source slot is
        released only after the wire is durably written; the destination
        restores against its own derived spec and re-admits
        token-identically."""
        if src == dst:
            raise ValueError(f"migrate({rid}): src == dst == {src}")
        hs, hd = self.replicas[src], self.replicas[dst]
        if not hd.alive:
            raise RuntimeError(f"migrate({rid}): replica {dst} is dead")
        request = self._requests[rid]
        if not hd.can_accept(request):
            raise RuntimeError(f"migrate({rid}): replica {dst} is full")
        with jax.default_device(hs.device):
            wire = hs.engine.export_session(rid)
        d = self._wire_dir(rid)
        step = self._mig_step.get(rid, 0) + 1
        cache = self._mig_cache.setdefault(rid, DigestCache())
        if step == 1:
            ckpt.save(d, step, wire, root_key=self.root_key)
            cache.digests(wire)        # prime: exact dirtiness on hop 2
            cache.mark_saved()
        else:
            # delta vs the previous hop: the prompt, ctx and any KV
            # prefix blocks identical since the last migration resolve
            # through the chain instead of being re-stored
            ckpt.save_delta(d, step, wire, root_key=self.root_key,
                            cache=cache)
        self._mig_step[rid] = step
        with jax.default_device(hd.device):
            like = hd.engine.export_spec(request)
            restored, _ = ckpt.restore(d, step, like, root_key=self.root_key)
            session = hd.engine.import_session(request, restored)
        hs.engine.release_migrated(rid)
        self._where[rid] = dst
        self.migrations.append((rid, src, dst, step))
        return session

    # -- fault drill ---------------------------------------------------------

    def kill(self, index: int) -> None:
        """Kill-a-replica drill: mark ``index`` dead, resubmit its queued
        sessions to the survivors, and drain every admitted session onto
        them via migration checkpoints — stepping the survivors forward
        whenever none can momentarily accept (finishing requests free
        slots and blocks, so the drain always makes progress)."""
        h = self.replicas[index]
        if not h.alive:
            raise RuntimeError(f"replica {index} is already dead")
        if len(self._alive()) < 2:
            raise RuntimeError("kill(): no surviving replica to drain onto")
        h.alive = False
        self.killed.append(index)
        for sess in h.engine.pool.drain_queue():
            rid = sess.request.rid
            del h.engine.sessions[rid]
            new = self.submit(sess.request)
            new.t_submit = sess.t_submit   # queue time survives the reroute
        admitted = sorted(s.request.rid
                          for s in h.engine.pool.active.values())
        for rid in admitted:
            dst = self._await_capacity(self._requests[rid])
            self.migrate(rid, index, dst.index)

    def _await_capacity(self, request: Request,
                        max_steps: int = 100_000) -> ReplicaHandle:
        """The least-loaded alive replica that can accept ``request``,
        stepping the alive replicas until one can."""
        for _ in range(max_steps):
            fits = [h for h in self._alive() if h.can_accept(request)]
            if fits:
                return min(fits, key=lambda h: (h.load, h.index))
            if not self.step():
                break     # everyone drained yet nobody fits: impossible
        raise RuntimeError(
            f"no replica can accept request {request.rid} "
            f"(prompt {request.prompt.shape[0]}, "
            f"budget {request.max_new_tokens})")

    # -- drive to completion -------------------------------------------------

    def run(self, kill_at: int | None = None,
            victim: int | None = None) -> RouterReport:
        """Drain every replica; with ``kill_at`` set, run the fault drill
        at that router step (victim defaults to the most-loaded replica —
        the worst case for the survivors)."""
        t0 = time.monotonic()
        while True:
            if kill_at is not None and self._step + 1 >= kill_at \
                    and len(self._alive()) > 1:
                v = victim if victim is not None else max(
                    self._alive(), key=lambda h: (h.load, -h.index)).index
                self.kill(v)
                kill_at = None
            if not self.step():
                break
        sessions = {rid: self.replicas[idx].engine.sessions[rid]
                    for rid, idx in self._where.items()}
        return RouterReport(sessions=sessions,
                            wall=time.monotonic() - t0,
                            replicas=[h.engine.stats for h in self.replicas],
                            migrations=list(self.migrations),
                            straggler_events=list(self.straggler_events),
                            killed=list(self.killed))
