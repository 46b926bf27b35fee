"""Live session migration, step by step (DESIGN.md §17).

A session decoding on engine A is frozen mid-flight, serialized to an
encrypted checkpoint (`export_session` -> `ckpt.save`), restored on a
brand-new engine B against a spec B derives from nothing but the request
(`export_spec` -> `ckpt.restore` -> `import_session`), and finished
there.  Because sampling folds only ``(rid, token index)`` and the wire
carries the session's exact device state — paged KV blocks by table
row, position, chunked-prefill progress — the stitched token stream is
bit-identical to a run that never moved.  The same mechanics power the
replica router's kill drill (``benchmarks/serve_replicated.py``).

Run:  PYTHONPATH=src python examples/serve_migrate.py
"""

import tempfile

import jax

import repro.configs as configs
from repro.checkpoint import ckpt
from repro.models import lm
from repro.serve import Request, Router, ServeEngine, synthetic_trace

cfg = configs.get("qwen3-4b").smoke()
params = lm.init_params(cfg, jax.random.PRNGKey(0))
trace = synthetic_trace(3, cfg.vocab, seed=5, prompt_lens=(6, 10),
                        new_tokens=(8, 12))
KW = dict(slots=2, s_max=32, seed=0)

# --- 1. the baseline: one engine, never migrated ----------------------------

base = ServeEngine(cfg, params, **KW)
for r in trace:
    base.submit(r)
base_rep = base.run()
want = {r.rid: list(base_rep.tokens(r.rid)) for r in trace}

# --- 2. freeze mid-decode, ship the encrypted wire, resume elsewhere --------

a = ServeEngine(cfg, params, **KW)
for r in trace:
    a.submit(r)
for _ in range(4):                      # a few decode steps: rid 0 is
    a.step()                            # mid-flight, tokens half-generated
rid = 0
done_before = len(a.sessions[rid].tokens)

with tempfile.TemporaryDirectory(prefix="mig_") as d:
    wire = a.export_session(rid)        # pure read of A's device state
    ckpt.save(d, 1, wire, root_key="demo-key")

    b = ServeEngine(cfg, params, **KW)  # fresh engine, empty pools
    req = next(r for r in trace if r.rid == rid)
    like = b.export_spec(req)           # shapes from (cfg, geometry, req)
    restored, _ = ckpt.restore(d, 1, like, root_key="demo-key")
    b.import_session(req, restored)
    a.release_migrated(rid)             # A frees the slot + blocks

rep_a, rep_b = a.run(), b.run()         # both engines drain independently
got = {r.rid: list((rep_b if r.rid == rid else rep_a).tokens(r.rid))
       for r in trace}
print(f"migrated rid {rid} after {done_before}/{len(want[rid])} tokens; "
      f"resumed on engine B with {len(got[rid]) - done_before} more")
assert got == want, "migration changed tokens"
print(f"all {len(trace)} token streams bit-identical to the "
      f"never-migrated baseline")

# --- 3. the same wire through the replica router's fault drill --------------

trace2 = synthetic_trace(6, cfg.vocab, seed=9, prompt_lens=(5, 8),
                         new_tokens=(6, 9))
single = ServeEngine(cfg, params, **KW)
for r in trace2:
    single.submit(r)
want2 = single.run()

with tempfile.TemporaryDirectory(prefix="mig_") as d:
    router = Router(cfg, params, 2, slots=2, s_max=32, seed=0,
                    ckpt_dir=d, epoch_steps=4)
    for r in trace2:
        router.submit(r)
    rep = router.run(kill_at=5)         # kill the most-loaded replica
div = [r.rid for r in trace2
       if rep.sessions[r.rid].tokens != want2.sessions[r.rid].tokens]
print(f"router drill: killed replica {rep.killed}, "
      f"{len(rep.migrations)} migration(s), "
      f"{rep.scrub_passes} scrubber pass(es), "
      f"{len(div)} divergent streams")
assert not div and rep.scrub_corruptions == 0
print("kill drill token-identical to the single engine; scrubber clean")
