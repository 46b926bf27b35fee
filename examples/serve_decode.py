"""Batched serving example: prefill + resident-state decode across three
architecture families (dense GQA, recurrent hybrid, enc-dec audio) via the
compatibility ``generate`` API, then the multi-request continuous-batching
engine directly — heterogeneous prompts/budgets sharing one resident batch,
with packed-weight residency on a binary (+xnor) arch, and finally
content-addressed prefix caching over the block-paged KV cache on a
shared-system-prompt trace.

Run:  PYTHONPATH=src python examples/serve_decode.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.models import lm
from repro.serve import Request, ServeEngine, synthetic_trace
from repro.train import serve_step

# --- 1. static-batch compatibility API (wraps the engine) -------------------

for arch in ["qwen3-4b", "recurrentgemma-2b", "whisper-tiny"]:
    cfg = configs.get(arch).smoke()
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    B, P, N = 4, 24, 12
    prompt = jax.random.randint(key, (B, P), 0, cfg.vocab)
    ctx = None
    if cfg.n_ctx_tokens:
        ctx = jax.random.normal(key, (B, cfg.n_ctx_tokens, cfg.d_model),
                                jnp.float32) * 0.1
    t0 = time.time()
    out = serve_step.generate(cfg, params, prompt, N, ctx=ctx,
                              temperature=0.8, key=key)
    dt = time.time() - t0
    print(f"{arch:20s} batch={B} prompt={P} +{N} tokens "
          f"in {dt:5.1f}s -> sample row: {out[0][:8].tolist()}...")
    assert out.shape == (B, N)
    assert int(out.max()) < cfg.vocab
print("serve path OK for dense / hybrid / enc-dec families")

# --- 2. the multi-request engine API ----------------------------------------
# Mixed prompt lengths and budgets share one resident batch: slots free at
# different times and queued requests are admitted (prefilled) into them
# while the others keep decoding.  On a +xnor arch the engine serves from
# packed weights — the binary filters exist only as uint32 sign-planes.

cfg = configs.get("qwen2-7b+xnor").smoke(dtype=jnp.float32)
params = lm.init_params(cfg, jax.random.PRNGKey(1))
eng = ServeEngine(cfg, params, slots=2, s_max=32, seed=0)
trace = synthetic_trace(6, cfg.vocab, seed=7, prompt_lens=(5, 9, 14),
                        new_tokens=(3, 6, 9))
for r in trace:
    eng.submit(r)
report = eng.run()
lat = report.latency_quantiles((0.5, 0.95))
print(f"engine: {len(trace)} requests over 2 slots -> "
      f"{report.generated} tokens, {report.tok_per_s:.1f} tok/s, "
      f"p50={lat[0.5]*1e3:.0f}ms p95={lat[0.95]*1e3:.0f}ms")
for r in trace:
    toks = report.tokens(r.rid)
    assert toks.shape[0] == r.max_new_tokens
    assert int(toks.max()) < cfg.vocab

# a fresh engine over the same trace reproduces the same tokens: sampling
# keys depend on (request, step), never on slot assignment
eng2 = ServeEngine(cfg, params, slots=3, s_max=32, seed=0)
for r in synthetic_trace(6, cfg.vocab, seed=7, prompt_lens=(5, 9, 14),
                         new_tokens=(3, 6, 9)):
    eng2.submit(r)
report2 = eng2.run()
assert all(np.array_equal(report.tokens(r.rid), report2.tokens(r.rid))
           for r in trace)
print("engine OK: deterministic across slot counts, packed-resident weights")

# --- 3. prefix caching on the block-paged engine -----------------------------
# 90% of requests open with the same 48-token "system prompt".  The paged
# engine content-hashes each full prompt block; later requests map the
# cached blocks read-only, skip their prefill chunks, and copy-on-write
# the divergence block before their first scatter.  Tokens stay
# bit-identical to an uncached engine — sharing reuses the exact KV the
# first request wrote.

cfg = configs.get("qwen3-4b").smoke()
params = lm.init_params(cfg, jax.random.PRNGKey(2))
# prefix ends mid-block, so every sharer's first write lands in a cached
# block and must copy-on-write it first
shared = synthetic_trace(6, cfg.vocab, seed=11, prompt_lens=(4, 7),
                         new_tokens=(3, 5), prefix_frac=0.9,
                         prefix_len=6 * cfg.block_size + 3)
reports = {}
for on in (True, False):
    eng3 = ServeEngine(cfg, params, slots=2, s_max=64, seed=0, n_blocks=40,
                       prefix_cache=on)
    for r in shared:
        eng3.submit(r)
    reports[on] = eng3.run()
assert all(np.array_equal(reports[True].tokens(r.rid),
                          reports[False].tokens(r.rid)) for r in shared)
st = reports[True].stats
print(f"prefix cache: hit rate {st.prefix_hit_rate:.0%} of prompt tokens, "
      f"{st.blocks_per_request:.1f} fresh blocks/request "
      f"(vs {reports[False].stats.blocks_per_request:.1f} uncached), "
      f"{st.cow_copies} copy-on-write copies — tokens identical to the "
      f"uncached engine")
