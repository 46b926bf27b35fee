"""``generate``: the historical static-batch generation API, kept as a
compatibility wrapper over the continuous-batching engine
(:mod:`repro.serve`), which serves it on its paged programs.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def generate(cfg, params, prompt: jnp.ndarray, n_new: int,
             ctx: jnp.ndarray | None = None, temperature: float = 0.0,
             key=None):
    """Greedy/temperature generation — compatibility wrapper.

    Each prompt row becomes one engine request (a single-trace B-request
    run); greedy outputs are token-identical to the per-request
    ``lm.prefill`` + ``lm.decode_step`` loop (per-row math is
    batch-composition independent).  ``pack=False`` keeps the float sign
    path for quant archs; use :class:`repro.serve.ServeEngine` directly for
    packed residency and heterogeneous traces.

    Logits are sliced to the true vocab inside the engine (the table is
    padded to 256-multiples for TP; pad ids must never be sampled).
    """
    from repro.serve import Request, ServeEngine

    b, p = prompt.shape
    seed = 0
    if key is not None:
        seed = int(np.asarray(jax.random.randint(key, (), 0, 2**31 - 1)))
    eng = ServeEngine(cfg, params, slots=b, s_max=p + n_new,
                      temperature=temperature, seed=seed, pack=False)
    prompt_h = np.asarray(prompt, np.int32)
    ctx_h = None if ctx is None else np.asarray(ctx)
    for i in range(b):
        eng.submit(Request(rid=i, prompt=prompt_h[i], max_new_tokens=n_new,
                           ctx=None if ctx_h is None else ctx_h[i]))
    report = eng.run()
    return jnp.asarray(np.stack([report.tokens(i) for i in range(b)]),
                       jnp.int32)
