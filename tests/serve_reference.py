"""The plain serving reference the engine tests compare against: each
request on its own, ``lm.prefill`` over the whole prompt and then
``lm.decode_step`` one token at a time, greedy.  It shares no scheduling,
paging or batching code with :class:`repro.serve.ServeEngine`."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm


def _greedy(cfg, logits):
    return jnp.argmax(logits[..., :cfg.vocab][:, -1],
                      -1).astype(jnp.int32)[:, None]


@functools.lru_cache(maxsize=None)
def _programs(cfg, s_max):
    """Jitted (prefill, decode step) for one (arch, cache length): the
    loop runs them once per request and once per token."""
    @jax.jit
    def prefill(params, tokens, ctx):
        lg, st = lm.prefill(cfg, params, tokens, ctx, s_max=s_max)
        return _greedy(cfg, lg), st

    @jax.jit
    def step(params, tok, st):
        lg, st = lm.decode_step(cfg, params, tok, st)
        return _greedy(cfg, lg), st

    return prefill, step


def ref_decode(cfg, params, req, s_max):
    """One request's greedy tokens and the decode state they leave."""
    prefill, step = _programs(cfg, s_max)
    ctx = None if req.ctx is None else jnp.asarray(np.asarray(req.ctx)[None])
    tok, st = prefill(params, jnp.asarray(req.prompt[None]), ctx)
    out = [int(tok[0, 0])]
    for _ in range(req.max_new_tokens - 1):
        tok, st = step(params, tok, st)
        out.append(int(tok[0, 0]))
    return out, st


def ref_generate(cfg, params, req, s_max):
    """One request's greedy tokens."""
    return ref_decode(cfg, params, req, s_max)[0]


def ref_tokens(cfg, params, trace, s_max):
    """{rid: greedy tokens} for every request of ``trace``."""
    return {r.rid: ref_generate(cfg, params, r, s_max) for r in trace}
