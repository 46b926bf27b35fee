"""The one traffic generator: a traffic file of parameters in, requests out.

A traffic file (``bench/traffic/<mix>.json``) sets:

  arrivals       ``{"kind": "poisson", "rate_rps": r}``: an open loop at a
                 fixed offered rate; or ``{"kind": "backlog", "requests": n}``:
                 n requests all due at the start (offline work)
  prompt         a length distribution (below), in tokens
  output         a length distribution, in generated tokens
  shared_prefix  optional ``{"tokens": t, "p": p}``: a share p of the
                 requests opens with one common t-token system prompt
  slots          the engine's concurrent requests
  order          optional ``"fixed"``: the same order of sizes and gaps for
                 every seed (below)
  in_flight      optional ``true`` (backlog only): the first ``slots``
                 requests are already part-way through their outputs when
                 the window opens, as in a system that has served the
                 backlog for a while (below)

A length distribution is ``{"dist": "lognormal", "median": m, "sigma": s,
"min": lo, "max": hi}`` (clipped) or ``{"dist": "uniform", "min": lo,
"max": hi}``.

Every seed gets the same set of sizes and gaps in another order: within
each group of requests (:func:`groups`) the lengths and gaps are the
distributions' quantiles at ``(i + 0.5) / n``, and the seed shuffles them
(and draws the token ids and which requests share the prefix, exactly
``round(p * n)`` of each group).  The work of a run is then the same from
seed to seed, and only its order and contents change; with ``"order":
"fixed"`` only its contents do.

In flight: a request in a busy slot at a random moment is drawn in
proportion to its length (a long request holds its slot longer), and it is
at a uniform share of its output.  So each in-flight request takes its
output length from the length-biased distribution (a lognormal's median
times ``exp(sigma**2)``) and its share from quantiles of a uniform; the
tokens it has already served (random ids, as its prompt's are) end its
prompt, so set-up builds their cache, and ``max_new`` is what remains.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Planned:
    """One request of the plan: due ``due`` seconds after the loop starts."""

    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    shared: bool
    served: int = 0             # output tokens already in ``prompt``


def quantile(dist: dict, u: float, biased: bool = False) -> int:
    """The distribution's length at quantile ``u`` in (0, 1); with
    ``biased``, of the distribution weighted by length."""
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        if biased:
            return int(round(math.sqrt(lo * lo + u * (hi * hi - lo * lo))))
        return int(lo + math.floor(u * (hi - lo + 1)))
    if dist["dist"] == "lognormal":
        sigma = dist["sigma"]
        median = dist["median"] * (math.exp(sigma * sigma) if biased else 1.0)
        x = median * math.exp(sigma * _NORMAL.inv_cdf(u))
        return int(min(max(round(x), lo), hi))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def lengths(dist: dict, n: int, biased: bool = False) -> np.ndarray:
    return np.array([quantile(dist, (i + 0.5) / n, biased) for i in range(n)],
                    np.int64)


def n_requests(traffic: dict, horizon_s: float) -> int:
    """How many requests an open loop offers within ``horizon_s`` seconds
    (a backlog's own count otherwise)."""
    arr = traffic["arrivals"]
    if arr["kind"] == "backlog":
        return int(arr["requests"])
    if arr["kind"] == "poisson":
        # a few more than the mean count, so the shuffled gaps, whose sum
        # is a little under n / rate, still reach past the horizon
        return math.ceil(arr["rate_rps"] * horizon_s * 1.05) + 2
    raise ValueError(f"unknown arrivals kind {arr['kind']!r}")


def max_context(traffic: dict) -> int:
    """The most cache positions any request of this mix can need."""
    pre = traffic.get("shared_prefix", {}).get("tokens", 0)
    return pre + traffic["prompt"]["max"] + traffic["output"]["max"]


def groups(traffic: dict, n: int) -> list[int]:
    """The plan's groups, each of which takes its own set of quantiles: the
    requests that fill the system before the window (the first ``slots`` of
    a backlog, or those due in an open loop's ramp) and the rest.  Every
    seed then starts its window from the same sizes, in another order."""
    arr = traffic["arrivals"]
    if arr["kind"] == "backlog":
        first = min(n, traffic["slots"])
    else:
        first = min(n, round(arr["rate_rps"] * traffic.get("ramp_s", 0)))
    return [g for g in (first, n - first) if g]


def generate(traffic: dict, seed: int, vocab: int,
             horizon_s: float) -> list[Planned]:
    """The seeded plan of requests due within ``horizon_s`` seconds."""
    n = n_requests(traffic, horizon_s)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    # "order": "fixed" puts the sizes and gaps in one order for every seed,
    # so the seed draws only the token ids: the schedule of work is then the
    # same in every run
    order = (np.random.default_rng(0x0DE4) if traffic.get("order") == "fixed"
             else rng)
    arr = traffic["arrivals"]
    pre = traffic.get("shared_prefix")
    in_flight = bool(traffic.get("in_flight")) and arr["kind"] == "backlog"
    p_len, o_len, gaps, shared, served = [], [], [], [], []
    for j, g in enumerate(groups(traffic, n)):
        aged = in_flight and j == 0
        p_len.append(order.permutation(lengths(traffic["prompt"], g)))
        o_len.append(order.permutation(lengths(traffic["output"], g, aged)))
        share = (order.permutation((np.arange(g) + 0.5) / g) if aged
                 else np.zeros(g))
        served.append(np.minimum((share * o_len[-1]).astype(np.int64),
                                 o_len[-1] - 1))
        gaps.append(order.permutation(
            [-math.log(1.0 - (i + 0.5) / g) for i in range(g)]))
        mark = np.zeros(g, bool)
        if pre:
            mark[order.permutation(g)[:round(pre["p"] * g)]] = True
        shared.append(mark)
    p_len, o_len = np.concatenate(p_len), np.concatenate(o_len)
    shared, served = np.concatenate(shared), np.concatenate(served)
    if arr["kind"] == "poisson":
        due = np.cumsum(np.concatenate(gaps)) / arr["rate_rps"]
    else:
        due = np.zeros(n)
    prefix = (rng.integers(0, vocab, pre["tokens"], dtype=np.int32) if pre
              else np.zeros(0, np.int32))
    out = []
    for i in range(n):
        body = rng.integers(0, vocab, int(p_len[i] + served[i]),
                            dtype=np.int32)
        prompt = np.concatenate([prefix, body]) if shared[i] else body
        out.append(Planned(i, float(due[i]), prompt,
                           int(o_len[i] - served[i]), bool(shared[i]),
                           int(served[i])))
    return out
