"""The traffic generator: seeded, its distributions, and due times."""

from __future__ import annotations

import collections
import json
import math

import numpy as np
import pytest

import bench_tiny
import traffic_gen

CHAT = json.loads((bench_tiny.BENCH / "traffic" / "chat_q3x.json").read_text())
DECODE = json.loads((bench_tiny.BENCH / "traffic" / "decode.json").read_text())
IN_FLIGHT = json.loads(
    (bench_tiny.BENCH / "traffic" / "decode_in_flight.json").read_text())
SHUFFLED = {k: v for k, v in CHAT.items() if k != "order"}


def _sizes(plan):
    return sorted((len(p.prompt) - (CHAT["shared_prefix"]["tokens"]
                                    if p.shared else 0), p.max_new)
                  for p in plan)


def test_same_seed_same_plan():
    a = traffic_gen.generate(CHAT, 12345678901234, 1000, 60.0)
    b = traffic_gen.generate(CHAT, 12345678901234, 1000, 60.0)
    assert [(p.due, p.max_new, p.shared) for p in a] == \
        [(p.due, p.max_new, p.shared) for p in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_sizes_and_gaps_in_another_order():
    a = traffic_gen.generate(SHUFFLED, 1, 1000, 60.0)
    b = traffic_gen.generate(SHUFFLED, 2, 1000, 60.0)
    assert len(a) == len(b)
    assert sorted(len(p.prompt) - 512 * p.shared for p in a) == \
        sorted(len(p.prompt) - 512 * p.shared for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    gaps = lambda plan: sorted(np.round(np.diff([0.0] + [p.due for p in plan]), 9))
    assert gaps(a) == pytest.approx(gaps(b))
    assert [p.max_new for p in a] != [p.max_new for p in b]
    assert not all(np.array_equal(x.prompt[-8:], y.prompt[-8:])
                   for x, y in zip(a, b))


@pytest.mark.parametrize("dist", [CHAT["prompt"], CHAT["output"],
                                  DECODE["output"]])
def test_lognormal_lengths(dist):
    n = 2001
    ls = traffic_gen.lengths(dist, n)
    assert ls.min() >= dist["min"] and ls.max() <= dist["max"]
    assert abs(np.median(ls) - dist["median"]) <= 1
    # the quartiles sit at median * exp(+-0.6745 sigma) unless clipped
    q1 = dist["median"] * math.exp(-0.6745 * dist["sigma"])
    if q1 > dist["min"]:
        assert np.quantile(ls, 0.25) == pytest.approx(q1, rel=0.01, abs=1)


def test_uniform_lengths_cover_the_range():
    ls = traffic_gen.lengths(DECODE["prompt"], 3850)
    lo, hi = DECODE["prompt"]["min"], DECODE["prompt"]["max"]
    assert ls.min() == lo and ls.max() == hi
    counts = collections.Counter(ls.tolist())
    assert len(counts) == hi - lo + 1


def test_due_times_are_an_open_loop_at_the_rate():
    rate = CHAT["arrivals"]["rate_rps"]
    plan = traffic_gen.generate(CHAT, 5, 1000, 200.0)
    due = np.array([p.due for p in plan])
    assert np.all(np.diff(due) > 0)
    # the horizon is covered, at the offered mean rate
    assert due[-1] >= 200.0
    assert len(plan) / due[-1] == pytest.approx(rate, rel=0.1)
    gaps = np.diff(np.concatenate([[0.0], due])) * rate
    assert np.mean(gaps) == pytest.approx(1.0, rel=0.05)
    assert np.std(gaps) == pytest.approx(1.0, rel=0.15)     # exponential


def test_backlog_is_due_at_once():
    plan = traffic_gen.generate(DECODE, 9, 1000, 0.0)
    assert len(plan) == DECODE["arrivals"]["requests"]
    assert {p.due for p in plan} == {0.0}
    assert not any(p.shared for p in plan)


def test_shared_prefix_share_and_tokens():
    plan = traffic_gen.generate(CHAT, 3, 1000, 100.0)
    t = CHAT["shared_prefix"]["tokens"]
    shared = [p for p in plan if p.shared]
    assert len(shared) == round(CHAT["shared_prefix"]["p"] * len(plan))
    head = shared[0].prompt[:t]
    assert all(np.array_equal(p.prompt[:t], head) for p in shared)
    assert all(0 <= p.prompt.min() and p.prompt.max() < 1000 for p in plan)


def test_max_context_bounds_every_request():
    plan = traffic_gen.generate(CHAT, 4, 151936, 100.0)
    cap = traffic_gen.max_context(CHAT)
    assert max(len(p.prompt) + p.max_new for p in plan) <= cap


@pytest.mark.parametrize("traffic", [CHAT, DECODE])
def test_the_fill_group_has_the_same_sizes_for_every_seed(traffic):
    """The requests that fill the system before the window (a backlog's
    first ``slots``, an open loop's ramp) take their own quantiles, so every
    seed opens its window on the same work."""
    horizon = traffic.get("ramp_s", 0) + 60.0
    plans = [traffic_gen.generate(traffic, s, 1000, horizon) for s in (1, 2)]
    first = traffic_gen.groups(traffic, len(plans[0]))[0]
    assert first >= 1
    pre = traffic.get("shared_prefix", {}).get("tokens", 0)
    for size in (lambda p: len(p.prompt) - pre * p.shared,
                 lambda p: p.max_new, lambda p: p.shared):
        a, b = ([size(p) for p in plan[:first]] for plan in plans)
        assert sorted(a) == sorted(b)
    if traffic["arrivals"]["kind"] == "poisson":
        ends = [plan[first - 1].due for plan in plans]
        assert ends[0] == pytest.approx(ends[1])


def test_fixed_order_replays_one_schedule_with_the_seed_s_tokens():
    assert CHAT["order"] == "fixed"
    a = traffic_gen.generate(CHAT, 1, 1000, 100.0)
    b = traffic_gen.generate(CHAT, 2**40 + 7, 1000, 100.0)
    assert [(p.due, len(p.prompt), p.max_new, p.shared) for p in a] == \
        [(p.due, len(p.prompt), p.max_new, p.shared) for p in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_in_flight_requests_carry_a_share_of_their_output():
    """A backlog's first ``slots`` requests start part-way through their
    outputs: the tokens already served end the prompt, ``max_new`` is what
    remains, and the outputs are drawn in proportion to their length."""
    assert IN_FLIGHT["in_flight"]
    slots = IN_FLIGHT["slots"]
    plan = traffic_gen.generate(IN_FLIGHT, 2**35 + 3, 1000, 0.0)
    lo, hi = IN_FLIGHT["prompt"]["min"], IN_FLIGHT["prompt"]["max"]
    aged, fresh = plan[:slots], plan[slots:]
    assert all(p.served == 0 for p in fresh)
    assert all(lo <= len(p.prompt) <= hi for p in fresh)
    assert all(p.served >= 0 and p.max_new >= 1 for p in aged)
    assert sum(p.served > 0 for p in aged) >= slots - 1
    assert all(lo <= len(p.prompt) - p.served <= hi for p in aged)
    total = np.array([p.served + p.max_new for p in aged])
    assert total.max() <= IN_FLIGHT["output"]["max"]
    # the share served is uniform: its quantiles, in some order
    share = sorted(p.served / (p.served + p.max_new) for p in aged)
    want = (np.arange(slots) + 0.5) / slots
    assert np.allclose(share, want, atol=1.0 / IN_FLIGHT["output"]["min"])
    # length-biased: the median is the median's times exp(sigma^2)
    out = IN_FLIGHT["output"]
    assert np.median(total) == pytest.approx(
        out["median"] * math.exp(out["sigma"] ** 2), rel=0.05)
    assert traffic_gen.max_context(IN_FLIGHT) >= max(
        len(p.prompt) + p.max_new for p in plan)


def test_in_flight_work_is_the_same_for_every_seed():
    a, b = (traffic_gen.generate(IN_FLIGHT, s, 1000, 0.0) for s in (3, 2**40))
    assert [(len(p.prompt), p.max_new, p.served) for p in a] == \
        [(len(p.prompt), p.max_new, p.served) for p in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


@pytest.mark.parametrize("dist", [IN_FLIGHT["prompt"], IN_FLIGHT["output"]])
def test_length_biased_quantiles_weight_by_length(dist):
    n = 4001
    plain = traffic_gen.lengths(dist, n).astype(float)
    biased = traffic_gen.lengths(dist, n, biased=True).astype(float)
    # E_biased[L] = E[L^2] / E[L], up to the clipping and rounding
    assert biased.mean() == pytest.approx(
        (plain ** 2).mean() / plain.mean(), rel=0.03)
