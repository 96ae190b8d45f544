"""The traffic generator: seeded, the same work for every seed, and the
lengths and rates its mix file states."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    a = traffic.schedule(mix, 2**31 + 7, 50, 1000)
    b = traffic.schedule(mix, 2**31 + 7, 50, 1000)
    assert len(a) == len(b) == traffic.n_requests(mix, 50)
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_seeds_put_the_same_work_in_each_phase(name):
    mix = _mix(name)
    a = traffic.schedule(mix, 1, 50, 1000)
    b = traffic.schedule(mix, 4_000_000_000, 50, 1000)
    if mix["arrival"]["process"] == "backlog":
        # one order of sizes for every seed; the seed draws the token ids
        assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
        assert [r.max_new for r in a] == [r.max_new for r in b]
        assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
        phases = [(0.0, 1.0)]
    else:
        assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
        phases = [(0.0, mix["warmup_s"]), (mix["warmup_s"], mix["warmup_s"] + 50)]
    for lo, hi in phases:
        pa = [r for r in a if lo <= r.due < hi]
        pb = [r for r in b if lo <= r.due < hi]
        assert pa and len(pa) == len(pb)
        assert sorted(len(r.prompt) for r in pa) == sorted(len(r.prompt) for r in pb)
        assert sorted(r.max_new for r in pa) == sorted(r.max_new for r in pb)
        if mix["arrival"]["process"] == "gamma":
            law = traffic.gaps(mix["arrival"], len(pa))
            for p in (pa, pb):
                d = np.diff([r.due for r in p] + [hi])
                np.testing.assert_allclose(np.sort(d) / (hi - lo),
                                           np.sort(law) / law.sum(), rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_lengths_clip_and_median(name):
    mix = _mix(name)
    for key in ("prompt_len", "output_len"):
        spec = mix[key]
        x = traffic.lengths(spec, 2001)
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) - spec["median"]) <= 1
    for r in traffic.schedule(mix, 3, 50, 1000):
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 1000


def test_gamma_gaps_have_the_stated_rate_and_cv():
    g = traffic.gaps({"process": "gamma", "rate_per_s": 2.5, "cv": 2.0}, 20000)
    assert abs(1 / g.mean() - 2.5) / 2.5 < 0.01
    assert abs(g.std() / g.mean() - 2.0) < 0.1


def test_backlog_is_due_at_once():
    mix = _mix("batch")
    s = traffic.schedule(mix, 5, 50, 1000)
    assert len(s) == mix["arrival"]["count"]
    assert all(r.due == 0.0 for r in s)


@pytest.mark.parametrize("key,base", [("prompt_len", 2), ("output_len", 3)])
def test_backlog_admissions_spread_over_the_lengths(key, base):
    # slots free one by one, so every stretch of the backlog's order is
    # what some window admits: each prefix holds about as many lengths
    # from every third of the law as the whole backlog does
    mix = _mix("batch")
    got = [len(r.prompt) if key == "prompt_len" else r.max_new
           for r in traffic.schedule(mix, 9, 50, 1000)]
    law = np.sort(traffic.lengths(mix[key], len(got)))
    assert sorted(got) == law.tolist()
    rank = np.argsort(traffic.spread_order(len(got), base))
    assert sorted(rank.tolist()) == list(range(len(got)))
    cuts = law[[len(got) // 3, 2 * len(got) // 3]]
    for k in range(6, len(got) + 1):
        third = np.searchsorted(cuts, got[:k], side="right")
        for j in range(3):
            assert abs(np.sum(third == j) - k / 3) <= 2


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_in_flight_population_is_the_same_for_every_seed(name):
    mix = _mix(name)
    a = traffic.in_flight(mix, 1, 64, 1000)
    b = traffic.in_flight(mix, 2**33 + 1, 64, 1000)
    assert len(a) == len(b) == 64
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    cap = mix["pool"]["max_seq_len"]
    for r in a:
        assert r.max_new >= 1 and len(r.prompt) + r.max_new - 1 <= cap


def test_in_flight_requests_are_long_and_part_done():
    # at a random moment a request is in flight for as long as it lives,
    # so the population's total lengths are drawn in proportion to the
    # length, and each is uniformly far through
    mix = _mix("chat")
    law = traffic.lengths(mix["output_len"], 4096)
    pop = traffic.in_flight(mix, 7, 2000, 1000)
    rest = np.array([r.max_new for r in pop])
    assert abs(rest.mean() - (law ** 2).mean() / (2 * law.mean())) < 0.05 * rest.mean()
    prompts = traffic.lengths(mix["prompt_len"], 2000)
    assert np.mean([len(r.prompt) for r in pop]) > prompts.mean() + 0.3 * rest.mean()


def test_window_tokens_per_s_counts_whole_steps():
    from bench import loop

    class G:
        def __init__(self, times):
            self.times, self.due, self.submit = times, 0.0, 0.0

    # two slots, a step every 1.0 s returning at 0.5, 1.5, ...; each token
    # stamped 0.1 s before its step returns
    ends = [0.5 + k for k in range(12)]
    logs = {i: G([e - 0.1 for e in ends]) for i in range(2)}
    st = loop.window_stats(logs, 2.0, 9.0, True, ends)
    # whole steps from the return at 1.5 to the one at 8.5: 7 steps x 2
    assert st["tokens_per_s"] == pytest.approx(14 / 7.0)
    assert st["tokens"] == 14
