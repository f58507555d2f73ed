"""The traffic generator: deterministic per seed, the stated medians and
clips, and the same work for every seed."""
import json

import numpy as np

import traffic
from bench_kit import BENCH

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
LEAD = CHAT["arrivals"]["lead_in_s"]
RATE = CHAT["arrivals"]["rate_per_s"]


def gen(mix, seed, seconds=30.0):
    return traffic.generate(mix, seed=seed, seconds=seconds, vocab=1000)


def window(reqs):
    return [r for r in reqs if r.due >= 0]


def test_same_seed_same_requests():
    a, b = gen(CHAT, 2**33 + 5), gen(CHAT, 2**33 + 5)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new for r in a] == [r.max_new for r in b]


def test_seeds_share_the_work_in_another_order():
    a, b = window(gen(CHAT, 1)), window(gen(CHAT, 2))
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert abs(a[-1].due - b[-1].due) < 1e-9    # the same total span
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_lengths_have_the_stated_median_and_clips():
    for key in ("prompt_tokens", "output_tokens"):
        spec = CHAT[key]
        x = traffic.lengths(spec, 1001)
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert np.median(x) == spec["median"]


def test_poisson_count_and_mean_gap():
    reqs = window(gen(CHAT, 7, seconds=40.0))
    assert len(reqs) == round(RATE * 40.0)
    assert all(np.diff([r.due for r in reqs]) >= 0)
    assert abs(reqs[-1].due - 40.0) < 0.1 * 40.0


def test_lead_in_comes_before_the_window_at_the_same_rate():
    for seed in (3, 2**33 + 9):
        reqs = gen(CHAT, seed)
        lead = [r for r in reqs if r.due < 0]
        assert len(lead) == round(RATE * LEAD)
        assert all(np.diff([r.due for r in reqs]) >= 0)
        assert lead[-1].due <= -0.5 / RATE       # none at the opening
        assert abs(lead[0].due + LEAD) < 0.25 * LEAD
        # the lead-in's length is the same for every seed
        assert -traffic.lead_in(CHAT) <= lead[0].due
        assert abs(traffic.lead_in(CHAT) - LEAD) < 0.25 * LEAD
        assert len(window(reqs)) == round(RATE * 30.0)


def test_token_ids_cover_the_vocabulary():
    ids = np.concatenate([r.prompt for r in gen(CHAT, 11, seconds=300.0)])
    assert ids.min() >= 0 and ids.max() < 1000 and len(set(ids)) > 900
