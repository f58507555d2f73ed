"""How ``check.readings`` walks its candidates: it checks requests in
order until the tokens and KV rows it could compare reach the sample's
minimums, counting only positions that are not routing near-ties."""
import types

import numpy as np
import pytest

import check
import reference


class FakeSequence:
    """The reference over one request, with its first served position a
    near-tie and every answer matching the program."""

    def __init__(self, c, params, prompt, served, *, control=False):
        self.n = len(served)
        self.P = len(prompt)

    def served_near_tie(self, tie):
        return np.arange(self.n) == 0

    def served_gaps(self, read=None):
        return np.zeros(self.n)

    def near_tie(self, tie):
        return np.zeros(self.P + self.n, bool)

    def prompt_kv(self, n, control=False):
        return [(np.ones((n, 1, 1)), np.ones((n, 1, 1)))]


def req(prompt_len, served):
    return types.SimpleNamespace(prompt=np.zeros(prompt_len, np.int32),
                                 out=list(range(served)))


def kv(n):
    return [(np.ones((n, 1, 1)), np.ones((n, 1, 1)))] if n else []


@pytest.fixture
def cfg(monkeypatch):
    monkeypatch.setattr(reference, "Sequence", FakeSequence)
    return {"vocab_size": 10, "correct": {
        "router_tie": 1e-4, "served_gap_max": 0.25, "kv_err_max": 4e-6,
        "sample": {"min_tokens": 5, "max_requests": 8, "min_kv_rows": 6}}}


def test_near_ties_do_not_count_toward_the_minimum(cfg):
    picked = [req(8, 5), req(8, 5), req(8, 5)]
    out = check.readings(cfg, None, picked, [kv(4), kv(4), kv(4)])
    # 5 served tokens a request, 1 of them a near-tie: two are needed
    assert out["requests"] == 2
    assert out["checked_tokens"] == 8 and out["near_ties"] == 2
    assert check.verdict(cfg, out)[0]


def test_once_the_tokens_are_met_prompts_without_kv_are_passed_over(cfg):
    picked = [req(8, 9), req(8, 9), req(8, 9), req(8, 9)]
    out = check.readings(cfg, None, picked, [kv(4), kv(0), kv(0), kv(4)])
    assert out["requests"] == 2
    assert out["checked_tokens"] == 16 and out["checked_kv_rows"] == 8


def test_too_few_candidates_leave_the_run_incorrect(cfg):
    out = check.readings(cfg, None, [req(8, 3)], [kv(8)])
    assert out["checked_tokens"] == 2
    assert not check.verdict(cfg, out)[0]


def layer(n_leaves, n=4):
    return tuple(np.ones((n, 1, 1)) for _ in range(n_leaves))


@pytest.mark.parametrize("got", [
    [layer(1), layer(1)],               # a leaf a layer left unread
    [layer(3), layer(3)],               # a leaf the reference lacks
    [layer(2)],                         # a layer left unread
    [layer(2), layer(2), layer(2)],     # a layer the reference lacks
])
def test_kv_err_judges_every_layer_and_leaf_or_raises(got):
    ref = [layer(2), layer(2)]
    assert check.kv_err([layer(2), layer(2)], ref, np.ones(4, bool)) == 0.0
    with pytest.raises(ValueError):
        check.kv_err(got, ref, np.ones(4, bool))
