"""The end-to-end arithmetic: what the window counts, times per output
token, gaps between tokens, the client's lag, nearest-rank percentiles."""
import math

import client


def rec(due, submit, tokens, done=True, failed=""):
    r = client.Record(due=due, prompt=None)
    r.submit, r.tokens, r.done, r.failed = submit, list(tokens), done, failed
    return r


def test_only_requests_due_in_the_window_are_counted_due():
    t0 = 100.0
    recs = [rec(-3.0, 97.0, [98.0, 99.0]), rec(0.0, 100.0, [101.0]),
            rec(4.9, 104.9, []), rec(5.0, 105.0, [])]
    assert client.latencies(recs, t0, t0 + 5.0)["n_due"] == 2


def test_tpot_counts_requests_finished_in_the_window_whatever_their_due():
    t0 = 10.0
    lead = rec(-5.0, 5.0, [6.0, 8.0, 10.5, 11.0])      # due in the lead-in
    inside = rec(1.0, 11.0, [12.0, 12.5, 13.0])
    unfinished = rec(2.0, 12.0, [12.2, 12.4], done=False)
    later = rec(3.0, 13.0, [14.0, 16.0])               # finishes after
    lat = client.latencies([lead, inside, unfinished, later], t0, 15.0)
    assert sorted(lat["tpot"]) == [500.0, 1000.0 * 5.0 / 3]


def test_itl_takes_every_gap_that_ends_in_the_window():
    t0 = 10.0
    r = rec(-5.0, 5.0, [9.0, 9.5, 10.1, 10.4, 15.5])
    lat = client.latencies([r], t0, 15.0)
    assert [round(g, 6) for g in lat["itl"]] == [600.0, 300.0]


def test_tpot_and_itl_of_one_request():
    r = rec(0.0, 0.0, [1.0, 1.1, 1.3, 1.6])
    lat = client.latencies([r], 0.0, 5.0)
    assert abs(lat["tpot"][0] - 200.0) < 1e-9
    assert [round(g, 6) for g in lat["itl"]] == [100.0, 200.0, 300.0]


def test_lag_runs_from_the_due_time():
    t0 = 100.0
    recs = [rec(1.0, 101.25, []), rec(-2.0, 98.0, []),
            rec(3.0, float("nan"), [])]
    assert client.lag_ms(recs, t0) == [250.0, 0.0]


def test_nearest_rank_percentile():
    v = list(range(1, 11))
    assert client.percentile(v, 90) == 9
    assert client.percentile(v, 91) == 10
    assert client.percentile(v, 50) == 5
    assert client.percentile([3.0], 99) == 3.0
    assert math.isnan(client.percentile([], 50))


def test_tokens_in_counts_the_window_only():
    rs = [rec(0.0, 0.0, [0.5, 1.5, 2.5]), rec(0.0, 0.0, [1.0, 3.5])]
    assert client.tokens_in(rs, 0.0, 2.0) == 3
