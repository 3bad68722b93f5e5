"""Window arithmetic of the end-to-end metrics on synthetic stamps."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import pytest  # noqa: E402

import window  # noqa: E402


def steady(n_req=20, n_tok=40, gap=0.010, start=0.0):
    """n_req requests, each submitted 5 ms before its first token, tokens
    every ``gap`` seconds, requests staggered by 1 ms."""
    recs = {}
    for r in range(n_req):
        t = start + 0.005 + r * 0.001
        recs[r] = {"submit": t - 0.005,
                   "times": [t + i * gap for i in range(n_tok)]}
    return recs


def test_rates_and_percentiles_by_hand():
    recs = {0: {"submit": 0.0, "times": [0.1, 0.2, 0.3, 0.4]},
            1: {"submit": 0.15, "times": [0.25, 0.35, 0.45]}}
    m = window.metrics(recs, 0.0, 0.5)
    assert m["output_tok_per_s"] == pytest.approx(7 / 0.5)
    assert m["ttft_p95_ms"] == pytest.approx(100.0)
    assert m["itl_p50_ms"] == pytest.approx(100.0)
    assert m["itl_p95_ms"] == pytest.approx(100.0)


def test_only_the_window_counts():
    recs = {0: {"submit": 0.0, "times": [0.1, 0.2, 0.3, 0.4]},
            1: {"submit": 0.15, "times": [0.25, 0.35, 0.45]}}
    # window [0.22, 0.5]: request 0's first token is outside, its tokens
    # at 0.3 and 0.4 count; gaps need both ends inside
    assert window.output_tokens(recs, 0.22, 0.5) == 5
    assert window.ttfts(recs, 0.22, 0.5) == pytest.approx([0.1])
    assert window.itls(recs, 0.22, 0.5) == pytest.approx([0.1, 0.1, 0.1])


def test_a_stall_moves_the_tail_and_not_the_median():
    m0 = window.metrics(steady(), 0.0, 2.0)
    stalled = steady()
    for r in range(20):     # every request stalls 300 ms, twice: 40 of
        ts = stalled[r]["times"]    # its 780 gaps, over 5% of them
        ts = ts[:5] + [t + 0.3 for t in ts[5:]]
        stalled[r]["times"] = ts[:20] + [t + 0.3 for t in ts[20:]]
    m1 = window.metrics(stalled, 0.0, 2.0)
    assert m0["itl_p95_ms"] == pytest.approx(10.0)
    assert m1["itl_p95_ms"] > 100.0
    assert m1["itl_p50_ms"] == pytest.approx(10.0)
    assert m1["output_tok_per_s"] == pytest.approx(m0["output_tok_per_s"])
    assert sorted(window.itls(stalled, 0.0, 2.0))[-40:] == pytest.approx(
        [0.31] * 40)


def test_an_empty_window_raises():
    with pytest.raises(RuntimeError):
        window.metrics(steady(), 5.0, 6.0)
