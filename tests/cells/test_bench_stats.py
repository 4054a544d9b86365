"""Percentiles and the token-weighted TPOT on hand-made timelines."""

import math

import pytest

from benchmark import stats


def rec(due, times, ok=True, in_window=True, sent=None):
    return {"due": due, "sent": due if sent is None else sent,
            "in_window": in_window, "ok": ok, "token_times": times}


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (100, 5.0),
                                    (25, 2.0), (90, 4.6)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tpot_is_weighted_by_tokens():
    # 3 tokens over 0.2 s and 11 tokens over 2.0 s: (0.2+2.0)/(2+10)
    a = rec(0.0, [1.0, 1.1, 1.2])
    b = rec(0.0, [2.0 + 0.2 * i for i in range(11)])
    assert stats.tpot_mean_ms([a, b]) == pytest.approx(
        1000 * 2.2 / 12)


def test_tpot_leaves_out_lead_in_failed_and_single_token():
    a = rec(0.0, [1.0, 1.5])
    lead = rec(-1.0, [0.0, 9.0], in_window=False)
    failed = rec(0.0, [1.0, 7.0], ok=False)
    single = rec(0.0, [1.0])
    assert stats.tpot_mean_ms([a, lead, failed, single]) == \
        pytest.approx(500.0)


def test_tpot_with_nothing_completed_raises():
    with pytest.raises(ValueError):
        stats.tpot_mean_ms([rec(0.0, [1.0], ok=False)])


def test_ttft_counts_from_due_not_from_sent():
    r = rec(10.0, [10.4, 10.5], sent=10.3)
    assert stats.ttft_ms(r) == pytest.approx(400.0)


def test_failed_request_lies_beyond_any_percentile():
    good = [rec(0.0, [0.1 * (i + 1)]) for i in range(9)]
    bad = rec(0.0, [], ok=False)
    assert stats.ttft_percentile_ms(good + [bad], 50) == \
        pytest.approx(550.0)
    assert math.isinf(stats.ttft_percentile_ms(good + [bad], 95))
    assert stats.counts(good + [bad]) == {"attempted": 10, "failed": 1}


def test_lead_in_requests_do_not_count():
    lead = rec(-5.0, [], ok=False, in_window=False)
    assert stats.counts([lead, rec(0.0, [0.2])]) == {
        "attempted": 1, "failed": 0}


def test_spread_is_the_quartile_distance_over_the_median():
    vals = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    import statistics
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))
