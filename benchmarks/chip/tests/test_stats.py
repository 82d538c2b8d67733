"""Latencies and attainment from the driver's token times, by hand."""
import pytest

import stats


def req(due, n_out, times, ttft_limit=1.0, tpot_limit=0.1):
    return stats.Timed(due, n_out, ttft_limit, tpot_limit, list(times))


def test_finished_request_ttft_tpot_and_limits():
    ok = req(1.0, 3, [1.5, 1.6, 1.8])
    slow = req(0.0, 3, [2.0, 2.05, 2.1])  # TTFT 2.0 over its 1.0 limit
    ttft, tpot, met, failed = stats.latencies([ok, slow], end=10.0)
    assert ttft == pytest.approx([0.5, 2.0])
    assert tpot == pytest.approx([0.15, 0.05])
    assert met == [False, False]  # ok's TPOT 0.15 is over 0.1
    assert failed == 0
    _, _, met, _ = stats.latencies([req(1.0, 3, [1.5, 1.6, 1.7])], end=10.0)
    assert met == [True]


def test_unfinished_request_fails_and_sorts_last():
    none = req(2.0, 4, [])
    part = req(1.0, 4, [1.5, 1.7])
    ttft, tpot, met, failed = stats.latencies([none, part], end=6.0)
    assert failed == 2 and met == [False, False]
    assert ttft == pytest.approx([4.0, 0.5])
    assert tpot == pytest.approx([4.0, 4.5])


def test_token_gaps_cover_every_asked_token():
    gaps = stats.token_gaps([req(0.0, 3, [1.0, 1.25, 1.5]), req(0.0, 4, [2.0, 2.5])], end=5.0)
    assert sorted(gaps) == pytest.approx(sorted([0.25, 0.25, 0.5, 2.5, 2.5]))
    assert stats.token_gaps([req(1.0, 3, [])], end=4.0) == pytest.approx([3.0, 3.0])


def test_attainment_and_percentile():
    assert stats.attainment([True, False, True, True]) == 75.0
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 90) == pytest.approx(4.6)
