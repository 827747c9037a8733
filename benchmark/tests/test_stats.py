"""Percentile and tpot arithmetic, with failed and one-chunk requests."""

import math

import pytest

from benchmark import stats
from benchmark.client import Record


def rec(due=0.0, first=None, last=None, n=0, first_n=0, ok=True):
    r = Record(0, [1, 2, 3], n, due)
    r.t_send, r.t_first, r.t_last, r.ok = due, first, last, ok
    r.tokens = [7] * n
    r.first_chunk_tokens = first_n
    return r


@pytest.mark.parametrize("values,q,want", [
    ([5], 50, 5), ([1, 2, 3, 4], 50, 2), ([1, 2, 3, 4], 90, 4),
    (list(range(1, 101)), 90, 90), (list(range(1, 101)), 50, 50),
    ([3, 1, 2], 100, 3)])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_counts_from_due_and_a_failure_misses():
    assert stats.ttft_ms(rec(due=1.0, first=1.25, last=2.0, n=32,
                             first_n=16)) == pytest.approx(250.0)
    assert stats.ttft_ms(rec(due=1.0, ok=False)) == math.inf


def test_tpot_is_time_after_first_chunk_over_tokens_after_it():
    r = rec(first=1.0, last=1.8, n=48, first_n=16)
    assert stats.tpot_ms(r) == pytest.approx(800.0 / 32)
    # one chunk: nothing after it, left out and counted
    assert stats.tpot_ms(rec(first=1.0, last=1.0, n=16, first_n=16)) is None
    assert stats.tpot_ms(rec(ok=False)) == math.inf


def test_summary_puts_failures_in_the_tail_and_counts_one_chunk():
    good = [rec(due=0, first=0.1 * (i + 1), last=0.1 * (i + 1) + 0.32, n=32,
                first_n=16) for i in range(8)]
    one = rec(due=0, first=0.05, last=0.05, n=16, first_n=16)
    bad = rec(due=0, ok=False)
    s = stats.summarize(good + [one, bad], window_s=2.0, tokens_in_window=300)
    assert (s["attempted"], s["failed"]) == (10, 1)
    assert s["one_chunk_requests"] == 1 and s["tpot_samples"] == 9
    assert s["ttft_p90_ms"] == pytest.approx(800.0)   # 9th of 10
    assert stats.percentile([stats.ttft_ms(r) for r in good + [one, bad]],
                            100) == math.inf
    assert s["tpot_p90_ms"] == math.inf               # 9 samples, worst fails
    assert s["out_tok_s"] == 150.0


def test_attainment_is_a_share_of_requests_sent():
    recs = [rec(due=0, first=0.2, last=0.5, n=32, first_n=16),   # meets
            rec(due=0, first=2.0, last=2.3, n=32, first_n=16),   # late first
            rec(due=0, first=0.2, last=3.4, n=32, first_n=16),   # slow gaps
            rec(due=0, ok=False)]
    assert stats.attainment(recs, 1000, 60) == 0.25
