"""The readers of the program's span aggregates against hand-made
``/metrics`` scrapes: a window is the difference of two scrapes; a program
without the ``spans`` block (any commit before PR 24) reads as nothing."""

import pytest

from benchmark import harness, span_delta

READERS = {"server_ttft_ms": "req.ttft", "sched_wait_ms": "req.sched",
           "admit_ms": "req.admit", "join_wait_ms": "req.join",
           "prefill_ms": "req.prefill", "first_chunk_ms": "req.first"}


def agg(count, sum_s):
    return {"count": count, "sum_s": sum_s, "buckets": [0] * 15}


def scrape(spans=None, segments=0):
    out = {"handler": {"batching": {"segments_run": segments, "segment": 16}}}
    if spans is not None:
        out["spans"] = spans
    return out


@pytest.mark.parametrize("metric,span", sorted(READERS.items()))
def test_a_request_span_reads_as_the_mean_over_the_window(metric, span):
    read = harness.layer_metric(metric).read
    # 40 requests before the window (the warm-up), 10 in it: 10 x 0.3 s
    ctx = {"m_open": scrape({span: agg(40, 100.0), "req": agg(40, 400.0)}),
           "m_close": scrape({span: agg(50, 103.0), "req": agg(50, 500.0)})}
    assert read(ctx) == pytest.approx(300.0)
    # a span that first occurs inside the window
    ctx["m_open"] = scrape({"req": agg(40, 400.0)})
    assert read(ctx) == pytest.approx(1000 * 103.0 / 50)
    # nothing ended in the window, or the program has no spans: no value
    assert read({"m_open": ctx["m_close"], "m_close": ctx["m_close"]}) is None
    assert read({"m_open": scrape(), "m_close": scrape()}) is None
    assert read({}) is None


def test_engine_host_ms_is_the_host_phases_over_the_segments_run():
    read = harness.layer_metric("engine_host_ms").read
    before = {"eng.barrier": agg(10, 0.10), "eng.pack": agg(10, 0.20),
              "eng.dispatch": agg(100, 1.00), "eng.fetch": agg(100, 0.05),
              "eng.book": agg(100, 0.05), "eng.wait": agg(100, 25.0),
              "eng.prefill": agg(10, 0.6)}
    after = {"eng.barrier": agg(30, 0.14), "eng.pack": agg(30, 0.26),
             "eng.dispatch": agg(300, 1.40), "eng.fetch": agg(300, 0.15),
             "eng.book": agg(300, 0.25), "eng.wait": agg(300, 80.0),
             "eng.prefill": agg(30, 1.9)}
    ctx = {"m_open": scrape(before, 100), "m_close": scrape(after, 300)}
    # 0.04 + 0.06 + 0.40 + 0.10 + 0.20 = 0.8 s over 200 segments: the
    # waits on the device and the prefill programs are not the host's work
    assert read(ctx) == pytest.approx(4.0)
    # a phase that never ran counts as zero
    del before["eng.pack"], after["eng.pack"]
    assert read(ctx) == pytest.approx(3.7)
    assert read({"m_open": scrape(before, 300),
                 "m_close": scrape(after, 300)}) is None
    assert read({"m_open": scrape(None, 100),
                 "m_close": scrape(None, 300)}) is None


def test_delta_of_a_window():
    ctx = {"m_open": scrape({"a": agg(1, 0.5)}),
           "m_close": scrape({"a": agg(4, 2.0), "b": agg(2, 1.0)})}
    assert span_delta.delta(ctx, "a") == (3, 1.5)
    assert span_delta.delta(ctx, "b") == (2, 1.0)
    assert span_delta.delta(ctx, "never") == (0, 0.0)
    assert span_delta.mean_ms(ctx, "never") is None
