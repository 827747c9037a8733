"""Pipelined continuous engine: >= 2 segments in flight so device
compute overlaps the host fetch + bookkeeping window. The contract under
test is BITWISE parity with the synchronous depth-1 loop — rows that
finish mid-pipeline have their over-decoded tails discarded host-side,
joiners force a bounded drain, and none of it may change a single
token."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from lambdipy_tpu.runtime.continuous import ContinuousBatcher

# tiny_server: the session-scoped shared LlamaServer from conftest.py
# (one compiled-program cache across the continuous-engine modules)


def test_depth_parity_greedy_and_sampled(tiny_server):
    """The same concurrent mix (greedy + seeded-sampled rows) produces
    bitwise identical outputs at pipeline depth 1 (the synchronous
    loop), 2 and 3 — and all of them equal solo."""
    reqs = [
        dict(prompt=[1, 2, 3], kw={}),
        dict(prompt=[9, 8, 7, 6], kw=dict(temperature=0.9, seed=7)),
        dict(prompt=[4, 4], kw=dict(temperature=1.2, top_k=3, seed=11)),
    ]
    solo = [tiny_server.generate(r["prompt"], max_new_tokens=12,
                                 **r["kw"]) for r in reqs]
    for depth in (1, 2, 3):
        cb = ContinuousBatcher(tiny_server, slots=4, segment=4,
                               pipeline_depth=depth)
        with ThreadPoolExecutor(max_workers=3) as ex:
            futs = [ex.submit(cb.generate, r["prompt"], max_new_tokens=12,
                              **r["kw"]) for r in reqs]
            for i, f in enumerate(futs):
                np.testing.assert_array_equal(
                    f.result(), solo[i],
                    err_msg=f"depth {depth} request {i} diverged")
        stats = cb.stats()
        assert stats["pipeline_depth"] == depth
        assert stats["requests_served"] == 3, stats


def test_eos_overdecode_truncated_exactly(tiny_server):
    """A row hitting eos mid-pipeline keeps decoding on the device until
    the next drain barrier; the over-decoded tail is discarded host-side
    and the output (eos latch + filler tail) is bitwise the solo
    path's. The discarded tokens show up in the wasted counter."""
    free = tiny_server.generate([5, 6, 7, 8], max_new_tokens=16)[0]
    eos = int(free[2])  # a token the row actually emits early
    solo = tiny_server.generate([5, 6, 7, 8], max_new_tokens=16,
                                eos_id=eos)
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4,
                           pipeline_depth=3)
    out = cb.generate([5, 6, 7, 8], max_new_tokens=16, eos_id=eos)
    np.testing.assert_array_equal(out, solo)
    # generate() returns the moment the row's finish is observed; the
    # over-decoded blocks behind the frontier are still draining — wait
    # for the collector to catch up before reading its counters
    deadline = time.monotonic() + 10
    pipe = cb.stats()["pipeline"]
    while time.monotonic() < deadline \
            and pipe["segments"] < pipe["dispatches"]:
        time.sleep(0.01)
        pipe = cb.stats()["pipeline"]
    # eos landed in the first segment while later segments were already
    # dispatched: those blocks were fetched and thrown away
    assert pipe["wasted_overdecode_tokens"] > 0, pipe
    assert pipe["drains"].get("complete", 0) >= 1, pipe


def test_midstream_joiner_forces_bounded_drain(tiny_server):
    """A joiner arriving while segments are in flight drains the
    pipeline (at most depth-1 segments), packs at the barrier, and both
    rows still match solo. The in-flight histogram proves the frontier
    never exceeded the configured depth."""
    depth = 3
    cb = ContinuousBatcher(tiny_server, slots=4, segment=4,
                           pipeline_depth=depth)
    long_prompt, late_prompt = [1, 2, 3, 4, 5], [9, 8, 7]
    solo_long = tiny_server.generate(long_prompt, max_new_tokens=64)
    solo_late = tiny_server.generate(late_prompt, max_new_tokens=8)

    out = {}

    def late():
        # join once the long row is demonstrably mid-decode (not a wall
        # clock guess): 2 of its 16 segments collected, 14 to go
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and cb.stats()["segments_run"] < 2:
            time.sleep(0.002)
        out["late"] = cb.generate(late_prompt, max_new_tokens=8)

    t = threading.Thread(target=late)
    t.start()
    out["long"] = cb.generate(long_prompt, max_new_tokens=64)
    t.join()
    np.testing.assert_array_equal(out["long"], solo_long)
    np.testing.assert_array_equal(out["late"], solo_late)
    pipe = cb.stats()["pipeline"]
    assert pipe["in_flight"], pipe
    assert max(int(d) for d in pipe["in_flight"]) <= depth, pipe
    # the joiner interrupted an in-flight frontier at least once (its
    # arrival is gated on the long row being mid-decode, so an engine
    # that never drained for it would mean joins no longer work
    # mid-flight)
    assert pipe["drains"].get("joiner", 0) >= 1, pipe


def test_prefix_join_and_stream_pipelined(tiny_server):
    """prefix= rows (cached-KV continuation carries) and streamed
    requests ride the pipelined engine with fused-path parity — the
    SAME shared scenarios test_continuous.py runs at the default depth,
    here at depth 3 (deeper frontier = more over-decode to discard)."""
    from tests.test_continuous import (assert_prefix_join_parity,
                                       assert_stream_eos_latch)

    cb = ContinuousBatcher(tiny_server, slots=4, segment=4,
                           pipeline_depth=3)
    assert_prefix_join_parity(tiny_server, cb)
    assert_stream_eos_latch(tiny_server, cb)


def test_depth1_keeps_synchronous_frontier(tiny_server):
    """pipeline_depth=1 is today's behavior: every segment is collected
    before the next dispatch, so the in-flight depth never exceeds 1 and
    no drain barriers fire."""
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4,
                           pipeline_depth=1)
    out = cb.generate([1, 2, 3], max_new_tokens=12)
    np.testing.assert_array_equal(
        out, tiny_server.generate([1, 2, 3], max_new_tokens=12))
    pipe = cb.stats()["pipeline"]
    assert set(pipe["in_flight"]) == {"1"}, pipe
    assert pipe["drains"] == {}, pipe
    assert pipe["segments"] == pipe["dispatches"], pipe
