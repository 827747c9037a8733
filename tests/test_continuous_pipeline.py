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
import pytest

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


# -- slot handover at a row's known end ---------------------------------------


def wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.001)
    return cond()


def hold_first_prefill(cb, until):
    """The engine's first group prefill waits for ``until()``: whoever is
    to be a WAITING joiner is enqueued while the first rows are still on
    their way into their slots, by construction and not by a sleep."""
    orig, first = cb._prefill_group, [True]

    def gated(entries):
        if first:
            first.clear()
            assert wait_for(until)
        return orig(entries)

    cb._prefill_group = gated


def drained(cb):
    """The pipeline counters once the collector has caught up."""
    def caught_up():
        pipe = cb.stats()["pipeline"]
        return pipe["segments"] == pipe["dispatches"]

    wait_for(caught_up)
    return cb.stats()["pipeline"]


LONG, SHORT, NEXT = [1, 2, 3, 4, 5], [5, 6, 7, 8], [9, 8, 7]


def start_beside_a_long_row(cb, out, second, waiting=None):
    """Two slots, three requests. A 64-token row and ``second`` take the
    slots at the engine's first two barriers (the long row's prefill
    waits until the others are enqueued, so both decode from the long
    row's first segment on); ``waiting``, if given, is enqueued behind
    them and waits for a slot from the start. Each is a ``(name, prompt,
    kwargs)``; results land in ``out`` by name. Returns the threads."""
    reqs = [r for r in (second, waiting) if r is not None]
    hold_first_prefill(cb, lambda: len(cb._joiners) == len(reqs))

    def run(name, prompt, kw):
        out[name] = cb.generate(prompt, **kw)

    threads = [threading.Thread(target=run, args=(
        "long", LONG, dict(max_new_tokens=64)))]
    threads[0].start()
    assert wait_for(lambda: any(cb._active))
    for i, req in enumerate(reqs):
        threads.append(threading.Thread(target=run, args=req))
        threads[-1].start()
        assert wait_for(lambda: len(cb._joiners) > i)
    return threads


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_slot_handover_at_a_rows_known_end(tiny_server, depth):
    """A row that ends at its ``max_new_tokens`` beside a running row,
    with a joiner waiting for its slot, hands the slot over at the
    segment in which it ends: ``row_ending_fn`` (the scheduler's grant
    ahead) is told once a row, at the dispatch that leaves it at most
    one segment, the request it sets off is a joiner when the row's last
    block is dispatched, and the engine drains THERE (cause
    ``handover``) instead of stepping the finished row through another
    segment. No token is over-decoded, no segment is added (the request
    set off asks for the 40 tokens that end with the long row's, so that
    no slot is left a garbage row at the tail either), and all outputs
    are bitwise the solo path's. At depth 1 every segment is
    collected before the next dispatch, so the row is already done at
    that check and there is no pipeline to drain: the same handover with
    no drain recorded."""
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4,
                           pipeline_depth=depth)
    solo = {"long": tiny_server.generate(LONG, max_new_tokens=64),
            "short": tiny_server.generate(SHORT, max_new_tokens=24),
            "next": tiny_server.generate(NEXT, max_new_tokens=40)}
    told, go, out = [], threading.Event(), {}

    def row_ending(max_prefill_tokens):
        # engine thread, outside the engine's lock
        assert max_prefill_tokens == cb.group_prefill_max
        told.append(cb.stats()["segments_run"])
        if len(told) == 1:
            go.set()  # the grant ahead: the next request sets off now
            assert wait_for(lambda: bool(cb._joiners))

    cb.row_ending_fn = row_ending

    def granted_ahead():
        assert go.wait(10)
        out["next"] = cb.generate(NEXT, max_new_tokens=40)

    threads = start_beside_a_long_row(
        cb, out, ("short", SHORT, dict(max_new_tokens=24)))
    threads.append(threading.Thread(target=granted_ahead))
    threads[-1].start()
    for t in threads:
        t.join()
    for name, want in solo.items():
        np.testing.assert_array_equal(out[name], want, err_msg=name)
    pipe, stats = drained(cb), cb.stats()
    assert len(told) == 3, told       # once a row, all three rows
    # told at the dispatch of the short row's last segment but one (its
    # 5th of 6): at most depth - 1 of the earlier ones were uncollected
    assert 4 - (depth - 1) <= told[0] <= 4, told
    assert pipe["wasted_overdecode_tokens"] == 0, pipe
    # the long row's 16 segments carried the others' 6 + 10: none added
    assert stats["segments_run"] == 16, stats
    assert stats["rows_in_segments"] == 16 + 6 + 10, stats
    assert stats["requests_served"] == 3
    if depth == 1:
        assert pipe["drains"] == {}, pipe
    else:
        assert pipe["drains"] == {"handover": 1, "complete": 1}, pipe


@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("case", ["eos", "no_joiner", "carried"])
def test_slot_handover_does_not_engage(tiny_server, depth, case):
    """What must NOT take the handover path, each beside a running row.
    ``eos``: a row that ends at an eos is seen at collect only; with a
    joiner waiting the drain's cause is ``joiner`` as before and the
    blocks dispatched past the eos are discarded. ``no_joiner``: a row
    that ends at its quota with nobody waiting forces no drain at all.
    ``carried``: the joiner that waits was prefilled on its request
    thread (a prompt over ``group_prefill_max``): ``joiner`` as before.
    Outputs bitwise solo's."""
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4,
                           pipeline_depth=depth)
    told, out = [], {}
    cb.row_ending_fn = lambda limit: told.append(1)
    solo = {"long": tiny_server.generate(LONG, max_new_tokens=64)}
    if case == "carried":
        # the waiting joiner arrives PREFILLED from its request thread (a
        # prompt over the group limit): the quota-ended row is not handed
        # over ahead of its collect, the drain is today's
        cb.group_prefill_max = len(LONG)
        over = [9, 8, 7, 6, 5, 4, 3]
        solo["short"] = tiny_server.generate(SHORT, max_new_tokens=24)
        solo["next"] = tiny_server.generate(over, max_new_tokens=8)
        threads = start_beside_a_long_row(
            cb, out, ("short", SHORT, dict(max_new_tokens=24)),
            ("next", over, dict(max_new_tokens=8)))
    elif case == "no_joiner":
        solo["short"] = tiny_server.generate(SHORT, max_new_tokens=24)
        threads = start_beside_a_long_row(
            cb, out, ("short", SHORT, dict(max_new_tokens=24)))
    else:
        free = tiny_server.generate(SHORT, max_new_tokens=64)[0]
        eos = int(free[2])  # a token the row emits in its first segment
        kw = dict(max_new_tokens=64, eos_id=eos)
        solo["short"] = tiny_server.generate(SHORT, **kw)
        solo["next"] = tiny_server.generate(NEXT, max_new_tokens=8)
        threads = start_beside_a_long_row(
            cb, out, ("short", SHORT, kw),
            ("next", NEXT, dict(max_new_tokens=8)))
    for t in threads:
        t.join()
    for name, want in solo.items():
        np.testing.assert_array_equal(out[name], want, err_msg=name)
    pipe = drained(cb)
    assert "handover" not in pipe["drains"], pipe
    assert pipe["wasted_overdecode_tokens"] > 0, pipe
    if case == "no_joiner":
        assert pipe["drains"] == {"complete": 1}, pipe
        assert told == [1, 1]
    elif case == "carried":
        assert pipe["drains"].get("joiner", 0) >= 1, pipe
        assert told == [1, 1, 1]  # announced all the same: once a row
    else:
        assert pipe["drains"] == {"joiner": 1, "complete": 1}, pipe
        # the eos row was far from its n when it ended: never told
        assert told == [1, 1]
