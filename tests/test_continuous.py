"""Continuous (in-flight) batching: requests join a running decode at
segment boundaries with bitwise solo parity (VERDICT r3 missing #3); a
row's first token leaves when the engine has packed it, a segment before
its first block is collected."""

import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from lambdipy_tpu.runtime.continuous import ContinuousBatcher

# tiny_server: the session-scoped shared LlamaServer from conftest.py
# (one compiled-program cache across the continuous-engine modules)


def test_staggered_concurrent_requests_match_solo(tiny_server):
    """8 staggered concurrent requests produce exactly their solo outputs
    while SHARING segment steps (the whole point: rows ride the same
    device calls instead of queueing end-to-end)."""
    cb = ContinuousBatcher(tiny_server, slots=8, segment=8)
    prompts = [[1 + i, 2 + i, 3 + i, 5] for i in range(8)]
    n = 16
    solo = [tiny_server.generate(p, max_new_tokens=n) for p in prompts]

    results = [None] * 8

    def run(i):
        time.sleep(0.02 * i)  # staggered arrivals, mid-flight joins
        results[i] = cb.generate(prompts[i], max_new_tokens=n)

    with ThreadPoolExecutor(max_workers=8) as ex:
        list(ex.map(run, range(8)))

    for i in range(8):
        np.testing.assert_array_equal(results[i], solo[i],
                                      err_msg=f"request {i} diverged")
    stats = cb.stats()
    # solo would cost 8 requests x ceil(16/8) = 16 segment runs; sharing
    # must beat that, and rows-per-segment > 1 proves actual fusion
    assert stats["segments_run"] < 16, stats
    assert stats["rows_in_segments"] > stats["segments_run"], stats
    assert stats["requests_served"] == 8, stats


def test_midflight_join(tiny_server):
    """A request arriving while another is decoding joins at the next
    segment boundary instead of waiting for the whole decode."""
    cb = ContinuousBatcher(tiny_server, slots=4, segment=4)
    long_prompt, short_prompt = [1, 2, 3, 4, 5], [9, 8, 7]
    n_long, n_short = 24, 8
    solo_long = tiny_server.generate(long_prompt, max_new_tokens=n_long)
    solo_short = tiny_server.generate(short_prompt, max_new_tokens=n_short)

    out = {}

    def late():
        time.sleep(0.05)
        out["short"] = cb.generate(short_prompt, max_new_tokens=n_short)

    t = threading.Thread(target=late)
    t.start()
    out["long"] = cb.generate(long_prompt, max_new_tokens=n_long)
    t.join()
    np.testing.assert_array_equal(out["long"], solo_long)
    np.testing.assert_array_equal(out["short"], solo_short)


def test_mixed_eos_rows_share_the_batch(tiny_server):
    """eos is host-side: rows with DIFFERENT eos ids fuse into one batch
    and still match their solo outputs (including the eos filler tail)."""
    cb = ContinuousBatcher(tiny_server, slots=4, segment=4)
    # find a token each row actually emits, to use as its eos
    free = tiny_server.generate([5, 6, 7, 8], max_new_tokens=8)[0]
    eos_a = int(free[2])
    free_b = tiny_server.generate([1, 2], max_new_tokens=8)[0]
    eos_b = int(free_b[3])
    solo_a = tiny_server.generate([5, 6, 7, 8], max_new_tokens=8,
                                  eos_id=eos_a)
    solo_b = tiny_server.generate([1, 2], max_new_tokens=8, eos_id=eos_b)

    with ThreadPoolExecutor(max_workers=2) as ex:
        fa = ex.submit(cb.generate, [5, 6, 7, 8], max_new_tokens=8,
                       eos_id=eos_a)
        fb = ex.submit(cb.generate, [1, 2], max_new_tokens=8, eos_id=eos_b)
        np.testing.assert_array_equal(fa.result(), solo_a)
        np.testing.assert_array_equal(fb.result(), solo_b)


def test_logprobs_ride_continuous_batching(tiny_server):
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4)
    toks, lps = cb.generate([1, 2, 3], max_new_tokens=8,
                            return_logprobs=True)
    st, sl = tiny_server.generate([1, 2, 3], max_new_tokens=8,
                                  return_logprobs=True)
    np.testing.assert_array_equal(toks, st)
    np.testing.assert_allclose(lps, sl, rtol=1e-5, atol=1e-6)


def test_sampled_requests_batch_with_parity(tiny_server):
    """Sampled (temperature > 0) requests ride the engine (VERDICT r5
    #2) and every row — sampled next to greedy next to differently-
    knobbed sampled traffic — produces exactly its solo output: per-row
    knob operands + seed-derived per-row PRNG chains make a row's
    sample independent of batch composition."""
    cb = ContinuousBatcher(tiny_server, slots=4, segment=4)
    reqs = [
        dict(prompt=[1, 2, 3], kw=dict(temperature=0.9, seed=7)),
        dict(prompt=[9, 8, 7, 6], kw={}),  # greedy neighbor
        dict(prompt=[4, 4], kw=dict(temperature=1.5, top_k=3, seed=11)),
        dict(prompt=[5, 6, 7], kw=dict(temperature=0.7, top_p=0.9,
                                       seed=3)),
    ]
    solo = [tiny_server.generate(r["prompt"], max_new_tokens=8, **r["kw"])
            for r in reqs]
    with ThreadPoolExecutor(max_workers=4) as ex:
        futs = [ex.submit(cb.generate, r["prompt"], max_new_tokens=8,
                          **r["kw"]) for r in reqs]
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(f.result(), solo[i],
                                          err_msg=f"request {i} diverged")
    stats = cb.stats()
    assert stats["requests_served"] == 4, stats
    assert stats["rows_in_segments"] > stats["segments_run"], stats


def test_over_cache_len_falls_back_to_solo(tiny_server):
    """A request over the engine's capped cache_len serves SOLO (the
    bundle could serve it before continuous mode was enabled — the cap
    must not become a client-visible error, ADVICE r4); what the model
    itself can't hold still raises."""
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4, cache_len=32)
    prompt = list(range(1, 30))
    out = cb.generate(prompt, max_new_tokens=16)
    np.testing.assert_array_equal(
        out, tiny_server.generate(prompt, max_new_tokens=16))
    assert cb.stats()["segments_run"] == 0  # never touched the engine
    with pytest.raises(ValueError):  # beyond max_len: still an error
        cb.generate(list(range(1, 100)), max_new_tokens=120)


def test_engine_failure_surfaces_to_callers(tiny_server, monkeypatch):
    """An engine crash must fail pending requests, not hang them, and the
    engine must restart cleanly afterwards."""
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4)

    def boom(self):
        raise RuntimeError("injected engine failure")

    monkeypatch.setattr(ContinuousBatcher, "_segment_fn", boom)
    with pytest.raises(RuntimeError, match="injected"):
        cb.generate([1, 2, 3], max_new_tokens=8)
    monkeypatch.undo()
    out = cb.generate([1, 2, 3], max_new_tokens=8)
    np.testing.assert_array_equal(
        out, tiny_server.generate([1, 2, 3], max_new_tokens=8))


def test_more_requests_than_slots(tiny_server):
    """Joiners beyond the slot count wait for a free slot and still
    complete correctly (slot turnover mid-engine-run)."""
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4)
    prompts = [[1 + i, 3, 5] for i in range(5)]
    solo = [tiny_server.generate(p, max_new_tokens=8) for p in prompts]
    with ThreadPoolExecutor(max_workers=5) as ex:
        futs = [ex.submit(cb.generate, p, max_new_tokens=8)
                for p in prompts]
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(f.result(), solo[i],
                                          err_msg=f"request {i}")


@pytest.mark.slow
def test_http_continuous_batching_end_to_end(tmp_path):
    """batch_mode='continuous' through the real bundle + threaded HTTP
    server: concurrent greedy invokes ride shared segment steps and
    /metrics exposes the engine counters."""
    import json
    import urllib.request

    from tests.test_runtime import make_model_bundle
    from lambdipy_tpu.runtime.server import BundleServer

    bundle = make_model_bundle(
        tmp_path, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "8", "batch_mode": "continuous",
               "batch_max": "4", "batch_segment": "4"})
    server = BundleServer(bundle, port=0).start_background()
    base = f"http://127.0.0.1:{server.port}"

    def post(payload):
        req = urllib.request.Request(
            f"{base}/invoke", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    try:
        ref = post({"tokens": [1, 2, 3]})
        assert ref["ok"], ref
        with ThreadPoolExecutor(max_workers=4) as ex:
            futs = [ex.submit(post, {"tokens": [1, 2, 3 + i]})
                    for i in range(4)]
            results = [f.result() for f in futs]
        assert all(r["ok"] and r["n_new"] == 8 for r in results)
        # same prompt, concurrent or not -> same tokens
        again = post({"tokens": [1, 2, 3]})
        assert again["tokens"] == ref["tokens"]
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            metrics = json.loads(r.read())
        engine = metrics["handler"]["batching"]
        assert engine["mode"] == "continuous"
        assert engine["requests_served"] >= 6
        assert engine["rows_in_segments"] > engine["segments_run"], engine
    finally:
        server.stop()


def test_stream_rides_the_engine(tiny_server):
    """A streamed request joins the SHARED engine batch (VERDICT r5
    #3b): its chunk concatenation equals the fused output while another
    request decodes concurrently in the same segments."""
    cb = ContinuousBatcher(tiny_server, slots=4, segment=4)
    fused = tiny_server.generate([1, 2, 3], max_new_tokens=11)
    with ThreadPoolExecutor(max_workers=2) as ex:
        f_other = ex.submit(cb.generate, [9, 8, 7], max_new_tokens=8)
        chunks = list(cb.generate_stream([1, 2, 3], max_new_tokens=11))
        other = f_other.result()
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), fused)
    np.testing.assert_array_equal(
        other, tiny_server.generate([9, 8, 7], max_new_tokens=8))
    stats = cb.stats()
    assert stats["rows_in_segments"] > stats["segments_run"], stats


# -- the first token leaves at pack time --------------------------------------

FIRST_N, FIRST_SEG = 11, 4
FIRST_PREFIX = list(range(1, 20))
# name -> (engine arguments, [(row, request arguments)]); "eos": the row's
# own first token is its eos; "busy": the requests arrive while another
# row decodes, so ONE barrier packs them all
FIRST_CASES = {
    "greedy": ({}, [([1, 2, 3], {})]),
    "temperature": ({}, [([1, 2, 3], dict(temperature=0.9, seed=7))]),
    "top_k": ({}, [([4, 4], dict(temperature=1.5, top_k=3, seed=11))]),
    "top_p": ({}, [([5, 6, 7], dict(temperature=0.7, top_p=0.9, seed=3))]),
    "logprobs": ({}, [([5, 6], dict(return_logprobs=True))]),
    "eos_first": ({}, [([1, 2, 3], dict(eos="first"))]),
    "max_new_tokens_1": ({}, [([9, 8, 7], dict(n=1))]),
    "group_at_one_barrier": (dict(busy=True),
                             [([1, 2, 3], {}),
                              ([9, 8, 7, 6], dict(temperature=0.9, seed=5)),
                              ([4, 4], dict(return_logprobs=True))]),
    "carried_long_prompt": (dict(group_prefill_max=8),
                            [(list(range(1, 21)), {})]),
    "carried_prefix": ({}, [([4, 5], dict(prefix=FIRST_PREFIX))]),
    "paged": (dict(paged=True), [([6, 5, 4, 3], {})]),
    "paged_carried": (dict(paged=True, group_prefill_max=0),
                      [([9, 8, 7, 6, 5], {})]),
    "pipeline_depth_1": (dict(pipeline_depth=1), [([1, 2, 3], {})]),
    "pipeline_depth_2": (dict(pipeline_depth=2), [([1, 2, 3], {})]),
    "pipeline_depth_3": (dict(pipeline_depth=3), [([1, 2, 3], {})]),
    "spec_verify": (dict(spec_k=4), [([1, 2, 3, 1, 2, 3, 1, 2], {})]),
}


class Turnstile:
    """Holds the engine at the device wait that opens each segment's
    collection (site ``transport``) until the test lets one through."""

    def __init__(self, cb):
        self.passes = threading.Semaphore(0)
        self.parked = threading.Event()   # the engine stands at the gate
        self.lifted = False
        real = cb._device_wait

        def gated(site, gen, fn=None, *args, **kw):
            if site == "transport" and not self.lifted:
                self.parked.set()
                assert self.passes.acquire(timeout=60), "never let through"
                self.parked.clear()
            return real(site, gen, fn, *args, **kw)

        cb._device_wait = gated

    def let(self, n=1):
        for _ in range(n):
            self.passes.release()

    def lift(self):
        self.lifted = True
        self.let(64)


def stream_into_queue(cb, row, **kw):
    """Consume a stream on a thread of its own: its chunks, then None (or
    the exception that ended it), in a queue."""
    q = queue.Queue()

    def run():
        try:
            for chunk in cb.generate_stream(row, **kw):
                q.put(chunk)
            q.put(None)
        except Exception as e:  # noqa: BLE001 — handed to the test
            q.put(e)

    threading.Thread(target=run, daemon=True).start()
    return q


def wait_for(cond, what):
    deadline = time.monotonic() + 60
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.005)


@pytest.mark.parametrize("case", FIRST_CASES)
def test_first_token_leaves_before_the_first_segment_is_collected(
        tiny_server, case):
    """The prefill's token is handed over when the engine has packed the
    row: the first chunk holds exactly that one token and arrives while
    the collection of the row's first segment is still HELD; then
    ``segment - 1`` tokens, then whole segments; the concatenated stream
    is the solo stream, the fused output and the engine's own
    non-streamed output token for token (logprobs too); and
    ``first_tokens_early`` counts every row."""
    opts, reqs = FIRST_CASES[case]
    opts = dict(opts)
    busy, paged = opts.pop("busy", False), opts.pop("paged", False)
    if paged:
        from tests.test_paged import mk_paged

        cb, _ = mk_paged(tiny_server, segment=FIRST_SEG,
                         depth=opts.pop("pipeline_depth", 2), **opts)
    else:
        cb = ContinuousBatcher(tiny_server, slots=4, segment=FIRST_SEG,
                               **opts)
    plans = []
    for row, kw in reqs:
        kw = dict(kw)
        n, prefix = kw.pop("n", FIRST_N), kw.pop("prefix", None)
        want_lp = kw.get("return_logprobs", False)
        eos_first = kw.pop("eos", None)
        ref = tiny_server.generate((prefix or []) + row, max_new_tokens=n,
                                   **kw)
        ref_t, ref_l = ref if want_lp else (ref, None)
        if eos_first:
            kw["eos_id"] = int(ref_t[0, 0])
            ref_t = tiny_server.generate(row, max_new_tokens=n, **kw)
        plans.append(dict(row=row, n=n, ref_t=ref_t, ref_l=ref_l,
                          kw=dict(kw, prefix=prefix)))
    gate = Turnstile(cb)
    held = 0                    # collections let through so far
    blocker = None
    if busy:
        blocker = ThreadPoolExecutor(max_workers=1).submit(
            cb.generate, [7, 7, 7], max_new_tokens=40)
        wait_for(gate.parked.is_set, "the blocker's first collection")
    early0 = cb.stats()["first_tokens_early"]
    groups0 = cb.stats()["prefill_groups"]
    queues = [stream_into_queue(cb, p["row"], max_new_tokens=p["n"],
                                **p["kw"]) for p in plans]
    if busy:
        wait_for(lambda: cb.stats()["waiting_joiners"] == len(plans),
                 "the joiners queue behind the held collection")
        held = cb.pipeline_depth   # the drain that reaches the barrier
        gate.let(held)
    # the first chunk of every request, while the first segment that holds
    # its row has not been collected
    firsts = [q.get(timeout=60) for q in queues]
    stats = cb.stats()
    assert stats["segments_run"] == held, stats
    assert stats["first_tokens_early"] == early0 + len(plans), stats
    if busy:
        assert stats["prefill_groups"] == groups0 + 1, stats
    chunks = [[f] for f in firsts]
    for p, first in zip(plans, firsts):
        assert not isinstance(first, Exception), first
        tok = first[0] if p["ref_l"] is not None else first
        assert tok.shape == (1, 1) and tok[0, 0] == p["ref_t"][0, 0]
    if "spec_k" in opts:
        gate.lift()             # a verify step books a variable count
    live = list(range(len(plans)))
    while live:
        gate.let()              # one collection: a chunk for every row
        for i in list(live):
            got = queues[i].get(timeout=60)
            assert not isinstance(got, Exception), got
            if got is None:
                live.remove(i)
            else:
                chunks[i].append(got)
    gate.lift()
    if blocker is not None:
        blocker.result(timeout=60)
    for p, mine in zip(plans, chunks):
        want_lp = p["ref_l"] is not None
        toks = np.concatenate([c[0] if want_lp else c for c in mine], axis=1)
        ref_t, n, kw = p["ref_t"], p["n"], p["kw"]
        # the fused output, up to the chunk that holds an eos
        np.testing.assert_array_equal(toks, ref_t[:, :toks.shape[1]])
        if "eos_id" in kw:
            assert toks.shape[1] == 1       # the first token WAS the eos
        else:
            assert toks.shape[1] == n
        # the solo stream (the server's own, which this engine never
        # touches): the same tokens, chunked by whole segments there
        solo_kw = {k: v for k, v in kw.items() if k != "prefix"}
        solo = list(tiny_server.generate_stream(
            (kw["prefix"] or []) + p["row"], max_new_tokens=n,
            segment=FIRST_SEG, **solo_kw))
        solo_t = np.concatenate([c[0] if want_lp else c for c in solo],
                                axis=1)
        np.testing.assert_array_equal(toks, solo_t[:, :toks.shape[1]])
        if want_lp:
            lps = np.concatenate([c[1] for c in mine], axis=1)
            np.testing.assert_allclose(lps, p["ref_l"], rtol=1e-5,
                                       atol=1e-6)
        if "spec_k" not in opts and "eos_id" not in kw:
            # chunks end after the first token, then at whole segments
            sizes = [(c[0] if want_lp else c).shape[1] for c in mine]
            ends = [0, 1] + [min(n, e) for e in
                             range(FIRST_SEG, n + FIRST_SEG, FIRST_SEG)]
            assert sizes == [b - a for a, b in zip(ends, ends[1:])
                             if b > a], sizes
        # and the engine's non-streamed path sees the same tokens
        out = cb.generate(p["row"], max_new_tokens=n, **kw)
        np.testing.assert_array_equal(out[0] if want_lp else out, ref_t)
    stats = cb.stats()
    served = 2 * len(plans) + (1 if busy else 0)
    assert stats["requests_served"] == served, stats
    assert stats["first_tokens_early"] == served, stats


def assert_stream_eos_latch(server, cb):
    """Shared scenario (also run at depth 3 by the pipelined-engine
    module): streaming latches eos with fused-path parity."""
    fused = server.generate([1, 2, 3], max_new_tokens=11)
    eos = int(fused[0, 1])
    ref = server.generate([1, 2, 3], max_new_tokens=11, eos_id=eos)
    got = np.concatenate(list(cb.generate_stream(
        [1, 2, 3], max_new_tokens=11, eos_id=eos)), axis=1)
    assert got.shape[1] < 11  # stopped at a segment boundary
    np.testing.assert_array_equal(got, ref[:, :got.shape[1]])


def test_stream_eos_and_logprobs_through_engine(tiny_server):
    """Engine streaming latches eos with fused-path parity and carries
    logprobs."""
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4)
    assert_stream_eos_latch(tiny_server, cb)
    ft, fl = tiny_server.generate([5, 6], max_new_tokens=8,
                                  return_logprobs=True)
    pairs = list(cb.generate_stream([5, 6], max_new_tokens=8,
                                    return_logprobs=True))
    np.testing.assert_array_equal(
        np.concatenate([p[0] for p in pairs], axis=1), ft)
    np.testing.assert_allclose(
        np.concatenate([p[1] for p in pairs], axis=1), fl,
        rtol=1e-5, atol=1e-6)


def assert_prefix_join_parity(server, cb):
    """Shared scenario (also run at depth 3 by the pipelined-engine
    module): a prefix-cached row's engine output equals the full-prompt
    fused output, streamed and not, while sharing segments with other
    traffic."""
    prefix = list(range(1, 20))
    full = server.generate(prefix + [4, 5], max_new_tokens=8)
    with ThreadPoolExecutor(max_workers=2) as ex:
        f_other = ex.submit(cb.generate, [9, 8, 7], max_new_tokens=8)
        via = cb.generate([4, 5], max_new_tokens=8, prefix=prefix)
        f_other.result()
    np.testing.assert_array_equal(via, full)
    st = np.concatenate(list(cb.generate_stream(
        [4, 5], max_new_tokens=8, prefix=prefix)), axis=1)
    np.testing.assert_array_equal(st, full)


def test_prefix_rows_join_the_engine(tiny_server):
    """A prefix-cached request packs its continuation carry into an
    engine slot (VERDICT r5 #3c): output equals the full-prompt fused
    output, streamed and not, while sharing segments with other
    traffic; a cache-capped engine falls back solo instead."""
    cb = ContinuousBatcher(tiny_server, slots=4, segment=4)
    assert_prefix_join_parity(tiny_server, cb)
    prefix = list(range(1, 20))
    full = tiny_server.generate(prefix + [4, 5], max_new_tokens=8)
    capped = ContinuousBatcher(tiny_server, slots=2, segment=4,
                               cache_len=32)
    np.testing.assert_array_equal(
        capped.generate([4, 5], max_new_tokens=8, prefix=prefix), full)
    assert capped.stats()["segments_run"] == 0  # solo fallback


def test_group_prefill_packs_waiting_joiners(tiny_server):
    """Short-prompt joiners enqueue raw and the engine prefills them in
    ONE ragged call (VERDICT r5 #4 batched prefill): parity per row and
    fewer prefill programs than requests."""
    cb = ContinuousBatcher(tiny_server, slots=4, segment=4)
    reqs = [([1, 2, 3], dict(temperature=0.9, seed=7)),
            ([9, 8, 7, 6], {}),
            ([4, 4], dict(temperature=1.5, top_k=3, seed=11)),
            ([5, 6, 7], {})]
    solo = [tiny_server.generate(p, max_new_tokens=8, **kw)
            for p, kw in reqs]
    with ThreadPoolExecutor(max_workers=4) as ex:
        futs = [ex.submit(cb.generate, p, max_new_tokens=8, **kw)
                for p, kw in reqs]
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(f.result(), solo[i],
                                          err_msg=f"request {i}")
    stats = cb.stats()
    assert stats["requests_served"] == 4
    assert stats["rows_in_segments"] > stats["segments_run"], stats


def test_chunked_joiner_prefill_matches_solo():
    """A long-prompt joiner on a prefill_chunk server prefills through
    chunks (request-thread dispatches) with solo-exact output, alone
    and next to short traffic."""
    from lambdipy_tpu.models import registry

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    server = adapter.make_server(params, prefill_chunk=16)
    cb = ContinuousBatcher(server, slots=2, segment=4,
                           group_prefill_max=8)
    long_prompt = list(range(1, 60))
    ref = server.generate(long_prompt, max_new_tokens=8)
    np.testing.assert_array_equal(
        cb.generate(long_prompt, max_new_tokens=8), ref)
    with ThreadPoolExecutor(max_workers=2) as ex:
        fa = ex.submit(cb.generate, long_prompt, max_new_tokens=8)
        fb = ex.submit(cb.generate, [5, 6, 7], max_new_tokens=8)
        np.testing.assert_array_equal(fa.result(), ref)
        np.testing.assert_array_equal(
            fb.result(), server.generate([5, 6, 7], max_new_tokens=8))


@pytest.mark.slow  # deliberate per-chunk sleeps (~17 s); chunked-joiner
# parity coverage stays fast via test_chunked_joiner_prefill_matches_solo
def test_decode_segments_proceed_while_joiner_prefills():
    """The interleave claim (VERDICT r5 #4): while a long joiner walks
    its prefill CHUNKS, the engine keeps running decode segments for
    in-flight rows — an already-active short request finishes before
    the slowed-down chunked prefill completes."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import LlamaServer

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    server = adapter.make_server(params, prefill_chunk=16)
    cb = ContinuousBatcher(server, slots=2, segment=4,
                           group_prefill_max=8)
    long_prompt = list(range(1, 100))  # 6 chunks of 16 + tail
    # warm every program first so the slow-chunk run times no compiles
    ref_long = server.generate(long_prompt, max_new_tokens=8)
    np.testing.assert_array_equal(
        cb.generate(long_prompt, max_new_tokens=8), ref_long)
    short_ref = server.generate([5, 6, 7], max_new_tokens=16)

    real_ext = LlamaServer._prefix_ext_fn

    def slow_ext(self, sbs):
        fn = real_ext(self, sbs)

        def wrapped(*a, **kw):
            time.sleep(0.25)  # make each chunk visibly slow
            return fn(*a, **kw)

        return wrapped

    done_at = {}
    with ThreadPoolExecutor(max_workers=2) as ex:
        orig = LlamaServer._prefix_ext_fn
        LlamaServer._prefix_ext_fn = slow_ext
        try:
            f_long = ex.submit(cb.generate, long_prompt,
                               max_new_tokens=8)
            time.sleep(0.05)  # the long joiner enters its chunk walk

            def short():
                out = cb.generate([5, 6, 7], max_new_tokens=16)
                done_at["short"] = time.monotonic()
                return out

            f_short = ex.submit(short)
            out_short = f_short.result()
            out_long = f_long.result()
            done_at["long"] = time.monotonic()
        finally:
            LlamaServer._prefix_ext_fn = orig
    np.testing.assert_array_equal(out_short, short_ref)
    np.testing.assert_array_equal(out_long, ref_long)
    # the short request finished while the long one was still chunking
    assert done_at["short"] < done_at["long"], done_at


def test_chunked_joiner_on_capped_engine():
    """A cache-capped engine (cache_len < max_len) chunk-prefills long
    joiners through its own continuation program key — solo parity
    holds and the program is AOT-able under the 3-tuple key."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import LlamaServer

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    server = adapter.make_server(params, prefill_chunk=16)
    cb = ContinuousBatcher(server, slots=2, segment=4, cache_len=64,
                           group_prefill_max=8)
    prompt = list(range(1, 41))  # 40 + 8 <= 64; 16 | 64
    ref = server.generate(prompt, max_new_tokens=8)
    np.testing.assert_array_equal(cb.generate(prompt, max_new_tokens=8),
                                  ref)
    key = next(k for k in server.buckets
               if k[0] == "stream_prefix" and len(k) == 3)
    assert key[2] == 64
    assert LlamaServer._aot_name(key) is not None
    assert server._aot_examples(key) is not None  # 3-tuple synthesizes


def test_engine_over_tp_sharded_server(cpu_devices):
    """The continuous engine over a TENSOR-PARALLEL server (the 8B
    recipe's default shape: batch_mode=continuous + tp mesh): packed
    decode matches the unsharded solo output."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
    from lambdipy_tpu.parallel.sharding import shard_params

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    ref_server = adapter.make_server(params)
    refs = [ref_server.generate(p, max_new_tokens=8)
            for p in ([1, 2, 3], [9, 8, 7, 6])]

    mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    with use_mesh(mesh):
        sharded = shard_params(params, mesh, adapter.tp_rules)
    server = adapter.make_server(sharded, mesh=mesh)
    cb = ContinuousBatcher(server, slots=2, segment=4)
    with ThreadPoolExecutor(max_workers=2) as ex:
        fa = ex.submit(cb.generate, [1, 2, 3], max_new_tokens=8)
        fb = ex.submit(cb.generate, [9, 8, 7, 6], max_new_tokens=8)
        np.testing.assert_array_equal(fa.result(), refs[0])
        np.testing.assert_array_equal(fb.result(), refs[1])
    stats = cb.stats()
    assert stats["rows_in_segments"] > stats["segments_run"], stats


def test_engine_over_sp_mesh_long_context_path(cpu_devices, count_sp_decode):
    """Continuous batching over the LONG-CONTEXT serving shape
    (attn_backend='ring' + sp mesh): engine-packed rows decode through
    sequence-sharded sp_decode steps (asserted to trace — code-review
    r5 caught the vacuous dense-vs-dense version) and match the dense
    unsharded solo outputs."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
    from lambdipy_tpu.parallel.sharding import shard_params

    calls = count_sp_decode

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    dense = adapter.make_server(params)
    refs = [dense.generate(p, max_new_tokens=8)
            for p in ([1, 2, 3], [9, 8, 7, 6])]

    ring = registry.get("llama-tiny").build(
        extra={"attn_backend": "ring"})
    assert ring.config.attn_backend == "ring"
    mesh = make_mesh({"sp": 2}, devices=cpu_devices[:2])
    with use_mesh(mesh):
        sp_params = shard_params(params, mesh, ring.tp_rules)
    server = ring.make_server(sp_params, mesh=mesh)
    cb = ContinuousBatcher(server, slots=2, segment=4)
    with ThreadPoolExecutor(max_workers=2) as ex:
        fa = ex.submit(cb.generate, [1, 2, 3], max_new_tokens=8)
        fb = ex.submit(cb.generate, [9, 8, 7, 6], max_new_tokens=8)
        np.testing.assert_array_equal(fa.result(), refs[0])
        np.testing.assert_array_equal(fb.result(), refs[1])
    assert calls["n"] > 0, "sp decode path never traced"
    stats = cb.stats()
    assert stats["rows_in_segments"] > stats["segments_run"], stats


def test_warm_group_prefill_precompiles_burst_programs(tiny_server):
    """warm_group_prefill compiles every power-of-two group-prefill
    program up to slots, so a later joiner burst compiles NOTHING — on
    a remote-compile transport the unwarmed first burst paid ~30 s of
    compiles inside request latency (round-5 concurrent measurement)."""
    cb = ContinuousBatcher(tiny_server, slots=4, segment=4)
    assert cb.warm_group_prefill() == 3  # bb = 2, 4 + the long bucket
    before = tiny_server.compile_count
    for k in (2, 3, 4):  # 3 rides the bb=4 bucket
        entries = [dict(row=[5, 6], s=2, temperature=None, top_k=None,
                        top_p=None, seed=None) for _ in range(k)]
        cb._prefill_group(entries)
    assert tiny_server.compile_count == before, \
        "burst group-prefill must reuse the warmed programs"


@pytest.mark.slow  # one extra 4x64 prefill compile; the warm COUNTS
# (which include the long bucket) are asserted non-slow above/below
def test_warm_group_prefill_covers_long_prompt_bucket(tiny_server):
    """Prompts above the min bucket used to stay a residual compile
    cliff (ADVICE r5 continuous.py:222): the warm now also compiles the
    longest group-prefillable prompt bucket at the full-burst joiner
    count, so a burst of long-ish prompts compiles nothing. Prompt
    buckets BETWEEN the two warmed families still compile at first use
    — that residual is documented in warm_group_prefill's docstring."""
    cb = ContinuousBatcher(tiny_server, slots=4, segment=4)
    cb.warm_group_prefill()
    before = tiny_server.compile_count
    s_warm = min(cb.group_prefill_max, cb.cache_len // 2)
    entries = [dict(row=list(range(1, s_warm + 1)), s=s_warm,
                    temperature=None, top_k=None, top_p=None, seed=None)
               for _ in range(4)]
    cb._prefill_group(entries)
    assert tiny_server.compile_count == before, \
        "a full burst at the long-prompt bucket must hit warm programs"


def test_handler_daemon_warms_group_prefill(tmp_path):
    """The background warm daemon reaches the engine's group-prefill
    programs after the first invoke and reports progress in stats —
    the wiring the warm_group_prefill flag controls."""
    from tests.test_runtime import make_model_bundle
    from lambdipy_tpu.runtime.loader import load_bundle

    bundle = make_model_bundle(
        tmp_path, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        # explicit: the test helper defaults the warm daemon OFF for
        # suite economy; this test IS the daemon wiring
        extra={"max_new_tokens": "4", "batch_mode": "continuous",
               "batch_max": "4", "warm_group_prefill": "1"})
    r = load_bundle(bundle, warmup=True)
    assert r.warmup_result["ok"]
    deadline = time.monotonic() + 60
    done: list = []
    while time.monotonic() < deadline:
        done = r.state.stats().get("warm_buckets", {}).get("done", [])
        if any(str(d).startswith("group_prefill:") for d in done):
            break
        time.sleep(0.5)
    assert any(str(d).startswith("group_prefill:") for d in done), \
        r.state.stats()


def test_warm_group_prefill_covers_non_pow2_slots(tiny_server):
    """A full burst on a 6-slot engine buckets UP to the 8-row program
    (_next_bucket(6) = 8): warm must compile that bucket too, or the
    largest burst pays the compile cliff the warm exists to remove."""
    cb = ContinuousBatcher(tiny_server, slots=6, segment=4)
    assert cb.warm_group_prefill() == 4  # buckets 2, 4, 8 + long bucket
    before = tiny_server.compile_count
    entries = [dict(row=[5, 6], s=2, temperature=None, top_k=None,
                    top_p=None, seed=None) for _ in range(6)]
    cb._prefill_group(entries)
    assert tiny_server.compile_count == before
