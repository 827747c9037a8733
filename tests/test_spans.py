"""The span primitive (``runtime/spans.py``) and where the program puts it:
aggregates that only grow, a request's tiles from the HTTP handler through
the scheduler and the engine to its first frame, and the engine loop's
phases in the profiler's own trace."""

import json
import threading
import time
import urllib.request

import pytest

from lambdipy_tpu.runtime import spans
from tests.test_runtime import _get, make_model_bundle

TILES = ["req.sched", "req.admit", "req.join", "req.prefill", "req.first"]


def window(a: dict, b: dict, name: str) -> tuple:
    zero = {"count": 0, "sum_s": 0.0, "buckets": [0] * 15}
    x, y = a.get(name, zero), b.get(name, zero)
    return (y["count"] - x["count"], y["sum_s"] - x["sum_s"],
            [q - p for p, q in zip(x["buckets"], y["buckets"])])


def test_aggregates_only_grow_and_two_scrapes_give_the_window():
    name = "test.window"
    with spans.span(name):
        time.sleep(0.003)
    before = spans.report()
    for pause in (0.0, 0.003, 0.02):
        with spans.span(name, rid=7, rows=2):
            time.sleep(pause)
    after = spans.report()
    count, sum_s, buckets = window(before, after, name)
    assert count == 3 and sum(buckets) == 3
    assert 0.023 <= sum_s < 0.5
    # nothing shrank, in any name
    for key, agg in before.items():
        assert after[key]["count"] >= agg["count"]
        assert after[key]["sum_s"] >= agg["sum_s"]
        assert all(q >= p for p, q in zip(agg["buckets"],
                                          after[key]["buckets"]))
    assert json.loads(json.dumps(after)) == after


@pytest.mark.parametrize("seconds,bucket", [
    (0.0, 0), (0.001, 0), (0.0011, 1), (0.002, 1), (0.0079, 3),
    (0.3, 9), (8.192, 13), (8.2, 14), (500.0, 14)])
def test_buckets_are_powers_of_two_in_milliseconds(seconds, bucket):
    assert spans.BUCKET_EDGES_MS == tuple(2 ** i for i in range(14))
    assert spans._bucket(seconds) == bucket


def test_concurrent_spans_and_requests_lose_no_update():
    """More threads than cores, a short switch interval: every span and
    every request record is counted once."""
    import sys

    name, threads, each = "test.stress", 16, 200
    before = spans.report()
    known = {r["rid"] for r in spans.requests()["requests"]}
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def work():
        for _ in range(each):
            with spans.span(name):
                pass
        for _ in range(20):
            rid = spans.begin_request()
            spans.mark(rid, "test.stress.tile")
            spans.first_frame(rid)
            spans.end_request(rid)

    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    after = spans.report()
    count, _, buckets = window(before, after, name)
    assert count == threads * each == sum(buckets)
    assert window(before, after, "test.stress.tile")[0] == threads * 20
    new = [r["rid"] for r in spans.requests()["requests"]
           if r["rid"] not in known]
    assert len(new) == len(set(new)) == threads * 20


def test_phases_never_overlap_and_close_on_exit():
    name_a, name_b = "test.phase_a", "test.phase_b"
    before = spans.report()
    phase = spans.phases()
    phase.enter(name_a)
    time.sleep(0.002)
    second = phase.enter(name_b, rids=spans.rids_arg([3, None, 5]))
    second.set(rows=2)
    phase.enter(name_a)
    phase.exit()
    phase.exit()   # closing twice is harmless
    after = spans.report()
    assert window(before, after, name_a)[0] == 2
    assert window(before, after, name_b)[0] == 1
    assert spans.rids_arg([3, None, 5]) == "3/5"


def test_a_request_record_tiles_without_gaps():
    rid = spans.begin_request(time.monotonic() - 0.01)
    spans.request_args(rid, prompt_tokens=3, max_tokens=8)
    spans.mark(rid, None)
    for name in TILES[:-1]:
        time.sleep(0.001)
        spans.mark(rid, name)
    time.sleep(0.001)
    spans.first_frame(rid)
    spans.first_frame(rid)   # later frames do not count
    spans.end_request(rid)
    spans.mark(rid, "req.join")     # a finished rid is ignored
    spans.end_request(rid)
    rec = next(r for r in spans.requests()["requests"] if r["rid"] == rid)
    by = {}
    for s in rec["spans"]:
        by.setdefault(s["name"], []).append(s)
    assert all(len(v) == 1 for v in by.values())
    assert rec["args"] == {"prompt_tokens": 3, "max_tokens": 8}
    tiles = [by[n][0] for n in TILES]
    for a, b in zip(tiles, tiles[1:]):
        assert a["t1"] == pytest.approx(b["t0"], abs=1e-9)
    assert tiles[0]["t0"] >= 0.01 - 1e-6       # what came before sched.admit
    assert tiles[-1]["t1"] == by["req.ttft"][0]["t1"]
    assert by["req.decode"][0]["t0"] == by["req.ttft"][0]["t1"]
    assert by["req"][0]["parent"] is None
    assert by["req"][0]["t1"] == by["req.decode"][0]["t1"]
    assert spans.requests(last=0)["requests"] == []


def test_the_request_context_carries_the_rid_into_the_ticket():
    from lambdipy_tpu.sched import (Scheduler, clear_request_context,
                                    current_request_rid,
                                    set_request_context)

    assert current_request_rid() is None
    set_request_context(cls="batch", rid=41)
    assert current_request_rid() == 41
    seen = []
    th = threading.Thread(target=lambda: seen.append(current_request_rid()))
    th.start()
    th.join()
    assert seen == [None]          # the context is the thread's own
    clear_request_context()
    assert current_request_rid() is None
    ticket = Scheduler().admit(rid=41)
    assert ticket.rid == 41


@pytest.fixture(scope="module")
def engine_server(tmp_path_factory):
    from lambdipy_tpu.runtime.server import BundleServer

    bundle = make_model_bundle(
        tmp_path_factory.mktemp("spans-bundle"), model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"batch_mode": "continuous", "batch_max": "4",
               "batch_segment": "4", "max_new_tokens": "16"})
    server = BundleServer(bundle, port=0).start_background()
    yield server
    server.stop()


def stream_completion(port: int, prompt: list, max_tokens: int) -> list:
    """One streamed /v1/completions; the served tokens."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                         "temperature": 0, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    tokens = []
    with urllib.request.urlopen(req, timeout=120) as resp:
        for line in resp:
            line = line.strip()
            if line.startswith(b"data: ") and line != b"data: [DONE]":
                event = json.loads(line[6:])
                assert "error" not in event, event
                tokens += event["choices"][0]["tokens"]
    return tokens


def test_a_streamed_request_leaves_a_tiled_record(engine_server):
    base = f"http://127.0.0.1:{engine_server.port}"
    known = {r["rid"] for r in _get(f"{base}/spans")["requests"]}
    m0 = _get(f"{base}/metrics")["spans"]
    assert len(stream_completion(engine_server.port, [1, 2, 3, 4, 5], 12)) \
        == 12
    m1 = _get(f"{base}/metrics")["spans"]
    new = [r for r in _get(f"{base}/spans")["requests"]
           if r["rid"] not in known]
    assert len(new) == 1
    rec = new[0]
    assert rec["args"] == {"prompt_tokens": 5, "max_tokens": 12}
    by = {s["name"]: s for s in rec["spans"]}
    assert set(by) == set(TILES) | {"req", "req.ttft", "req.decode"}
    tiles = [by[n] for n in TILES]
    assert all(0 <= s["t0"] <= s["t1"] for s in rec["spans"])
    for a, b in zip(tiles, tiles[1:]):
        assert a["t1"] == pytest.approx(b["t0"], abs=1e-9)
    # the tiles cover req.ttft but for what the handler does before admit
    assert 0 <= tiles[0]["t0"] < 0.05
    assert tiles[-1]["t1"] == by["req.ttft"]["t1"]
    assert by["req.ttft"]["t0"] == 0
    covered = sum(s["t1"] - s["t0"] for s in tiles)
    assert covered == pytest.approx(by["req.ttft"]["t1"] - tiles[0]["t0"],
                                    abs=1e-6)
    assert by["req.decode"]["t1"] == by["req"]["t1"] >= by["req.ttft"]["t1"]
    # and the same spans are in /metrics, as a window between two scrapes
    for name in list(TILES) + ["req", "req.ttft", "req.decode"]:
        count, sum_s, _ = window(m0, m1, name)
        assert count == 1, name
        assert sum_s == pytest.approx(by[name]["t1"] - by[name]["t0"],
                                      abs=1e-6)
    assert window(m0, m1, "eng.dispatch")[0] >= 3
    assert _get(f"{base}/spans?last=1")["requests"][0]["rid"] == rec["rid"]


def test_an_unstreamed_request_is_its_own_first_frame(engine_server):
    from tests.test_runtime import _post

    base = f"http://127.0.0.1:{engine_server.port}"
    out = _post(f"{base}/invoke", {"tokens": [5, 6, 7], "max_new_tokens": 6})
    assert out["ok"]
    rec = _get(f"{base}/spans?last=1")["requests"][0]
    by = {s["name"]: s for s in rec["spans"]}
    assert set(TILES) <= set(by)
    assert by["req.ttft"]["t1"] == by["req.first"]["t1"]
    assert by["req"]["t1"] - by["req.ttft"]["t1"] < 0.01


def test_a_profiler_capture_holds_the_engine_phases_with_the_rid(
        engine_server, tmp_path):
    import jax
    from jax.profiler import ProfileData

    base = f"http://127.0.0.1:{engine_server.port}"
    stream_completion(engine_server.port, [1, 2, 3], 8)    # all compiled
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1      # what the benchmark's traced server uses
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        stream_completion(engine_server.port, [9, 8, 7, 6], 12)
    finally:
        jax.profiler.stop_trace()
    rid = _get(f"{base}/spans?last=1")["requests"][0]["rid"]
    trace = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    engine = []   # (start, end, name, rids) of the eng.* events, per line
    for plane in ProfileData.from_file(str(trace)).planes:
        for line in plane.lines:
            events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                       dict(ev.stats)) for ev in line.events
                      if ev.name.startswith("eng.")]
            if events:
                engine.append(sorted(events, key=lambda e: e[:2]))
    assert len(engine) == 1          # one engine thread
    events = engine[0]
    names = {e[2] for e in events}
    assert {"eng.barrier", "eng.prefill", "eng.pack", "eng.dispatch",
            "eng.first", "eng.wait", "eng.fetch", "eng.book"} <= names
    named = {}
    for name in ("eng.dispatch", "eng.first", "eng.wait", "eng.fetch",
                 "eng.book", "eng.prefill"):
        named[name] = [e for e in events if e[2] == name
                       and str(rid) in str(e[3].get("rids", "")).split("/")]
        assert named[name], name
    # the request's first token is read and handed over once, after the
    # dispatch of its first segment and BEFORE that segment is collected
    assert len(named["eng.first"]) == 1
    assert named["eng.dispatch"][0][1] <= named["eng.first"][0][0]
    assert named["eng.first"][0][1] <= named["eng.wait"][0][0]
    assert named["eng.first"][0][1] <= named["eng.fetch"][0][0]
    dispatch = next(e for e in events if e[2] == "eng.dispatch"
                    and "window" in e[3])
    assert int(dispatch[3]["rows"]) == 1 and int(dispatch[3]["window"]) >= 16
    # leaf phases: no two overlap, none encloses another
    for a, b in zip(events, events[1:]):
        assert a[1] <= b[0], (a[:3], b[:3])
