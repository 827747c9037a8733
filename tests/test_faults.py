"""Engine fault isolation (runtime/faults.py + the recovery machinery in
runtime/continuous.py): deterministic injection grammar, replay-on-restart
bitwise parity, the watchdog wedging a hung engine and aborting its
waiters, drain-barrier cancellation (closed streams / expired deadlines),
the degradation ladder, wedged-aware fleet health (stub replicas — no
device), and — marked ``slow`` — the real-bundle-server e2e: /healthz
flipping wedged and admission 503ing the accept hole. The engine-owned
site x {exception, delay, hang} matrix is ``test_engine_fault_matrix`` at
the end; the tests before it pin the individual contracts."""

import json
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from lambdipy_tpu.runtime.continuous import ContinuousBatcher, RequestCancelled
from lambdipy_tpu.runtime.faults import (
    HANG_CAP_S,
    EngineWatchdogTimeout,
    FaultPlan,
    InjectedFault,
)

# tiny_server: the session-scoped shared LlamaServer from conftest.py
# (one compiled-program cache across the continuous-engine modules)


# -- spec grammar (pure) -----------------------------------------------------


def test_fault_plan_parsing():
    p = FaultPlan.from_spec("segment_fetch:hang@seg=3")
    assert p.describe() == ["segment_fetch:hang@seg=3,n=inf"]
    p = FaultPlan.from_spec(
        "transport:delay@ms=200,n=2; group_prefill:exception")
    assert p.describe() == ["transport:delay@seg=1,n=2,ms=200",
                            "group_prefill:exception@seg=1,n=1"]
    # aliases normalize; empty/None specs are inert no-op plans
    assert FaultPlan.from_spec("segment_fetch:raise").rules[0].kind \
        == "exception"
    assert not FaultPlan.from_spec(None).active()
    assert not FaultPlan.from_spec("  ").active()
    # a typo must fail the run loudly, not silently test nothing
    for bad in ("nosuchsite:hang", "segment_fetch:explode",
                "segment_fetch", "segment_fetch:hang@seg=x",
                "segment_fetch:hang@bogus=1"):
        with pytest.raises(ValueError):
            FaultPlan.from_spec(bad)


def test_fault_plan_deterministic_firing_window():
    """Rules key on per-site call counts: seg=N is where firing starts,
    n=K how many calls fire — bitwise-identical run after run."""
    plan = FaultPlan.from_spec("segment_fetch:exception@seg=2,n=2")
    plan.check("segment_fetch")            # call 1: before the window
    for _ in range(2):                     # calls 2-3: inside it
        with pytest.raises(InjectedFault):
            plan.check("segment_fetch")
    plan.check("segment_fetch")            # call 4: window exhausted
    plan.check("transport")                # other sites never match
    assert plan.counts() == {"segment_fetch": 4, "transport": 1}


def test_fault_plan_hang_releases_and_raises():
    """A released (or watchdog-aborted) hang still raises: a wait the
    system gave up on must not look like a success to its caller."""
    plan = FaultPlan.from_spec("transport:hang")
    out = {}

    def hangs():
        try:
            plan.check("transport")
            out["r"] = "returned"
        except InjectedFault as e:
            out["r"] = e.fault_kind

    t = threading.Thread(target=hangs, daemon=True)
    t.start()
    t.join(timeout=0.2)
    assert t.is_alive()          # genuinely blocked, far under the cap
    assert HANG_CAP_S >= 60      # the leak net is generous, not a timer
    plan.release()
    t.join(timeout=5.0)
    assert not t.is_alive() and out["r"] == "hang"
    # the interrupt event (the watchdog's abort path) unblocks the same
    # way, and still raises
    plan2 = FaultPlan.from_spec("transport:hang")
    aborted = threading.Event()
    aborted.set()
    with pytest.raises(InjectedFault):
        plan2.check("transport", interrupt=aborted)


def test_fault_plan_accepts_page_alloc_site():
    p = FaultPlan.from_spec("page_alloc:exception@seg=2,n=3")
    assert p.describe() == ["page_alloc:exception@seg=2,n=3"]
    p.check("page_alloc")                       # seg 1: clean
    with pytest.raises(InjectedFault) as exc:
        p.check("page_alloc")
    assert exc.value.fault_site == "page_alloc"


def test_injected_page_alloc_failure_sheds_one_row_only(tiny_server):
    """A page_alloc fault mid-admission sheds THAT row as priced
    backpressure (PagesExhausted, retry_after_s attached) while rows
    already in flight finish bitwise and later admissions serve — no
    engine wedge, no lost rows, failure attributed under ``page_alloc``
    in the fault stats."""
    from lambdipy_tpu.models.llama import init_page_arena, page_kv_bytes
    from lambdipy_tpu.runtime.pagepool import (PagePool, PagesExhausted,
                                               page_width)

    cfg = tiny_server.model.cfg
    page = page_width(cfg.max_len, 16)
    n_pages = 4 * (cfg.max_len // page) + 1
    pool = PagePool(n_pages=n_pages, page=page,
                    page_bytes=page_kv_bytes(cfg, page),
                    make_arena=lambda: init_page_arena(cfg, n_pages,
                                                       page))
    # the 2nd allocator call fails: the in-flight first row must not
    # notice; the engine's armed plan drives the pool site (ctor wiring)
    eng = ContinuousBatcher(
        tiny_server, slots=4, segment=8, page_pool=pool,
        faults=FaultPlan.from_spec("page_alloc:exception@seg=2"))
    assert pool.faults is eng.faults
    rows = [[1, 2, 3], [4, 5, 6], [7, 8, 9]]
    solo = [tiny_server.generate(r, max_new_tokens=16) for r in rows]

    out0 = {}
    started = threading.Event()

    def first():
        started.set()
        out0["v"] = eng.generate(rows[0], max_new_tokens=16)

    t = threading.Thread(target=first)
    t.start()
    started.wait()
    time.sleep(0.05)        # let row 0 enter the engine
    with pytest.raises(PagesExhausted) as exc:
        eng.generate(rows[1], max_new_tokens=16)
    assert exc.value.retry_after_s > 0
    t.join()
    np.testing.assert_array_equal(out0["v"], solo[0])   # no lost row
    # the engine never wedged and keeps serving
    assert not eng.wedged
    np.testing.assert_array_equal(
        eng.generate(rows[2], max_new_tokens=16), solo[2])
    rep = eng.fault_stats.report()
    assert rep["failures"].get("page_alloc") == 1, rep
    with eng._lock:
        while eng._engine_running:
            eng._lock.wait(0.05)
    pool.check_invariants()
    st = pool.stats()
    assert st["pages_free"] == st["pages_total"], st


# -- replay-on-restart (the acceptance-criteria parity claim) ----------------


def test_injected_fetch_fault_replays_bitwise(tiny_server):
    """A request whose first attempt dies at an injected segment_fetch
    exception is transparently requeued and replayed — the caller sees
    only its bitwise solo output. Greedy AND seeded-sampled rows (the
    sampled row is the stronger claim: its per-row PRNG chain must
    restart bitwise)."""
    reqs = [dict(prompt=[1, 2, 3, 4], kw={}),
            dict(prompt=[9, 8, 7], kw=dict(temperature=0.8, seed=7))]
    solo = [tiny_server.generate(r["prompt"], max_new_tokens=12, **r["kw"])
            for r in reqs]
    cb = ContinuousBatcher(
        tiny_server, slots=4, segment=4,
        faults=FaultPlan.from_spec("segment_fetch:exception@seg=1"))
    with ThreadPoolExecutor(max_workers=2) as ex:
        futs = [ex.submit(cb.generate, r["prompt"], max_new_tokens=12,
                          **r["kw"]) for r in reqs]
        for f, ref in zip(futs, solo):
            np.testing.assert_array_equal(f.result(), ref)
    faults = cb.stats()["faults"]
    assert faults["failures"].get("segment_fetch") == 1
    # whichever rows were in flight at the failure replayed — and every
    # replay delivered (arrival timing decides whether the second row
    # was already admitted when the fault fired)
    assert faults["replays"]["attempted"] >= 1
    assert faults["replays"]["succeeded"] == faults["replays"]["attempted"]
    assert faults["recoveries"] == 1
    assert not cb.wedged


def test_replay_budget_exhausts_to_explicit_error(tiny_server):
    """Past max_replays the row errors explicitly — never silently lost,
    never an infinite requeue loop against a persistent fault."""
    cb = ContinuousBatcher(
        tiny_server, slots=2, segment=4, max_replays=1,
        faults=FaultPlan.from_spec("segment_fetch:exception@seg=1,n=2"))
    with pytest.raises(InjectedFault):
        cb.generate([1, 2, 3], max_new_tokens=8)
    faults = cb.stats()["faults"]
    assert faults["replays"] == {"attempted": 1, "succeeded": 0}
    # the engine itself recovers: the next request serves bitwise
    np.testing.assert_array_equal(
        cb.generate([1, 2, 3], max_new_tokens=8),
        tiny_server.generate([1, 2, 3], max_new_tokens=8))


def test_long_prompt_row_replays_through_chunked_path(tiny_server):
    """A replayed row whose prompt exceeds group_prefill_max must NOT
    re-prefill through the ragged group program — that shape was never
    compiled or warmed, and under a production watchdog the fresh
    compile would trip mid-recovery and burn the replay budget. The
    replay re-runs the same chunked/solo prefill path the row was
    admitted with (already-compiled programs), bitwise the fault-free
    run."""
    prompt = list(range(1, 13))   # 12 tokens > group_prefill_max=4
    solo = tiny_server.generate(prompt, max_new_tokens=8)
    cb = ContinuousBatcher(
        tiny_server, slots=2, segment=4, group_prefill_max=4,
        faults=FaultPlan.from_spec("segment_fetch:exception@seg=1"))
    np.testing.assert_array_equal(cb.generate(prompt, max_new_tokens=8),
                                  solo)
    faults = cb.stats()["faults"]
    assert faults["replays"] == {"attempted": 1, "succeeded": 1}
    # the replay prefilled the row solo — the ragged group program
    # (never compiled for this length) was not touched
    assert cb.prefill_groups == 0


def test_done_but_undrained_row_survives_engine_error(tiny_server):
    """The PR 5 preservation path, now exercised deterministically: a
    row that completed mid-pipeline (done=True, slot held as garbage
    until the drain barrier) keeps its bitwise result through an engine
    failure injected UNDER it — only the unfinished neighbor replays."""
    short, long_ = [5, 6, 7], [1, 2, 3, 4]
    solo_short = tiny_server.generate(short, max_new_tokens=4)
    solo_long = tiny_server.generate(long_, max_new_tokens=12)
    # segment 4, depth 2: fetch #1 (slowed 120 ms by the transport
    # delay, so the long row reliably arrives while it is in flight)
    # completes the short row mid-pipeline; fetch #2 fails. At failure
    # time the short row is done-but-undrained, the long row mid-decode.
    cb = ContinuousBatcher(
        tiny_server, slots=2, segment=4, pipeline_depth=2,
        faults=FaultPlan.from_spec(
            "transport:delay@ms=120,n=2;segment_fetch:exception@seg=2"))
    with ThreadPoolExecutor(max_workers=2) as ex:
        f_short = ex.submit(cb.generate, short, max_new_tokens=4)
        time.sleep(0.05)  # the short row packs first and is in flight
        f_long = ex.submit(cb.generate, long_, max_new_tokens=12)
        np.testing.assert_array_equal(f_short.result(), solo_short)
        np.testing.assert_array_equal(f_long.result(), solo_long)
    faults = cb.stats()["faults"]
    # exactly one row replayed: the finished one kept its result
    assert faults["replays"]["attempted"] == 1
    assert faults["replays"]["succeeded"] == 1
    assert faults["failures"].get("segment_fetch") == 1


@pytest.mark.parametrize("fetch,delivered", [(1, 1), (2, 4)],
                         ids=["after_the_first_token",
                              "after_the_first_segment"])
def test_streamed_row_with_delivered_bytes_errors_not_replays(
        tiny_server, fetch, delivered):
    """Once bytes reached the client a replay could splice a restarted
    decode onto the open stream — the row must surface the error as a
    terminal event instead (and the stream must not hang). The first
    bytes are the prefill's token, sent when the row is packed: a failure
    of the row's FIRST segment already finds the stream started."""
    # the transport delay before the failing fetch gives the consumer
    # 300 ms to latch entry["streamed"] on what was booked before it —
    # deterministic ordering, not a scheduler race
    cb = ContinuousBatcher(
        tiny_server, slots=2, segment=4,
        faults=FaultPlan.from_spec(
            f"transport:delay@seg={fetch},ms=300;"
            f"segment_fetch:exception@seg={fetch}"))
    chunks = []
    with pytest.raises(InjectedFault):
        for chunk in cb.generate_stream([1, 2, 3], max_new_tokens=16):
            chunks.append(chunk)
    # exactly what went out before the fault, a prefix of the solo tokens,
    # and nothing of a replay spliced behind it
    got = np.concatenate(chunks, axis=1)
    np.testing.assert_array_equal(
        got, tiny_server.generate([1, 2, 3], max_new_tokens=16)[:, :delivered])
    assert cb.stats()["faults"]["replays"]["attempted"] == 0


def test_failure_before_the_first_token_replays_a_stream_bitwise(tiny_server):
    """The early read of the first tokens fails: no byte has reached the
    client, so the row is requeued and its stream is, chunk for chunk, the
    one a clean engine gives."""
    cb = ContinuousBatcher(
        tiny_server, slots=2, segment=4,
        faults=FaultPlan.from_spec("first_fetch:exception@seg=1"))
    kw = dict(temperature=0.9, seed=7)       # the PRNG chain restarts too
    chunks = list(cb.generate_stream([1, 2, 3], max_new_tokens=11, **kw))
    np.testing.assert_array_equal(
        np.concatenate(chunks, axis=1),
        tiny_server.generate([1, 2, 3], max_new_tokens=11, **kw))
    stats = cb.stats()
    assert stats["faults"]["failures"].get("first_fetch") == 1
    assert stats["faults"]["replays"]["attempted"] == 1
    assert stats["faults"]["replays"]["succeeded"] == 1
    # the failed attempt delivered nothing: one row, one early token
    assert stats["first_tokens_early"] == 1
    assert stats["requests_served"] == 1


def test_stale_generation_at_the_early_delivery_books_nothing(tiny_server):
    """A failure handler requeues the rows between the early read and its
    booking: the superseded engine thread raises ``_StaleEngine`` under
    the lock and leaves the reset entries alone — a token booked there
    would lead the replay's own."""
    from lambdipy_tpu.runtime.continuous import _StaleEngine

    cb = ContinuousBatcher(tiny_server, slots=2, segment=4)
    real_wait, real_body = cb._device_wait, cb._engine_body
    fired, ended = [], []

    def racing(site, gen, fn=None, *args, **kw):
        out = real_wait(site, gen, fn, *args, **kw)
        if site == "first_fetch" and not fired:
            fired.append(gen)
            cb._fail_engine(RuntimeError("failed beside the early read"),
                            site="test", gen=gen)
        return out

    def spy(gen):
        try:
            real_body(gen)
        except BaseException as e:
            ended.append((gen, type(e)))
            raise

    cb._device_wait, cb._engine_body = racing, spy
    out = cb.generate([1, 2, 3], max_new_tokens=11)
    np.testing.assert_array_equal(
        out, tiny_server.generate([1, 2, 3], max_new_tokens=11))
    assert ended == [(fired[0], _StaleEngine)]
    stats = cb.stats()
    assert stats["first_tokens_early"] == 1     # the replay's, not the stale
    assert stats["faults"]["replays"]["succeeded"] == 1


def test_hung_early_fetch_trips_the_watchdog_under_its_own_site(tiny_server):
    """The early read is a device wait like the loop's others: one that
    never answers trips the watchdog as ``watchdog:first_fetch``, and the
    replay lands bitwise."""
    cb = ContinuousBatcher(
        tiny_server, slots=2, segment=4, watchdog_s=0.4,
        faults=FaultPlan.from_spec("first_fetch:hang@seg=1,n=1"))
    np.testing.assert_array_equal(
        cb.generate([4, 2, 1], max_new_tokens=8),
        tiny_server.generate([4, 2, 1], max_new_tokens=8))
    faults = cb.stats()["faults"]
    assert faults["watchdog_trips"] >= 1
    assert faults["failures"].get("watchdog:first_fetch", 0) >= 1
    assert faults["replays"]["succeeded"] == 1
    assert not cb.wedged


# -- watchdog ----------------------------------------------------------------


def test_watchdog_wedges_hung_engine_and_aborts_waiters(tiny_server):
    """A hung device wait (injected)
    trips the watchdog within its bound: with no replay budget every
    waiter gets an explicit error instead of blocking forever, the
    engine reports wedged on its O(1) fault surface, and nothing is
    silently lost."""
    plan = FaultPlan.from_spec("segment_fetch:hang@seg=1,n=1")
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4,
                           faults=plan, watchdog_s=0.4, max_replays=0)
    t0 = time.monotonic()
    try:
        with pytest.raises(EngineWatchdogTimeout):
            cb.generate([1, 2, 3], max_new_tokens=8)
        elapsed = time.monotonic() - t0
        assert elapsed < 8.0, f"waiter outlived the bound: {elapsed:.1f}s"
        assert cb.wedged
        state = cb.fault_state()
        assert state["wedged"] and not state["restarting"]
        faults = cb.stats()["faults"]
        assert faults["watchdog_trips"] >= 1
        assert faults["failures"].get("watchdog:segment_fetch", 0) >= 1
    finally:
        plan.release()
    # a clean request IS the recovery probe: serving again clears the
    # wedge and counts the recovery
    np.testing.assert_array_equal(
        cb.generate([1, 2, 3], max_new_tokens=8),
        tiny_server.generate([1, 2, 3], max_new_tokens=8))
    assert not cb.wedged
    assert cb.stats()["faults"]["recoveries"] >= 1


def test_watchdog_bounded_hang_recovers_via_replay(tiny_server):
    """A one-shot hang (transient transport stall) trips the watchdog,
    which requeues the rows; the replay through the restarted engine is
    bitwise and the wedge clears on the first successful fetch."""
    cb = ContinuousBatcher(
        tiny_server, slots=2, segment=4, watchdog_s=0.4,
        faults=FaultPlan.from_spec("segment_fetch:hang@seg=1,n=1"))
    np.testing.assert_array_equal(
        cb.generate([4, 2, 1], max_new_tokens=8),
        tiny_server.generate([4, 2, 1], max_new_tokens=8))
    faults = cb.stats()["faults"]
    assert faults["watchdog_trips"] >= 1
    assert faults["replays"]["succeeded"] == 1
    assert not cb.wedged


def test_tripped_wait_does_not_block_wedged_self_probe(tiny_server):
    """A REAL (non-injected) permanent hang never returns, so its wait
    record lingers in the registry forever — the finally-pop can't run.
    The monitor must treat a tripped record as disowned: the wedged-idle
    self-probe still fires and clears the wedge once the transport
    answers again (here: immediately, the CPU device is fine)."""
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4,
                           watchdog_s=0.3, max_replays=0)
    release = threading.Event()
    gen0 = cb._gen

    def waiter():
        try:
            # a genuine hang: blocks regardless of the watchdog's abort
            cb._device_wait("segment_fetch", gen0, release.wait, 30)
        except Exception:  # noqa: BLE001 — post-release unwind
            pass

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not cb.wedged:
            time.sleep(0.02)
        assert cb.wedged
        # the hung record is tripped but still registered — the hang
        # is real, nothing will ever pop it
        assert any(rec["tripped"] for rec in cb._waits.values())
        # the self-probe fires despite it (base cadence 2x watchdog)
        # and clears the wedge
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and cb.wedged:
            time.sleep(0.05)
        assert not cb.wedged
        assert cb.stats()["faults"]["recoveries"] >= 1
    finally:
        release.set()
        t.join(timeout=5)


# -- drain-barrier cancellation ----------------------------------------------


def test_expired_deadline_cancels_at_barrier(tiny_server):
    """A queued row whose x-deadline-ms expired cancels at the next
    drain barrier instead of burning a slot on an answer nobody can
    use. The single-slot engine is kept busy (transport delays) past
    the second request's deadline, so the cancellation is
    deterministic."""
    from lambdipy_tpu.sched import clear_request_context, set_request_context

    cb = ContinuousBatcher(
        tiny_server, slots=1, segment=4,
        faults=FaultPlan.from_spec("transport:delay@ms=120,n=2"))
    solo = tiny_server.generate([7, 7], max_new_tokens=32)
    results = {}

    def busy():
        results["a"] = cb.generate([7, 7], max_new_tokens=32)

    ta = threading.Thread(target=busy, daemon=True)
    ta.start()
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:          # A holds the only slot
        if cb.stats()["active_rows"] >= 1:
            break
        time.sleep(0.005)
    assert cb.stats()["active_rows"] >= 1
    set_request_context(cls="interactive", deadline_ms=50.0)
    try:
        with pytest.raises(RequestCancelled):
            cb.generate([1, 2, 3], max_new_tokens=8)
    finally:
        clear_request_context()
    ta.join(timeout=60)
    np.testing.assert_array_equal(results["a"], solo)  # A unaffected
    assert cb.stats()["faults"]["cancelled"] == 1


def test_abandoned_stream_cancels_and_frees_slot(tiny_server):
    """Closing a stream mid-decode (client disconnect) flags the row;
    the next drain barrier (forced here by a joiner — the churn case the
    satellite is about) cancels it instead of decoding its remaining
    ~100 tokens for nobody, and the neighbor's output is untouched."""
    cb = ContinuousBatcher(tiny_server, slots=2, segment=4)
    stream = cb.generate_stream([1, 2, 3], max_new_tokens=100)
    next(stream)          # first chunk delivered, decode is in flight
    stream.close()        # client went away
    # a joiner forces the bounded drain + barrier where the abandoned
    # row is cancelled, then decodes normally in the freed engine
    np.testing.assert_array_equal(
        cb.generate([9, 8], max_new_tokens=8),
        tiny_server.generate([9, 8], max_new_tokens=8))
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        stats = cb.stats()
        if stats["faults"]["cancelled"] and not stats["active_rows"]:
            break
        time.sleep(0.05)
    stats = cb.stats()
    assert stats["faults"]["cancelled"] == 1
    assert stats["active_rows"] == 0


# -- degradation ladder ------------------------------------------------------


def test_degradation_ladder_steps_and_restores(tiny_server):
    """Two failures inside the window step the ladder (level 1 forces
    the synchronous depth-1 loop); a clean interval restores level 0 and
    counts the restore."""
    cb = ContinuousBatcher(
        tiny_server, slots=2, segment=4, pipeline_depth=2, max_replays=2,
        degrade_window_s=60.0, degrade_clean_s=1.0,
        faults=FaultPlan.from_spec("segment_fetch:exception@seg=1,n=2"))
    # attempt 1 fails (failure #1), replay 1 fails (failure #2 -> level
    # 1), replay 2 runs clean through the degraded engine — bitwise
    np.testing.assert_array_equal(
        cb.generate([3, 1, 4], max_new_tokens=8),
        tiny_server.generate([3, 1, 4], max_new_tokens=8))
    faults = cb.stats()["faults"]
    assert faults["degrade_level"] == 1
    assert faults["degrade_steps"] == {"1": 1}
    assert faults["last_degrade_cause"] == "segment_fetch"
    time.sleep(1.2)  # a clean interval passes with no failures
    np.testing.assert_array_equal(
        cb.generate([3, 1, 4], max_new_tokens=8),
        tiny_server.generate([3, 1, 4], max_new_tokens=8))
    faults = cb.stats()["faults"]
    assert faults["degrade_level"] == 0
    assert faults["restores"] == 1


# -- wedged-aware fleet health (stub replicas, no device) --------------------


class _WedgeableStub:
    """Minimal bundle-server stand-in speaking the /healthz + /invoke
    contract, with a flip-able wedged flag — the fleet-side view of a
    replica whose engine watchdog declared the device transport dead."""

    def __init__(self):
        self.cfg = {"wedged": False}
        self.invokes = 0
        stub = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _send(self, code, payload):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    w = stub.cfg["wedged"]
                    self._send(200, {"ok": True, "ready": not w,
                                     "wedged": w, "pid": 1000})
                elif self.path == "/metrics":
                    self._send(200, {"count": stub.invokes})
                else:
                    self._send(404, {"ok": False})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                if stub.cfg["wedged"]:
                    # a wedged engine's admission gate sheds — the stub
                    # stands in for server.py's accept-hole 503
                    self._send(503, {"ok": False, "shed": True,
                                     "reason": "wedged",
                                     "retry_after_s": 2.0})
                    return
                stub.invokes += 1
                self._send(200, {"ok": True, "echo": body.get("tokens")})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def kill(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_pool_ejects_wedged_replica_and_router_routes_around():
    """The watchdog e2e acceptance check, fleet side: a replica whose
    /healthz reports wedged:true is EJECTED at probe speed (a liveness
    200 notwithstanding), never offered as a warming-degraded fallback,
    and concurrent traffic through the router all lands on the healthy
    replica — zero lost requests. Clearing the wedge readmits it through
    the normal consecutive-passes path."""
    from lambdipy_tpu.fleet import EJECTED, READY, FleetRouter, ReplicaPool

    s0, s1 = _WedgeableStub(), _WedgeableStub()
    pool = ReplicaPool(probe_interval=0.1, fail_threshold=1,
                       readmit_passes=2, probe_timeout=2.0)
    pool.attach("r0", s0.url)
    pool.attach("r1", s1.url)
    router = FleetRouter(pool, affinity_on=False, max_retries=2,
                         backoff_s=0.01, backoff_cap_s=0.2)
    router.start_background()
    try:
        pool.probe_all()
        assert {r.name for r in pool.routable()} == {"r0", "r1"}
        s0.cfg["wedged"] = True
        pool.probe_all()
        r0 = pool.replicas["r0"]
        assert r0.state == EJECTED and r0.wedged
        assert [r.name for r in pool.routable()] == ["r1"]
        # wedged-but-live is NOT a brownout fallback: degrading to it
        # would turn fleet-wide warmups into guaranteed timeouts
        assert pool.live_fallback() == []
        # fleet /healthz surfaces which replicas are wedged
        with urllib.request.urlopen(
                f"http://127.0.0.1:{router.port}/healthz", timeout=10) as r:
            h = json.loads(r.read())
        assert h["ok"] and h["wedged"] == ["r0"]

        results = []

        def worker(i):
            req = urllib.request.Request(
                f"http://127.0.0.1:{router.port}/invoke",
                data=json.dumps({"tokens": [i]}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                results.append(json.loads(r.read()))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 8 and all(r["ok"] for r in results)
        assert s1.invokes == 8 and s0.invokes == 0

        # recovery: wedge clears -> readmitted after readmit_passes
        s0.cfg["wedged"] = False
        for _ in range(3):
            pool.probe_all()
        assert pool.replicas["r0"].state == READY
        assert {r.name for r in pool.routable()} == {"r0", "r1"}
    finally:
        router.stop()
        pool.close()
        for s in (s0, s1):
            s.kill()


def test_server_maps_request_cancelled_to_shed_503(monkeypatch, tmp_path):
    """A RequestCancelled escaping handler.invoke (the engine cancelled
    the row at a drain barrier: deadline expired / waiter gone) is NOT a
    server fault: /invoke answers shed-style — 503 + Retry-After with a
    shed body — instead of a generic 500, and the shed counter gains a
    ``cancelled`` reason."""
    from pathlib import Path
    from types import SimpleNamespace

    import lambdipy_tpu.runtime.server as server_mod
    from lambdipy_tpu.runtime.loader import BootReport

    def invoke(st, request):
        raise RequestCancelled("cancelled at drain barrier: "
                               "deadline expired")

    def stub_boot(bundle_dir, warmup=True):
        return BootReport(
            bundle_dir=Path(bundle_dir),
            handler=SimpleNamespace(invoke=invoke),
            state=SimpleNamespace(meta={"model": "stub"},
                                  stats=lambda: {"stub": True}),
            stages={"init": 0.0}, manifest={"payload": {"extra": {}}})

    monkeypatch.setattr(server_mod, "load_bundle", stub_boot)
    srv = server_mod.BundleServer(tmp_path, port=0,
                                  warmup=False).start_background()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/invoke",
            data=json.dumps({"tokens": [1, 2], "n": 4}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 503
        assert int(exc.value.headers["Retry-After"]) >= 1
        body = json.loads(exc.value.read())
        assert not body["ok"] and "deadline expired" in body["error"]
        shed = srv.sched.admission.shed_report()
        assert shed["by_reason"].get("cancelled") == 1
        # a cancellation is not an error: record_error() was never hit
        assert srv.stats.report()["errors"] == 0
    finally:
        threading.Thread(target=srv.stop, daemon=True).start()


# -- real-bundle-server e2e (slow: boots a server) ---------------------------


@pytest.mark.slow
def test_server_healthz_wedged_and_admission_accept_hole(tmp_path):
    """End to end on a real bundle server: an injected segment_fetch
    hang flips /healthz to ready:false wedged:true within the watchdog
    bound, admission 503s (the accept hole) while the wedged engine is
    restarting instead of queueing into it, and once the bounded hang
    rule burns out the replay succeeds and the wedge clears."""
    from lambdipy_tpu.runtime.server import BundleServer

    from test_runtime import make_model_bundle

    # the watchdog is sized ABOVE the tiny model's first-use compile
    # wall (the operator contract: a monitor cannot tell a cold XLA
    # compile from a wedge — warmup=False here makes every program
    # cold, including the degraded-ladder variants compiled mid-replay)
    # but far UNDER the injected hang's duration, so only the hang trips
    bundle = make_model_bundle(
        tmp_path, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"batch_mode": "continuous", "batch_max": "2",
               "batch_segment": "4", "engine_watchdog_s": "3.0",
               "max_replays": "8",
               "fault_spec": "segment_fetch:hang@seg=2,n=5"})
    server = BundleServer(bundle, warmup=False).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        def get(path):
            with urllib.request.urlopen(base + path, timeout=10) as r:
                return json.loads(r.read())

        h = get("/healthz")
        assert h["ok"] and h["ready"] and not h["wedged"]

        # first request: segment fetch #1 succeeds, fetches #2-#6 hang
        # -> the watchdog trips + requeues ~5 times (each trip ~3 s),
        # keeping the engine wedged+restarting for seconds; the 6th
        # attempt's fetch runs clean, so the request ultimately succeeds
        # via transparent replay
        done = {}

        def doomed():
            try:
                with urllib.request.urlopen(urllib.request.Request(
                        base + "/invoke",
                        data=json.dumps({"tokens": [1, 2, 3],
                                         "n": 16}).encode(),
                        headers={"Content-Type": "application/json"}),
                        timeout=120) as r:
                    done["out"] = json.loads(r.read())
            except Exception as e:  # noqa: BLE001 — inspected below
                done["err"] = e

        t = threading.Thread(target=doomed, daemon=True)
        t.start()
        deadline = time.monotonic() + 30.0
        h = {}
        while time.monotonic() < deadline:
            h = get("/healthz")
            if h.get("wedged"):
                break
            time.sleep(0.05)
        assert h.get("wedged") and not h["ready"], h
        assert h["engine"]["wedged"]

        # the accept hole: while wedged AND restarting, new work sheds
        # 503 + Retry-After instead of queueing into a dead engine
        sheds = 0
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline and not sheds:
            eng = get("/healthz").get("engine", {})
            if not (eng.get("wedged") and eng.get("restarting")):
                if "out" in done or "err" in done:
                    break  # the recovery already landed — too late
                time.sleep(0.02)
                continue
            try:
                urllib.request.urlopen(urllib.request.Request(
                    base + "/invoke",
                    data=json.dumps({"tokens": [9, 9], "n": 4}).encode(),
                    headers={"Content-Type": "application/json"}),
                    timeout=10).read()
            except urllib.error.HTTPError as e:
                if e.code == 503:
                    body = json.loads(e.read())
                    assert body.get("shed") == "wedged"
                    assert e.headers.get("Retry-After")
                    sheds += 1
        assert sheds, "admission never shed while wedged+restarting"
        t.join(timeout=120)
        assert not t.is_alive(), "doomed request never resolved"
        # the hang was transient (n=5): the replay delivered a real
        # result — transparently, the client never saw the trips
        assert done.get("out", {}).get("ok"), done

        # wedge cleared by the successful fetch; admission open again
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            h = get("/healthz")
            if h["ready"] and not h["wedged"]:
                break
            time.sleep(0.1)
        assert h["ready"] and not h["wedged"], h
        m = get("/metrics")
        faults = m["handler"]["batching"]["faults"]
        assert faults["watchdog_trips"] >= 1
        assert faults["replays"]["succeeded"] >= 1
        assert faults["wedged"] is False
    finally:
        threading.Thread(target=server.stop, daemon=True).start()


# -- the structured site registry (chaos-soak satellite) ---------------------


def test_site_registry_metadata():
    """Every site carries an owner, its arming env var, and a note; the
    tuple view stays in sync; list_sites filters feed the nemesis menu
    and the docs table."""
    from lambdipy_tpu.runtime.faults import REGISTRY, SITES, list_sites

    assert tuple(REGISTRY) == SITES
    for site in REGISTRY.values():
        assert site.owner in ("engine", "store", "pool", "router"), site
        assert site.env in ("LAMBDIPY_FAULT", "LAMBDIPY_FLEET_FAULT")
        assert site.note
        # the env var follows the owner: replica-process sites arm via
        # LAMBDIPY_FAULT, fleet-process sites via LAMBDIPY_FLEET_FAULT
        want = ("LAMBDIPY_FAULT" if site.owner in ("engine", "store")
                else "LAMBDIPY_FLEET_FAULT")
        assert site.env == want, site
    engine = {s.name for s in list_sites(owner="engine")}
    assert "segment_fetch" in engine and "probe" not in engine
    fleet = {s.name for s in list_sites(env="LAMBDIPY_FLEET_FAULT")}
    assert "route_connect" in fleet and "prefix_walk" not in fleet


def test_every_fire_site_in_the_tree_is_registered():
    """Grep-based completeness: every literal fault-site reference in
    lambdipy_tpu/ (``faults.check("x")`` and ``_device_wait("x", ...)``
    call sites) names a registered site, and every registered site has
    at least one call site — a new site cannot silently dodge the
    chaos soak's registry-derived nemesis menu."""
    import re
    from pathlib import Path

    from lambdipy_tpu.runtime.faults import REGISTRY

    root = Path(__file__).resolve().parents[1] / "lambdipy_tpu"
    check_re = re.compile(r"\.check\(\s*[\"']([a-z_]+)[\"']")
    wait_re = re.compile(r"_device_wait\(\s*[\"']([a-z_]+)[\"']")
    found: set = set()
    for path in root.rglob("*.py"):
        text = path.read_text()
        found.update(check_re.findall(text))
        found.update(wait_re.findall(text))
    unregistered = found - set(REGISTRY)
    assert not unregistered, (
        f"fault sites fired in the tree but missing from the "
        f"faults.py REGISTRY: {sorted(unregistered)}")
    unfired = set(REGISTRY) - found
    assert not unfired, (
        f"registered fault sites with no check()/_device_wait() call "
        f"site anywhere in lambdipy_tpu/: {sorted(unfired)}")


# -- runtime arm/clear (the nemesis control surface) -------------------------


def test_fault_plan_runtime_arm_and_clear():
    plan = FaultPlan.empty()
    assert not plan.armed()["active"]
    added = plan.arm("transport:exception@n=1;probe:delay@ms=5,n=2")
    assert len(added) == 2
    with pytest.raises(InjectedFault):
        plan.check("transport")
    assert plan.clear() == 2
    plan.check("transport")  # cleared: no-op fast path, no fire
    # counters survived the clear (the deterministic replay spine)
    assert plan.counts()["transport"] == 1
    # a bad runtime spec touches nothing
    with pytest.raises(ValueError):
        plan.arm("transport:nope")
    assert not plan.armed()["active"]


def test_fault_plan_clear_releases_hangs_without_poisoning_later_ones():
    """clear() resolves in-flight hangs (raising InjectedFault — an
    abandoned wait must not look like success) while hangs armed LATER
    still block: the release event is swapped, not left set."""
    plan = FaultPlan.empty()
    plan.arm("transport:hang")
    results: list = []

    def waiter(tag):
        try:
            plan.check("transport")
            results.append((tag, "passed"))
        except InjectedFault:
            results.append((tag, "released"))

    t1 = threading.Thread(target=waiter, args=("first",), daemon=True)
    t1.start()
    time.sleep(0.15)
    plan.clear()
    t1.join(5.0)
    assert ("first", "released") in results
    # re-arm: the fresh hang must actually block again
    plan.arm("transport:hang")
    t2 = threading.Thread(target=waiter, args=("second",), daemon=True)
    t2.start()
    t2.join(0.4)
    assert t2.is_alive(), "a re-armed hang resolved instantly — the " \
        "released event leaked into the new rule"
    plan.release()
    t2.join(5.0)
    assert ("second", "released") in results


# -- the engine fault matrix -------------------------------------------------

ENGINE_SITES = ("segment_dispatch", "segment_fetch", "first_fetch",
                "group_prefill", "prefix_assemble", "transport")
MATRIX_SPECS = {"exception": "{site}:exception@seg=1",
                "delay": "{site}:delay@ms=120,n=2",
                # bounded: the watchdog trips, the replay lands on the
                # recovered site; the permanent variant is its own case
                "hang": "{site}:hang@seg=1,n=1"}
MATRIX = [(site, kind, MATRIX_SPECS[kind].format(site=site))
          for site in ENGINE_SITES for kind in MATRIX_SPECS]
MATRIX.append(("segment_fetch", "hang_permanent", "segment_fetch:hang"))
MATRIX_NEW, MATRIX_WATCHDOG_S = 16, 1.0
MATRIX_PREFIX = list(range(1, 20))
# one greedy and one seeded-sampled row (the sampled row's PRNG chain must
# restart bitwise on a replay); the prefix row reaches prefix_assemble
MATRIX_REQS = [([1, 2, 3, 4], {}, None),
               ([9, 8, 7], dict(temperature=0.8, seed=7), None),
               ([4, 5], {}, MATRIX_PREFIX)]


@pytest.fixture(scope="module")
def matrix_refs(tiny_server):
    """Solo references, and every engine program the matrix can dispatch
    compiled through a fault-free engine first (group prefill at 1-3
    joiners, pack, the segment windows, prefix continuation): a 1 s
    watchdog cannot tell a first-use compile from a wedge."""
    refs = [tiny_server.generate((pfx or []) + row,
                                 max_new_tokens=MATRIX_NEW, **kw)
            for row, kw, pfx in MATRIX_REQS]
    warm = ContinuousBatcher(tiny_server, slots=4, segment=4)
    with ThreadPoolExecutor(max_workers=3) as ex:
        for f in [ex.submit(warm.generate, row, max_new_tokens=MATRIX_NEW,
                            prefix=pfx, **kw)
                  for row, kw, pfx in MATRIX_REQS]:
            f.result()
    for row, kw, pfx in MATRIX_REQS:   # solo joins: the 1-row programs
        warm.generate(row, max_new_tokens=MATRIX_NEW, prefix=pfx, **kw)
    return refs


@pytest.mark.parametrize("site,kind,spec", MATRIX,
                         ids=[f"{s}-{k}" for s, k, _ in MATRIX])
def test_engine_fault_matrix(tiny_server, matrix_refs, site, kind, spec):
    """Every engine-owned site of ``faults.REGISTRY`` x {exception, delay,
    hang} injected into a live continuous engine, plus one permanent
    hang: no waiter outlives the watchdog's bound; every request returns
    its bitwise solo tokens or an explicit error, never other tokens; a
    delay never errors; an exception or a bounded hang at a site the
    engine thread owns is replayed, and the replay delivers (at
    ``prefix_assemble``, on the request's own thread, it is that request's
    explicit error); the SAME batcher then serves bitwise and is not
    wedged; against the permanent hang the waiters get errors and the
    engine reports wedged. Nothing here is timed but the bound."""
    refs = matrix_refs
    plan = FaultPlan.from_spec(spec)
    engine = ContinuousBatcher(tiny_server, slots=4, segment=4, faults=plan,
                               watchdog_s=MATRIX_WATCHDOG_S, max_replays=1)
    reqs = MATRIX_REQS if site == "prefix_assemble" else MATRIX_REQS[:2]
    results: dict = {}

    def one(i, row, kw, pfx):
        try:
            results[i] = engine.generate(row, max_new_tokens=MATRIX_NEW,
                                         prefix=pfx, **kw)
        except Exception as e:  # noqa: BLE001 — an explicit error is fine
            results[i] = e

    workers = [threading.Thread(target=one, args=(i, *req), daemon=True)
               for i, req in enumerate(reqs)]
    try:
        for w in workers:
            w.start()
        deadline = time.monotonic() + 30.0
        for w in workers:
            w.join(timeout=max(0.0, deadline - time.monotonic()))
        hung = [i for i, w in enumerate(workers) if w.is_alive()]
        assert not hung, f"waiters {hung} still blocked past the bound"
        errors = [i for i in results if isinstance(results[i], Exception)]
        for i in set(results) - set(errors):
            np.testing.assert_array_equal(
                results[i], refs[i], err_msg=f"request {i}: WRONG tokens")
        assert len(results) == len(reqs)
        if kind == "delay":
            assert not errors, f"a pure delay errored {errors}: {results}"
        faults = engine.stats()["faults"]
        if kind == "hang_permanent":
            assert errors, "every waiter 'succeeded' against a dead site"
            assert faults["wedged"] and engine.wedged
            return
    finally:
        plan.release()
    if kind != "delay":
        if site == "prefix_assemble":
            assert set(errors) <= {2}, results
        else:
            assert faults["replays"]["succeeded"] >= 1, faults
    np.testing.assert_array_equal(
        engine.generate(MATRIX_REQS[0][0], max_new_tokens=MATRIX_NEW),
        refs[0])
    assert not engine.wedged
