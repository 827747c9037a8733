"""SLO scheduler: queue/policy/admission/estimator units + the synthetic
overload test from the acceptance criteria — more concurrent requests
than queue capacity against a stub model must produce bounded queue
depth, explicit 429/503 + Retry-After, and nonzero shed counters on
/metrics, while an unloaded server sheds nothing."""

import json
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import pytest

from lambdipy_tpu.sched import (
    CLASSES,
    SchedConfig,
    Scheduler,
    Shed,
    clear_request_context,
    current_request_class,
    set_request_context,
)
from lambdipy_tpu.sched.admission import AdmissionController, TokenBucket
from lambdipy_tpu.sched.estimator import CostEstimator
from lambdipy_tpu.sched.policy import make_policy
from lambdipy_tpu.sched.queue import RequestQueue, Ticket


# -- queue -------------------------------------------------------------------


def test_queue_lanes_bound_and_remove():
    q = RequestQueue(capacity=3)
    t1 = Ticket(cls="interactive")
    t2 = Ticket(cls="batch")
    t3 = Ticket(cls="background")
    assert q.push(t1) and q.push(t2) and q.push(t3)
    assert q.full() and not q.push(Ticket(cls="interactive"))
    assert q.depth() == 3 and q.depth("batch") == 1
    assert q.remove(t2) and not q.remove(t2)
    assert q.snapshot() == {"interactive": 1, "batch": 0, "background": 1}


def test_queue_pop_follows_policy():
    q = RequestQueue()
    bg = Ticket(cls="background")
    ia = Ticket(cls="interactive")
    q.push(bg)
    q.push(ia)
    assert q.pop(make_policy("priority")) is ia  # class rank beats arrival
    assert q.pop(make_policy("priority")) is bg
    q.push(bg)
    q.push(ia)
    assert q.pop(make_policy("fifo")) is bg  # arrival order


# -- policies ----------------------------------------------------------------


def test_fifo_policy_ignores_class():
    entries = [{"cls": "background", "seq": 1}, {"cls": "interactive", "seq": 2}]
    assert make_policy("fifo").order(entries) == entries


def test_priority_policy_strict_order():
    entries = [{"cls": "background", "seq": 1}, {"cls": "batch", "seq": 2},
               {"cls": "interactive", "seq": 3}]
    ordered = make_policy("priority").order(entries)
    assert [e["cls"] for e in ordered] == ["interactive", "batch",
                                          "background"]
    assert make_policy("priority").head(entries)["cls"] == "interactive"


def test_fair_share_is_proportional_not_starving():
    """Weighted round-robin: over many selects with all lanes contending,
    each class is served roughly in proportion to its weight — and the
    lowest class is never starved (the strict-priority failure mode)."""
    policy = make_policy("fair")
    lanes = {c: [SimpleNamespace(seq=0)] for c in CLASSES}
    served = {c: 0 for c in CLASSES}
    for _ in range(120):
        served[policy.select(lanes)] += 1
    assert served["background"] >= 5          # never starved
    assert served["interactive"] > served["batch"] > served["background"]
    # 8:3:1 weights over 120 picks -> 80/30/10
    assert abs(served["interactive"] - 80) <= 8


def test_fair_share_order_interleaves():
    entries = ([{"cls": "batch", "seq": i} for i in range(6)]
               + [{"cls": "interactive", "seq": 10 + i} for i in range(6)])
    ordered = make_policy("fair").order(entries)
    first_batch = next(i for i, e in enumerate(ordered)
                       if e["cls"] == "batch")
    # interleaved, not all-interactive-then-all-batch
    assert first_batch < 6
    assert ordered != entries


def test_make_policy_names_and_aliases():
    assert make_policy("fair-share").name == "fair"
    assert make_policy("FIFO").name == "fifo"
    with pytest.raises(ValueError, match="unknown scheduling policy"):
        make_policy("lifo")


# -- estimator ---------------------------------------------------------------


def test_estimator_defaults_then_learns_affine_cost():
    est = CostEstimator(default_ms=50.0)
    assert est.estimate(0, 0) == 50.0
    # service time 10ms overhead + 0.5 ms/decode-token
    for _ in range(400):
        for d in (8, 32, 128):
            est.observe(10.0 + 0.5 * d, prefill_tokens=0, decode_tokens=d)
    assert est.estimate(0, 100) == pytest.approx(60.0, rel=0.25)
    # longer decode must cost more
    assert est.estimate(0, 256) > est.estimate(0, 16)
    rep = est.report()
    assert rep["samples"] == 1200 and rep["ms_per_decode_token"] > 0


def test_estimator_plain_ewma_without_token_counts():
    est = CostEstimator(default_ms=50.0)
    for _ in range(50):
        est.observe(200.0)
    assert est.mean_ms() == pytest.approx(200.0, rel=0.05)
    assert est.estimate() == pytest.approx(200.0, rel=0.25)


# -- admission ---------------------------------------------------------------


def test_token_bucket_burst_then_throttle():
    bucket = TokenBucket(rate=1.0, burst=2.0)
    now = time.monotonic()
    assert bucket.take(now) == 0.0
    assert bucket.take(now) == 0.0
    wait = bucket.take(now)
    assert 0.0 < wait <= 1.0
    # a second later one token is back
    assert bucket.take(now + 1.0) == 0.0


def test_admission_check_order_and_reasons():
    adm = AdmissionController(rate=100.0)
    common = dict(tenant="t", cls="interactive", deadline_ms=None,
                  queue_depth=0, queue_cap=4, est_wait_ms=0.0,
                  est_cost_ms=10.0)
    assert adm.check(draining=False, **common) is None
    shed = adm.check(draining=True, **common)
    assert shed.code == 503 and shed.reason == "draining"
    full = adm.check(draining=False, **{**common, "queue_depth": 4})
    assert full.code == 503 and full.reason == "queue_full"
    late = adm.check(draining=False,
                     **{**common, "deadline_ms": 5.0, "est_wait_ms": 100.0})
    assert late.code == 503 and late.reason == "deadline"
    assert late.retry_after_s > 0
    rep = adm.shed_report()
    assert rep["total"] == 3 and rep["by_class"]["interactive"] == 3


def test_tenant_eviction_is_lru_not_token_count():
    """At max_tenants, the LEAST RECENTLY USED bucket is evicted. Token-
    count eviction picked fresh full-burst buckets as perpetual victims,
    letting a hammering tenant recreate its bucket (full burst again)
    every request and bypass the limit entirely."""
    adm = AdmissionController(rate=100.0, burst=1.0, max_tenants=2)
    adm._bucket("old")
    time.sleep(0.01)
    hot = adm._bucket("hot")
    time.sleep(0.01)
    hot.take()               # refreshes hot's stamp (recently used)
    adm._bucket("new")       # map full -> must evict "old", not "hot"
    assert "old" not in adm._buckets
    assert {"hot", "new"} <= set(adm._buckets)


def test_per_tenant_rate_isolation():
    sched = Scheduler(SchedConfig(rate=1.0, burst=1.0))
    assert not isinstance(sched.admit(tenant="a"), Shed)
    over = sched.admit(tenant="a")
    assert isinstance(over, Shed) and over.code == 429
    assert not isinstance(sched.admit(tenant="b"), Shed)  # b unaffected


# -- scheduler slot handoff --------------------------------------------------


def test_priority_grant_order_under_contention():
    """With one slot busy, a later interactive arrival is granted before
    an earlier background one under the priority policy."""
    sched = Scheduler(SchedConfig(policy="priority", max_concurrency=1))
    holder = sched.admit(cls="interactive")
    assert sched.wait_turn(holder, timeout=2)
    bg = sched.admit(cls="background")
    ia = sched.admit(cls="interactive")
    grants = []

    def waiter(ticket, name):
        if sched.wait_turn(ticket, timeout=5):
            grants.append(name)
            sched.finish(ticket, service_ms=1.0)

    threads = [threading.Thread(target=waiter, args=(bg, "bg")),
               threading.Thread(target=waiter, args=(ia, "ia"))]
    for t in threads:
        t.start()
    time.sleep(0.05)          # both parked before the slot frees
    sched.finish(holder, service_ms=1.0)
    for t in threads:
        t.join()
    assert grants == ["ia", "bg"]


def test_deadline_shed_at_grant_time():
    """A deadline that became unmeetable WHILE queued sheds at grant time
    (wait_turn returns False) instead of burning the run slot."""
    sched = Scheduler(SchedConfig(max_concurrency=1))
    sched.estimator.observe(50.0)
    holder = sched.admit()
    assert sched.wait_turn(holder, timeout=2)
    # feasible at admit (wait ~50ms + cost ~50ms <= 120ms deadline)...
    late = sched.admit(deadline_ms=120.0)
    assert not isinstance(late, Shed)
    time.sleep(0.15)          # ...but the slot holder overstays
    sched.finish(holder, service_ms=150.0)
    assert sched.wait_turn(late, timeout=2) is False
    assert late.expired
    assert sched.report()["shed"]["by_reason"]["deadline"] == 1


def _parked(sched, n, **kw):
    """``n`` tickets admitted behind a full gate, in admission order."""
    out = [sched.admit(**kw) for _ in range(n)]
    assert not any(isinstance(t, Shed) or t.granted for t in out)
    return out


def test_grant_ahead_is_one_ticket_on_credit_repaid_by_finish():
    """A grant ahead raises ``running`` by ONE over the gate; the next
    finish() repays it (no further grant), the one after grants as ever.
    With a free run slot or an empty queue it grants nothing and keeps
    no credit."""
    sched = Scheduler(SchedConfig(policy="fifo", max_concurrency=2))
    assert sched.grant_ahead() is False          # empty queue
    held = [sched.admit() for _ in range(2)]
    assert all(t.granted for t in held)
    assert sched.grant_ahead() is False          # gate full, nobody queued
    a, b, c = _parked(sched, 3)
    assert sched.grant_ahead() is True
    assert a.granted and not b.granted
    rep = sched.report()
    assert rep["running"] == 3 and rep["granted_ahead"] == 1
    assert rep["queued"]["interactive"] == 2
    # an arrival behind a gate that stands one over is still parked
    d = _parked(sched, 1)[0]
    sched.finish(held[0], service_ms=1.0)        # repays: no new grant
    assert sched.report()["running"] == 2 and not b.granted
    sched.finish(held[1], service_ms=1.0)        # an ordinary release
    assert b.granted and not c.granted
    assert sched.report()["running"] == 2
    for t in (a, b):
        sched.finish(t, service_ms=1.0)
    assert c.granted and d.granted
    rep = sched.report()
    assert rep["running"] == 2 and rep["granted_ahead"] == 1


@pytest.mark.parametrize("ending", [1, 2, 3])
def test_grant_ahead_bounded_by_rows_ending(ending):
    """``running`` never exceeds max_concurrency + the releases announced
    and not yet made, however deep the queue; the credits are repaid one
    a finish()."""
    sched = Scheduler(SchedConfig(policy="fifo", max_concurrency=2))
    held = [sched.admit() for _ in range(2)]
    parked = _parked(sched, 6)
    for i in range(ending):
        assert sched.grant_ahead() is True
        assert sched.report()["running"] == 2 + i + 1
    assert [t.granted for t in parked] == [True] * ending \
        + [False] * (6 - ending)
    for i, t in enumerate(held + parked[:ending]):
        sched.finish(t, service_ms=1.0)
        # the first `ending` releases repay, the later ones grant anew
        assert sched.report()["running"] == max(2, 2 + ending - i - 1)
    assert sched.report()["granted_ahead"] == ending


def test_grant_ahead_follows_policy_and_deadline_recheck():
    """The ticket granted ahead is the policy's next (not the oldest),
    and one whose deadline became unmeetable while queued is shed at
    that moment like at any grant: the credit goes to the next."""
    sched = Scheduler(SchedConfig(policy="priority", max_concurrency=1))
    sched.estimator.observe(50.0)
    holder = sched.admit()
    late = sched.admit(cls="interactive", deadline_ms=120.0)
    assert not isinstance(late, Shed)            # feasible when admitted
    bg = sched.admit(cls="background")
    ia = sched.admit(cls="interactive")
    time.sleep(0.15)                             # late can no longer make it
    assert sched.grant_ahead() is True
    assert late.expired and sched.wait_turn(late, timeout=2) is False
    assert ia.granted and not ia.expired and not bg.granted
    rep = sched.report()
    assert rep["running"] == 2 and rep["granted_ahead"] == 1
    assert rep["shed"]["by_reason"]["deadline"] == 1
    sched.finish(holder, service_ms=1.0)
    assert not bg.granted
    sched.finish(ia, service_ms=1.0)
    assert bg.granted


def test_grant_ahead_leaves_a_long_prompt_in_its_place():
    """With ``max_prefill_tokens`` (the engine's group limit) a ticket
    whose prompt is longer is not granted ahead, and nobody overtakes
    it: it is the first grant of the release itself. Shorter ones are
    granted as ever."""
    sched = Scheduler(SchedConfig(policy="fifo", max_concurrency=1))
    holder = sched.admit(prefill_tokens=10)
    long_one = sched.admit(prefill_tokens=300)
    short = sched.admit(prefill_tokens=20)
    assert sched.grant_ahead(max_prefill_tokens=256) is False
    assert not long_one.granted and not short.granted
    rep = sched.report()
    assert rep["running"] == 1 and rep["granted_ahead"] == 0
    assert rep["queued"]["interactive"] == 2
    sched.finish(holder, service_ms=1.0)
    assert long_one.granted and not short.granted  # its place was kept
    assert sched.grant_ahead(max_prefill_tokens=256) is True
    assert short.granted and sched.report()["running"] == 2
    assert sched.grant_ahead() is False             # nobody queued


def test_grant_ahead_under_contention_keeps_the_gate():
    """More request threads than cores admit, wait and finish while an
    'engine' thread announces releases as fast as it can: the gate never
    stands further over max_concurrency than the credits owed, every
    credit is repaid, and nobody is left parked."""
    import sys

    sched = Scheduler(SchedConfig(policy="fifo", max_concurrency=3,
                                  queue_cap=100_000))
    stop, over, granted = threading.Event(), [], [0]

    def requests():
        for _ in range(40):
            ticket = sched.admit()
            assert sched.wait_turn(ticket, timeout=20)
            with sched._cond:
                if sched._running > 3 + sched._ahead:
                    over.append((sched._running, sched._ahead))
            time.sleep(0.0005)  # hold the run slot: a queue stands behind
            sched.finish(ticket, service_ms=0.5)

    def engine():
        while not stop.is_set():
            granted[0] += sched.grant_ahead()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        eng = threading.Thread(target=engine)
        eng.start()
        workers = [threading.Thread(target=requests) for _ in range(24)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(timeout=60)
        stop.set()
        eng.join(timeout=10)
    finally:
        sys.setswitchinterval(old)
    assert not eng.is_alive() and not any(t.is_alive() for t in workers)
    assert over == []
    rep = sched.report()
    assert rep["admitted"] == rep["completed"] == 24 * 40
    assert rep["running"] == 0 and sched._ahead == 0
    assert rep["granted_ahead"] == granted[0] > 0


def test_server_hands_grant_ahead_to_the_engine(stub_server, monkeypatch):
    """Where the scheduler is built the server gives the handler's engine
    its grant_ahead (HandlerState.row_ending_hook); a handler without an
    engine has no hook and nothing happens."""
    got = []
    real = _stub_boot

    def boot(d, **kw):
        rep = real(d, **kw)
        rep.state.row_ending_hook = got.append
        return rep

    monkeypatch.setitem(globals(), "_stub_boot", boot)
    srv = stub_server()
    assert got == [srv.sched.grant_ahead]


def test_degenerate_config_is_floored():
    """queue_cap=0 / max_concurrency=0 must not turn into a total outage
    (0 >= 0 would shed every request on an idle server)."""
    sched = Scheduler(SchedConfig(queue_cap=0, max_concurrency=0))
    assert sched.config.queue_cap == 1 and sched.config.max_concurrency == 1
    ticket = sched.admit()
    assert not isinstance(ticket, Shed)
    assert sched.wait_turn(ticket, timeout=2)
    sched.finish(ticket, service_ms=1.0)


def test_request_context_roundtrip():
    assert current_request_class() == "interactive"  # default
    set_request_context(cls="batch", tenant="t9", deadline_ms=5.0)
    assert current_request_class() == "batch"
    clear_request_context()
    assert current_request_class() == "interactive"


def test_sched_config_from_bundle_extra_and_overrides():
    extra = {"sched_policy": "priority", "sched_queue_cap": "8",
             "sched_rate": "2.5", "batch_window_ms": "2"}
    cfg = SchedConfig.from_extra(extra)
    assert (cfg.policy, cfg.queue_cap, cfg.rate) == ("priority", 8, 2.5)
    cfg2 = SchedConfig.from_extra(extra, policy="fifo", queue_cap=None)
    assert cfg2.policy == "fifo" and cfg2.queue_cap == 8


# -- micro-batcher drain order ----------------------------------------------


def test_microbatcher_drains_in_policy_order():
    from lambdipy_tpu.runtime.batching import MicroBatcher

    fake = SimpleNamespace(
        model=SimpleNamespace(cfg=SimpleNamespace(max_len=1024)),
        decode_cap=1024)
    mb = MicroBatcher(fake, window_ms=1.0, max_batch=2,
                      policy=make_policy("priority"))
    entries = [
        {"row": [1], "n": 4, "cls": "background", "seq": 0},
        {"row": [1], "n": 4, "cls": "batch", "seq": 1},
        {"row": [1], "n": 4, "cls": "interactive", "seq": 2},
    ]
    mb._pending = list(entries)
    batch = mb._drain_locked()
    assert [e["cls"] for e in batch] == ["interactive", "batch"]
    assert [e["cls"] for e in mb._pending] == ["background"]


# -- HTTP overload (acceptance criteria) -------------------------------------


def _stub_boot(bundle_dir, *, service_s, extra=None):
    from lambdipy_tpu.runtime.loader import BootReport

    state = SimpleNamespace(meta={"model": "stub"},
                            stats=lambda: {"stub": True})

    def invoke(st, request):
        time.sleep(service_s)
        return {"ok": True, "echo": request.get("echo")}

    return BootReport(
        bundle_dir=Path(bundle_dir), handler=SimpleNamespace(invoke=invoke),
        state=state, stages={"init": 0.0},
        manifest={"payload": {"extra": dict(extra or {})}})


@pytest.fixture()
def stub_server(monkeypatch, tmp_path):
    """BundleServer over a stub model (no JAX, no bundle build): the
    handler just sleeps — exactly what's needed to fill the queue."""
    import lambdipy_tpu.runtime.server as server_mod

    servers = []

    def make(service_s=0.0, sched=None, extra=None):
        monkeypatch.setattr(
            server_mod, "load_bundle",
            lambda d, warmup=True: _stub_boot(d, service_s=service_s,
                                              extra=extra))
        srv = server_mod.BundleServer(tmp_path, port=0, warmup=False,
                                      sched=sched).start_background()
        servers.append(srv)
        return srv

    yield make
    for srv in servers:
        threading.Thread(target=srv.stop, daemon=True).start()


def _post(base, payload, headers=None, timeout=30):
    req = urllib.request.Request(
        f"{base}/invoke", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read()), dict(resp.headers)


def _get(base, path):
    with urllib.request.urlopen(f"{base}{path}", timeout=30) as resp:
        return json.loads(resp.read())


def test_unloaded_server_sheds_nothing(stub_server):
    srv = stub_server(service_s=0.0)
    base = f"http://127.0.0.1:{srv.port}"
    for i in range(5):
        status, body, _ = _post(base, {"echo": i})
        assert status == 200 and body["ok"] and body["echo"] == i
    metrics = _get(base, "/metrics")
    assert metrics["count"] == 5 and metrics["errors"] == 0
    sched = metrics["sched"]
    assert sched["shed"]["total"] == 0
    assert sched["completed"] == 5
    assert sched["queue_wait"]["interactive"]["count"] == 5
    assert _get(base, "/healthz")["sched"]["queued"] == 0


def test_overload_sheds_explicitly_with_retry_after(stub_server):
    """More concurrent requests than queue capacity: queue depth stays
    bounded, the excess gets 503 + Retry-After, /metrics reports nonzero
    shed counts and per-class queue-wait percentiles."""
    srv = stub_server(service_s=0.25,
                      sched={"max_concurrency": 1, "queue_cap": 3,
                             "policy": "fair"})
    base = f"http://127.0.0.1:{srv.port}"
    results = []
    lock = threading.Lock()

    def fire(i):
        cls = ("interactive", "batch", "background")[i % 3]
        try:
            status, body, headers = _post(
                base, {"echo": i}, headers={"x-priority": cls}, timeout=60)
            with lock:
                results.append((status, body, headers))
        except urllib.error.HTTPError as e:
            with lock:
                results.append((e.code, json.loads(e.read()),
                                dict(e.headers)))

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    time.sleep(0.3)  # mid-overload: the queue must be bounded
    mid = _get(base, "/metrics")["sched"]
    assert sum(mid["queued"].values()) <= 3
    assert mid["running"] <= 1
    for t in threads:
        t.join()

    codes = [status for status, _, _ in results]
    assert codes.count(200) >= 4          # 1 running + 3 queued at least
    shed = [(status, body, headers) for status, body, headers in results
            if status in (429, 503)]
    assert shed, f"no requests shed under overload: {codes}"
    for status, body, headers in shed:
        assert headers.get("Retry-After"), (status, headers)
        assert int(headers["Retry-After"]) >= 1
        assert body["shed"] in ("queue_full", "deadline")
        assert body["retry_after_s"] > 0

    metrics = _get(base, "/metrics")["sched"]
    assert metrics["shed"]["total"] == len(shed)
    assert metrics["shed"]["by_reason"].get("queue_full", 0) > 0
    waits = metrics["queue_wait"]
    served_classes = {("interactive", "batch", "background")[i % 3]
                      for i, (status, _, _) in enumerate(results)}
    assert waits, metrics
    for cls, rep in waits.items():
        assert rep["p50_ms"] is not None and rep["p99_ms"] >= rep["p50_ms"]
    assert metrics["estimator"]["samples"] == codes.count(200)


def test_http_deadline_shedding(stub_server):
    srv = stub_server(service_s=0.0)
    base = f"http://127.0.0.1:{srv.port}"
    # generous deadline: served
    status, body, _ = _post(base, {"echo": 1},
                            headers={"x-deadline-ms": "60000"})
    assert status == 200 and body["ok"]
    # unmeetable deadline (below the estimator's cost): immediate 503
    with pytest.raises(urllib.error.HTTPError) as err:
        _post(base, {"echo": 2}, headers={"x-deadline-ms": "0.001"})
    assert err.value.code == 503
    body = json.loads(err.value.read())
    assert body["shed"] == "deadline"
    assert err.value.headers.get("Retry-After")
    assert _get(base, "/metrics")["sched"]["shed"]["by_reason"][
        "deadline"] == 1


def test_http_per_tenant_rate_limit(stub_server):
    srv = stub_server(service_s=0.0, sched={"rate": 0.5, "burst": 1.0})
    base = f"http://127.0.0.1:{srv.port}"
    status, _, _ = _post(base, {}, headers={"x-api-key": "k1"})
    assert status == 200
    with pytest.raises(urllib.error.HTTPError) as err:
        _post(base, {}, headers={"x-api-key": "k1"})
    assert err.value.code == 429
    assert err.value.headers.get("Retry-After")
    assert json.loads(err.value.read())["shed"] == "rate"
    # a different tenant still gets in
    status, _, _ = _post(base, {}, headers={"x-api-key": "k2"})
    assert status == 200


def test_bundle_extra_configures_scheduler(stub_server):
    srv = stub_server(service_s=0.0,
                      extra={"sched_policy": "priority",
                             "sched_queue_cap": "5"})
    assert srv.sched.policy.name == "priority"
    assert srv.sched.config.queue_cap == 5
    base = f"http://127.0.0.1:{srv.port}"
    assert _get(base, "/healthz")["sched"]["policy"] == "priority"


def test_resolved_policy_bridged_to_handler_load(monkeypatch, tmp_path):
    """The effective scheduler policy (ctor/CLI override included) must
    be visible to the handler's batch formation, which is built INSIDE
    load_bundle — the server bridges it via LAMBDIPY_SCHED_POLICY for
    the duration of the boot, restoring the env after."""
    import os

    import lambdipy_tpu.runtime.server as server_mod

    seen = {}

    def fake_load(d, warmup=True):
        seen["policy"] = os.environ.get("LAMBDIPY_SCHED_POLICY")
        return _stub_boot(d, service_s=0.0)

    monkeypatch.setattr(server_mod, "load_bundle", fake_load)
    monkeypatch.delenv("LAMBDIPY_SCHED_POLICY", raising=False)
    srv = server_mod.BundleServer(tmp_path, port=0, warmup=False,
                                  sched={"policy": "fifo"})
    try:
        assert seen["policy"] == "fifo"
        assert srv.sched.policy.name == "fifo"
        assert "LAMBDIPY_SCHED_POLICY" not in os.environ  # restored
    finally:
        threading.Thread(target=srv.stop, daemon=True).start()


def test_concurrency_floored_at_batcher_width(stub_server):
    """A batching bundle sized past the default run-slot count must not
    be silently throttled: unless the operator pins it, max_concurrency
    rises to batch_max so every batch slot can fill."""
    srv = stub_server(extra={"batch_mode": "continuous", "batch_max": "32"})
    assert srv.sched.config.max_concurrency == 32
    pinned = stub_server(extra={"batch_mode": "continuous",
                                "batch_max": "32"},
                         sched={"max_concurrency": 4})
    assert pinned.sched.config.max_concurrency == 4
    plain = stub_server()          # no batching: default stands
    assert plain.sched.config.max_concurrency == 8


def test_drain_stops_admission_with_retry_after(stub_server):
    srv = stub_server(service_s=0.0)
    base = f"http://127.0.0.1:{srv.port}"
    assert _post(base, {})[0] == 200
    srv.draining = True
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(base, {})
        assert err.value.code == 503
        assert json.loads(err.value.read())["shed"] == "draining"
        assert err.value.headers.get("Retry-After")
    finally:
        srv.draining = False
