"""Multi-turn session pins (runtime/prefixstore.py session layer + the
server's session surface).

The invariant under test is the tentpole's: an open session never loses
its KV to eviction or cache pressure — pinned radix nodes are excluded
from the LRU budget sweep and the cold-page reclaim, leases (TTL + idle,
renewed per turn) bound retention, the pin budget sheds new sessions
priced by the lease horizon instead of starving live traffic, and an
arena reset invalidates pins OBSERVABLY (counted, next turn re-prefills
through the normal walk). Fleet-side stickiness/failover lives in
tests/test_fleet_sessions.py."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from lambdipy_tpu.models.llama import init_page_arena, page_kv_bytes
from lambdipy_tpu.runtime.faults import FaultPlan
from lambdipy_tpu.runtime.pagepool import PagePool, page_width
from lambdipy_tpu.runtime.prefixstore import (PrefixStore,
                                              SessionPinsExceeded)


@pytest.fixture(scope="module")
def tiny_server():
    from lambdipy_tpu.models import registry

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    return adapter.make_server(params)


def _rows(seed, n, length, vocab=500):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, size=length)]
            for _ in range(n)]


def mk_paged_store(server, *, n_windows=2, block=16, **kw):
    cfg = server.model.cfg
    page = page_width(cfg.max_len, block)
    n_pages = n_windows * (cfg.max_len // page) + 1
    pool = PagePool(n_pages=n_pages, page=page,
                    page_bytes=page_kv_bytes(cfg, page),
                    make_arena=lambda: init_page_arena(cfg, n_pages,
                                                       page))
    return PrefixStore(server, block=block, budget_mb=8, pool=pool,
                       **kw), pool


# -- pin lifecycle (dense) ----------------------------------------------------


def test_pin_renew_release_and_gauges(tiny_server):
    """Turn 1 pins the routed head, turn 2 extends the pin along the
    longer head, end_session returns every gauge to zero."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    (row,) = _rows(0, 1, 72)
    m1 = store.route(row[:40])
    assert m1 == 32
    assert store.pin_session("s1", row[:40]) == 32
    st = store.stats()
    assert st["sessions_active"] == 1
    assert st["pinned_leaves"] == 2 and st["pinned_bytes"] > 0
    per_block = st["pinned_bytes"] // 2
    # turn 2: the history grew — the pin follows the longer head
    store.route(row)
    assert store.pin_session("s1", row) == 64
    st = store.stats()
    assert st["pinned_leaves"] == 4
    assert st["pinned_bytes"] == 4 * per_block
    out = store.end_session("s1")
    assert out["released"] and out["pinned_leaves"] == 4
    st = store.stats()
    assert st["sessions_active"] == 0
    assert st["pinned_leaves"] == 0 and st["pinned_bytes"] == 0
    # idempotent close (the router fans DELETE out to every replica)
    assert store.end_session("s1")["released"] is False


def test_pins_survive_lru_budget_pressure(tiny_server):
    """The point of the pin: cache pressure that evicts every unpinned
    leaf leaves the session's conversation KV untouched."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    (pinned_row,) = _rows(1, 1, 40)
    store.route(pinned_row)
    store.pin_session("chat", pinned_row)
    per_block = store.stats()["pinned_bytes"] // 2
    # shrink the budget to ~3 blocks and pour distinct prefixes through
    store.budget_bytes = 3 * per_block
    for row in _rows(2, 6, 40):
        store.route(row)
    st = store.stats()
    assert st["evictions"] > 0
    # the pinned head is still fully matchable; total bytes may sit
    # ABOVE the LRU budget by exactly the pinned share (bounded by the
    # PIN budget, not the LRU budget)
    assert store.match_len(pinned_row) == 32
    assert st["pinned_leaves"] == 2
    store.end_session("chat")
    # unpinned again: the next insert's sweep may now reclaim them
    for row in _rows(3, 3, 40):
        store.route(row)
    assert store.stats()["bytes"] <= store.budget_bytes


def test_pin_budget_sheds_priced_by_lease_horizon(tiny_server):
    """A pin past the budget raises SessionPinsExceeded WITHOUT mutating
    pin state; Retry-After is the earliest lease-expiry horizon."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8,
                        session_idle_s=30.0)
    (row_a, row_b) = _rows(4, 2, 40)
    store.route(row_a)
    store.pin_session("a", row_a)
    st = store.stats()
    store.pin_budget_bytes = st["pinned_bytes"] + 1  # no room for b
    store.route(row_b)
    with pytest.raises(SessionPinsExceeded) as exc:
        store.pin_session("b", row_b)
    # horizon = a's idle lease (~30 s), clamped sane
    assert 1.0 <= exc.value.retry_after_s <= 30.0
    assert exc.value.retry_after_s > 20.0
    st = store.stats()
    assert st["pin_sheds"] == 1
    assert st["sessions_active"] == 1 and st["pinned_leaves"] == 2
    # a's own renewal still fits (its nodes are already pinned)
    store.pin_session("a", row_a)


def test_grown_conversation_overflow_serves_with_existing_pins(
        tiny_server):
    """An EXISTING session whose head outgrows the pin budget keeps its
    pins and keeps serving (counted pin_overflows) — only NEW sessions
    shed; a retention optimization must never make a mid-conversation
    turn permanently unservable."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    (row,) = _rows(20, 1, 72)
    store.route(row[:40])
    assert store.pin_session("grow", row[:40]) == 32
    st = store.stats()
    store.pin_budget_bytes = st["pinned_bytes"]  # no room to extend
    store.route(row)  # the conversation grew to 4 blocks
    got = store.pin_session("grow", row)  # serves, pins unchanged
    assert got == 32  # still the old 2-block pin
    st = store.stats()
    assert st["pin_overflows"] == 1 and st["pin_sheds"] == 0
    assert st["pinned_leaves"] == 2 and st["sessions_active"] == 1
    store.end_session("grow")
    assert store.stats()["pinned_leaves"] == 0


def test_pin_budget_clamped_to_cache_budget(tiny_server):
    """An operator pin budget above the cache budget is clamped: pins
    live inside the store's accounting, and an unclamped budget would
    let sessions hold the whole cache out of eviction's reach."""
    store = PrefixStore(tiny_server, block=16, budget_mb=1,
                        pin_budget_mb=1024.0)
    assert store.pin_budget_bytes == store.budget_bytes
    store = PrefixStore(tiny_server, block=16, budget_mb=1,
                        pin_budget_mb=0.25)
    assert store.pin_budget_bytes == int(0.25 * 2**20)


def test_overflow_renewal_still_applies_tightened_lease(tiny_server):
    """A session_ttl_s tightening sent while the pin budget is full
    must still land — the overflow branch renews at the TIGHT window."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8,
                        session_idle_s=600.0)
    (row,) = _rows(22, 1, 72)
    store.route(row[:40])
    store.pin_session("t", row[:40])
    store.pin_budget_bytes = store.stats()["pinned_bytes"]  # full
    store.route(row)
    store.pin_session("t", row, ttl_s=0.5)  # overflow + tighten
    assert store.stats()["pin_overflows"] == 1
    time.sleep(0.7)
    st = store.stats()
    assert st["sessions_active"] == 0 and st["pin_expiries"] == 1


def test_tightened_lease_sticks_across_touch(tiny_server):
    """A client-tightened idle lease must not be silently expanded back
    to the store default by touch_session (stand-down turns)."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8,
                        session_idle_s=600.0)
    (row,) = _rows(21, 1, 40)
    store.route(row)
    store.pin_session("tight", row, ttl_s=0.5)
    assert store.touch_session("tight")  # renews at the TIGHT window
    time.sleep(0.7)
    st = store.stats()
    assert st["sessions_active"] == 0 and st["pin_expiries"] == 1


def test_ttl_expiry_under_concurrent_renewal(tiny_server):
    """A session whose client vanished lapses on schedule while a
    concurrently RENEWING session keeps its pins — expiry is per-lease,
    never a global sweep of live conversations."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    row_a, row_b = _rows(5, 2, 40)
    store.route(row_a)
    store.route(row_b)
    store.pin_session("gone", row_a, ttl_s=0.6)
    store.pin_session("live", row_b)
    stop = threading.Event()

    def renew():
        while not stop.is_set():
            store.pin_session("live", row_b)
            time.sleep(0.1)

    t = threading.Thread(target=renew, daemon=True)
    t.start()
    try:
        time.sleep(0.9)
        st = store.stats()  # the scrape runs the lazy lease sweep
        assert st["pin_expiries"] == 1
        assert st["sessions_active"] == 1
        assert st["pinned_leaves"] == 2  # live's two blocks, gone's none
    finally:
        stop.set()
        t.join(timeout=5)
    store.end_session("live")
    assert store.stats()["pinned_leaves"] == 0


def test_absolute_ttl_caps_renewal(tiny_server):
    """The absolute TTL bounds a session's lifetime even when turns
    keep renewing the idle lease — retention is never unbounded."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8,
                        session_ttl_s=1.0, session_idle_s=30.0)
    (row,) = _rows(6, 1, 40)
    store.route(row)
    store.pin_session("s", row)
    deadline = time.monotonic() + 1.1
    while time.monotonic() < deadline:
        store.touch_session("s")  # renewals cannot outlive the deadline
        time.sleep(0.1)
    st = store.stats()
    assert st["sessions_active"] == 0 and st["pin_expiries"] == 1


def test_session_pin_fault_fails_open(tiny_server):
    """An injected session_pin fault costs the PIN, never the turn:
    route still returns the match and the fault is counted."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8,
                        faults=FaultPlan.from_spec(
                            "session_pin:exception@seg=1,n=1"))
    (row,) = _rows(7, 1, 40)
    store.route(row)
    assert store.pin_session("s", row) == 0  # failed open
    st = store.stats()
    assert st["pin_faults"] == 1 and st["sessions_active"] == 0
    # the next turn's pin (fault exhausted) succeeds
    assert store.pin_session("s", row) == 32


# -- paged mode: reclaim exclusion + arena reset ------------------------------


def test_paged_pins_excluded_from_cold_page_reclaim(tiny_server):
    """reclaim_fn's cold-page sweep (admission pressure) releases
    unpinned cold leaves but never a pinned session's pages."""
    store, pool = mk_paged_store(tiny_server, n_windows=3)
    (pinned_row,) = _rows(8, 1, 40)
    store.route(pinned_row)
    store.pin_session("chat", pinned_row)
    cold = _rows(9, 2, 40)
    for row in cold:
        store.route(row)
    freed = store.reclaim_pages(64)  # ask for more than exists
    assert freed >= 1  # the cold unpinned leaves went
    assert store.match_len(pinned_row) == 32  # the pinned head did not
    gauges = pool.stats()
    assert gauges["pinned_pages"] == 2
    assert gauges["pinned_bytes"] == 2 * pool.page_bytes
    assert "pin_budget_bytes" in gauges and "pin_sheds" in gauges
    store.end_session("chat")
    assert store.reclaim_pages(64) >= 2  # now they are reclaimable
    pool.check_invariants()


def test_arena_reset_invalidates_pins_observably(tiny_server):
    """An engine-failure arena reset drops every pin WITH a counter —
    the next turn re-prefills through the normal walk and re-pins."""
    store, pool = mk_paged_store(tiny_server, n_windows=3)
    (row,) = _rows(10, 1, 40)
    store.route(row)
    store.pin_session("chat", row)
    pool.reset_arena()
    st = store.stats()  # the scrape flushes the stale tree lazily
    assert st["pin_invalidations"] == 1
    assert st["sessions_active"] == 0 and st["pinned_leaves"] == 0
    # turn 2 re-prefills (counted as a miss) and re-pins cleanly
    assert store.match_len(row) == 0
    store.route(row)
    assert store.pin_session("chat", row) == 32
    assert store.stats()["pinned_leaves"] == 2
    pool.check_invariants()


def test_pin_unpin_churn_invariants_fuzz(tiny_server):
    """Pin/unpin churn against concurrent route/reclaim traffic keeps
    the pool's invariants and the pinned-gauge shadow model exact."""
    store, pool = mk_paged_store(tiny_server, n_windows=4)
    rows = _rows(11, 6, 40)
    for row in rows:
        store.route(row)
    rng = np.random.default_rng(12)
    shadow: dict[str, int] = {}  # sid -> pinned leaves
    for step in range(200):
        op = rng.integers(0, 10)
        sid = f"s{int(rng.integers(0, 4))}"
        row = rows[int(rng.integers(0, len(rows)))]
        if op < 5:
            try:
                got = store.pin_session(sid, row)
                shadow[sid] = got // store.block
            except SessionPinsExceeded:
                pass
        elif op < 7:
            out = store.end_session(sid)
            if out["released"]:
                assert shadow.pop(sid, None) is not None
            else:
                assert sid not in shadow
        elif op < 9:
            store.reclaim_pages(int(rng.integers(1, 4)))
            # reclaimed leaves may need re-prefill; keep the tree warm
            store.route(row)
        else:
            pool.check_invariants()
    st = store.stats()
    # sessions pin DISTINCT rows, but the shadow only needs the sum to
    # bound the surface: every pinned leaf belongs to exactly one live
    # row path here (rows are random, overlaps vanishingly unlikely)
    assert st["sessions_active"] == len(shadow)
    for sid in list(shadow):
        store.end_session(sid)
    st = store.stats()
    assert st["pinned_leaves"] == 0 and st["pinned_bytes"] == 0
    pool.check_invariants()


# -- engine degradation ladder ------------------------------------------------


def test_pins_survive_degradation_ladder_step(tiny_server):
    """An engine failure that steps the degradation ladder does not
    touch the (dense) store's pins: after the bitwise replay the
    session's head still matches and the pin renews."""
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher

    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    (row,) = _rows(13, 1, 40)
    m = store.route(row)
    store.pin_session("lad", row)
    cb = ContinuousBatcher(
        tiny_server, slots=2, segment=4, pipeline_depth=2, max_replays=2,
        degrade_window_s=60.0, degrade_clean_s=60.0,
        faults=FaultPlan.from_spec("segment_fetch:exception@seg=1,n=2"))
    try:
        out = cb.generate(row[m:], max_new_tokens=8,
                          prefix=np.asarray(row[:m], np.int32))
        np.testing.assert_array_equal(
            out, tiny_server.generate([row[m:]], max_new_tokens=8,
                                      prefix=np.asarray(row[:m],
                                                        np.int32)))
        assert cb.stats()["faults"]["degrade_level"] >= 1
        st = store.stats()
        assert st["sessions_active"] == 1 and st["pinned_leaves"] == 2
        assert store.match_len(row) == m  # the head survived the step
        store.pin_session("lad", row)  # renewal through the degraded spell
    finally:
        store.end_session("lad")
    with tiny_server._prefix_lock:
        tiny_server._prefixes.clear()


# -- server HTTP surface ------------------------------------------------------


def _stub_server(monkeypatch, tmp_path, invoke, state_extra=None):
    from pathlib import Path
    from types import SimpleNamespace

    import lambdipy_tpu.runtime.server as server_mod
    from lambdipy_tpu.runtime.loader import BootReport

    def stub_boot(bundle_dir, warmup=True):
        return BootReport(
            bundle_dir=Path(bundle_dir),
            handler=SimpleNamespace(invoke=invoke),
            state=SimpleNamespace(meta={"model": "stub"},
                                  stats=lambda: {},
                                  **(state_extra or {})),
            stages={"init": 0.0}, manifest={"payload": {"extra": {}}})

    monkeypatch.setattr(server_mod, "load_bundle", stub_boot)
    return server_mod.BundleServer(tmp_path, port=0,
                                   warmup=False).start_background()


def test_server_maps_session_pins_to_shed_503(monkeypatch, tmp_path):
    """SessionPinsExceeded escaping the handler answers shed-style: 503
    + Retry-After from the lease horizon, reason ``session_pins``, no
    error counted — backpressure on NEW sessions, not a fault."""

    def invoke(st, request):
        raise SessionPinsExceeded(4096, 1024, retry_after_s=7.5)

    srv = _stub_server(monkeypatch, tmp_path, invoke)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/invoke",
            data=json.dumps({"tokens": [1, 2],
                             "session_id": "c1"}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 503
        assert int(exc.value.headers["Retry-After"]) == 8  # ceil(7.5)
        body = json.loads(exc.value.read())
        assert not body["ok"] and body["retry_after_s"] == 7.5
        shed = srv.sched.admission.shed_report()
        assert shed["by_reason"].get("session_pins") == 1
        assert srv.stats.report()["errors"] == 0
    finally:
        threading.Thread(target=srv.stop, daemon=True).start()


def test_server_session_header_injection_and_delete(monkeypatch,
                                                    tmp_path):
    """x-session-id rides into the handler request (body field wins);
    DELETE /v1/sessions/{id} hits the handler's session_end_fn."""
    seen: list = []
    ended: list = []

    def invoke(st, request):
        seen.append(request.get("session_id"))
        return {"ok": True}

    srv = _stub_server(
        monkeypatch, tmp_path, invoke,
        state_extra={"session_end_fn":
                     lambda sid: (ended.append(sid) or
                                  {"released": True,
                                   "pinned_leaves": 2})})
    try:
        base = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            f"{base}/invoke", data=json.dumps({"tokens": [1]}).encode(),
            headers={"Content-Type": "application/json",
                     "x-session-id": "hdr-1"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["ok"]
        req = urllib.request.Request(
            f"{base}/invoke",
            data=json.dumps({"tokens": [1],
                             "session_id": "body-1"}).encode(),
            headers={"Content-Type": "application/json",
                     "x-session-id": "hdr-2"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["ok"]
        assert seen == ["hdr-1", "body-1"]  # body beats header
        req = urllib.request.Request(f"{base}/v1/sessions/hdr-1",
                                     method="DELETE")
        with urllib.request.urlopen(req, timeout=10) as r:
            out = json.loads(r.read())
        assert out["ok"] and out["released"] and out["session"] == "hdr-1"
        assert ended == ["hdr-1"]
    finally:
        threading.Thread(target=srv.stop, daemon=True).start()


def test_server_kv_probe_surface(monkeypatch, tmp_path):
    """/v1/kv/probe answers the handler's host-only presence probe (and
    404s when there is no prefix store)."""
    srv = _stub_server(
        monkeypatch, tmp_path, lambda st, request: {"ok": True},
        state_extra={"kv_probe_fn":
                     lambda req: {"ok": True,
                                  "matched": len(req["tokens"]) // 2}})
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/kv/probe",
            data=json.dumps({"tokens": [1, 2, 3, 4]}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["matched"] == 2
    finally:
        threading.Thread(target=srv.stop, daemon=True).start()
