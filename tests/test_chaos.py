"""Chaos-soak unit tests: timeline grammar + determinism, the nemesis
executor, the workload plan, and the history/quiesce checker — the fast
half of the soak contract. The live composed-fault run itself is
``python -m lambdipy_tpu.chaos.soak`` (about 6 minutes, run by hand),
which also re-runs a seed to prove determinism on a real fleet."""

import json
import threading
import urllib.error
import urllib.request

import pytest

from lambdipy_tpu.chaos.checker import check_history, check_quiesce
from lambdipy_tpu.chaos.nemesis import (
    ROUTER,
    FleetOps,
    Nemesis,
    NemesisEvent,
    generate_timeline,
    parse_timeline,
    render_timeline,
    timeline_properties,
)
from lambdipy_tpu.chaos.workload import (
    Outcome,
    build_plan,
    precompute_expected,
)
from lambdipy_tpu.runtime.faults import REGISTRY

REPLICAS = ["r0", "r1"]


# -- timeline grammar ---------------------------------------------------------


def test_event_grammar_round_trip():
    events = [
        NemesisEvent(1.25, "arm", "r0", "segment_fetch:exception@n=2"),
        NemesisEvent(3.5, "clear", "r0"),
        NemesisEvent(4.0, "kill", "r1"),
        NemesisEvent(5.125, "drain", "r0"),
        NemesisEvent(7.0, "undrain", "r0"),
        NemesisEvent(8.0, "arm", ROUTER,
                     "route_latency:delay@ms=120,n=3"),
    ]
    text = render_timeline(events)
    parsed = parse_timeline(text)
    assert render_timeline(parsed) == text
    assert parsed[0].spec == "segment_fetch:exception@n=2"


def test_parse_timeline_skips_comments_and_sorts():
    text = ("# a hand-edited replay file\n"
            "@5.0 kill r1\n"
            "\n"
            "@1.0 arm r0 transport:delay@ms=50\n")
    events = parse_timeline(text)
    assert [e.action for e in events] == ["arm", "kill"]


@pytest.mark.parametrize("line", [
    "no-at arm r0 transport:delay",          # missing @T
    "@1.0 explode r0",                       # unknown action
    "@1.0 arm r0",                           # arm without a spec
    "@1.0 arm r0 not_a_site:exception",      # unregistered site
    "@1.0 arm r0 transport:sideways",        # unknown kind
    "@1.0 kill r0 transport:delay@ms=5",     # spec on a non-arm event
    "@x arm r0 transport:delay",             # bad time
])
def test_parse_rejects_bad_lines(line):
    with pytest.raises(ValueError):
        NemesisEvent.parse(line)


# -- schedule generation ------------------------------------------------------


def test_same_seed_byte_identical_timeline():
    a = render_timeline(generate_timeline(seed=11, duration_s=22.0,
                                          replicas=REPLICAS))
    b = render_timeline(generate_timeline(seed=11, duration_s=22.0,
                                          replicas=REPLICAS))
    assert a == b
    c = render_timeline(generate_timeline(seed=12, duration_s=22.0,
                                          replicas=REPLICAS))
    assert c != a


@pytest.mark.parametrize("seed", [0, 7, 11, 23, 99, 1234])
def test_generated_schedule_structural_floor(seed):
    """Every generated schedule meets the composed-fault acceptance
    floor: >= 1 kill, >= 1 drain, a sustained >= 2-fault overlap, peak
    overlap bounded, arm specs drawn from the site registry, and never
    two concurrent faults on one target (clearing one would clear the
    other — the per-target plan is one namespace)."""
    events = generate_timeline(seed=seed, duration_s=22.0,
                               replicas=REPLICAS)
    props = timeline_properties(events)
    assert props["kills"] >= 1 and props["drains"] >= 1
    assert props["peak_overlap"] >= 2
    assert props["peak_overlap"] <= 3
    assert props["sustained_overlap_s"] >= 1.0
    open_by_target: dict = {}
    for e in sorted(events, key=lambda e: e.t):
        if e.action == "arm":
            assert e.target not in open_by_target, \
                f"two concurrent faults on {e.target}"
            open_by_target[e.target] = e.t
            site = e.spec.partition(":")[0]
            assert site in REGISTRY
        elif e.action == "clear":
            open_by_target.pop(e.target, None)
    assert not open_by_target, "an armed fault was never cleared"


def test_generated_schedule_respects_kill_window():
    """Faults never target a replica after its worker was SIGKILLed —
    an arm against a respawning process would no-op for the rest of the
    window and silently thin the schedule."""
    for seed in range(20):
        events = generate_timeline(seed=seed, duration_s=22.0,
                                   replicas=REPLICAS)
        kill = next(e for e in events if e.action == "kill")
        for e in events:
            if e.action == "arm" and e.target == kill.target:
                clear = next(c for c in events
                             if c.action == "clear"
                             and c.target == e.target and c.t > e.t)
                assert clear.t <= kill.t


# -- the executor -------------------------------------------------------------


class _FakeOps(FleetOps):
    def __init__(self):
        self.calls = []

    def arm(self, target, spec):
        if spec.startswith("page_alloc"):
            raise RuntimeError("replica is mid-respawn")
        self.calls.append(("arm", target, spec))

    def clear(self, target):
        self.calls.append(("clear", target))

    def kill(self, target):
        self.calls.append(("kill", target))

    def drain(self, target):
        self.calls.append(("drain", target))

    def undrain(self, target):
        self.calls.append(("undrain", target))


def test_nemesis_executor_applies_in_order_and_survives_errors():
    timeline = [
        NemesisEvent(0.02, "arm", "r0", "transport:delay@ms=10"),
        NemesisEvent(0.04, "arm", "r1", "page_alloc:exception"),  # raises
        NemesisEvent(0.06, "kill", "r1"),
        NemesisEvent(0.08, "clear", "r0"),
    ]
    ops = _FakeOps()
    applied = Nemesis(timeline, ops).run()
    assert [a.event.action for a in applied] == \
        ["arm", "arm", "kill", "clear"]
    errors = [a for a in applied if a.error]
    assert len(errors) == 1 and "mid-respawn" in errors[0].error
    # the failing arm did not derail the rest of the schedule
    assert ("kill", "r1") in ops.calls and ("clear", "r0") in ops.calls


# -- the workload plan --------------------------------------------------------


def test_build_plan_deterministic_and_mixed():
    a = build_plan(seed=5, duration_s=20.0)
    b = build_plan(seed=5, duration_s=20.0)
    assert a.requests == b.requests
    assert sorted(a.sessions) == sorted(b.sessions)
    for sid in a.sessions:
        assert a.sessions[sid]["turns"] == b.sessions[sid]["turns"]
    reqs = a.all_requests()
    kinds = {r.kind for r in reqs}
    assert kinds == {"cold", "prefix", "session"}
    assert any(r.stream for r in reqs) and any(not r.stream for r in reqs)
    assert any("seed" in r.kw for r in reqs) \
        and any(not r.kw for r in reqs)
    assert len({r.rid for r in reqs}) == len(reqs)


def test_precompute_expected_builds_session_transcripts():
    plan = build_plan(seed=3, duration_s=10.0, n_sessions=1, turns=3,
                      n_cold=1, n_prefix_groups=0)

    def fake_completion(row, kw, max_tokens):
        # deterministic fake: answer depends on the prompt, like a model
        return [sum(row) % 97, len(row) % 89][:max_tokens]

    precompute_expected(plan, fake_completion)
    (conv,) = plan.sessions.values()
    history = list(conv["first"])
    for turn, req in enumerate(conv["turns"]):
        assert req.row == history
        assert req.expected == fake_completion(history, req.kw,
                                               req.max_tokens)
        history = history + req.expected + conv["users"][turn]


# -- the history checker ------------------------------------------------------


def _outcome(rid, status, *, tokens=None, expected=(1, 2, 3), took=0.5,
             **kw):
    return Outcome(rid=rid, kind=kw.pop("kind", "cold"),
                   streamed=kw.pop("streamed", False),
                   sampled=False, t_start=100.0, t_end=100.0 + took,
                   status=status, tokens=tokens,
                   expected=list(expected), **kw)


def test_checker_accepts_clean_history():
    v = check_history([
        _outcome(1, "ok", tokens=[1, 2, 3]),
        _outcome(2, "shed", http_status=503, shed_reason="kv_pages",
                 retry_after_s=2.0),
        _outcome(3, "shed", http_status=504, shed_reason="timeout"),
        _outcome(4, "stream_error", streamed=True, tokens=[1, 2]),
        _outcome(5, "stream_truncated", streamed=True, tokens=[1]),
    ], waiter_bound_s=60.0)
    assert v["ok"], v["violations"]
    assert v["tallies"]["delivered"] == 1 and v["tallies"]["sheds"] == 2


def test_checker_rejects_wrong_bytes_as_silent_corruption():
    v = check_history([_outcome(1, "ok", tokens=[9, 9, 9])],
                      waiter_bound_s=60.0)
    assert not v["ok"]
    assert any("WRONG tokens" in x for x in v["violations"])


def test_checker_rejects_diverged_stream_prefix():
    v = check_history(
        [_outcome(1, "stream_truncated", streamed=True, tokens=[1, 9])],
        waiter_bound_s=60.0)
    assert not v["ok"]
    assert any("diverged" in x for x in v["violations"])


def test_checker_rejects_uncontracted_failures_and_slow_waiters():
    v = check_history([
        _outcome(1, "http_error", http_status=500),
        _outcome(2, "exception", detail="ConnectionResetError"),
        _outcome(3, "ok", tokens=[1, 2, 3], took=120.0),
    ], waiter_bound_s=60.0)
    assert not v["ok"]
    joined = "\n".join(v["violations"])
    assert "silent loss" in joined and "waiter outlived" in joined


def test_checker_canary_suppressed_shed_fails_the_oracle():
    """The acceptance-criteria canary: the same history passes the
    normal oracle and FAILS when the shed counter is suppressed —
    the checker can actually reject, it is not a rubber stamp."""
    history = [
        _outcome(1, "ok", tokens=[1, 2, 3]),
        _outcome(2, "shed", http_status=503, shed_reason="canary",
                 retry_after_s=1.0),
    ]
    assert check_history(history, waiter_bound_s=60.0)["ok"]
    v = check_history(history, waiter_bound_s=60.0,
                      suppress_sheds=True)
    assert not v["ok"]
    assert any("accounting does not converge" in x
               for x in v["violations"])


# -- the quiesce checker ------------------------------------------------------


def _clean_metrics(pinned=0, sessions=0, armed=False):
    return {"handler": {
        "prefix_cache": {"pinned_leaves": pinned, "pinned_bytes": pinned,
                         "sessions_active": sessions},
        "faults": {"armed": {"active": armed,
                             "sites": ["transport"] if armed else []}},
    }}


def test_quiesce_accepts_converged_fleet():
    v = check_quiesce(
        {"ok": True, "replicas": {"r0": {"ok": True}}, "spill_depth": 0},
        {"r0": _clean_metrics()},
        router_metrics={"fleet": {"sessions": {"active": 0}},
                        "faults": {"armed": {"active": False}}})
    assert v["ok"], v["violations"]


def test_quiesce_rejects_leaks_and_leftover_faults():
    v = check_quiesce(
        {"ok": False,
         "replicas": {"r0": {"ok": False, "violations": ["x"]}},
         "spill_depth": 2},
        {"r0": _clean_metrics(pinned=3),
         "r1": _clean_metrics(armed=True),
         "r2": None},
        router_metrics={"fleet": {"sessions": {"active": 1}},
                        "faults": {"armed": {"active": True,
                                             "sites": ["kv_ship"]}}})
    joined = "\n".join(v["violations"])
    for needle in ("invariant sweep failed", "spill depth 2",
                   "pinned_leaves=3", "still armed", "no /metrics",
                   "open session"):
        assert needle in joined, (needle, joined)


# -- prefix-store invariant sweep --------------------------------------------


def test_prefixstore_check_invariants_clean_and_corrupted(tiny_server):
    from lambdipy_tpu.runtime.prefixstore import PrefixStore

    store = PrefixStore(tiny_server, block=16, budget_mb=4)
    out = store.check_invariants()
    assert out["ok"] and out["violations"] == []
    assert out["pinned_leaves"] == 0 and out["blocks"] == 0
    # corrupt a counter: the sweep must notice the books don't balance
    store._pinned_leaves = 5
    out = store.check_invariants()
    assert not out["ok"]
    assert any("pinned_leaves" in x for x in out["violations"])
    store._pinned_leaves = 0


# -- the server debug surfaces ------------------------------------------------


def _stub_server(monkeypatch, tmp_path, state_extra):
    from pathlib import Path
    from types import SimpleNamespace

    import lambdipy_tpu.runtime.server as server_mod
    from lambdipy_tpu.runtime.loader import BootReport

    def stub_boot(bundle_dir, warmup=True):
        return BootReport(
            bundle_dir=Path(bundle_dir),
            handler=SimpleNamespace(invoke=lambda st, req: {"ok": True}),
            state=SimpleNamespace(meta={"model": "stub"},
                                  stats=lambda: {}, **state_extra),
            stages={"init": 0.0}, manifest={"payload": {"extra": {}}})

    monkeypatch.setattr(server_mod, "load_bundle", stub_boot)
    return server_mod.BundleServer(tmp_path, port=0,
                                   warmup=False).start_background()


def test_server_debug_invariants_endpoint(monkeypatch, tmp_path):
    srv = _stub_server(monkeypatch, tmp_path, {
        "debug_invariants_fn":
            lambda: {"ok": True, "checks": {"prefix_store": {"ok": True}}}
    })
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/debug/invariants",
                timeout=10) as r:
            out = json.loads(r.read())
        assert out["ok"] and out["checks"]["prefix_store"]["ok"]
    finally:
        threading.Thread(target=srv.stop, daemon=True).start()


def test_server_debug_faults_endpoint_arms_live_plan(monkeypatch,
                                                     tmp_path):
    """POST /v1/debug/faults drives a REAL FaultPlan: arm fires on the
    next matching call, clear releases the rules — the nemesis's whole
    control contract, minus the fleet."""
    from lambdipy_tpu.runtime.faults import FaultPlan, InjectedFault

    plan = FaultPlan.empty()

    def faults_admin(req):
        if req.get("clear"):
            return {"ok": True, "cleared": plan.clear(),
                    "armed": plan.armed()}
        try:
            return {"ok": True, "added": plan.arm(req["spec"]),
                    "armed": plan.armed()}
        except (KeyError, ValueError) as e:
            return {"ok": False, "error": str(e)}

    srv = _stub_server(monkeypatch, tmp_path,
                       {"faults_admin_fn": faults_admin})
    base = f"http://127.0.0.1:{srv.port}"

    def post(payload):
        req = urllib.request.Request(
            f"{base}/v1/debug/faults",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        code, out = post({"spec": "transport:exception@n=1"})
        assert code == 200 and out["armed"]["active"]
        with pytest.raises(InjectedFault):
            plan.check("transport")
        code, out = post({"spec": "not_a_site:exception"})
        assert code == 400 and "bad fault spec" in out["error"]
        code, out = post({"clear": True})
        assert code == 200 and not out["armed"]["active"]
        plan.check("transport")  # cleared: no fire
    finally:
        threading.Thread(target=srv.stop, daemon=True).start()


def test_replay_timeline_drives_identical_event_sequence():
    """The --replay-timeline contract at executor level: a timeline
    rendered to a file and parsed back drives EXACTLY the same action
    sequence as the original — rendering loses nothing the executor
    reads."""
    original = generate_timeline(seed=11, duration_s=22.0,
                                 replicas=REPLICAS)
    replayed = parse_timeline(render_timeline(original))
    ops_a, ops_b = _FakeOps(), _FakeOps()
    # compress the clock: the executor honors relative timing, the
    # sequence (not the wall time) is the replay contract
    Nemesis(original, ops_a, time_scale=0.002).run()
    Nemesis(replayed, ops_b, time_scale=0.002).run()
    assert ops_a.calls == ops_b.calls
    assert len(ops_a.calls) >= 5


def test_generate_timeline_rejects_unfittable_configs():
    """The mandatory-event draw windows invert below ~12 s, and a
    1-replica fleet leaves the overlap pair only one non-kill target —
    both must fail loudly instead of producing out-of-window events or
    an empty-menu crash mid-draw."""
    with pytest.raises(ValueError, match="too short"):
        generate_timeline(seed=1, duration_s=5.0, replicas=REPLICAS)
    with pytest.raises(ValueError, match=">= 2 replicas"):
        generate_timeline(seed=1, duration_s=22.0, replicas=["r0"])


# -- the offload_stall nemesis legs -------------------------------------------


def test_fault_menu_offers_offload_stall_legs():
    """The nemesis menu (derived from the site registry) must offer
    offload_stall on replicas in BOTH store-owned kinds — delay (a slow
    re-online, timed as a stall) and exception (a failed re-online,
    degraded to a counted recompute) — and never hang (a store-owned
    hang has no replay machinery to resolve it)."""
    from lambdipy_tpu.chaos.nemesis import _fault_menu

    menu = _fault_menu(REPLICAS + [ROUTER])
    assert ("r0", "offload_stall", "delay") in menu
    assert ("r1", "offload_stall", "exception") in menu
    assert not any(site == "offload_stall" and kind == "hang"
                   for _, site, kind in menu)
    assert not any(t == ROUTER and site == "offload_stall"
                   for t, site, _ in menu)


@pytest.mark.parametrize("seed", [0, 7, 11, 23, 99, 1234])
def test_timeline_must_include_guarantees_offload_stall(seed):
    """must_include="offload_stall" puts at least one armed
    offload_stall leg in EVERY seed's schedule (the soak composes the
    offload tier's failure mode deliberately, not when the dice feel
    like it), without breaking the structural floor or the byte-
    identical-replay contract."""
    events = generate_timeline(seed=seed, duration_s=22.0,
                               replicas=REPLICAS,
                               must_include="offload_stall")
    arms = [e for e in events if e.action == "arm"
            and e.spec.partition(":")[0] == "offload_stall"]
    assert arms, "no offload_stall leg in the guaranteed schedule"
    props = timeline_properties(events)
    assert props["kills"] >= 1 and props["drains"] >= 1
    assert props["peak_overlap"] <= 3
    # same seed + same knob -> byte-identical schedule
    again = generate_timeline(seed=seed, duration_s=22.0,
                              replicas=REPLICAS,
                              must_include="offload_stall")
    assert render_timeline(events) == render_timeline(again)
    with pytest.raises(ValueError, match="no menu legs"):
        generate_timeline(seed=seed, duration_s=22.0,
                          replicas=REPLICAS,
                          must_include="no_such_site")


def test_soak_window_composed_offload_stall_zero_silent_loss(tiny_server):
    """A soak-style window with offload_stall composed in, in-process:
    requests riding SPILLED prefixes under an armed offload_stall
    still deliver bitwise tokens. The delay leg is a timed re-online
    stall; the exception leg degrades to a counted recompute through
    the dense fallback (deterministic — the prefill replays the same
    math the pages held). The history checker is the oracle: zero
    silent losses, every outcome delivered."""
    import time as _time

    import numpy as np

    from lambdipy_tpu.runtime.continuous import ContinuousBatcher
    from lambdipy_tpu.runtime.faults import FaultPlan
    from lambdipy_tpu.runtime.offload import OffloadArena
    from lambdipy_tpu.runtime.prefixstore import PrefixStore
    from tests.test_long_context import mk_pool

    plan = FaultPlan.empty()
    pool = mk_pool(tiny_server, extra_pages=4)
    store = PrefixStore(tiny_server, pool=pool)
    off = OffloadArena(page=pool.page,
                       layers=tiny_server.model.cfg.layers,
                       faults=plan)
    store.attach_offload(off)
    eng = ContinuousBatcher(tiny_server, slots=2, segment=4,
                            page_pool=pool)
    eng.prefix_pages_fn = store.acquire_pages

    row = np.random.default_rng(31).integers(
        5, 100, size=65).tolist()
    ref = np.asarray(tiny_server.generate(row, max_new_tokens=8))

    def request(rid, kind):
        t0 = _time.monotonic()
        m = store.route(row)
        assert m == 64
        out = eng.generate(row[m:], max_new_tokens=8,
                           prefix=np.asarray(row[:m], np.int32))
        return Outcome(rid=rid, kind=kind, streamed=False,
                       sampled=False, t_start=t0,
                       t_end=_time.monotonic(), status="ok",
                       tokens=np.asarray(out).ravel().tolist(),
                       expected=np.asarray(ref).ravel().tolist())

    outcomes = [request(1, "cold")]
    # spill the whole prefix to the host tier, then hit it under the
    # DELAY leg: the batched re-online pays the injected stall
    while store.reclaim_pages(1):
        pass
    assert store.check_invariants()["offloaded_blocks"] == 4
    plan.arm("offload_stall:delay@ms=60,n=1")
    outcomes.append(request(2, "hit"))
    assert off.report()["reonlines"] >= 1
    # spill again and hit under the EXCEPTION leg: the failed
    # re-online degrades to the dense-fallback recompute, counted
    while store.reclaim_pages(1):
        pass
    plan.clear()
    plan.arm("offload_stall:exception@n=1")
    outcomes.append(request(3, "hit"))
    assert off.report()["recomputes"] >= 1
    v = check_history(outcomes, waiter_bound_s=60.0)
    assert v["ok"], v["violations"]
    assert v["tallies"]["delivered"] == 3
    assert v["tallies"]["silent"] == 0
