"""Fleet subsystem: affinity hashing, pool health state machine, router
failover/retry/hedging (scriptable stub replicas — no device, so these
stay in the tight tier-1 budget), and — marked ``slow``, run by hand
(``pytest tests/test_fleet.py -m slow``, 3-4 min) — everything that
boots real bundle servers:
router-vs-direct bitwise parity, the readiness split on a live server,
affinity concentrating the fleet prefix-cache hit rate, and subprocess
fault injection with SIGKILL + supervisor re-admission and a rolling
restart under traffic."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from lambdipy_tpu.fleet import (
    DRAINING,
    EJECTED,
    READY,
    FleetRouter,
    ReplicaPool,
    affinity,
)

from test_runtime import make_model_bundle


def _get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _post(url, payload, timeout=120, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


# -- affinity hashing (pure) -------------------------------------------------


def test_prefix_key_leading_blocks():
    # keys depend only on the leading WHOLE blocks: same 64-token prefix,
    # different suffixes -> same key
    a = affinity.prefix_key({"tokens": list(range(64)) + [7, 8]}, block=32)
    b = affinity.prefix_key({"tokens": list(range(64)) + [9]}, block=32)
    assert a is not None and a == b
    # a different prefix changes the key
    c = affinity.prefix_key({"tokens": [5] * 64 + [7, 8]}, block=32)
    assert c != a
    # sub-block prompts key on the whole prompt (co-locate exact repeats)
    s1 = affinity.prefix_key({"tokens": [1, 2, 3]}, block=32)
    s2 = affinity.prefix_key({"tokens": [1, 2, 3]}, block=32)
    s3 = affinity.prefix_key({"tokens": [1, 2, 4]}, block=32)
    assert s1 == s2 and s1 != s3
    # an explicit client prefix is part of the effective prompt
    p1 = affinity.prefix_key({"prefix": list(range(32)), "tokens": [1, 2]},
                             block=32)
    p2 = affinity.prefix_key({"tokens": list(range(32)) + [3, 4]}, block=32)
    assert p1 == p2
    # ...including for string-suffix and prefix-only bodies: the prefix
    # is the reusable KV, so all three co-locate
    t1 = affinity.prefix_key({"prefix": list(range(32)), "text": "abc"},
                             block=32)
    t2 = affinity.prefix_key({"prefix": list(range(32)), "text": "xyz"},
                             block=32)
    t3 = affinity.prefix_key({"prefix": list(range(32))}, block=32)
    assert t1 == t2 == t3
    assert affinity.prefix_key({"prefix": [9] * 32, "text": "abc"},
                               block=32) != t1
    # the key window is BOUNDED: prompts sharing the first key_blocks
    # blocks co-locate even when their (multi-block) suffixes diverge —
    # a 512-token system prompt + distinct long user turns is exactly
    # the traffic affinity exists for
    shared = list(range(512))
    long_a = affinity.prefix_key(
        {"tokens": shared + [1] * 100}, block=32)
    long_b = affinity.prefix_key(
        {"tokens": shared + [2] * 100}, block=32)
    assert long_a == long_b
    assert affinity.prefix_key(
        {"tokens": list(range(7, 519)) + [1] * 100}, block=32) != long_a
    # OpenAI shape: token-array prompt and string prompt both key
    assert affinity.prefix_key({"prompt": list(range(40))}, block=32) \
        == affinity.prefix_key({"tokens": list(range(40))}, block=32)
    assert affinity.prefix_key({"prompt": "x" * 200}, block=32) \
        == affinity.prefix_key({"text": "x" * 200}, block=32)
    # nothing routable -> None
    assert affinity.prefix_key({"n": 3}, block=32) is None


def test_rendezvous_membership_stability():
    import random

    names = ["r0", "r1", "r2", "r3"]
    rng = random.Random(0)
    keys = [affinity.prefix_key(
        {"tokens": [rng.randrange(500) for _ in range(40)]})
        for _ in range(300)]
    before = {k: affinity.pick_replica(k, names) for k in keys}
    assert len(set(before.values())) == len(names)  # all replicas used
    # removing one replica remaps ONLY the keys that lived on it
    survivors = [n for n in names if n != "r2"]
    for k in keys:
        after = affinity.pick_replica(k, survivors)
        if before[k] != "r2":
            assert after == before[k]
        else:
            assert after in survivors


# -- stub replica ------------------------------------------------------------


class StubReplica:
    """Scriptable bundle-server stand-in: the /healthz /metrics /invoke
    /v1/completions contract the router needs, plus knobs tests flip
    mid-flight (shed / draining / warming / delay / pid)."""

    def __init__(self, name, *, port=0):
        self.name = name
        self.cfg = {"shed": False, "draining": False, "warming": False,
                    "delay_s": 0.0, "retry_after": 1, "pid": 1000,
                    "prefix_cache": {"hits": 0, "misses": 0,
                                     "hit_tokens": 0},
                    "spec": {"sp_standdown": 0,
                             "sp_standdown_reasons": {}}}
        self.cfg["kv_shed"] = False   # /v1/kv/import answers 503
        self.cfg["kv_frame"] = b"LKV1-stub-frame"  # /v1/kv/export body
        # opt-in chunked export: a list of wire frames (LKVS header +
        # LKVC chunks, e.g. from kvwire.encode_stream) served as a
        # chunked response when the export request asks stream=true —
        # the pipelined-relay tests ride this; None keeps the
        # monolithic LKV1 behavior above
        self.cfg["kv_stream_frames"] = None
        # /v1/kv/probe: None = report the whole asked head as present
        # (the dedup-preserving default); an int scripts a partial/empty
        # match (a stale ship-dedup entry the router should PULL for)
        self.cfg["kv_probe_matched"] = None
        self.invokes = 0
        self.exports = 0
        self.probes = 0
        self.imports = []  # raw frames received on /v1/kv/import
        self.deletes = []  # session ids received on DELETE /v1/sessions
        self.bodies = []  # (path, parsed body) of every POST received
        stub = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _send(self, code, payload, headers=None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    ready = (not stub.cfg["draining"]
                             and not stub.cfg["warming"])
                    self._send(200, {"ok": True, "ready": ready,
                                     "draining": stub.cfg["draining"],
                                     "warming": stub.cfg["warming"],
                                     "pid": stub.cfg["pid"]})
                elif self.path == "/metrics":
                    self._send(200, {
                        "count": stub.invokes,
                        "handler": {"prefix_cache": stub.cfg["prefix_cache"],
                                    "spec": stub.cfg["spec"]},
                    })
                else:
                    self._send(404, {"ok": False})

            def _frame(self, b):
                self.wfile.write(f"{len(b):x}\r\n".encode() + b + b"\r\n")

            def do_POST(self):
                if "chunked" in (self.headers.get("Transfer-Encoding")
                                 or "").lower():
                    # de-chunk a streamed import body (the pipelined
                    # relay's import leg); the reassembled bytes land
                    # in stub.imports like a monolithic frame would. A
                    # relay dying mid-stream (no terminal chunk) closes
                    # the connection without recording an import — the
                    # rollback behavior the real server implements.
                    raw = b""
                    try:
                        while True:
                            size = int(
                                self.rfile.readline(66).strip(), 16)
                            if size == 0:
                                self.rfile.readline()
                                break
                            raw += self.rfile.read(size)
                            self.rfile.read(2)
                    except (ValueError, OSError):
                        self.close_connection = True
                        return
                else:
                    length = int(self.headers.get("Content-Length", 0))
                    raw = self.rfile.read(length)
                if self.path == "/v1/kv/import":
                    # binary frame, not JSON; scriptable backpressure
                    if stub.cfg["kv_shed"]:
                        ra = stub.cfg["retry_after"]
                        self._send(503, {"ok": False, "shed": True,
                                         "reason": "kv_import",
                                         "retry_after_s": float(ra)},
                                   {"Retry-After": str(ra)})
                        return
                    stub.imports.append(raw)
                    self._send(200, {"ok": True, "inserted": 2,
                                     "present": 0, "mode": "dense"})
                    return
                body = json.loads(raw or b"{}")
                stub.bodies.append((self.path, body))
                if self.path == "/v1/kv/probe":
                    stub.probes += 1
                    matched = stub.cfg["kv_probe_matched"]
                    if matched is None:
                        matched = len(body.get("tokens") or [])
                    self._send(200, {"ok": True, "matched": int(matched)})
                    return
                if self.path == "/v1/kv/export":
                    if stub.cfg["shed"] or stub.cfg["draining"]:
                        ra = stub.cfg["retry_after"]
                        self._send(503, {"ok": False, "shed": True,
                                         "reason": "draining",
                                         "retry_after_s": float(ra)},
                                   {"Retry-After": str(ra)})
                        return
                    stub.exports += 1
                    frames = stub.cfg["kv_stream_frames"]
                    if body.get("stream") and frames is not None:
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/x-lkv-stream")
                        self.send_header("Transfer-Encoding", "chunked")
                        self.end_headers()
                        for f in frames:
                            self._frame(f)
                        self.wfile.write(b"0\r\n\r\n")
                        return
                    frame = stub.cfg["kv_frame"]
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/octet-stream")
                    self.send_header("Content-Length", str(len(frame)))
                    self.end_headers()
                    self.wfile.write(frame)
                    return
                if stub.cfg["delay_s"]:
                    time.sleep(stub.cfg["delay_s"])
                if stub.cfg["shed"] or stub.cfg["draining"]:
                    ra = stub.cfg["retry_after"]
                    self._send(503, {"ok": False, "shed": True,
                                     "reason": "draining",
                                     "retry_after_s": float(ra)},
                               {"Retry-After": str(ra)})
                    return
                stub.invokes += 1
                if body.get("stream"):
                    sse = self.path == "/v1/completions"
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/event-stream" if sse
                                     else "application/x-ndjson")
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()
                    if sse:
                        self._frame(b'data: {"choices": [{"tokens": [1],'
                                    b' "text": ""}]}\n\n')
                        self._frame(b"data: [DONE]\n\n")
                    else:
                        self._frame(json.dumps(
                            {"ok": True, "tokens": [[1]],
                             "replica": stub.name}).encode() + b"\n")
                        self._frame(json.dumps(
                            {"ok": True, "done": True, "n_new": 1,
                             "replica": stub.name}).encode() + b"\n")
                    self.wfile.write(b"0\r\n\r\n")
                    return
                self._send(200, {"ok": True, "replica": stub.name,
                                 "echo": body.get("tokens"),
                                 "session":
                                     self.headers.get("x-session-id")
                                     or body.get("session_id"),
                                 "priority":
                                     self.headers.get("x-priority")})

            def do_DELETE(self):
                if self.path.startswith("/v1/sessions/"):
                    sid = self.path[len("/v1/sessions/"):]
                    stub.deletes.append(sid)
                    self._send(200, {"ok": True, "session": sid,
                                     "released": True})
                    return
                self._send(404, {"ok": False})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", port), H)
        self.port = self.httpd.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def kill(self):
        """Abrupt death: the port refuses connections afterwards."""
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture()
def stub_pair():
    s0, s1 = StubReplica("r0"), StubReplica("r1")
    pool = ReplicaPool(probe_interval=0.1, fail_threshold=1,
                       readmit_passes=2, probe_timeout=2.0)
    pool.attach("r0", s0.url)
    pool.attach("r1", s1.url)
    yield s0, s1, pool
    pool.close()
    for s in (s0, s1):
        try:
            s.kill()
        except Exception:
            pass


# -- pool health state machine ----------------------------------------------


def test_pool_eject_readmit_and_draining(stub_pair):
    s0, s1, pool = stub_pair
    pool.probe_all()
    assert {r.name for r in pool.routable()} == {"r0", "r1"}

    # readiness false (drain begun) = alive but NOT routable, NOT ejected
    s0.cfg["draining"] = True
    pool.probe_all()
    r0 = pool.replicas["r0"]
    assert [r.name for r in pool.routable()] == ["r1"]
    assert r0.state == READY and not r0.ready and r0.ejections == 0
    s0.cfg["draining"] = False
    pool.probe_all()
    assert len(pool.routable()) == 2

    # warm-in-flight is the same not-routable-but-live story
    s0.cfg["warming"] = True
    pool.probe_all()
    assert [r.name for r in pool.routable()] == ["r1"]
    s0.cfg["warming"] = False
    pool.probe_all()

    # abrupt death -> ejected after fail_threshold(=1) consecutive fails
    port = s0.port
    s0.kill()
    pool.probe_all()
    assert r0.state == EJECTED and r0.ejections == 1

    # revival (same port, new worker pid) -> readmitted only after
    # readmit_passes consecutive passes, with the restart counted
    s0b = StubReplica("r0", port=port)
    s0b.cfg["pid"] = 2000
    pool.probe_all()
    assert r0.state == EJECTED  # one pass is not enough
    pool.probe_all()
    assert r0.state == READY and r0.restarts == 1
    s0b.kill()


# -- router: routing, failover, retry ---------------------------------------


def test_router_spreads_and_fails_over(stub_pair):
    s0, s1, pool = stub_pair
    pool.probe_all()
    router = FleetRouter(pool, affinity_on=False,
                         max_retries=2, backoff_cap_s=0.2)
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        for i in range(6):
            out = _post(f"{base}/invoke", {"tokens": [i], "n": 1})
            assert out["ok"] and out["echo"] == [i]
        # round-robin tie-break spreads affinity-off traffic
        assert s0.invokes >= 2 and s1.invokes >= 2
        assert s0.invokes + s1.invokes == 6

        # kill one replica: concurrent traffic must all succeed via
        # retries, and the dead replica ejects at TRAFFIC speed (the
        # router reports the connection failure; no probe needed)
        s0.kill()
        results = []

        def worker(i):
            results.append(_post(f"{base}/invoke", {"tokens": [i]}))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 8 and all(r["ok"] for r in results)
        assert all(r["replica"] == "r1" for r in results)
        assert pool.replicas["r0"].state == EJECTED
        rep = router.stats.report()
        assert rep["failovers"] >= 1 and rep["retries"] >= 1
        assert rep["completed"] >= 14
    finally:
        router.stop()


def test_router_honors_retry_after_shed(stub_pair):
    s0, s1, pool = stub_pair
    pool.probe_all()
    router = FleetRouter(pool, affinity_on=False, max_retries=2,
                         backoff_s=0.01, backoff_cap_s=0.2)
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        # one replica shedding: every request still lands on the other
        s0.cfg["shed"] = True
        for i in range(4):
            out = _post(f"{base}/invoke", {"tokens": [i]})
            assert out["ok"] and out["replica"] == "r1"
        assert router.stats.report()["retries"] >= 1

        # the WHOLE fleet shedding: the shed response is relayed to the
        # client with its Retry-After intact, not a synthetic error
        s1.cfg["shed"] = True
        s0.cfg["retry_after"] = s1.cfg["retry_after"] = 7
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/invoke", {"tokens": [1]})
        assert e.value.code == 503
        assert e.value.headers["Retry-After"] == "7"
        body = json.loads(e.value.read())
        assert body["shed"] and body["retry_after_s"] == 7.0
    finally:
        router.stop()


def test_router_streaming_passthrough_and_stream_failover(stub_pair):
    s0, s1, pool = stub_pair
    pool.probe_all()
    router = FleetRouter(pool, affinity_on=False, max_retries=2,
                         backoff_cap_s=0.2)
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        # ndjson /invoke pass-through
        req = urllib.request.Request(
            f"{base}/invoke",
            data=json.dumps({"tokens": [1], "stream": True}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["Content-Type"] == "application/x-ndjson"
            lines = [json.loads(ln) for ln in resp if ln.strip()]
        assert lines[-1]["done"] and lines[0]["tokens"] == [[1]]

        # SSE /v1/completions pass-through
        req = urllib.request.Request(
            f"{base}/v1/completions",
            data=json.dumps({"prompt": [1], "stream": True}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["Content-Type"] == "text/event-stream"
            events = [ln.decode().strip()[6:] for ln in resp
                      if ln.strip().startswith(b"data: ")]
        assert events[-1] == "[DONE]"

        # a dead replica is retried BEFORE any bytes are forwarded
        s0.kill()
        served = set()
        for i in range(4):
            req = urllib.request.Request(
                f"{base}/invoke",
                data=json.dumps({"tokens": [i],
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=30) as resp:
                lines = [json.loads(ln) for ln in resp if ln.strip()]
            assert lines[-1]["done"]
            served.add(lines[-1]["replica"])
        assert served == {"r1"}
    finally:
        router.stop()


def test_router_hedges_slow_primary(stub_pair):
    s0, s1, pool = stub_pair
    pool.probe_all()
    # a key whose rendezvous target we can find out, then slow down
    key = affinity.prefix_key({"tokens": list(range(64))}, block=32)
    target = affinity.pick_replica(key, ["r0", "r1"])
    slow, fast = (s0, s1) if target == "r0" else (s1, s0)
    slow.cfg["delay_s"] = 1.5
    router = FleetRouter(pool, affinity_on=True, block=32,
                         hedge_ms=100, hedge_floor_ms=50)
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        t0 = time.monotonic()
        out = _post(f"{base}/invoke", {"tokens": list(range(64))})
        elapsed = time.monotonic() - t0
        assert out["ok"] and out["replica"] == fast.name
        assert elapsed < 1.4  # did not wait out the slow primary
        rep = router.stats.report()
        assert rep["hedges"] == 1 and rep["hedge_wins"] == 1
        assert pool.replicas[fast.name].hedged == 1
    finally:
        router.stop()


def test_router_healthz_and_metrics_aggregation(stub_pair):
    s0, s1, pool = stub_pair
    pool.probe_all()
    s0.cfg["prefix_cache"] = {"hits": 3, "misses": 1, "hit_tokens": 96}
    s1.cfg["prefix_cache"] = {"hits": 1, "misses": 1, "hit_tokens": 32}
    # sp-decode stand-downs aggregate BY REASON at the router (a sharded
    # replica quietly replicating its cache must be visible fleet-wide)
    s0.cfg["spec"] = {"sp_standdown": 2, "sp_standdown_reasons":
                      {"attn_backend=blocked": 2}}
    s1.cfg["spec"] = {"sp_standdown": 1, "sp_standdown_reasons":
                      {"spec_k_under_sp_mesh": 1}}
    router = FleetRouter(pool, affinity_on=True, block=32)
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        for i in range(3):
            _post(f"{base}/invoke", {"tokens": list(range(32 + i))})
        health = _get(f"{base}/healthz")
        assert health["ok"] and health["routable"] == 2
        assert health["replicas"] == {"r0": READY, "r1": READY}
        m = _get(f"{base}/metrics")
        # fleet-wide prefix cache is the SUM over replicas
        assert m["fleet"]["prefix_cache"] == {
            "hits": 4, "misses": 2, "hit_tokens": 128,
            "hit_rate": round(4 / 6, 4)}
        assert m["fleet"]["spec_standdown"] == {
            "total": 3, "reasons": {"attn_backend=blocked": 2,
                                    "spec_k_under_sp_mesh": 1}}
        assert m["router"]["completed"] == 3
        assert m["router"]["affinity"]["requests"] == 3
        assert sum(rep["routed"] for rep in m["pool"].values()) == 3
        # per-replica raw /metrics ride along
        assert m["replicas"]["r0"]["count"] == s0.invokes
        # distinct 32-token prefixes: affinity keys differ, but each is
        # a HIT (target routable)
        assert m["router"]["affinity"]["hit_rate"] == 1.0
    finally:
        router.stop()


def test_router_draining_replica_loses_traffic_before_shedding(stub_pair):
    """The readiness split in action: once a replica reports
    ready: false, the router stops routing there BEFORE any request has
    to eat its 503."""
    s0, s1, pool = stub_pair
    pool.probe_all()
    router = FleetRouter(pool, affinity_on=False)
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        s0.cfg["draining"] = True  # server would 503, probe says not ready
        pool.probe_all()
        before = s0.invokes
        for i in range(4):
            out = _post(f"{base}/invoke", {"tokens": [i]})
            assert out["replica"] == "r1"
        assert s0.invokes == before  # zero requests even reached it
        assert router.stats.report()["retries"] == 0
    finally:
        router.stop()


def test_router_serves_through_whole_fleet_warming(stub_pair):
    """Brownout guard: when EVERY replica reports ready: false because
    its background warm is still compiling (a fresh fleet's first burst
    of traffic), the router degrades to the live-but-warming replicas —
    they serve fine — instead of 503ing the fleet."""
    s0, s1, pool = stub_pair
    s0.cfg["warming"] = s1.cfg["warming"] = True
    pool.probe_all()
    assert pool.routable() == [] and len(pool.live_fallback()) == 2
    router = FleetRouter(pool, affinity_on=False)
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        for i in range(4):
            assert _post(f"{base}/invoke", {"tokens": [i]})["ok"]
        assert router.stats.report()["no_replica"] == 0
        # once warm finishes, strict readiness routing resumes
        s0.cfg["warming"] = s1.cfg["warming"] = False
        pool.probe_all()
        assert len(pool.routable()) == 2
    finally:
        router.stop()


def test_pool_begin_drain_routes_away_immediately(stub_pair):
    """Rolling-drain step 1: begin_drain() flips routing away without
    waiting for the next probe. (The stubs stand in for MANAGED
    replicas here — begin_drain refuses attached ones, see
    tests/test_fleet_resilience.py.)"""
    s0, s1, pool = stub_pair
    pool.probe_all()
    for r in pool.replicas.values():
        r.managed = True
    router = FleetRouter(pool, affinity_on=False)
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        pool.begin_drain("r1")
        assert pool.replicas["r1"].state == DRAINING
        for i in range(4):
            assert _post(f"{base}/invoke",
                         {"tokens": [i]})["replica"] == "r0"
        # end_drain aborts the drain (the chaos nemesis's undrain, an
        # operator changing their mind): r1 routes again, and a second
        # end_drain on a non-draining replica is a no-op
        pool.end_drain("r1")
        assert pool.replicas["r1"].routable
        pool.end_drain("r1")
        seen = {_post(f"{base}/invoke", {"tokens": [i]})["replica"]
                for i in range(8)}
        assert "r1" in seen
    finally:
        router.stop()


# -- deploy/_http_json edges the router leans on -----------------------------


def test_http_json_connection_refused_and_timeout():
    from lambdipy_tpu.runtime.deploy import _http_json

    # refused: nothing listening on a fresh port -> URLError, fast
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    t0 = time.monotonic()
    with pytest.raises(urllib.error.URLError):
        _http_json(f"http://127.0.0.1:{port}/healthz", timeout=5)
    assert time.monotonic() - t0 < 2.0

    # timeout: a listener that accepts but never answers must raise at
    # the caller's deadline, not hang the router's probe thread
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    try:
        t0 = time.monotonic()
        with pytest.raises(Exception) as e:
            _http_json(
                f"http://127.0.0.1:{srv.getsockname()[1]}/healthz",
                timeout=0.3)
        assert isinstance(e.value, (TimeoutError, urllib.error.URLError,
                                    socket.timeout))
        assert time.monotonic() - t0 < 3.0
    finally:
        srv.close()


# -- real-bundle parity through the router -----------------------------------


@pytest.fixture(scope="module")
def fleet_bundle(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fleet-bundle")
    return make_model_bundle(
        tmp, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "4"})


@pytest.fixture(scope="module")
def real_pair(fleet_bundle):
    from lambdipy_tpu.runtime.server import BundleServer

    servers = [BundleServer(fleet_bundle, warmup=False).start_background()
               for _ in range(2)]
    pool = ReplicaPool(probe_interval=0.2, fail_threshold=1,
                       readmit_passes=2)
    for i, s in enumerate(servers):
        pool.attach(f"b{i}", f"http://127.0.0.1:{s.port}")
    pool.probe_all()
    yield servers, pool
    pool.close()
    for s in servers:
        threading.Thread(target=s.stop, daemon=True).start()


@pytest.mark.slow
def test_bundle_server_readiness_split(real_pair, monkeypatch):
    servers, pool = real_pair
    s = servers[0]
    base = f"http://127.0.0.1:{s.port}"
    h = _get(f"{base}/healthz")
    assert h["ok"] and h["ready"] and not h["warming"]
    # warm in flight: still 200/ok (liveness) but flagged not ready
    monkeypatch.setattr(s.boot.state, "warming_fn", lambda: True)
    h = _get(f"{base}/healthz")
    assert h["ok"] and not h["ready"] and h["warming"]
    monkeypatch.undo()
    # drain begun: same split
    s.draining = True
    try:
        h = _get(f"{base}/healthz")
        assert h["ok"] and not h["ready"] and h["draining"]
    finally:
        s.draining = False


@pytest.mark.slow
def test_router_parity_real_servers(real_pair):
    """Acceptance: router-fronted responses are bitwise identical to
    direct single-replica responses — greedy and seeded-sampled,
    streamed and non-streamed."""
    servers, pool = real_pair
    direct = f"http://127.0.0.1:{servers[0].port}"
    router = FleetRouter(pool, affinity_on=True, block=32)
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        greedy = {"prompt": [1, 2, 3], "max_tokens": 6, "temperature": 0}
        sampled = {"prompt": [1, 2, 3], "max_tokens": 6,
                   "temperature": 0.8, "top_k": 5, "seed": 7}
        for body in (greedy, sampled):
            d = _post(f"{direct}/v1/completions", body)
            r = _post(f"{base}/v1/completions", body)
            # queue_wait_ms is a per-request timing measurement (stamped
            # at sched grant) — each request measures its own wait, so
            # bitwise parity applies to everything BUT it
            assert d.pop("queue_wait_ms", None) is not None
            assert r.pop("queue_wait_ms", None) is not None
            assert d == r  # whole response: tokens, usage, finish_reason

        def sse_events(url, body):
            req = urllib.request.Request(
                url, data=json.dumps({**body, "stream": True,
                                      "segment": 2}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=120) as resp:
                return [ln for ln in resp if ln.strip()]

        for body in (greedy, sampled):
            assert sse_events(f"{direct}/v1/completions", body) == \
                sse_events(f"{base}/v1/completions", body)

        # /invoke ndjson streaming parity
        body = {"tokens": [1, 2, 3], "max_new_tokens": 6, "stream": True,
                "segment": 3}
        assert sse_events(f"{direct}/invoke", body) == \
            sse_events(f"{base}/invoke", body)

        # affinity keeps a repeated prompt on one replica
        routed_before = {n: r.routed for n, r in pool.replicas.items()}
        for _ in range(4):
            _post(f"{base}/v1/completions", greedy)
        moved = {n: pool.replicas[n].routed - routed_before[n]
                 for n in routed_before}
        assert sorted(moved.values()) == [0, 4]
    finally:
        router.stop()


# -- slow: affinity concentrates the prefix cache ----------------------------


@pytest.mark.slow
def test_affinity_raises_fleet_prefix_hit_rate(tmp_path):
    """Acceptance: shared-prefix traffic achieves a HIGHER fleet
    prefix-cache hit rate with affinity on than off. Fresh prefix groups
    per phase keep the comparison cold-for-cold on the same servers."""
    import numpy as np

    from lambdipy_tpu.runtime.server import BundleServer

    bundle = make_model_bundle(
        tmp_path, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "4", "prefix_cache_mb": "64",
               "prefix_block": "16"})
    servers = [BundleServer(bundle, warmup=False).start_background()
               for _ in range(2)]
    pool = ReplicaPool(probe_interval=0.2)
    for i, s in enumerate(servers):
        pool.attach(f"p{i}", f"http://127.0.0.1:{s.port}")
    pool.probe_all()

    def run_phase(affinity_on, seed):
        phase_rng = np.random.default_rng(seed)
        router = FleetRouter(pool, affinity_on=affinity_on, block=16)
        router.start_background()
        base = f"http://127.0.0.1:{router.port}"
        try:
            before = router.metrics()["fleet"]["prefix_cache"]
            for _ in range(2):  # two distinct shared-prefix groups
                shared = phase_rng.integers(1, 500, 32).tolist()
                for _ in range(5):
                    suffix = phase_rng.integers(1, 500, 4).tolist()
                    out = _post(f"{base}/v1/completions",
                                {"prompt": shared + suffix,
                                 "max_tokens": 4, "temperature": 0},
                                timeout=600)
                    assert out["choices"][0]["tokens"]
            after = router.metrics()["fleet"]["prefix_cache"]
        finally:
            router.stop()
        hits = after["hits"] - before["hits"]
        misses = after["misses"] - before["misses"]
        assert hits + misses == 10
        return hits / 10

    try:
        rate_on = run_phase(True, seed=1)
        rate_off = run_phase(False, seed=2)
        assert rate_on > rate_off, (rate_on, rate_off)
        # with affinity each group pays ONE cold miss; round-robin
        # spreads each group across both replicas' caches
        assert rate_on >= 0.8
    finally:
        pool.close()
        for s in servers:
            threading.Thread(target=s.stop, daemon=True).start()


# -- slow: subprocess fault injection + rolling restart ----------------------


@pytest.mark.slow
def test_fleet_fault_injection_and_rolling_restart(tmp_path):
    """Acceptance: with 2 supervised replicas under concurrent traffic,
    SIGKILL of one replica's worker loses zero requests (retries route
    to the survivor), the dead replica is ejected within one probe
    interval, the supervisor respawns it AT ITS REGISTERED URL
    (port-pinning) and the pool re-admits it — all visible in the fleet
    metrics. Then a rolling restart drains both replicas one at a time
    without ever dropping below the live floor."""
    import os
    import signal

    from lambdipy_tpu.runtime.deploy import LocalRuntime

    bundle = make_model_bundle(
        tmp_path, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "2"})
    env = {
        "LAMBDIPY_PLATFORM": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "LAMBDIPY_STABLE_UPTIME_S": "5",
        "LAMBDIPY_MAX_BACKOFF_S": "1",
    }
    rt = LocalRuntime(tmp_path / "deployments.json")
    pool = ReplicaPool(probe_interval=0.5, fail_threshold=1,
                       readmit_passes=2)
    pool.spawn_fleet(bundle, 2, base_name="fi", runtime=rt, env=env)
    pool.start()
    router = FleetRouter(pool, affinity_on=True, block=32, max_retries=3,
                         backoff_cap_s=0.5,
                         request_timeout=120).start_background()
    base = f"http://127.0.0.1:{router.port}"
    stop_traffic = threading.Event()
    ok_count = [0]
    failures = []

    def traffic():
        i = 0
        while not stop_traffic.is_set():
            i += 1
            try:
                out = _post(f"{base}/invoke",
                            {"tokens": [1 + (i % 7), 2, 3],
                             "max_new_tokens": 2}, timeout=120)
                assert out["ok"]
                ok_count[0] += 1
            except Exception as e:  # noqa: BLE001 — collected for assert
                failures.append(repr(e))
            time.sleep(0.05)

    threads = [threading.Thread(target=traffic) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(2)  # let traffic establish on the healthy fleet

        # SIGKILL the WORKER of fi-r1 (healthz pid — the supervisor in
        # front of it must stay up to respawn)
        victim = pool.replicas["fi-r1"]
        url_before, worker_pid = victim.url, victim.pid
        assert worker_pid and worker_pid != rt.get("fi-r1").pid
        os.kill(worker_pid, signal.SIGKILL)

        deadline = time.monotonic() + 30
        while victim.state != EJECTED and time.monotonic() < deadline:
            time.sleep(0.1)
        assert victim.state == EJECTED, "dead replica was not ejected"

        # supervisor respawn -> probe passes -> re-admission, same URL
        deadline = time.monotonic() + 180
        while victim.state != READY and time.monotonic() < deadline:
            time.sleep(0.5)
        assert victim.state == READY, "replica was never re-admitted"
        assert victim.url == url_before  # port pinned across restart
        assert victim.pid != worker_pid and victim.restarts >= 1
        time.sleep(2)  # traffic over the healed fleet

        assert not failures, f"lost requests: {failures[:3]}"
        assert ok_count[0] > 20
        m = router.metrics()
        assert m["router"]["retries"] >= 1
        assert m["pool"]["fi-r1"]["ejections"] == 1

        # rolling restart under (light) traffic: floor holds, zero lost
        pool.rolling_restart(live_floor=1, ready_timeout=180)
        deadline = time.monotonic() + 30  # a stale probe may flap one
        while time.monotonic() < deadline and \
                not all(r.routable for r in pool.replicas.values()):
            time.sleep(0.5)
        assert all(r.routable for r in pool.replicas.values())
        time.sleep(1)
        assert not failures, f"rolling restart lost: {failures[:3]}"
    finally:
        stop_traffic.set()
        for t in threads:
            t.join(timeout=30)
        router.stop()
        pool.stop_all()
    assert rt.list() == []
