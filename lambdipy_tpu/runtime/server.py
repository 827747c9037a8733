"""HTTP serve loop for a booted bundle.

stdlib ThreadingHTTPServer (SURVEY.md §9.5: enough for v1; invokes are
device-bound so Python threading overhead is noise next to device dispatch).
Endpoints:

- ``GET  /healthz``  liveness + boot/cold-start report (watchdog surface)
- ``GET  /metrics``  latency percentiles + error counts (JSON)
- ``POST /invoke``   JSON request -> handler -> JSON response

Every invoke passes the SLO scheduler (lambdipy_tpu/sched): admission
control (per-tenant token buckets, a bounded queue, deadline-based
shedding on ``x-deadline-ms``) then a policy-ordered wait for one of
``max_concurrency`` run slots. Overload turns into explicit 429/503
responses carrying ``Retry-After`` instead of unbounded latency; request
class rides the ``x-priority`` header (interactive | batch | background),
tenant identity the ``x-api-key`` / ``x-tenant`` header.

Failure behavior (SURVEY.md §6 failure-detection row): handler exceptions
return 500 with the error type and are counted; the process stays up.
``POST /shutdown`` drains and stops (used by the deploy controller).
"""

from __future__ import annotations

import json
import math
import os
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from urllib.parse import parse_qs, urlsplit

from lambdipy_tpu.runtime import spans
from lambdipy_tpu.runtime.continuous import RequestCancelled
from lambdipy_tpu.runtime.loader import BootReport, load_bundle
from lambdipy_tpu.runtime.pagepool import PagesExhausted
from lambdipy_tpu.runtime.prefixstore import SessionPinsExceeded
from lambdipy_tpu.runtime.metrics import LatencyStats
from lambdipy_tpu.sched import (
    SchedConfig,
    Scheduler,
    Shed,
    clear_request_context,
    set_request_context,
)
from lambdipy_tpu.utils.logs import get_logger, log_event
from lambdipy_tpu.utils.platform import device_report

log = get_logger("lambdipy.server")

# the longest wall window POST /profile traces: the profiler needed over two
# minutes to write out 4 s of a serving 7B (PERF.md section 6)
PROFILE_MAX_S = 4.0


def _request_token_counts(request: dict | None,
                          prefix_probe=None) -> tuple[int, int]:
    """Best-effort (prefill, decode) token counts for the cost estimator:
    wrong-shaped fields count as zero — sizing is advisory, validation
    belongs to the handler.

    ``prefix_probe`` is the handler's automatic-prefix-cache probe
    (prompt ids -> tokens the radix store would reuse): admission prices
    the SUFFIX a cache-hit request will actually prefill, not the full
    prompt — otherwise deadline shedding keeps rejecting exactly the
    requests the cache makes cheap."""
    if not isinstance(request, dict):
        return 0, 0
    prefill = 0
    toks = request.get("tokens")
    flat_row = None
    if isinstance(toks, (list, tuple)):
        if toks and isinstance(toks[0], (list, tuple)):
            prefill = sum(len(r) for r in toks
                          if isinstance(r, (list, tuple)))
        else:
            prefill = len(toks)
            flat_row = toks
    prefix = request.get("prefix")
    if isinstance(prefix, (list, tuple)):
        prefill += len(prefix)
    elif prefix_probe is not None and flat_row is not None and prefill:
        try:
            prefill = max(0, prefill - int(prefix_probe(flat_row)))
        except Exception:  # noqa: BLE001 — pricing is advisory
            pass
    decode = 0
    for key in ("max_new_tokens", "max_tokens"):
        raw = request.get(key)
        if isinstance(raw, (int, float)):
            decode = max(0, int(raw))
            break
    return prefill, decode


def _openai_to_internal(req: dict) -> tuple[dict, str | None]:
    """Translate an OpenAI /v1/completions body into the generate
    handler's request shape. ``prompt`` may be a string (bundle tokenizer
    required) or an int token array (tokenizer-free). OpenAI sampling
    defaults apply: temperature/top_p default to 1.0 (sampled) — send
    temperature 0 for greedy."""
    prompt = req.get("prompt")
    internal: dict = {}
    if isinstance(prompt, str):
        internal["text"] = prompt
    elif isinstance(prompt, list) and prompt and \
            all(isinstance(t, int) for t in prompt):
        internal["tokens"] = prompt
    else:
        return {}, "prompt must be a string or an array of token ids"
    if req.get("stop") is not None:
        return {}, "stop sequences are not supported; pass eos_id"
    if req.get("n", 1) != 1:
        return {}, "n > 1 is not supported"
    try:
        if req.get("max_tokens") is not None:
            internal["max_new_tokens"] = int(req["max_tokens"])
        internal["temperature"] = float(req.get("temperature", 1.0))
        internal["top_p"] = float(req.get("top_p", 1.0))
    except (TypeError, ValueError) as e:
        return {}, f"max_tokens/temperature/top_p must be numbers: {e}"
    for knob in ("top_k", "seed", "eos_id", "prefix", "segment",
                 "speculative", "session_id", "session_ttl_s"):
        if req.get(knob) is not None:
            internal[knob] = req[knob]
    lp = req.get("logprobs")
    if lp:
        try:
            if lp is not True and int(lp) > 1:
                return {}, ("top_logprobs > 1 is not supported "
                            "(send logprobs: 1)")
        except (TypeError, ValueError):
            return {}, "logprobs must be a boolean or small integer"
        internal["logprobs"] = True
    internal["stream"] = bool(req.get("stream"))
    return internal, None


def _internal_to_openai(internal: dict, result: dict) -> dict:
    row = list((result.get("tokens") or [[]])[0])
    # the handler reports the EFFECTIVE eos (a string prompt inherits the
    # tokenizer's) and the real prompt token count; fall back to what the
    # request carried
    eos = result.get("eos_id", internal.get("eos_id"))
    finish = "length"
    if eos is not None and eos in row:
        # eos latching pads the row to the full decode width — trim so
        # tokens and usage reflect what was actually generated
        row = row[: row.index(eos) + 1]
        finish = "stop"
    n_prompt = int(result.get("n_prompt",
                              len(internal.get("tokens") or [])))
    choice = {"index": 0, "text": result.get("completion", ""),
              "tokens": row, "finish_reason": finish,
              "logprobs": None}
    if result.get("logprobs"):
        lp_row = result["logprobs"][0][: len(row)]
        choice["logprobs"] = {"tokens": [str(t) for t in row],
                              "token_logprobs": lp_row,
                              "top_logprobs": None, "text_offset": None}
    return {
        "object": "text_completion",
        "model": "lambdipy-bundle",
        "choices": [choice],
        "usage": {"prompt_tokens": n_prompt,
                  "completion_tokens": len(row),
                  "total_tokens": n_prompt + len(row)},
    }


class BundleServer:
    def __init__(self, bundle_dir: Path, host: str = "127.0.0.1", port: int = 0,
                 *, warmup: bool = True, sched: dict | None = None):
        self.bundle_dir = Path(bundle_dir)
        self.stats = LatencyStats()
        self._profile_lock = threading.Lock()
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self.draining = False
        self.started = time.time()
        # The generate handler builds its batchers INSIDE load_bundle, so
        # the effective policy must be resolved first and bridged through
        # the env var the handler reads — otherwise a programmatic
        # sched={"policy": ...} would report one policy on /metrics while
        # batch formation ordered by another. (Pre-read the manifest
        # best-effort; the authoritative extra comes from the boot below.)
        pre_extra: dict = {}
        try:
            pre_extra = (json.loads(
                (self.bundle_dir / "manifest.json").read_text())
                .get("payload") or {}).get("extra") or {}
        except (OSError, ValueError):
            pass
        pre_policy = SchedConfig.from_extra(pre_extra, **(sched or {})).policy
        prev_env = os.environ.get("LAMBDIPY_SCHED_POLICY")
        os.environ["LAMBDIPY_SCHED_POLICY"] = pre_policy
        try:
            self.boot: BootReport = load_bundle(self.bundle_dir,
                                                warmup=warmup)
        finally:
            if prev_env is None:
                os.environ.pop("LAMBDIPY_SCHED_POLICY", None)
            else:
                os.environ["LAMBDIPY_SCHED_POLICY"] = prev_env
        # SLO scheduler config layers: bundle [payload.extra] sched_* keys,
        # overridden by explicit ctor/CLI values
        extra = (self.boot.manifest.get("payload") or {}).get("extra") or {}
        cfg = SchedConfig.from_extra(extra, **(sched or {}))
        # a batching bundle sized past the default run-slot count must not
        # be silently throttled to 8 concurrent invokes: unless the
        # operator pinned sched_max_concurrency, floor the slots at the
        # batcher's own width so every batch slot can actually fill
        explicit = (extra.get("sched_max_concurrency") is not None
                    or (sched or {}).get("max_concurrency") is not None)
        batching = (str(extra.get("batch_mode", "")).lower() == "continuous"
                    or float(extra.get("batch_window_ms", 0) or 0) > 0)
        if not explicit and batching:
            cfg.max_concurrency = max(cfg.max_concurrency,
                                      int(extra.get("batch_max", 8)))
        self.sched = Scheduler(cfg)
        # slot handover: a continuous engine tells the scheduler when a
        # row's last segment is next, and the scheduler grants one queued
        # ticket ahead of that run slot's release (Scheduler.grant_ahead),
        # so the next request is at the engine when the batch slot frees.
        # This, not a higher floor above, is what keeps batch slots full
        # under a queue: a standing lookahead would hold long prompts'
        # prefilled carries on the device through every burst
        hook = getattr(self.boot.state, "row_ending_hook", None)
        if hook is not None:
            hook(self.sched.grant_ahead)
        self._httpd = ThreadingHTTPServer((host, port), self._make_handler())
        self.port = self._httpd.server_address[1]
        self._thread: threading.Thread | None = None

    # -- request handling ---------------------------------------------------

    def _make_handler(server_self):
        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # the request being served on this connection: its span id and
            # the moment its body had been read (runtime/spans.py)
            _rid = None
            _t_read = None

            def log_message(self, fmt, *args):  # route through structured logs
                log.debug(fmt % args)

            def _send(self, code: int, payload: dict,
                      headers: dict | None = None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for name, value in (headers or {}).items():
                    self.send_header(name, value)
                self.end_headers()
                self.wfile.write(body)

            def _require_loopback(self) -> bool:
                """Host-only endpoints (/v1/debug/*): refuse
                non-loopback clients with a 403 BEFORE touching the
                request body (the connection closes, so keep-alive
                cannot misparse unread bytes). These surfaces expose a
                fault-injection control plane and cache internals —
                operator/debugger tools on the host, never a path a
                fronting proxy should forward. /v1/kv/probe stays OPEN
                like /v1/kv/export|import: it is part of the fleet KV
                wire surface — the router's import-miss pull calls it
                cross-host, and its error path deliberately reads a
                refusal as blocks-present (plain dedup semantics), so
                gating it would silently disable the pull."""
                if self.client_address[0] in ("127.0.0.1", "::1"):
                    return True
                self.close_connection = True
                self._send(403, {"ok": False, "error":
                                 "host-only endpoint (loopback clients "
                                 "only)"})
                return False

            def do_GET(self):
                if self.path == "/v1/debug/invariants":
                    self._debug_invariants()
                    return
                if self.path == "/healthz":
                    # liveness vs readiness split: "ok" is liveness (the
                    # process answers — always 200 so watchdog tooling
                    # keeps working), "ready" says ROUTE TO ME. A
                    # replica reports ready: false while the background
                    # warmup/group-prefill is still compiling or once
                    # drain has begun, so the fleet router deprioritizes
                    # it BEFORE the 503s start. warming_fn is the
                    # handler's O(1) flag — NOT the full stats()
                    # document, which takes the serving path's locks
                    # and would be recomputed every probe interval.
                    warming_fn = getattr(server_self.boot.state,
                                         "warming_fn", None)
                    try:
                        warming = bool(warming_fn()) if warming_fn else False
                    except Exception:  # noqa: BLE001 — health never 500s
                        warming = False
                    # wedged = the engine watchdog gave up on a device
                    # wait: liveness stays 200 (the process answers) but
                    # ready flips false and the explicit wedged flag
                    # lets the fleet prober EJECT (not merely
                    # deprioritize) the replica at probe speed
                    engine = server_self._engine_fault_state()
                    wedged = bool(engine.get("wedged"))
                    self._send(200, {
                        "ok": True,
                        "ready": (not server_self.draining and not warming
                                  and not wedged),
                        "warming": warming,
                        "wedged": wedged,
                        **({"engine": engine} if engine else {}),
                        "pid": os.getpid(),
                        "draining": server_self.draining,
                        "bundle": str(server_self.bundle_dir),
                        "uptime_s": round(time.time() - server_self.started, 1),
                        "cold_start": server_self.boot.stages,
                        # what THIS process runs on, from jax.devices()
                        # (None for non-jax bundles): the one place a
                        # parent that must stay off the chip can check
                        # that its server is on it
                        "device": server_self.boot.device,
                        "skew": server_self.boot.skew,
                        "handler_meta": getattr(server_self.boot.state, "meta", {}),
                        # build-time warm outcome from the manifest: a
                        # failed warm explains a slow cold_start downstream
                        "warm": server_self.boot.manifest.get("warm"),
                        # non-empty = numerics sanitizer on (per-call sync)
                        "debug_flags": server_self.boot.debug_flags,
                        "sched": {"policy": server_self.sched.policy.name,
                                  "queued": server_self.sched.queue.depth()},
                    })
                elif self.path == "/metrics":
                    report = server_self.stats.report()
                    # admission/scheduling surface: queue depths, shed
                    # counts by reason/class, per-class queue-wait
                    # percentiles, cost-model state
                    report["sched"] = server_self.sched.report()
                    # per-name span aggregates (count, sum_s, buckets):
                    # they only grow, so two scrapes give a window
                    report["spans"] = spans.report()
                    handler_stats = getattr(server_self.boot.state, "stats",
                                            lambda: {})()
                    if handler_stats:
                        report["handler"] = handler_stats
                    if server_self.boot.device is not None:
                        # live allocator statistics per device, and the
                        # process's compile requests / persistent-cache
                        # hits since boot
                        report["device"] = device_report()
                        report["compile"] = \
                            server_self.boot.compile_counters.report()
                    self._send(200, report)
                elif urlsplit(self.path).path == "/spans":
                    # the last finished requests, each with its tiles
                    # (?last=N: only the newest N of the ring)
                    last = parse_qs(urlsplit(self.path).query).get("last")
                    try:
                        self._send(200, spans.requests(
                            int(last[0]) if last else None))
                    except ValueError:
                        self._send(400, {"ok": False, "error":
                                         "last must be an integer"})
                else:
                    self._send(404, {"ok": False, "error": "not found"})

            def _read_json(self) -> dict | None:
                """Parse the request body; sends a 400 and returns None on
                client errors."""
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(length) or b"{}")
                    self._t_read = time.monotonic()
                    if not isinstance(body, dict):
                        raise ValueError("body must be a JSON object")
                    return body
                except (ValueError, json.JSONDecodeError) as e:
                    self._send(400, {"ok": False, "error": f"bad request: {e}"})
                    return None

            def _send_shed(self, shed: Shed, *, openai: bool = False):
                """An explicit overload rejection: 429/503 + Retry-After
                (integer seconds per RFC 9110; the body carries the exact
                float for clients that want tighter backoff)."""
                headers = {"Retry-After":
                           str(max(1, math.ceil(shed.retry_after_s)))}
                if openai:
                    payload = {"error": {
                        "message": f"shed: {shed.reason}",
                        "type": ("rate_limit_error" if shed.code == 429
                                 else "overloaded_error"),
                        "retry_after_s": round(shed.retry_after_s, 3)}}
                else:
                    payload = shed.payload()
                self._send(shed.code, payload, headers)

            def _begin_invoke(self, request: dict | None = None, *,
                              openai: bool = False):
                """Open the request's span record (its ``rid`` rides the
                ticket and the request context down to the engine), then
                the admission gate; a refused request's record closes
                here, with ``req`` alone."""
                t_read, self._t_read = self._t_read, None  # of THIS request
                self._rid = spans.begin_request(t_read)
                ticket = self._admit_invoke(request, openai=openai)
                if ticket is None:
                    spans.end_request(self._rid)
                    self._rid = None
                return ticket

            def _admit_invoke(self, request: dict | None = None, *,
                              openai: bool = False):
                """Admission gate every invoke passes: draining check +
                in-flight increment as one atomic step (stop() can then
                never observe inflight==0 while an accepted invoke is
                still on its way to dispatch), then scheduler admission
                (rate / queue-depth / deadline shedding) and a
                policy-ordered wait for a run slot. Returns a live
                ticket, or None after sending the 429/503 (with
                Retry-After) itself."""
                cls = (self.headers.get("x-priority")
                       or "interactive").strip().lower()
                tenant = (self.headers.get("x-api-key")
                          or self.headers.get("x-tenant") or "anon")
                try:
                    deadline_ms = float(self.headers["x-deadline-ms"])
                except (KeyError, TypeError, ValueError):
                    deadline_ms = None
                with server_self._inflight_lock:
                    draining = server_self.draining
                    if not draining:
                        server_self._inflight += 1
                if draining:
                    server_self.sched.admission.count_shed("draining", cls)
                    self._send_shed(Shed(503, "draining", 1.0),
                                    openai=openai)
                    return None
                # wedged-engine accept hole: while the engine is wedged
                # AND a restart is in flight (replays queued behind a
                # dead device), admitting more work would queue requests
                # into an engine that cannot serve them — shed instead.
                # A wedged engine with NO restart running still admits:
                # that request IS the recovery probe (it restarts the
                # engine; success clears the wedge, another trip re-503s
                # followers).
                engine = server_self._engine_fault_state()
                if engine.get("wedged") and engine.get("restarting"):
                    with server_self._inflight_lock:
                        server_self._inflight -= 1
                    server_self.sched.admission.count_shed("wedged", cls)
                    self._send_shed(Shed(503, "wedged", 2.0),
                                    openai=openai)
                    return None
                prefill, decode = _request_token_counts(
                    request,
                    prefix_probe=getattr(server_self.boot.state,
                                         "prefix_probe", None))
                spans.request_args(self._rid, prompt_tokens=prefill,
                                   max_tokens=decode)
                spans.mark(self._rid, None)  # req.sched starts here
                out = server_self.sched.admit(
                    tenant=tenant, cls=cls, deadline_ms=deadline_ms,
                    prefill_tokens=prefill, decode_tokens=decode,
                    rid=self._rid)
                if isinstance(out, Shed):
                    with server_self._inflight_lock:
                        server_self._inflight -= 1
                    self._send_shed(out, openai=openai)
                    return None
                if not server_self.sched.wait_turn(out):
                    # deadline became unmeetable while queued: shed at
                    # grant time instead of burning the slot
                    with server_self._inflight_lock:
                        server_self._inflight -= 1
                    self._send_shed(
                        Shed(503, "deadline",
                             max(0.05, out.cost_ms / 1e3)), openai=openai)
                    return None
                spans.mark(self._rid, "req.sched")
                # the batchers read the request's class from this context
                # when forming batches (policy-ordered handoff)
                set_request_context(cls=out.cls, tenant=tenant,
                                    deadline_ms=deadline_ms, rid=self._rid)
                return out

            def _end_invoke(self, ticket, t0: float) -> None:
                clear_request_context()
                # everything is written: an unstreamed response was the
                # request's first frame and its last
                spans.first_frame(self._rid)
                spans.end_request(self._rid)
                self._rid = None
                # feed the estimator with slot-occupancy time (errors
                # included — an erroring request still held the slot)
                server_self.sched.finish(
                    ticket, service_ms=(time.monotonic() - t0) * 1e3)
                with server_self._inflight_lock:
                    server_self._inflight -= 1

            def _session_header(self, request: dict | None) -> None:
                """`x-session-id` (+ optional `x-session-ttl-s`) are the
                header spelling of the body's session fields — the body
                wins when both are present (explicit beats transport)."""
                if not isinstance(request, dict):
                    return
                sid = self.headers.get("x-session-id")
                if sid and not request.get("session_id"):
                    request["session_id"] = sid
                ttl = self.headers.get("x-session-ttl-s")
                if ttl and request.get("session_ttl_s") is None:
                    request["session_ttl_s"] = ttl

            def do_DELETE(self):
                """DELETE /v1/sessions/{id}: release the session's
                prefix-store pins NOW (lease expiry would get there
                eventually; a well-behaved client closes explicitly)."""
                if not self.path.startswith("/v1/sessions/"):
                    self._send(404, {"ok": False, "error": "not found"})
                    return
                sid = self.path[len("/v1/sessions/"):]
                fn = getattr(server_self.boot.state, "session_end_fn",
                             None)
                if fn is None or not sid:
                    self._send(404, {"ok": False, "error":
                                     "no session surface (prefix cache "
                                     "off or unsupported handler)"})
                    return
                try:
                    out = fn(sid)
                except Exception as e:  # noqa: BLE001
                    server_self.stats.record_error()
                    self._send(500, {"ok": False, "error": str(e)})
                    return
                self._send(200, {"ok": True, "session": sid, **out})

            def do_POST(self):
                if self.path == "/v1/completions":
                    self._openai_completions()
                    return
                if self.path == "/v1/kv/export":
                    self._kv_export()
                    return
                if self.path == "/v1/kv/import":
                    self._kv_import()
                    return
                if self.path == "/v1/kv/probe":
                    self._kv_probe()
                    return
                if self.path == "/v1/debug/faults":
                    self._debug_faults()
                    return
                if self.path == "/v1/debug/knobs":
                    self._debug_knobs()
                    return
                if self.path == "/profile":
                    req = self._read_json()
                    if req is None:
                        return
                    try:
                        seconds = float(req.get("seconds"))
                        if not 0 < seconds <= PROFILE_MAX_S:
                            raise ValueError
                    except (TypeError, ValueError):
                        self._send(400, {"ok": False, "error":
                                         f"seconds must be a number in "
                                         f"(0, {PROFILE_MAX_S:g}]"})
                        return
                    # trace that wall window of whatever traffic is live;
                    # serialized — concurrent start_trace calls would fail
                    try:
                        from lambdipy_tpu.utils.trace import (
                            latest_trace_files,
                            profile_trace,
                        )

                        out_dir = server_self.bundle_dir / "profiles" / str(int(time.time()))
                        with server_self._profile_lock:
                            with profile_trace(out_dir) as capture:
                                time.sleep(seconds)
                        payload = {"ok": capture.started, "dir": str(out_dir),
                                   "files": latest_trace_files(out_dir)}
                        if capture.error:
                            payload["error"] = capture.error
                        self._send(200 if capture.started else 503, payload)
                    except Exception as e:
                        self._send(500, {"ok": False, "error": str(e)})
                    return
                if self.path == "/shutdown":
                    self._send(200, {"ok": True, "draining": True})
                    threading.Thread(target=server_self.stop, daemon=True).start()
                    return
                if self.path != "/invoke":
                    self._send(404, {"ok": False, "error": "not found"})
                    return
                # body must be consumed before any early reply: on a
                # keep-alive connection unread body bytes would be parsed
                # as the next request line
                request = self._read_json()
                if request is None:
                    server_self.stats.record_error()
                    return
                self._session_header(request)
                ticket = self._begin_invoke(request)
                if ticket is None:
                    return
                t0 = time.monotonic()
                # in-flight covers the response write too: drain must not
                # observe 0 (and let the process exit) between handler
                # completion and the 200 actually reaching the client
                try:
                    state = server_self.boot.state
                    if request.get("stream") and \
                            getattr(state, "invoke_stream_fn", None) is not None:
                        # the HandlerState method owns the call convention
                        # (request copy, support check)
                        self._send_stream(state.invoke_stream, request, t0)
                        return
                    try:
                        result = server_self.boot.handler.invoke(
                            server_self.boot.state, request)
                    except RequestCancelled as e:
                        # not a handler bug: the engine cancelled the row
                        # at a drain barrier (deadline expired / waiter
                        # gone). Answer shed-style — 503 + Retry-After —
                        # so clients back off and retry instead of
                        # treating it as a server fault.
                        cls = (self.headers.get("x-priority")
                               or "interactive").strip().lower()
                        server_self.sched.admission.count_shed(
                            "cancelled", cls)
                        self._send_shed(Shed(503, str(e), 1.0))
                        return
                    except PagesExhausted as e:
                        # the paged KV arena is transiently full —
                        # backpressure priced by the pool's own release
                        # cadence, exactly like a queue-depth shed
                        cls = (self.headers.get("x-priority")
                               or "interactive").strip().lower()
                        server_self.sched.admission.count_shed(
                            "kv_pages", cls)
                        self._send_shed(
                            Shed(503, "kv_pages", e.retry_after_s))
                        return
                    except SessionPinsExceeded as e:
                        # the session-pin budget is full: shed the NEW
                        # session, priced by the earliest lease-expiry
                        # horizon — pins never starve live traffic
                        cls = (self.headers.get("x-priority")
                               or "interactive").strip().lower()
                        server_self.sched.admission.count_shed(
                            "session_pins", cls)
                        self._send_shed(
                            Shed(503, "session_pins", e.retry_after_s))
                        return
                    except Exception as e:  # handler bug or bad payload shape
                        server_self.stats.record_error()
                        log_event(log, "invoke failed", error=str(e),
                                  kind=type(e).__name__)
                        self._send(500, {"ok": False, "error": str(e),
                                         "kind": type(e).__name__})
                        return
                    server_self.stats.record((time.monotonic() - t0) * 1e3)
                    self._send(200, result)
                finally:
                    self._end_invoke(ticket, t0)

            def _openai_completions(self):
                """OpenAI-compatible shim over the generate handler:
                "prompt" may be a string (needs the bundle tokenizer) or
                a token array (works without one). Shares the /invoke
                drain bracket — graceful shutdown waits for these too."""
                req = self._read_json()
                if req is None:
                    server_self.stats.record_error()
                    return
                internal, err = _openai_to_internal(req)
                if err is not None:
                    self._send(400, {"error": {"message": err,
                                               "type": "invalid_request_error"}})
                    return
                self._session_header(internal)
                # admit on the TRANSLATED request: the internal shape
                # carries "tokens"/"max_new_tokens", so the estimator
                # sees real prefill/decode counts (the raw OpenAI body
                # keys them "prompt"/"max_tokens")
                ticket = self._begin_invoke(internal, openai=True)
                if ticket is None:
                    return
                t_start = time.monotonic()
                try:
                    if internal.pop("stream", False):
                        state = server_self.boot.state
                        if getattr(state, "invoke_stream_fn", None) is None:
                            self._send(400, {"error": {
                                "message": "handler does not support streaming",
                                "type": "invalid_request_error"}})
                            return
                        self._send_sse(state.invoke_stream, internal)
                        return
                    t0 = time.monotonic()
                    try:
                        result = server_self.boot.handler.invoke(
                            server_self.boot.state, internal)
                    except RequestCancelled as e:
                        # drain-barrier cancellation, not a server fault:
                        # shed-style 503 so OpenAI clients retry/back off
                        cls = (self.headers.get("x-priority")
                               or "interactive").strip().lower()
                        server_self.sched.admission.count_shed(
                            "cancelled", cls)
                        self._send_shed(Shed(503, str(e), 1.0), openai=True)
                        return
                    except PagesExhausted as e:
                        # transiently full KV page arena: priced
                        # backpressure, not a server fault
                        cls = (self.headers.get("x-priority")
                               or "interactive").strip().lower()
                        server_self.sched.admission.count_shed(
                            "kv_pages", cls)
                        self._send_shed(
                            Shed(503, "kv_pages", e.retry_after_s),
                            openai=True)
                        return
                    except SessionPinsExceeded as e:
                        # session-pin budget full: priced shed of the
                        # NEW session, Retry-After = lease horizon
                        cls = (self.headers.get("x-priority")
                               or "interactive").strip().lower()
                        server_self.sched.admission.count_shed(
                            "session_pins", cls)
                        self._send_shed(
                            Shed(503, "session_pins", e.retry_after_s),
                            openai=True)
                        return
                    except Exception as e:
                        server_self.stats.record_error()
                        self._send(500, {"error": {"message": str(e),
                                                   "type": type(e).__name__}})
                        return
                    if not result.get("ok"):
                        server_self.stats.record_error()
                        self._send(400, {"error": {
                            "message": result.get("error", "invoke failed"),
                            "type": "invalid_request_error"}})
                        return
                    server_self.stats.record((time.monotonic() - t0) * 1e3)
                    out = _internal_to_openai(internal, result)
                    # echo the ACTUAL sched queue wait (stamped on the
                    # ticket at grant) so a client can window latency
                    # attribution per-request instead of reading the
                    # replica's cumulative percentile reservoir
                    wait_ms = getattr(ticket, "wait_ms", None)
                    if wait_ms is not None:
                        out["queue_wait_ms"] = round(wait_ms, 3)
                    self._send(200, out)
                finally:
                    self._end_invoke(ticket, t_start)

            def _kv_export(self):
                """Disaggregated-serving export: the request's whole-
                block prompt head leaves as a binary KV frame
                (runtime/kvwire.py). Missing blocks prefill here — on a
                prefill-class replica this call IS the request's
                prefill phase, so it passes the same admission gate as
                an invoke (the estimator prices the suffix via the
                prefix probe, exactly like a generate)."""
                fn = getattr(server_self.boot.state, "kv_export_fn", None)
                request = self._read_json()
                if request is None:
                    server_self.stats.record_error()
                    return
                stream_fn = getattr(server_self.boot.state,
                                    "kv_export_stream_fn", None)
                if request.get("stream") and stream_fn is not None:
                    self._kv_export_stream(stream_fn, request)
                    return
                if fn is None:
                    self._send(404, {"ok": False, "error":
                                     "no KV export surface (prefix "
                                     "cache off or unsupported handler)"})
                    return
                ticket = self._begin_invoke(request)
                if ticket is None:
                    return
                t0 = time.monotonic()
                try:
                    try:
                        out = fn(request)
                    except RequestCancelled as e:
                        cls = (self.headers.get("x-priority")
                               or "interactive").strip().lower()
                        server_self.sched.admission.count_shed(
                            "cancelled", cls)
                        self._send_shed(Shed(503, str(e), 1.0))
                        return
                    except PagesExhausted as e:
                        cls = (self.headers.get("x-priority")
                               or "interactive").strip().lower()
                        server_self.sched.admission.count_shed(
                            "kv_pages", cls)
                        self._send_shed(
                            Shed(503, "kv_pages", e.retry_after_s))
                        return
                    except Exception as e:  # noqa: BLE001
                        server_self.stats.record_error()
                        log_event(log, "kv export failed", error=str(e),
                                  kind=type(e).__name__)
                        self._send(500, {"ok": False, "error": str(e),
                                         "kind": type(e).__name__})
                        return
                    if isinstance(out, dict):  # handler-level refusal
                        self._send(400, out)
                        return
                    server_self.stats.record(
                        (time.monotonic() - t0) * 1e3)
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/octet-stream")
                    self.send_header("Content-Length", str(len(out)))
                    self.end_headers()
                    try:
                        self.wfile.write(out)
                    except OSError:
                        self.close_connection = True
                finally:
                    self._end_invoke(ticket, t0)

            def _kv_export_stream(self, stream_fn, request: dict):
                """Chunked (pipelined-ship) export: one HTTP chunk per
                wire frame, flushed as soon as the prefix-store walk
                produces its block group — the router's relay reads
                frame k while this replica prefills chunk k+1. Same
                admission bracket as the monolithic export (the export
                IS the request's prefill). A mid-walk failure after
                headers are committed TRUNCATES the stream (no terminal
                chunk): the receiver's block accounting makes
                truncation self-evident, so there is no honest 500 left
                to send and no dishonest clean EOF sent instead."""
                ticket = self._begin_invoke(request)
                if ticket is None:
                    return
                t0 = time.monotonic()
                committed = False
                try:
                    gen = stream_fn(request)
                    if isinstance(gen, dict):  # handler-level refusal
                        self._send(400, gen)
                        return
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/x-lkv-stream")
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()
                    committed = True
                    for frame in gen:
                        if not self._write_frame(frame):
                            return  # client gone; generator closed
                    server_self.stats.record(
                        (time.monotonic() - t0) * 1e3)
                    self._end_frames()
                except Exception as e:  # noqa: BLE001
                    server_self.stats.record_error()
                    log_event(log, "kv export stream failed",
                              error=str(e), kind=type(e).__name__)
                    if not committed:
                        self._send(500, {"ok": False, "error": str(e),
                                         "kind": type(e).__name__})
                    else:
                        self.close_connection = True
                finally:
                    self._end_invoke(ticket, t0)

            def _read_chunked_body(self):
                """Generator over a chunked-transfer request body's
                chunks (stdlib BaseHTTPRequestHandler does not de-chunk
                requests). A malformed framing line raises ValueError;
                a connection dying mid-chunk raises ConnectionError —
                both roll the streaming import back."""
                from lambdipy_tpu.runtime.kvwire import _MAX_CHUNK_BODY

                while True:
                    line = self.rfile.readline(66)
                    if not line:
                        raise ConnectionError(
                            "connection closed mid-chunk-stream")
                    size = int(line.strip().split(b";")[0], 16)
                    if size > _MAX_CHUNK_BODY + 4096:
                        # the wire format already bounds what a chunk
                        # may carry (kvwire validates nbody); bound the
                        # HTTP-chunk allocation the same way, or a
                        # hostile hex length buffers arbitrary bytes
                        # BEFORE the validator ever sees one
                        raise ValueError(
                            f"chunk size {size} exceeds the KV stream "
                            f"bound")
                    if size == 0:
                        self.rfile.readline()  # trailing CRLF
                        return
                    data = self.rfile.read(size)
                    if len(data) < size:
                        raise ConnectionError(
                            "connection closed mid-chunk")
                    self.rfile.read(2)  # chunk CRLF
                    yield data

            def _kv_import_stream(self, stream_fn):
                """Chunked (pipelined-ship) import: each arriving frame
                stages immediately (device page writes overlap the rest
                of the transfer); the radix tree is only touched when
                the complete stream commits. Any failure — truncated
                body, garbage chunk, full arena — rolls the staged
                pages back and the tree reads as if the stream never
                happened.

                Admission brackets ONLY the commit (via the gate the
                handler honors): the body arrives over the exporting
                replica's prefill, and a run slot held across that wait
                would serialize this replica's decode batch behind
                every in-flight ship. Staging is backpressured by the
                page arena itself (strict up-front reservation), not by
                the scheduler."""
                t0 = time.monotonic()

                class _CommitShed(Exception):
                    pass

                handler = self

                class _Gate:
                    def __enter__(gate):
                        gate.ticket = handler._begin_invoke(None)
                        if gate.ticket is None:
                            # _begin_invoke already sent the priced 503
                            raise _CommitShed()
                        gate.t0 = time.monotonic()
                        return gate

                    def __exit__(gate, *exc):
                        handler._end_invoke(gate.ticket, gate.t0)
                        return False

                try:
                    out = stream_fn(self._read_chunked_body(),
                                    commit_gate=_Gate())
                except _CommitShed:
                    self.close_connection = True  # shed already sent
                    return
                except PagesExhausted as e:
                    cls = (self.headers.get("x-priority")
                           or "interactive").strip().lower()
                    server_self.sched.admission.count_shed(
                        "kv_import", cls)
                    self.close_connection = True
                    self._send_shed(
                        Shed(503, "kv_import", e.retry_after_s))
                    return
                except ValueError as e:
                    self.close_connection = True
                    self._send(400, {"ok": False,
                                     "error": f"bad KV stream: {e}"})
                    return
                except ConnectionError as e:
                    # the relay died mid-stream: staged pages are
                    # already rolled back; there is nobody left to
                    # answer
                    log_event(log, "kv import stream died",
                              error=str(e))
                    self.close_connection = True
                    return
                except Exception as e:  # noqa: BLE001
                    server_self.stats.record_error()
                    log_event(log, "kv import stream failed",
                              error=str(e), kind=type(e).__name__)
                    self.close_connection = True
                    self._send(500, {"ok": False, "error": str(e),
                                     "kind": type(e).__name__})
                    return
                server_self.stats.record((time.monotonic() - t0) * 1e3)
                self._send(200, out)

            def _kv_import(self):
                """Disaggregated-serving import: a shipped KV frame
                becomes a radix insert. A full page arena answers the
                priced-shed 503 (reason ``kv_import``) so the router
                falls back to mixed-mode local prefill; a malformed
                frame is a 400 and touches nothing. A CHUNKED request
                body routes to the streaming twin."""
                te = (self.headers.get("Transfer-Encoding")
                      or "").lower()
                stream_fn = getattr(server_self.boot.state,
                                    "kv_import_stream_fn", None)
                if "chunked" in te:
                    if stream_fn is None:
                        self.close_connection = True  # unread body
                        self._send(404, {"ok": False, "error":
                                         "no chunked KV import surface "
                                         "(prefix cache off or "
                                         "unsupported handler)"})
                        return
                    self._kv_import_stream(stream_fn)
                    return
                fn = getattr(server_self.boot.state, "kv_import_fn", None)
                # consume the body before any early reply: on keep-alive
                # the unread frame bytes would parse as the next request
                try:
                    length = int(self.headers.get("Content-Length", 0))
                except (TypeError, ValueError):
                    length = 0
                data = self.rfile.read(length) if length > 0 else b""
                if fn is None:
                    self._send(404, {"ok": False, "error":
                                     "no KV import surface (prefix "
                                     "cache off or unsupported handler)"})
                    return
                ticket = self._begin_invoke(None)
                if ticket is None:
                    return
                t0 = time.monotonic()
                try:
                    try:
                        out = fn(data)
                    except PagesExhausted as e:
                        # decode-side import backpressure: same priced-
                        # shed wire shape as every other 503, distinct
                        # reason so operators can tell a full arena
                        # from a full queue
                        cls = (self.headers.get("x-priority")
                               or "interactive").strip().lower()
                        server_self.sched.admission.count_shed(
                            "kv_import", cls)
                        self._send_shed(
                            Shed(503, "kv_import", e.retry_after_s))
                        return
                    except ValueError as e:
                        self._send(400, {"ok": False,
                                         "error": f"bad KV frame: {e}"})
                        return
                    except Exception as e:  # noqa: BLE001
                        server_self.stats.record_error()
                        log_event(log, "kv import failed", error=str(e),
                                  kind=type(e).__name__)
                        self._send(500, {"ok": False, "error": str(e),
                                         "kind": type(e).__name__})
                        return
                    server_self.stats.record(
                        (time.monotonic() - t0) * 1e3)
                    self._send(200, out)
                finally:
                    self._end_invoke(ticket, t0)

            def _debug_invariants(self):
                """GET /v1/debug/invariants (host-only): the cheap
                invariant sweep — pagepool conservation, prefix-store
                pin accounting — as pass/fail + detail JSON. The chaos
                checker's quiesce probe; also a live debugging aid. No
                admission gate: host-side accounting reads only."""
                if not self._require_loopback():
                    return
                fn = getattr(server_self.boot.state,
                             "debug_invariants_fn", None)
                if fn is None:
                    self._send(404, {"ok": False, "error":
                                     "no invariants surface (handler "
                                     "has no serve-path state)"})
                    return
                try:
                    self._send(200, fn())
                except Exception as e:  # noqa: BLE001
                    self._send(500, {"ok": False, "error": str(e)})

            def _debug_faults(self):
                """POST /v1/debug/faults (host-only): arm/clear fault
                rules on the replica's live plan — the chaos soak's
                nemesis control surface. The loopback check runs FIRST:
                a control plane must not parse non-loopback bytes, and
                the refusal closes the connection so the unread body
                cannot poison keep-alive."""
                if not self._require_loopback():
                    return
                request = self._read_json()
                if request is None:
                    return
                fn = getattr(server_self.boot.state, "faults_admin_fn",
                             None)
                if fn is None:
                    self._send(404, {"ok": False, "error":
                                     "no fault-control surface "
                                     "(unsupported handler)"})
                    return
                try:
                    out = fn(request)
                except Exception as e:  # noqa: BLE001
                    self._send(500, {"ok": False, "error": str(e)})
                    return
                self._send(200 if out.get("ok") else 400, out)

            def _debug_knobs(self):
                """POST /v1/debug/knobs (host-only): live-retune the
                continuous engine's per-dispatch knobs (pipeline_depth,
                spec_k) — the elastic fleet controller's actuator.
                Same control-plane shape as _debug_faults: loopback
                refusal first, clamping in the handler closure."""
                if not self._require_loopback():
                    return
                request = self._read_json()
                if request is None:
                    return
                fn = getattr(server_self.boot.state, "knobs_admin_fn",
                             None)
                if fn is None:
                    self._send(404, {"ok": False, "error":
                                     "no knob-control surface "
                                     "(unsupported handler)"})
                    return
                try:
                    out = fn(request)
                except Exception as e:  # noqa: BLE001
                    self._send(500, {"ok": False, "error": str(e)})
                    return
                self._send(200 if out.get("ok") else 400, out)

            def _kv_probe(self):
                """KV presence probe: how many head tokens the radix
                tree actually holds. No admission gate — it is an
                O(depth) dict walk with no device work, and the router
                calls it on the import-miss pull path (cross-host in a
                multi-host fleet, so no loopback refusal — see
                _require_loopback) where queueing behind a run slot
                would cost more than the re-ship it guards."""
                fn = getattr(server_self.boot.state, "kv_probe_fn", None)
                request = self._read_json()
                if request is None:
                    return
                if fn is None:
                    self._send(404, {"ok": False, "error":
                                     "no KV probe surface (prefix "
                                     "cache off or unsupported handler)"})
                    return
                try:
                    out = fn(request)
                except Exception as e:  # noqa: BLE001
                    self._send(500, {"ok": False, "error": str(e)})
                    return
                self._send(200 if out.get("ok") else 400, out)

            def _write_frame(self, body: bytes) -> bool:
                """One chunked-transfer frame; False = client went away
                (recorded on the connection, never raised — the failure
                mode of a streaming response IS the socket)."""
                try:
                    self.wfile.write(f"{len(body):x}\r\n".encode())
                    self.wfile.write(body)
                    self.wfile.write(b"\r\n")
                    spans.first_frame(self._rid)  # counts once a request
                    return True
                except OSError:
                    self.close_connection = True
                    return False

            def _end_frames(self) -> None:
                try:
                    self.wfile.write(b"0\r\n\r\n")
                except OSError:
                    self.close_connection = True

            def _send_sse(self, stream_invoke, internal: dict):
                """OpenAI-style server-sent events: one `data:` event per
                decode segment, closed by `data: [DONE]`. The final
                summary record becomes a last event carrying the decoded
                ``text`` (string prompts) and ``finish_reason``."""
                t0 = time.monotonic()
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def event(obj) -> bool:
                    body = b"data: " + (obj if isinstance(obj, bytes)
                                        else json.dumps(obj).encode()) + b"\n\n"
                    return self._write_frame(body)

                def chunk_event(tokens, text="", finish=None,
                                logprobs=None) -> bool:
                    choice = {"index": 0, "text": text, "tokens": tokens,
                              "finish_reason": finish}
                    if logprobs is not None:
                        choice["logprobs"] = {
                            "tokens": [str(t) for t in tokens],
                            "token_logprobs": logprobs,
                            "top_logprobs": None, "text_offset": None}
                    return event({"object": "text_completion.chunk",
                                  "model": "lambdipy-bundle",
                                  "choices": [choice]})

                emitted: list = []
                text_sent = ""
                final = None
                try:
                    for payload in stream_invoke(internal):
                        if not payload.get("ok"):
                            server_self.stats.record_error()
                            event({"error": {"message": payload.get("error"),
                                             "type": "invoke_error"}})
                            self._end_frames()
                            return
                        if payload.get("done"):
                            final = payload
                            continue
                        emitted.extend(payload["tokens"][0])
                        # incremental text (string prompts): each chunk
                        # carries the delta the handler decoded for it
                        delta = payload.get("text", "")
                        text_sent += delta
                        if not chunk_event(
                                payload["tokens"][0], text=delta,
                                logprobs=(payload.get("logprobs") or
                                          [None])[0]):
                            return
                except SessionPinsExceeded as e:
                    # the 200 is already committed (streams send headers
                    # first), so the shed arrives as the terminal event —
                    # shed-shaped and COUNTED as one, never an error
                    cls = (self.headers.get("x-priority")
                           or "interactive").strip().lower()
                    server_self.sched.admission.count_shed(
                        "session_pins", cls)
                    event({"error": {
                        "message": "shed: session_pins",
                        "type": "overloaded_error",
                        "retry_after_s": round(e.retry_after_s, 3)}})
                    self._end_frames()
                    return
                except Exception as e:
                    server_self.stats.record_error()
                    log_event(log, "sse invoke failed", error=str(e),
                              kind=type(e).__name__)
                    event({"error": {"message": str(e),
                                     "type": type(e).__name__}})
                    self._end_frames()
                    return
                eos = (final or {}).get("eos_id", internal.get("eos_id"))
                finish = ("stop" if eos is not None and eos in emitted
                          else "length")
                # the final event completes the text: the handler computes
                # the tail a delta-concatenating client still needs (it
                # knows exactly what the chunks carried); fall back to
                # completion-minus-sent for handlers without the field
                final_rec = final or {}
                if "text" in final_rec:
                    tail = final_rec["text"]
                else:
                    completion = final_rec.get("completion", "")
                    tail = (completion[len(text_sent):]
                            if completion.startswith(text_sent)
                            else completion)
                chunk_event([], text=tail, finish=finish)
                server_self.stats.record((time.monotonic() - t0) * 1e3)
                if event(b"[DONE]"):
                    self._end_frames()

            def _send_stream(self, stream_fn, request: dict, t0: float):
                """Chunked ndjson response: one JSON line per decode
                segment, so clients see tokens at time-to-first-segment
                instead of end-to-end latency. A mid-stream handler error
                becomes a final {"ok": false} line (headers are already
                on the wire — there is no 500 to send)."""
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def write_chunk(payload: dict) -> bool:
                    return self._write_frame(json.dumps(payload).encode() + b"\n")

                try:
                    for payload in stream_fn(request):
                        if not write_chunk(payload):
                            return
                except SessionPinsExceeded as e:
                    # headers are committed: the shed becomes the
                    # terminal line, shed-shaped and counted as a shed
                    # (not an error) like the non-streamed 503
                    cls = (self.headers.get("x-priority")
                           or "interactive").strip().lower()
                    server_self.sched.admission.count_shed(
                        "session_pins", cls)
                    write_chunk({"ok": False, "shed": True,
                                 "reason": "session_pins",
                                 "retry_after_s":
                                     round(e.retry_after_s, 3)})
                    self._end_frames()
                    return
                except Exception as e:
                    server_self.stats.record_error()
                    log_event(log, "stream invoke failed", error=str(e),
                              kind=type(e).__name__)
                    write_chunk({"ok": False, "error": str(e),
                                 "kind": type(e).__name__})
                    self._end_frames()
                    return
                server_self.stats.record((time.monotonic() - t0) * 1e3)
                self._end_frames()

        return Handler

    # -- lifecycle ----------------------------------------------------------

    def _engine_fault_state(self) -> dict:
        """O(1) snapshot of the continuous engine's fault layer (empty
        for handlers without one) — feeds /healthz and the admission
        gate, so it must never raise or take serving-path locks."""
        fn = getattr(self.boot.state, "engine_fault_fn", None)
        if fn is None:
            return {}
        try:
            return dict(fn())
        except Exception:  # noqa: BLE001 — health must never 500
            return {}

    def serve_forever(self):
        log_event(log, "serving", port=self.port, bundle=str(self.bundle_dir))
        self._httpd.serve_forever()

    def start_background(self):
        self._thread = threading.Thread(target=self.serve_forever, daemon=True)
        self._thread.start()
        return self

    def stop(self, *, drain_grace: float = 10.0):
        """Drain then stop: admission closes FIRST (new invokes get 503 +
        Retry-After from both the server gate and the scheduler), then
        in-flight AND already-queued invokes finish (handler threads are
        daemonic — without this wait a process exit would cut device work
        mid-dispatch)."""
        with self._inflight_lock:
            self.draining = True
        self.sched.drain()
        deadline = time.monotonic() + drain_grace
        while self._inflight > 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        self._httpd.shutdown()
        self._httpd.server_close()
        self.boot.close()


def main(argv=None) -> int:
    """``python -m lambdipy_tpu.runtime.server <bundle_dir> [port]``"""
    import sys

    argv = argv if argv is not None else sys.argv[1:]
    if not argv:
        print("usage: server <bundle_dir> [port]", file=sys.stderr)
        return 2
    from lambdipy_tpu.utils.platform import apply_platform_override

    apply_platform_override()
    bundle = Path(argv[0])
    port = int(argv[1]) if len(argv) > 1 else 0
    server = BundleServer(bundle, port=port)

    # SIGTERM = graceful drain (supervisor/controller stop path). stop()
    # must run off the serve_forever thread — shutdown() from inside the
    # serving thread deadlocks — so the handler hands it to a worker.
    def _term(signum, frame):
        threading.Thread(target=server.stop, daemon=True).start()

    signal.signal(signal.SIGTERM, _term)

    # readiness line on stdout: the deploy controller parses this
    print(json.dumps({"ready": True, "pid": os.getpid(), "port": server.port,
                      "cold_start": server.boot.stages}), flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
