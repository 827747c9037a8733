"""The client side: one streamed greedy ``POST /v1/completions`` per
request, timed on the host clock as a user would see it.

Due-time firing follows ``lambdipy_tpu/chaos/workload.py`` (commit fb0103a):
each request sleeps until its planned arrival and fires whatever the server
is doing; its latencies count from when it was due.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from dataclasses import dataclass, field


@dataclass
class Record:
    rid: int
    prompt: list
    max_tokens: int
    due: float                 # monotonic time the request was due
    t_send: float = 0.0
    t_first: float | None = None
    t_last: float | None = None
    tokens: list = field(default_factory=list)
    first_chunk_tokens: int = 0
    chunk_times: list = field(default_factory=list)  # (t, n_tokens) per chunk
    ok: bool = False
    error: str | None = None

    @property
    def n_tokens(self) -> int:
        return len(self.tokens)


def complete(host: str, port: int, rec: Record, stop: threading.Event | None
             = None, timeout: float = 300.0, on_first=None) -> Record:
    """Send ``rec`` and fill it in. ``ok`` needs HTTP 200, no error event,
    the closing ``[DONE]`` and exactly ``max_tokens`` tokens. A set ``stop``
    abandons the stream (the record stays not-ok and is not counted)."""
    rec.t_send = time.monotonic()
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        body = json.dumps({"prompt": rec.prompt, "max_tokens": rec.max_tokens,
                           "temperature": 0, "stream": True})
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec.error = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return rec
        done = False
        for raw in resp:
            if stop is not None and stop.is_set():
                rec.error = "abandoned at window end"
                return rec
            line = raw.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[6:]
            if data == b"[DONE]":
                done = True
                break
            event = json.loads(data)
            if "error" in event:
                rec.error = json.dumps(event["error"])[:200]
                return rec
            toks = event["choices"][0].get("tokens") or []
            if toks:
                now = time.monotonic()
                if rec.t_first is None:
                    rec.t_first = now
                    rec.first_chunk_tokens = len(toks)
                    if on_first is not None:
                        on_first()
                rec.t_last = now
                rec.tokens.extend(toks)
                rec.chunk_times.append((now, len(toks)))
        if not done:
            rec.error = "stream ended without [DONE]"
        elif len(rec.tokens) != rec.max_tokens:
            rec.error = (f"asked {rec.max_tokens} tokens, got "
                         f"{len(rec.tokens)}")
        else:
            rec.ok = True
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def fire_at_due(host: str, port: int, records: list, join_s: float) -> float:
    """Open loop: one thread per request, each sleeping until its due time.
    Returns the worst lateness (seconds) with which a request was sent."""
    def fire(rec):
        delay = rec.due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        complete(host, port, rec)

    threads = [threading.Thread(target=fire, args=(r,), daemon=True)
               for r in records]
    for t in threads:
        t.start()
    deadline = max(r.due for r in records) + join_s
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    for r in records:
        if not r.ok and r.error is None:
            r.error = "not finished when the drain time ran out"
    return max((r.t_send - r.due for r in records if r.t_send), default=0.0)
