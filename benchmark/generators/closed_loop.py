"""Closed loop: ``clients`` callers that each wait for their reply and send
the next request at once. A slow server is offered less, so the metric is
what completes: tokens delivered inside the window.

The clients start ``lead_in_s`` before the window opens (set-up, not
measured), so the window sees them out of step with each other rather than
sixteen prefills at once. Requests come from one pool of ``pool`` sizes —
the evenly spaced quantiles of the file's ranges, in the file's order
rotated by the seed —
which every client draws from in turn. A request still running when the
window closes is abandoned and not judged; its tokens that arrived inside
the window count.
"""

from __future__ import annotations

import random
import threading
import time

from benchmark import client, traffic as T


def run(traffic: dict, seed: int, seconds: float, vocab: int, host: str,
        port: int, on_open=None) -> dict:
    rng = random.Random(seed)
    pool = [(T.prompt_ids(plen, vocab, rng), new)
            for plen, new in T.sizes(traffic, traffic["pool"], seed)]
    lock = threading.Lock()
    stop = threading.Event()
    records: list = []
    state = {"next": 0}

    def one_client():
        while not stop.is_set():
            with lock:
                rid = state["next"]
                state["next"] += 1
                prompt, new = pool[rid % len(pool)]
                rec = client.Record(rid, prompt, new, time.monotonic())
                records.append(rec)
            client.complete(host, port, rec, stop=stop)

    threads = [threading.Thread(target=one_client, daemon=True)
               for _ in range(traffic["clients"])]
    for t in threads:
        t.start()
    time.sleep(traffic["lead_in_s"])
    t0 = time.monotonic()
    if on_open:
        on_open(t0)
    time.sleep(seconds)
    t_close = time.monotonic()
    stop.set()
    for t in threads:
        t.join(timeout=30)
    with lock:
        seen = list(records)
    # judged: requests that ENDED inside the window (whenever they began)
    judged = [r for r in seen if (r.ok or r.error != "abandoned at window end")
              and r.t_send and (r.t_last or r.t_send) >= t0
              and (r.t_last or r.t_send) <= t_close + 0.5]
    tokens = sum(n for r in seen for t, n in r.chunk_times
                 if t0 <= t <= t_close)
    return {"records": judged, "t_open": t0, "window_s": t_close - t0,
            "tokens_in_window": tokens, "generator_late_s": 0.0}
