"""Open loop: independent users. Requests are sent at planned times at the
file's fixed ``rate_rps`` whether or not earlier ones have finished, and
timed from when they were due. The window holds round(rate x seconds)
requests; after it closes the generator waits (``drain_s``) for the ones in
flight, since the tail is the tail of all requests."""

from __future__ import annotations

import random
import time

from benchmark import client, traffic as T


def plan(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """[(offset s, prompt ids, max_tokens)] — the same sizes and the same
    gaps for every seed, in another order."""
    rng = random.Random(seed)
    n = max(1, round(traffic["rate_rps"] * seconds))
    sizes = T.sizes(traffic, n, seed)
    gaps = T.exponential_gaps(traffic, n, seed)
    # the n quantile gaps sum to ~n/rate = seconds; scale so the last
    # request is due just inside the window
    scale = seconds * (n - 0.5) / n / sum(gaps)
    out, t = [], 0.0
    for (plen, new), gap in zip(sizes, gaps):
        t += gap * scale
        out.append((t, T.prompt_ids(plen, vocab, rng), new))
    return out


def run(traffic: dict, seed: int, seconds: float, vocab: int, host: str,
        port: int, on_open=None) -> dict:
    planned = plan(traffic, seed, seconds, vocab)
    t0 = time.monotonic() + 0.05
    if on_open:
        on_open(t0)
    records = [client.Record(i, p, new, t0 + off)
               for i, (off, p, new) in enumerate(planned)]
    late = client.fire_at_due(host, port, records,
                              join_s=traffic["drain_s"])
    t_close = t0 + seconds
    tokens = sum(n for r in records if r.ok
                 for t, n in r.chunk_times if t <= t_close)
    return {"records": records, "t_open": t0, "window_s": seconds,
            "tokens_in_window": tokens, "generator_late_s": late}
