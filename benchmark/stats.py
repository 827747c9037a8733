"""Arithmetic from request records to end-to-end metrics.

A record is one request as the client saw it (``client.Record``). A request
that failed or was refused misses every latency: it enters a percentile as
the largest value (infinity), so a tail that reaches into the failures
reads as infinity rather than as the tail of the survivors.
"""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile, ``q`` in (0, 100]: the smallest value with
    at least q% of the sample at or below it. No interpolation, so every
    reported number is one that was measured."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def ttft_ms(rec) -> float:
    """First chunk's arrival, from when the request was DUE."""
    if not rec.ok or rec.t_first is None:
        return math.inf
    return (rec.t_first - rec.due) * 1e3


def tpot_ms(rec) -> float | None:
    """A request's mean gap between output tokens after its first chunk:
    (last chunk - first chunk) / tokens after the first chunk. None for a
    request answered in one chunk (nothing to divide); infinity for one
    that failed."""
    if not rec.ok:
        return math.inf
    later = rec.n_tokens - rec.first_chunk_tokens
    if later <= 0 or rec.t_last is None or rec.t_last <= rec.t_first:
        return None
    return (rec.t_last - rec.t_first) * 1e3 / later


def summarize(records: list, window_s: float, tokens_in_window: int) -> dict:
    """Every end-to-end number the window gives, with sample counts."""
    ttfts = [ttft_ms(r) for r in records]
    tpots = [tpot_ms(r) for r in records]
    one_chunk = sum(1 for t in tpots if t is None)
    tpots = [t for t in tpots if t is not None]
    out = {"attempted": len(records),
           "failed": sum(1 for r in records if not r.ok),
           "one_chunk_requests": one_chunk, "tpot_samples": len(tpots),
           "out_tok_s": tokens_in_window / window_s if window_s > 0 else 0.0}
    if ttfts:
        out["ttft_p50_ms"] = percentile(ttfts, 50)
        out["ttft_p90_ms"] = percentile(ttfts, 90)
    if tpots:
        out["tpot_p50_ms"] = percentile(tpots, 50)
        out["tpot_p90_ms"] = percentile(tpots, 90)
    return out


def attainment(records: list, ttft_limit_ms: float,
               tpot_limit_ms: float) -> float:
    """Share of requests SENT that met both limits; a failure misses."""
    if not records:
        return 0.0
    met = 0
    for r in records:
        t = tpot_ms(r)
        if ttft_ms(r) <= ttft_limit_ms and (t is None or t <= tpot_limit_ms):
            met += 1
    return met / len(records)
