"""Window deltas of the program's span aggregates (``/metrics`` ->
``spans``: per name ``count``, ``sum_s`` and bucket counts that only grow).
The readers in ``layer_metrics/`` take them between ``m_open`` and
``m_close``, the two scrapes at the window's ends. A program without spans
(every commit before PR 24) has no such block: the readers then return
None and the result line leaves the metric out."""

from __future__ import annotations


def delta(ctx: dict, name: str) -> tuple | None:
    """``(d count, d sum_s)`` of span ``name`` over the window, or None
    where a scrape lacks the ``spans`` block. A name that never occurred
    counts as zero."""
    try:
        a, b = ctx["m_open"]["spans"], ctx["m_close"]["spans"]
    except (KeyError, TypeError):
        return None
    zero = {"count": 0, "sum_s": 0.0}
    a, b = a.get(name, zero), b.get(name, zero)
    return b["count"] - a["count"], b["sum_s"] - a["sum_s"]


def mean_ms(ctx: dict, name: str) -> float | None:
    """Mean duration in ms of the spans ``name`` that ended in the window."""
    d = delta(ctx, name)
    if d is None or d[0] <= 0:
        return None
    return 1e3 * d[1] / d[0]
