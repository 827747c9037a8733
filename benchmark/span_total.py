"""Totals of the program's span aggregates up to the window (``/metrics`` ->
``spans``: per name ``count`` and ``sum_s`` that only grow from process
start). The set-up readers in ``layer_metrics/`` take them from ``m_open``,
the scrape at the window's opening: everything since the serving process
started, which is ``setup_s``'s own interval but for the harness's own start
(and, in a closed-loop cell, the lead-in, which the scrape precedes). A
program without that span (every commit before PR 37) gives None and the
result line leaves the metric out."""

from __future__ import annotations


def total(ctx: dict, *names: str, field: str = "sum_s"):
    """``field`` summed over those of the spans ``names`` that the opening
    scrape holds; None where it has no ``spans`` block or none of them (a
    name beside one that is there never occurred: zero)."""
    try:
        spans = ctx["m_open"]["spans"]
    except (KeyError, TypeError):
        return None
    have = [spans[name][field] for name in names if name in spans]
    return sum(have) if have else None
