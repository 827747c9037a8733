"""Composed-fault chaos soak over the live CPU fleet.

Every per-feature fault test arms ONE fault site in a hand-curated
scenario; the bugs that survived those gates lived in *cross-feature
interactions* under *overlapping* faults (the PR-8/13/14 post-review
hardening lists). This package is the Jepsen-style answer:

- :mod:`nemesis` — a seeded scheduler that draws composed fault events
  (every ``runtime/faults.py`` registry site x kind, plus process-level
  nemeses: SIGKILL a replica's worker, drain/undrain) onto a randomized
  timeline with controlled overlap, every decision derived from one
  seed so any failing schedule replays exactly;
- :mod:`workload` — a mixed open-loop client driving the full feature
  matrix concurrently (greedy + seeded-sampled, streamed + plain, cold
  + shared-prefix + multi-turn sessions) with per-request expected
  outputs precomputed against a direct reference server;
- :mod:`checker` — the global oracle: every request is delivered
  bitwise vs the reference or is an explicit, priced, counted failure;
  no waiter outlives its bound; and at quiesce all accounting converges
  (pagepool conservation, pins -> 0, spill depth -> 0);
- :mod:`soak` — the orchestrator and its entry point (``python -m
  lambdipy_tpu.chaos.soak``), with the ``--replay-timeline`` workflow.
"""

from lambdipy_tpu.chaos.checker import check_history, check_quiesce
from lambdipy_tpu.chaos.nemesis import (
    Nemesis,
    NemesisEvent,
    generate_timeline,
    parse_timeline,
    render_timeline,
    timeline_properties,
)
from lambdipy_tpu.chaos.workload import Outcome, build_plan

__all__ = [
    "Nemesis",
    "NemesisEvent",
    "Outcome",
    "build_plan",
    "check_history",
    "check_quiesce",
    "generate_timeline",
    "parse_timeline",
    "render_timeline",
    "timeline_properties",
]
