"""One run of a benchmark cell, plus the engine's and the scheduler's own
counters over it, which no benchmark metric reads: garbage segments a request
(``wasted_overdecode_tokens``), the drains by cause (``handover``, ``joiner``,
``complete``), the in-flight histogram, ``rows_per_segment`` untraced, and
``sched`` ``running`` / ``queued`` / ``granted_ahead`` in the middle of the
window, and of a model with recurrent states the row-steps its states took
and how many of them the in-place kernel stepped (``handler.kda`` /
``handler.sala`` ``row_steps``, ``kernel_row_steps``: equal on the chip, the
second 0 on a CPU), and of a sparse-attention model the keys its steps
selected and the pairs its prefills ran over those causality needs
(``handler.dsa``; ``prefill_overwork``). It wraps ``python3 -m benchmark.run`` (same arguments after ``--``,
same result line) and changes nothing under ``benchmark/``: the harness's two
scrapes are kept and one more is made mid-window.

    cd <checkout> && python3 <repo>/scripts/bench_scrapes.py --tag C1 \\
        --out chiprun_out/p40 -- --workload mistral7b.decode-saturated \\
        --seed 2986401127 --seconds 50 --trace 0

The checkout is the working directory's (so a parent commit unpacked beside
the tree runs ITS program under this script). ``--clients N`` overrides a
closed loop's client count: with more clients than run slots a CPU rehearsal
drives the grant ahead (``--manifest benchmark/rehearsal.json --workload
rehearsal-tiny.rehearsal-closed`` with ``--clients 12``: 8 run slots, 4 batch
slots); never use it for a number. Writes ``<out>/<tag>.scrapes.json`` and
prints one ``SCRAPE`` line of window deltas after the run's own output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path


def pick(metrics: dict) -> dict:
    """The blocks of one ``/metrics`` document this script keeps."""
    handler = metrics.get("handler") or {}
    batching = handler.get("batching") or {}
    spans = {k: {"count": v.get("count"), "sum_s": v.get("sum_s")}
             for k, v in (metrics.get("spans") or {}).items()
             if k.startswith(("eng.", "req."))}
    return {"batching": {k: batching[k] for k in (
                "segments_run", "rows_in_segments", "requests_served",
                "prefill_groups", "rows_group_prefilled", "pipeline")
                if k in batching},
            "states": {kind: {k: handler[kind].get(k) for k in (
                "row_steps", "kernel_row_steps")}
                for kind in ("kda", "sala") if kind in handler},
            "dsa": {k: v for k, v in (handler.get("dsa") or {}).items()
                    if k in ("keys_selected", "keys_visible",
                             "prefill_pairs_run", "prefill_pairs_causal")},
            "sched": metrics.get("sched"), "spans": spans,
            "peak_bytes": [d.get("peak_bytes_in_use") for d in (
                metrics.get("device") or {}).get("memory", [])]}


def deltas(opened: dict, closed: dict) -> dict:
    """Window deltas of the engine's counters between two picks."""
    b0, b1 = opened["batching"], closed["batching"]
    p0, p1 = b0.get("pipeline") or {}, b1.get("pipeline") or {}

    def d(a, b, key):
        return (b.get(key) or 0) - (a.get(key) or 0)

    def dd(key):
        return {k: v - (p0.get(key) or {}).get(k, 0)
                for k, v in (p1.get(key) or {}).items()}

    served, segs = d(b0, b1, "requests_served"), d(b0, b1, "segments_run")
    wasted = d(p0, p1, "wasted_overdecode_tokens")
    dsa = {k: d(opened["dsa"], closed["dsa"], k) for k in closed["dsa"]}
    if dsa.get("prefill_pairs_causal"):
        # pairs the window's sparse prefills ran over those causality needs
        dsa["prefill_overwork"] = round(
            dsa["prefill_pairs_run"] / dsa["prefill_pairs_causal"], 4)
    return {"served": served, "segments": segs, "dsa": dsa,
            "rows_per_segment": round(
                d(b0, b1, "rows_in_segments") / max(segs, 1), 4),
            "wasted_overdecode_tokens": wasted,
            "wasted_a_request": round(wasted / max(served, 1), 2),
            "prefill_groups": d(b0, b1, "prefill_groups"),
            "states": {kind: {k: d(opened["states"].get(kind, {}), now, k)
                              for k in now}
                       for kind, now in closed["states"].items()},
            "drains": dd("drains"), "in_flight": dd("in_flight")}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    cut = argv.index("--") if "--" in argv else len(argv)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--clients", type=int, default=0)
    args = ap.parse_args(argv[:cut])
    out_dir = Path(args.out).resolve()
    out_dir.mkdir(parents=True, exist_ok=True)

    sys.path.insert(0, os.getcwd())  # the checkout we stand in, not ours
    from benchmark import harness, run

    run_window = harness.run_window

    def scraped(ctx, served, seed, seconds, traffic=None, trace=False):
        traffic = dict(traffic or ctx["traffic"])
        if args.clients:
            traffic["clients"] = args.clients
        mid: list = []

        def scrape_mid():
            time.sleep(float(traffic.get("lead_in_s", 0)) + seconds / 2)
            try:
                mid.append(pick(served.metrics()))
            except Exception as e:  # noqa: BLE001 — the run goes on
                mid.append({"error": repr(e)})

        threading.Thread(target=scrape_mid, daemon=True).start()
        win = run_window(ctx, served, seed, seconds, traffic=traffic,
                         trace=trace)
        opened, closed = pick(win["m_open"]), pick(win["m_close"])
        doc = {"tag": args.tag, "seed": seed, "window_s": win["window_s"],
               "summary": win["summary"], "deltas": deltas(opened, closed),
               "open": opened, "mid": mid, "close": closed}
        (out_dir / f"{args.tag}.scrapes.json").write_text(
            json.dumps(doc, indent=1, default=str))
        return win

    harness.run_window = scraped
    rc = run.main(argv[cut + 1:])
    path = out_dir / f"{args.tag}.scrapes.json"
    if path.exists():
        doc = json.loads(path.read_text())
        sched = [(m.get("sched") or {}) for m in doc["mid"] + [doc["close"]]]
        print("SCRAPE", json.dumps({
            "tag": args.tag, **doc["deltas"],
            "sched_mid_close": [{k: s.get(k) for k in (
                "running", "queued", "granted_ahead", "completed")}
                for s in sched],
            "peak_bytes": doc["close"]["peak_bytes"]}), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
