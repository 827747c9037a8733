"""One run of one cell:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Bundle (found, or built on the cell's first run in this checkout) -> deploy
-> warm the traffic's envelope -> window -> stop the server -> output check
against the float32 reference -> one JSON object on the last line. Earlier
lines are free-form notes, one JSON object each. This process starts no TPU
backend while the server lives: the server is its child, and the reference
runs here only after that child has exited.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as python lets us see it

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]


def live_rows(records: list, t: float, slots: int) -> tuple:
    """(rows decoding at time ``t``, their mean context) from the client's
    own records: a request is decoding between its first and last chunk;
    its context is its prompt plus the tokens received by then."""
    ctxs = []
    for r in records:
        if r.t_first is None or r.t_first > t or (r.t_last or 0) < t:
            continue
        got = sum(n for ct, n in r.chunk_times if ct <= t)
        ctxs.append(len(r.prompt) + got)
    ctxs = sorted(ctxs, reverse=True)[:slots]
    return (len(ctxs), sum(ctxs) / len(ctxs)) if ctxs else (0, 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=str(REPO / "BENCHMARK.json"),
                    help="BENCHMARK.json, or the rehearsal manifest")
    ap.add_argument("--work-dir", default=None,
                    help="bundles and traces (default benchmark/.work)")
    args = ap.parse_args(argv)

    try:
        import lambdipy_tpu  # noqa: F401 — the program under test

        from benchmark import harness as H
        from benchmark import warmup, xplane
        from benchmark.bundle import DEFAULT_WORK, BenchFailure, note
        from benchmark.serve import Served
    except ImportError as e:
        print(f"benchmark: run from a checkout of the repo: {e}",
              file=sys.stderr)
        return 2

    try:
        ctx = H.load_cell(Path(args.manifest), args.workload)
        work = Path(args.work_dir) if args.work_dir else DEFAULT_WORK
        work.mkdir(parents=True, exist_ok=True)
        cfg, slots = ctx["config"], warmup.coverage(
            ctx["traffic"], ctx["config"])["slots"]
        bundle = H.prepare(ctx, work)
        t_deploy = time.monotonic()
        with Served(bundle, work, traced=bool(args.trace),
                    env=H.server_env(ctx)) as served:
            H.check_device(ctx, served.device)
            m_ready = served.metrics()
            note(stage="deploy", ready_s=round(served.ready_s, 2),
                 cold_start=served.health.get("cold_start"),
                 device=served.device, compile=H.compile_marks(m_ready))
            warm = warmup.send(served, ctx["traffic"], cfg)
            m_warm = served.metrics()
            compile_warm = H.compile_marks(m_warm)
            note(stage="warmup", **warm, compile=compile_warm,
                 cache_misses=compile_warm["requests"]
                 - compile_warm["cache_hits"])
            win = H.run_window(ctx, served, args.seed, args.seconds,
                               trace=bool(args.trace))
            memory = [d.get("peak_bytes_in_use", 0) for d in
                      (win["m_close"].get("device") or {}).get("memory", [])]
            device = served.device
        setup_s = win["t_open"] - T0
        summary = win["summary"]
        compile_open, compile_close = (H.compile_marks(win[k])
                                       for k in ("m_open", "m_close"))
        in_window = max(compile_close["requests"] - compile_open["requests"],
                        compile_close["programs"] - compile_open["programs"])
        keys = [[json.dumps(k) for k in (win[m].get("handler") or {}).get(
            "decode_buckets", [])] for m in ("m_open", "m_close")]
        note(stage="window", seconds=win["window_s"], **summary,
             new_programs=[k for k in keys[1] if k not in keys[0]],
             generator_late_s=round(win["generator_late_s"], 4),
             compiles_in_window=in_window, setup_s=round(setup_s, 2),
             built_this_run=ctx["built"],
             errors=[r.error for r in win["records"] if not r.ok][:5])

        trace = None
        if args.trace and win["slice"]:
            sl = win["slice"]
            found = xplane.find_trace(served.trace_dir)
            trace = found and xplane.reduce(found, window_s=sl["t1"] - sl["t0"])
            sl["live"] = [live_rows(win["records"], t, slots)
                          for t in (sl["t0"], sl["t1"])]
            note(stage="trace", file=str(found), reduced=trace,
                 live=sl["live"])
            if trace is None and not ctx["rehearsal"]:
                raise BenchFailure("traced run: no operation ran on a device "
                                   "plane of the trace")

        # the server has exited: the chip is free for the reference
        H.enable_reference_cache(work)
        check = H.check_outputs(ctx, win["records"], args.seed)
        note(stage="check", **check, failed_requests=summary["failed"])
        correct = bool(check["correct"]) and summary["failed"] == 0 \
            and summary["attempted"] > 0
        # each number compared, beside its limit: the result line's last key
        # and this run's last lines on standard error
        compared = {"widest_gap": {"value": check["widest_gap"],
                                   "limit": check["limit"]},
                    "failed_requests": {"value": summary["failed"],
                                        "limit": 0}}

        values = dict(summary, setup_s=setup_s)
        if args.trace:
            rctx = dict(ctx, summary=summary, boot_ready_s=served.ready_s,
                        compile_warm=compile_warm, compile_open=compile_open,
                        compile_close=compile_close, m_open=win["m_open"],
                        m_close=win["m_close"], slice=win["slice"],
                        trace=trace, device=device,
                        memory_peak_bytes=max(memory, default=0))
            metrics = {}
            for m in H.metrics_of(ctx["manifest"], "per_layer",
                                  args.workload):
                v = H.layer_metric(m["name"]).read(rctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in H.metrics_of(ctx["manifest"], "end_to_end",
                                             args.workload)}
        dev = {"platform": device.get("platform"), "kind": device.get("kind"),
               "count": device.get("count"),
               "memory_peak_bytes": max(memory, default=0)}
        line = {"correct": correct, "attempted": summary["attempted"],
                "failed": summary["failed"], "metrics": metrics,
                "device": dev}
        if args.trace and trace:
            dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
        line["compared"] = compared
        print(json.dumps(line), flush=True)
        for name, c in compared.items():
            print(f"compared {name} = {c['value']} limit {c['limit']}",
                  file=sys.stderr, flush=True)
        return 0
    except BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    except Exception:  # noqa: BLE001 — any fault: no result line, non-zero
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
