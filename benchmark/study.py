"""One boot, many windows: the tools a benchmark PR needs once, on the chip.

    python3 -m benchmark.study --workload <cell> --seconds <s> --seeds 1,2,3 \\
        [--rates 2,3,4] [--control 1]

``--rates``: the rate sweep of an open-loop cell. One window per rate and
seed at that ``rate_rps`` in place of the file's; each prints the share of
requests sent that met both limits of the traffic file, the latencies of
the window's two halves (a backlog that grows shows as a second half slower
than the first) and how long the last request took to drain.

``--control 1``: the readings a ``correct`` limit is set from. After the
server has stopped, every window's sample goes through the float32
reference AND through the control of the configuration's family (the
nearest precision below the stated one); each prints the program's widest
gap and the control's. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from benchmark import harness as H
from benchmark import stats, warmup
from benchmark.bundle import DEFAULT_WORK, REPO, BenchFailure, note
from benchmark.serve import Served


def halves(records: list, t_open: float, seconds: float) -> dict:
    out = {}
    for label, lo, hi in (("first_half", 0.0, seconds / 2),
                          ("second_half", seconds / 2, seconds + 1e9)):
        part = [r for r in records if lo <= r.due - t_open < hi]
        if part:
            out[label] = {"n": len(part),
                          "ttft_p50_ms": stats.percentile(
                              [stats.ttft_ms(r) for r in part], 50)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=str(REPO / "BENCHMARK.json"))
    ap.add_argument("--work-dir", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    try:
        ctx = H.load_cell(Path(args.manifest), args.workload)
        work = Path(args.work_dir) if args.work_dir else DEFAULT_WORK
        work.mkdir(parents=True, exist_ok=True)
        bundle = H.prepare(ctx, work)
        windows = []
        with Served(bundle, work, traced=False,
                    env=H.server_env(ctx)) as served:
            H.check_device(ctx, served.device)
            note(stage="deploy", ready_s=round(served.ready_s, 2),
                 device=served.device)
            # the widest envelope of the sweep is the file's own: a rate
            # changes arrivals, not shapes
            note(stage="warmup", **warmup.send(served, ctx["traffic"],
                                               ctx["config"]))
            for rate in rates:
                traffic = dict(ctx["traffic"])
                if rate is not None:
                    traffic["rate_rps"] = rate
                for seed in seeds:
                    win = H.run_window(ctx, served, seed, args.seconds,
                                       traffic=traffic)
                    recs = win["records"]
                    line = {"stage": "window", "rate_rps": rate, "seed": seed,
                            **win["summary"],
                            "generator_late_s": win["generator_late_s"],
                            "compiles_in_window":
                                H.compile_marks(win["m_close"])["requests"]
                                - H.compile_marks(win["m_open"])["requests"]}
                    if "ttft_limit_ms" in traffic:
                        line["attainment"] = stats.attainment(
                            recs, traffic["ttft_limit_ms"],
                            traffic["tpot_limit_ms"])
                        line.update(halves(recs, win["t_open"], args.seconds))
                        line["drain_s"] = max(
                            (r.t_last for r in recs if r.t_last),
                            default=win["t_open"]) - win["t_open"] \
                            - args.seconds
                    note(**line)
                    windows.append((rate, seed, recs))
        if args.control:
            H.enable_reference_cache(work)
            for rate, seed, recs in windows:
                note(stage="check", rate_rps=rate, seed=seed,
                     **H.check_outputs(ctx, recs, seed, control=True))
        return 0
    except BenchFailure as e:
        print(f"benchmark.study: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
