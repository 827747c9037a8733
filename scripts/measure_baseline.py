"""Measure BASELINE.json's staged configs through the REAL serve path and
record the numbers into ``BASELINE.json.published`` (SURVEY.md §5.3 /
VERDICT r2 missing #2: the suite never exercised the chip and ``published``
stayed empty).

Per config: ``lambdipy build <recipe>`` -> LocalRuntime.deploy (boot = the
actual cold start, through the supervisor + HTTP server) -> N timed
``/invoke`` round-trips -> p50/p99 + cold-start seconds. Configs 1-2 are
CPU configs and always run; configs 3-5 are device configs and run only
when a probe SUBPROCESS finds a TPU (this parent never starts a jax
backend: the warm steps and servers it starts need the chip). Each record
is stamped with the device its server reported on /healthz.

Usage: python scripts/measure_baseline.py [--configs 1,2] [--invokes 30]
The tpu-marked tests (tests/test_tpu.py) call the same machinery and
assert the north-star budgets.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

CONFIGS = {
    1: {"recipe": "hello-numpy", "platform": "cpu",
        "request": {"n": 64, "seed": 1}},
    2: {"recipe": "tabular-sklearn", "platform": "cpu",
        "request": {"instances": [[0.1] * 16]}},
    3: {"recipe": "jax-resnet50", "platform": "device",
        "request": {"random": True}},
    4: {"recipe": "jax-bert", "platform": "device",
        "request": {"input_ids": [[101, 2054, 2003, 102]]}},
    # config 5 exemplar: the 8B recipe needs a v5e-4; this is the same
    # int8 + compile-once-decode serve path at single-chip scale. The
    # multi-chip sharding evidence for the full recipe is the CPU-mesh
    # dryrun (__graft_entry__.dryrun_multichip).
    5: {"recipe": "jax-llama-micro", "platform": "device",
        "request": {"tokens": [[1, 2, 3, 4, 5, 6, 7, 8]],
                    "max_new_tokens": 32}},
    # config 4's literal "pytorch recipe" path: torch-xla has no wheel in
    # this offline env, so the bundle degrades to the documented CPU-torch
    # smoke (the jax path above is the full-TPU sibling). Recorded as
    # "config4_torch" so both halves of config 4 carry measurements.
    "4t": {"recipe": "torch-xla-bert", "platform": "cpu",
           "request": {"input_ids": [[101, 2054, 2003, 102]]}},
}


def tpu_reachable() -> bool:
    """True when a probe CHILD finds a TPU; False when it finds another
    platform, fails, or hangs (a chip held by another process)."""
    from lambdipy_tpu.utils.platform import probe_device

    try:
        return probe_device()["platform"] == "tpu"
    except (RuntimeError, subprocess.TimeoutExpired):
        return False


def measure_config(num: int, *, invokes: int = 30,
                   work: Path | None = None) -> dict:
    """Build + deploy + invoke one config; returns the measured record.
    Latencies are wall-clock HTTP round-trips on the host clock. The
    bundle is built at a fixed path inside the checkout (its warm compile
    cache is looked up under that path) unless ``work`` says otherwise."""
    from lambdipy_tpu.runtime.deploy import LocalRuntime
    from lambdipy_tpu.utils.platform import child_env

    cfg = CONFIGS[num]
    if work is None:
        work = REPO / ".lambdipy_cache" / f"baseline-c{num}"
        shutil.rmtree(work, ignore_errors=True)
    work = Path(work)
    work.mkdir(parents=True, exist_ok=True)
    bundle = work / "bundle"
    env = child_env({"LAMBDIPY_PLATFORM": "cpu"}
                    if cfg["platform"] == "cpu" else None)
    build_cmd = [sys.executable, "-m", "lambdipy_tpu", "build", cfg["recipe"],
                 "--out", str(bundle)]
    t0 = time.monotonic()
    proc = subprocess.run(build_cmd, capture_output=True, text=True, env=env,
                          cwd=str(REPO), timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"build failed: {proc.stderr[-500:]}")
    build_s = time.monotonic() - t0

    rt = LocalRuntime(work / "deployments.json")
    dep_env = ({"LAMBDIPY_PLATFORM": "cpu"}
               if cfg["platform"] == "cpu" else None)
    name = f"baseline-c{num}"
    t0 = time.monotonic()
    rt.deploy(name, bundle, env=dep_env)
    deploy_wall_s = time.monotonic() - t0
    try:
        health = rt.health(name)
        # warmup invokes are excluded from the latency sample
        for _ in range(3):
            rt.invoke(name, dict(cfg["request"]))
        times = []
        for _ in range(invokes):
            t = time.monotonic()
            out = rt.invoke(name, dict(cfg["request"]))
            times.append((time.monotonic() - t) * 1000.0)
            assert out.get("ok"), out
        times.sort()
        # the cold_start stage dict carries its own "total"; summing every
        # value would double-count it against the component stages
        cs = health["cold_start"]
        cold_start_s = cs.get("total", sum(v for k, v in cs.items()
                                           if k != "total"))
        device = health.get("device") or {}
        record = {
            "recipe": cfg["recipe"],
            # what the SERVING process holds (None = not a jax bundle)
            "platform": device.get("platform", cfg["platform"]),
            "device_kind": device.get("kind"),
            # e.g. config4_torch: the handler flags its degraded CPU path
            # so the published number can never read as a TPU number
            **({"degraded": health["handler_meta"]["degraded"]}
               if health.get("handler_meta", {}).get("degraded") else {}),
            "invoke_p50_ms": round(statistics.median(times), 3),
            "invoke_p99_ms": round(times[min(len(times) - 1,
                                             int(len(times) * 0.99))], 3),
            "cold_start_s": round(cold_start_s, 2),
            "deploy_wall_s": round(deploy_wall_s, 2),
            "build_s": round(build_s, 1),
            "n_invokes": invokes,
            "warm_ok": bool((health.get("warm") or {}).get("ok")),
            "measured_at": time.strftime("%Y-%m-%d"),
        }
        n_new = cfg["request"].get("max_new_tokens")
        if n_new and record["invoke_p50_ms"] > 0:
            record["decode_tok_s"] = round(
                n_new / (record["invoke_p50_ms"] / 1e3), 1)
        _attach_roofline(record, cfg, n_new)
    finally:
        rt.stop(name)
    return record


def _attach_roofline(record: dict, cfg: dict, n_new: int | None) -> None:
    """Relate the measured number to the published peaks of the device
    that served it (VERDICT r3 missing #2): mfu/hbm_util for the ResNet
    north star and per-token decode utilization for the Llama configs,
    computed from the recipe's own dims (read from its TOML, so the
    record can never drift from what was actually served). Nothing is
    attached off the TPU; a TPU kind without a peaks entry raises."""
    from lambdipy_tpu.utils import roofline
    import tomllib

    measured_ms = record.get("invoke_p50_ms", 0)
    if not measured_ms or record.get("platform") != "tpu":
        return
    peaks = roofline.peaks_for(record["device_kind"])
    if cfg["recipe"] == "jax-resnet50":
        cost = roofline.resnet50_cost(batch=1)
        record.update({k: v for k, v in
                       cost.utilization(measured_ms / 1e3, peaks).items()
                       if k in ("mfu", "hbm_util", "roofline_ms")})
    elif cfg["recipe"].startswith("jax-llama") and n_new:
        path = (REPO / "lambdipy_tpu" / "recipes" / "builtin"
                / f"{cfg['recipe']}.toml")
        rec = tomllib.loads(path.read_text())
        payload = rec["payload"]
        extra = payload.get("extra", {})
        from lambdipy_tpu.models.llama import LLAMA3_8B
        import dataclasses

        fields = {f.name for f in dataclasses.fields(LLAMA3_8B)}
        lcfg = dataclasses.replace(
            LLAMA3_8B, quant=payload.get("quant"),
            **{k: v for k, v in extra.items() if k in fields})
        prompt_len = len(cfg["request"]["tokens"][0])
        cost = roofline.llama_decode_step_cost(
            lcfg, batch=1, cache_len=prompt_len + n_new // 2)
        per_tok_s = measured_ms / n_new / 1e3
        record["dims"] = f"{lcfg.hidden}x{lcfg.layers}x{lcfg.vocab_size}"
        record.update({f"decode_{k}": v for k, v in
                       cost.utilization(per_tok_s, peaks).items()
                       if k in ("mfu", "hbm_util", "roofline_ms")})


def publish(records: dict) -> None:
    # shared merge+atomic writer: preserves config5's dict-valued
    # sub-records (published by measure_8b modes) and never leaves a
    # truncated BASELINE.json when a timeout kills the process mid-write
    from publish_util import merge_publish

    merge_publish(records)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=None,
                    help="comma-separated config numbers (default: all runnable)")
    ap.add_argument("--invokes", type=int, default=30)
    ap.add_argument("--no-publish", action="store_true")
    args = ap.parse_args()

    if args.configs:
        nums = [n if n in CONFIGS else int(n)
                for n in args.configs.split(",")]
    else:
        nums = [1, 2, "4t"]
        if tpu_reachable():
            nums += [3, 4, 5]
        else:
            print("no TPU found; measuring the CPU configs only",
                  file=sys.stderr)
    records = {}
    failed = []
    for num in nums:
        print(f"config {num}: {CONFIGS[num]['recipe']} ...", file=sys.stderr)
        label = "config4_torch" if num == "4t" else f"config{num}"
        try:
            rec = measure_config(num, invokes=args.invokes)
        except Exception as e:  # one config must not discard the others
            failed.append(label)
            print(f"{label} FAILED: {e}", file=sys.stderr)
            continue
        records[label] = rec
        print(json.dumps({label: rec}))
    if records and not args.no_publish:
        publish(records)
        print(f"published -> {REPO / 'BASELINE.json'}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
