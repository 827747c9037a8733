"""The phases of a run, shared by ``run.py`` (one cell, one window, one
result line) and ``study.py`` (one boot, many windows: the rate sweep and
the readings the ``correct`` limit is set from)."""

from __future__ import annotations

import importlib.util
import json
import os
import random
import time
from pathlib import Path

from benchmark import bundle as B
from benchmark import families, stats, traffic as T
from benchmark.bundle import REPO, BenchFailure
from benchmark.serve import Served

HERE = Path(__file__).resolve().parent
SAMPLE_ROWS = 4          # rows of the output check: the longest and three more
TRACE_SLICE_S = 2.0      # a longer slice takes the profiler minutes to write out


def load_cell(manifest_path: Path, workload: str) -> dict:
    manifest = json.loads(Path(manifest_path).read_text())
    cell = next((w for w in manifest["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise BenchFailure(f"no workload {workload!r} in {manifest_path}")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config_path = REPO / entry["file"]
    config = json.loads(config_path.read_text())
    return {"manifest": manifest, "cell": cell, "config_path": config_path,
            "config": config, "family": families.of(config),
            "traffic": T.load(cell["traffic"]),
            "rehearsal": bool(manifest.get("rehearsal"))}


def metrics_of(manifest: dict, kind: str, workload: str) -> list:
    """The manifest's metrics of ``kind`` that this cell reports."""
    return [m for m in manifest[kind]
            if workload in m.get("workloads", [workload])]


def rehearsal_env(ctx: dict) -> dict:
    """A rehearsal runs on the CPU with as many virtual devices as the cell
    has chips."""
    return {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count="
                         f"{ctx['cell']['chips']}"}


def server_env(ctx: dict) -> dict:
    """Extra environment of the server process: none on the chip; a
    rehearsal pins it to the CPU."""
    if not ctx["rehearsal"]:
        return {}
    return {"LAMBDIPY_PLATFORM": "cpu", **rehearsal_env(ctx)}


def check_device(ctx: dict, device: dict) -> None:
    want = "cpu" if ctx["rehearsal"] else "tpu"
    if device.get("platform") != want or \
            int(device.get("count") or 0) < ctx["cell"]["chips"]:
        raise BenchFailure(
            f"cell needs {ctx['cell']['chips']} x {want}; the serving "
            f"process reports {device}")


def prepare(ctx: dict, work: Path) -> Path:
    """The cell's bundle: found, or built on this first run."""
    if not ctx["rehearsal"] and not (
            work / B.bundle_key(ctx["config_path"]) / "bundle"
            / "manifest.json").exists():
        # a build takes minutes: ask a child what jax finds before it
        from lambdipy_tpu.utils.platform import probe_device

        check_device(ctx, probe_device())
    if ctx["rehearsal"]:
        # the build's warm step is a child that inherits this environment
        os.environ.update(rehearsal_env(ctx))
    path, built = B.ensure_bundle(ctx["config_path"], ctx["config"], work)
    ctx["built"] = built
    return path


def compile_marks(m: dict) -> dict:
    c = m.get("compile") or {}
    return {"requests": c.get("requests", 0),
            "cache_hits": c.get("persistent_cache_hits",
                                c.get("cache_hits", 0)),
            "seconds": c.get("seconds", c.get("request_s", 0.0)),
            "programs": (m.get("handler") or {}).get("compile_count", 0)}


def run_window(ctx: dict, served: Served, seed: int, seconds: float,
               traffic: dict | None = None, trace: bool = False) -> dict:
    """One measured window of the cell's traffic. Returns the generator's
    result plus ``/metrics`` before and after, and (traced) the slice."""
    import threading

    traffic = traffic or ctx["traffic"]
    gen = T.generator(traffic)
    out: dict = {"slice": None}
    timer = None

    def traced_slice(t_open):
        # the middle of the window; scraped and traced from a side thread
        def body():
            delay = t_open + max(0.0, (seconds - TRACE_SLICE_S) / 2) \
                - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            served.trace(True)
            # counters are scraped INSIDE the traced interval, so the steps
            # they count ran while the device was being traced
            m0, t0 = served.metrics(), time.monotonic()
            time.sleep(min(TRACE_SLICE_S, seconds / 2))
            m1, t1 = served.metrics(), time.monotonic()
            served.trace(False)   # returns when the trace is written: slow
            out["slice"] = {"t0": t0, "t1": t1, "m0": m0, "m1": m1}
        nonlocal timer
        timer = threading.Thread(target=body, daemon=True)
        timer.start()

    m_open = served.metrics()
    res = gen.run(traffic, seed, seconds, ctx["config"]["vocab_size"],
                  "127.0.0.1", served.port,
                  on_open=traced_slice if trace else None)
    if timer is not None:
        timer.join(timeout=300)
    out.update(res, m_open=m_open, m_close=served.metrics())
    out["summary"] = stats.summarize(res["records"], res["window_s"],
                                     res["tokens_in_window"])
    return out


def sample_rows(records: list, seed: int, shape: tuple) -> list:
    """The requests the reference re-computes: the longest finished one and
    ``SAMPLE_ROWS - 1`` more drawn from the seed. ``(tokens, n_prompt)``."""
    done = [r for r in records if r.ok and r.tokens
            and len(r.prompt) + len(r.tokens) <= shape[1]
            and len(r.tokens) <= shape[2]]
    if not done:
        return []
    done.sort(key=lambda r: (-(len(r.prompt) + len(r.tokens)), r.rid))
    rest = done[1:]
    random.Random(seed ^ 0xC0FFEE).shuffle(rest)
    return [(list(r.prompt) + list(r.tokens), len(r.prompt))
            for r in [done[0]] + rest[: shape[0] - 1]]


def reference_shape(ctx: dict) -> tuple:
    env = T.lengths(ctx["traffic"])
    return (SAMPLE_ROWS, -(-env["total_max"] // 128) * 128, env["new_max"])


def enable_reference_cache(work: Path) -> None:
    """The parent's own compiles (the reference) go to the persistent cache
    too: where the environment places it, else a fixed directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(work / "reference_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_outputs(ctx: dict, records: list, seed: int, *,
                  control: bool = False) -> dict:
    """``correct``: every finished request holds exactly the tokens it asked
    for (the client has checked that: ``ok``), none failed, and the widest
    gap of the sampled served tokens is inside the configuration's limit.
    Call only after the server has stopped: the reference takes the chip."""
    from benchmark import reference

    shape = reference_shape(ctx)
    rows = sample_rows(records, seed, shape)
    limit = ctx["config"]["correct"]["limit"]
    if not rows:
        return {"correct": False, "why": "no finished request to check",
                "widest_gap": None, "limit": limit}
    ref = reference.served_gaps(ctx["config"], rows, shape=shape,
                                control=control)
    widest = max(ref["gap"])
    out = {"widest_gap": widest, "limit": limit,
           "gap_nonzero_share": sum(1 for g in ref["gap"] if g > 0)
           / len(ref["gap"]),
           "rows": len(rows), "served_tokens": ref["served_tokens"],
           "positions": ref["positions"],
           "reference_s": round(ref["seconds"], 2),
           "reference_platform": ref["platform"]}
    if control:
        out["control_widest_gap"] = max(ref["control_gap"])
        out["control_nonzero_share"] = sum(
            1 for g in ref["control_gap"] if g > 0) / len(ref["control_gap"])
    out["correct"] = limit is not None and widest <= limit
    return out


def layer_metric(name: str):
    path = HERE / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmark.layer_metrics.{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
