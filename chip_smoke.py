#!/usr/bin/env python3
"""Chip smoke: the quickest proof that build -> deploy -> serve runs on the
attached TPU, at the full width of the one full-size model the repo has.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the recipe's own tp=4 deployment

One chip: seeded int8 Llama-3-8B parameters (hidden 4096, 32 heads / 8 KV
heads, MLP 14336, vocab 128256) are written as an fpk, a recipe derived
from ``recipes/builtin/jax-llama3-8b.toml`` with the mesh off is built with
``lambdipy build`` (warm step included), deployed with
``LocalRuntime.deploy``, and asked a few things over HTTP: one greedy
``/invoke`` (twice), four ``/v1/completions`` of different lengths alone
and then at once (the continuous engine packs them), one stream. The server
is then started a second time (its compiles must come from the persistent
cache), and a 2-layer bundle of the same widths is checked against a
float32 ``jax.numpy`` forward computed here on the CPU.

``--chips 4`` runs the sharded path and what it is compared with, nothing
else: the same bundle served with the mesh off on one of the four chips,
then over ``tp=4``.

This process never starts a TPU backend: a chip belongs to one process at a
time, and every process that needs it (device probe, warm step, server) is
a child that has exited before the next one starts. Every line of stdout is
one JSON object; the last is ``{"ok": ..., "device": {"platform", "kind",
"count"}}`` as the serving process reported it, and the exit code is 0 only
when every check held on a TPU. Work files live under ``.chip_smoke/`` in
the checkout and the multi-GB parameter files are deleted on the way out.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import subprocess
import sys
import threading
import time
import tomllib
import traceback
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / ".chip_smoke"
BASE_RECIPE = REPO / "lambdipy_tpu" / "recipes" / "builtin" / "jax-llama3-8b.toml"

# Llama-3-8B's published widths and depth, none of them cut (depth buys
# little anyway: ~24 s of a program's ~37 s compile does not depend on it,
# CHANGES.md PR 21).
DIMS = dict(vocab_size=128256, hidden=4096, layers=32, heads=32, kv_heads=8,
            mlp=14336)
REF_LAYERS = 2
# One cut of STATE size on one chip, stated on the `config` line: the
# recipe sizes its engine KV window (2048 x 8 slots) for four chips, each
# holding a quarter of it. On one 16 GB chip that cache is 2.1 GB per live
# copy, and the pipelined engine keeps up to three beside 8.6 GB of weights.
ENGINE_WINDOW = 1024
# Tolerance of the reference check, on chosen-token logprobs: activations
# and dequantized weights are bfloat16 in the served model (8 significant
# bits, ~0.4 % per rounding) and float32 in the reference; over 2 layers
# of width 4096 into 128256 logits of unit scale that stays well inside
# 0.1 nat (0.02-0.04 observed on the CPU rehearsal).
LOGPROB_TOL = 0.1
# --chips 4 only: engine slots of the compared deployments (the recipe has 8)
TP4_SLOTS = 2

PROMPTS = [[11, 23, 5], [17, 3, 99, 41, 7, 123, 64],
           [9, 8, 7, 6, 5, 4, 3, 2, 1, 12, 13],
           [101, 102, 103, 104, 105, 106, 107, 108, 109, 110, 111, 112, 113,
            114, 115]]
# every row finishes inside ONE 16-step engine segment after its prefill, so
# the decode window the engine picks (next power of two above position +
# segment) is the 32 the warm step compiled: a longer request would compile
# the next window variant at first use (the documented residual cliff of
# window bucketing), inside the window this smoke counts compiles in
NEW_TOKENS = [6, 10, 14, 16]


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class SmokeFailure(Exception):
    """A phase could not finish; the smoke stops and reports not-ok."""


def _http(url: str, payload: dict | None = None, timeout: float = 300.0):
    """(wall ms, parsed JSON body) of one GET/POST."""
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method="GET" if payload is None else "POST")
    t0 = time.monotonic()
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        body = json.loads(resp.read())
    return (time.monotonic() - t0) * 1e3, body


# -- phases -------------------------------------------------------------------


def _toml_value(v) -> str:
    return json.dumps(v) if isinstance(v, (str, list)) else str(v)


def write_recipe(name: str, params: Path, dims: dict, *, mesh: dict | None,
                 extra: dict) -> Path:
    """A recipe DERIVED from the builtin 8B recipe: same handler, dtype,
    quantization, base layer and engine settings; seeded parameters from
    ``params`` instead of a float init, the mesh as given (None = off), the
    width/depth keys and ``extra`` on top."""
    base = tomllib.loads(BASE_RECIPE.read_text())
    payload = {k: v for k, v in base["payload"].items()
               if k not in ("mesh", "extra")}
    payload["params"] = str(params)
    top = {"schema": base["schema"], "name": name,
           "version": base["version"],
           "description": f"chip smoke: {base['description']}",
           "python": base["python"],
           "device": "tpu-v5e-4" if mesh else "tpu-v5e-1",
           "base_layer": base["base_layer"], "requires": base["requires"]}
    tables = [("", top), ("prune", base.get("prune", {})),
              ("payload", payload)]
    if mesh:
        tables.append(("payload.mesh", mesh))
    tables.append(("payload.extra",
                   {**base["payload"]["extra"], **dims, **extra}))
    lines = []
    for title, table in tables:
        if title:
            lines.append(f"\n[{title}]")
        lines += [f"{k} = {_toml_value(v)}" for k, v in table.items()]
    path = WORK / "recipes" / f"{name}.toml"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text("\n".join(lines) + "\n")
    return path


def make_params(name: str, dims: dict, seed: int) -> Path:
    from lambdipy_tpu.models import registry

    path = WORK / f"{name}.fpk"
    t0 = time.monotonic()
    info = registry.save_random_params("llama3-8b", path, dtype="bfloat16",
                                       quant="int8", extra=dims, seed=seed)
    emit(stage="params", name=name, layers=dims["layers"], seed=seed,
         seconds=round(time.monotonic() - t0, 1), bytes=info["bytes"],
         n_params=info["n_params"])
    return path


def build_bundle(name: str) -> Path:
    """``lambdipy build`` through the CLI, as a user would; the warm step
    must succeed and the command must exit 0."""
    from lambdipy_tpu.utils.platform import child_env

    bundle = WORK / f"bundle-{name}"
    shutil.rmtree(bundle, ignore_errors=True)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "lambdipy_tpu", "build", name,
         "--recipe-dir", str(WORK / "recipes"), "--out", str(bundle)],
        cwd=str(REPO), capture_output=True, text=True, timeout=1500,
        # a 32-layer warm compiles ~10 programs at ~40 s each
        env=child_env({"LAMBDIPY_WARM_TIMEOUT": "1200"}))
    seconds = round(time.monotonic() - t0, 1)
    if proc.returncode != 0:
        raise SmokeFailure(f"lambdipy build {name} rc={proc.returncode}: "
                           f"{proc.stderr.strip()[-600:]}")
    warm = json.loads((bundle / "manifest.json").read_text()).get("warm") or {}
    emit(stage="build", name=name, seconds=seconds, warm_ok=warm.get("ok"),
         warm_s=warm.get("wall_s"), warm_stages=warm.get("stages"),
         warm_device=warm.get("device"), warm_compile=warm.get("compile"),
         cache_dir=warm.get("cache_dir"),
         cache_entries=warm.get("cache_entries"))
    if not warm.get("ok"):
        raise SmokeFailure(f"warm record not ok: {warm}")
    return bundle


class Served:
    """One deployment of a bundle, ready to answer; a context manager that
    stops the server on the way out."""

    def __init__(self, runtime, name: str, bundle: Path, env: dict | None = None):
        self.rt, self.name = runtime, name
        t0 = time.monotonic()
        # no supervisor: a boot failure here should be one failure, not
        # five restarts each uploading the weights again
        self.dep = runtime.deploy(name, bundle, ready_timeout=1200.0, env=env,
                                  watchdog=False)
        deploy_s = time.monotonic() - t0
        # the readiness line is printed while the engine's group-prefill
        # programs are still warming in the background: wait for /healthz
        deadline = time.monotonic() + 900
        while True:
            self.health = runtime.health(name)
            if self.health.get("ready"):
                break
            if time.monotonic() > deadline:
                raise SmokeFailure(f"{name}: never became ready: {self.health}")
            time.sleep(0.5)
        m = self.metrics()
        emit(stage="deploy", name=name, deploy_s=round(deploy_s, 1),
             ready_s=round(time.monotonic() - t0, 1),
             cold_start=self.health.get("cold_start"),
             device=self.health.get("device"), compile=m.get("compile"),
             handler_compile_count=m["handler"].get("compile_count"),
             aot_hits=m["handler"].get("aot_hits"),
             aot_preload=m["handler"].get("aot_preload"),
             warm_buckets=m["handler"].get("warm_buckets"),
             memory=(m.get("device") or {}).get("memory"))

    @property
    def device(self) -> dict:
        return self.health.get("device") or {}

    def metrics(self) -> dict:
        return self.rt.metrics(self.name)

    def invoke(self, tokens, n_new, **extra):
        ms, out = _http(f"{self.dep.url}/invoke",
                        {"tokens": tokens, "max_new_tokens": n_new, **extra})
        if not out.get("ok"):
            raise SmokeFailure(f"/invoke failed: {out}")
        return ms, out

    def complete(self, tokens, n_new):
        """Greedy /v1/completions -> (ms, tokens, chosen-token logprobs)."""
        ms, out = _http(f"{self.dep.url}/v1/completions",
                        {"prompt": tokens, "max_tokens": n_new,
                         "temperature": 0, "logprobs": 1})
        choice = out["choices"][0]
        return ms, choice["tokens"], choice["logprobs"]["token_logprobs"]

    def stream(self, tokens, n_new) -> list[int]:
        row: list[int] = []
        for chunk in self.rt.invoke_stream(
                self.name, {"tokens": tokens, "max_new_tokens": n_new},
                timeout=300.0):
            if not chunk.get("ok"):
                raise SmokeFailure(f"stream chunk failed: {chunk}")
            if not chunk.get("done"):
                row += chunk["tokens"][0]
        return row

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.rt.stop(self.name)


def compare_runs(a: list, b: list) -> dict:
    """Two greedy runs of the same prompts, each ``(ms, tokens, chosen-token
    logprobs)`` per prompt. Where the runs went through programs of
    different width or sharding, bfloat16 reductions run in another order
    and a near-tie between two tokens can flip (seeded random weights have
    many): tokens are then identical only up to the flip. What must hold
    either way is that the logprobs agree up to and including the first
    divergence — a flip between near-tied tokens moves them by little, a
    wrong row by a lot."""
    identical, deltas = [], []
    for (_, ta, la), (_, tb, lb) in zip(a, b):
        identical.append(ta == tb)
        upto = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y),
                    len(ta) - 1)
        deltas.append(max(abs(x - y)
                          for x, y in zip(la[:upto + 1], lb[:upto + 1])))
    return {"tokens_identical": identical,
            "max_logprob_delta_to_divergence": [round(d, 5) for d in deltas],
            "tolerance": LOGPROB_TOL,
            "agree": all(d <= LOGPROB_TOL for d in deltas)}


def _compile_marks(m: dict) -> tuple:
    return (m["handler"].get("compile_count"),
            (m.get("compile") or {}).get("requests"))


def drive_requests(srv: Served) -> dict:
    """The request window. Returns the checks it decides."""
    before = srv.metrics()
    ms1, first = srv.invoke(PROMPTS[1], 16, logprobs=True)
    ms2, again = srv.invoke(PROMPTS[1], 16, logprobs=True)
    emit(stage="request", kind="invoke", ms=[round(ms1, 1), round(ms2, 1)],
         n_new=first["n_new"], tokens=first["tokens"][0])

    alone = [srv.complete(p, n) for p, n in zip(PROMPTS, NEW_TOKENS)]
    together: list = [None] * len(PROMPTS)
    errors: list = []

    def fire(i):
        try:
            together[i] = srv.complete(PROMPTS[i], NEW_TOKENS[i])
        except Exception as e:  # noqa: BLE001 — judged after the join
            errors.append(f"request {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(len(PROMPTS))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    if errors or any(r is None for r in together):
        raise SmokeFailure(f"concurrent completions failed: {errors}")
    packed = compare_runs(alone, together)
    emit(stage="request", kind="completions",
         prompt_lens=[len(p) for p in PROMPTS], n_new=NEW_TOKENS,
         alone_ms=[round(r[0], 1) for r in alone],
         together_ms=[round(r[0], 1) for r in together],
         together_vs_alone=packed)

    t0 = time.monotonic()
    streamed = srv.stream(PROMPTS[1], 16)
    emit(stage="request", kind="stream",
         ms=round((time.monotonic() - t0) * 1e3, 1), n_new=len(streamed))
    after = srv.metrics()
    engine = {k: (after["handler"]["batching"][k]
                  - before["handler"]["batching"][k])
              for k in ("segments_run", "rows_in_segments",
                        "requests_served")}
    emit(stage="window", compile_before=_compile_marks(before),
         compile_after=_compile_marks(after), errors=after.get("errors"),
         engine=engine, memory=(after.get("device") or {}).get("memory"))
    return {
        "repeat_identical": first["tokens"] == again["tokens"],
        # bitwise on the CPU in float32 (tests/test_continuous.py); on the
        # chip in bfloat16 only up to near-tie flips — see compare_runs
        "batched_agrees_with_alone": packed["agree"],
        "lengths_answered": all(len(r[1]) == n
                                for r, n in zip(together, NEW_TOKENS)),
        "stream_equals_invoke": streamed == first["tokens"][0],
        "rows_packed": engine["rows_in_segments"] > engine["segments_run"],
        "no_compile_in_window": _compile_marks(before) == _compile_marks(after),
        "no_request_failed": not after.get("errors"),
    }


def reference_logprobs(params_path: Path, dims: dict, tokens: list[int],
                       n_prompt: int):
    """Chosen-token logprobs of ``tokens[n_prompt:]`` under a plain float32
    ``jax.numpy`` forward of the same parameters, computed on the CPU in
    this process — independent of ``models/llama.py``: int8 kernels
    dequantized to float32, RMSNorm, rotate-half RoPE, causal grouped-query
    attention, SwiGLU. Returns (logprob of each chosen token, the
    reference's own best logprob at each of those positions)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.nn import log_softmax, silu, softmax

    from lambdipy_tpu.bundle import flatpack

    p = flatpack.load(params_path)["params"]
    h, kvh = dims["heads"], dims["kv_heads"]
    d = dims["hidden"] // h
    ids = np.asarray(tokens)
    s = len(ids)

    def dense(x, leaf):
        w = (jnp.asarray(leaf["kernel_int8"], jnp.float32)
             * jnp.asarray(leaf["scale"], jnp.float32))
        return x @ w

    def norm(x, leaf):
        return (x * (jnp.mean(x * x, -1, keepdims=True) + 1e-5) ** -0.5
                * jnp.asarray(leaf["scale"], jnp.float32))

    freqs = 1.0 / (500000.0 ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):  # [s, heads, d]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    x = jnp.asarray(np.asarray(p["embed"]["embedding"])[ids], jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(dims["layers"]):
        lp = p[f"layer_{i}"]
        a = norm(x, lp["attn_norm"])
        q = rope(dense(a, lp["q_proj"]).reshape(s, h, d))
        k = rope(dense(a, lp["k_proj"]).reshape(s, kvh, d))
        v = dense(a, lp["v_proj"]).reshape(s, kvh, d)
        k, v = (jnp.repeat(t, h // kvh, axis=1) for t in (k, v))
        scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
        probs = softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        att = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h * d)
        x = x + dense(att, lp["o_proj"])
        m = norm(x, lp["mlp_norm"])
        x = x + dense(silu(dense(m, lp["gate_proj"])) * dense(m, lp["up_proj"]),
                      lp["down_proj"])
    # position t predicts token t+1
    logits = dense(norm(x, p["final_norm"])[n_prompt - 1: s - 1], p["lm_head"])
    lps = np.asarray(log_softmax(logits, axis=-1))
    chosen = lps[np.arange(s - n_prompt), ids[n_prompt:]]
    return chosen, lps.max(axis=-1)


def check_against_reference(runtime, seed: int) -> dict:
    """A second bundle of the same widths, 2 layers deep, against the
    float32 reference. Solo serving (the recipe's documented
    ``batch_mode = ""`` opt-out): this phase checks the model's numerics,
    and the engine's programs would only add compiles."""
    dims = dict(DIMS, layers=REF_LAYERS)
    params = make_params("smoke-ref", dims, seed + 1)
    write_recipe("smoke-ref", params, dims, mesh=None,
                 extra={"batch_mode": "", "max_new_tokens": 16})
    with Served(runtime, "smoke-ref", build_bundle("smoke-ref")) as srv:
        platform = srv.device.get("platform")
        _, out = srv.invoke(PROMPTS[2], 16, logprobs=True)
    tokens, served = out["tokens"][0], out["logprobs"][0]
    t0 = time.monotonic()
    chosen, best = reference_logprobs(params, dims, PROMPTS[2] + tokens,
                                      len(PROMPTS[2]))
    delta = float(abs(chosen - served).max())
    # "top-1" with the tie the tolerance allows: the served token's
    # reference logprob is within LOGPROB_TOL of the reference's best
    near_top1 = float(((best - chosen) <= LOGPROB_TOL).mean())
    emit(stage="reference", layers=REF_LAYERS, platform=platform,
         seconds=round(time.monotonic() - t0, 1), n_tokens=len(tokens),
         max_abs_logprob_delta=round(delta, 5), tolerance=LOGPROB_TOL,
         top1_agreement=float((best == chosen).mean()),
         top1_within_tolerance=near_top1,
         finite=all(math.isfinite(x) for x in served))
    params.unlink(missing_ok=True)
    return {"reference_logprobs_agree": delta <= LOGPROB_TOL,
            "reference_top1_within_tolerance": near_top1 == 1.0}


def smoke_one_chip(seed: int) -> tuple[dict, dict]:
    """All one-chip phases. Returns (checks, device of the main server)."""
    from lambdipy_tpu.runtime.deploy import LocalRuntime

    emit(stage="config", model="llama3-8b int8", **DIMS,
         engine_window=ENGINE_WINDOW,
         reduced=[f"engine KV window 2048 -> {ENGINE_WINDOW}: sized for "
                  "tp=4 in the recipe; one 16 GB chip holds the weights "
                  "and up to three copies of the 8-slot cache"])
    runtime = LocalRuntime(WORK / "deployments.json")
    params = make_params("smoke-8b", DIMS, seed)
    write_recipe("smoke-8b", params, DIMS, mesh=None,
                 extra={"batch_cache_len": ENGINE_WINDOW,
                        "max_new_tokens": 16})
    bundle = build_bundle("smoke-8b")
    with Served(runtime, "smoke-8b", bundle) as srv:
        device = srv.device
        checks = {"served_on_one_tpu": (device.get("platform") == "tpu"
                                        and device.get("count") == 1)}
        checks.update(drive_requests(srv))
    # second start of the same bundle at the same path: what is not loaded
    # from the AOT store must come from the persistent compile cache
    with Served(runtime, "smoke-8b", bundle) as again:
        hits = (again.metrics().get("compile") or {}).get(
            "persistent_cache_hits", 0)
    emit(stage="second_start", persistent_cache_hits=hits)
    checks["second_start_hits_cache"] = hits > 0
    shutil.rmtree(bundle, ignore_errors=True)
    params.unlink(missing_ok=True)
    checks.update(check_against_reference(runtime, seed))
    return checks, device


def smoke_four_chips(seed: int) -> tuple[dict, dict]:
    """The recipe's own tp=4 deployment against the same bundle served
    with the mesh off on one of the four chips: same prompts, greedy."""
    from lambdipy_tpu.runtime.deploy import LocalRuntime

    emit(stage="config", model="llama3-8b int8", **DIMS, mesh={"tp": 4},
         engine_window=ENGINE_WINDOW, engine_slots=TP4_SLOTS,
         reduced=[f"engine slots 8 -> {TP4_SLOTS} in this comparison: every "
                  "slot count is a group-prefill program (~40 s of compile "
                  "at this width) in BOTH deployments, on four billed chips"])
    runtime = LocalRuntime(WORK / "deployments.json")
    params = make_params("smoke-8b-tp4", DIMS, seed)
    write_recipe("smoke-8b-tp4", params, DIMS, mesh={"dp": 1, "tp": 4},
                 extra={"batch_cache_len": ENGINE_WINDOW,
                        "batch_max": TP4_SLOTS, "max_new_tokens": 16})
    bundle = build_bundle("smoke-8b-tp4")
    results, memory, mesh_stats, device = {}, {}, {}, {}
    for label, env in (("mesh_off", {"LAMBDIPY_MESH": "off"}), ("tp4", None)):
        with Served(runtime, f"smoke-{label}", bundle, env=env) as srv:
            results[label] = [srv.complete(p, n)
                              for p, n in zip(PROMPTS, NEW_TOKENS)]
            m = srv.metrics()
            mesh_stats[label] = \
                (m["handler"].get("batching") or {}).get("mesh") or {}
            memory[label] = [d.get("bytes_in_use")
                             for d in m["device"]["memory"]]
            emit(stage="sharding", deployment=label, device=srv.device,
                 meta_mesh=srv.health.get("handler_meta", {}).get("mesh"),
                 bytes_in_use=memory[label], batching_mesh=mesh_stats[label],
                 ms=[round(r[0], 1) for r in results[label]])
            device = srv.device  # the last one is the tp=4 deployment
    shutil.rmtree(bundle, ignore_errors=True)
    params.unlink(missing_ok=True)

    sharded = compare_runs(results["mesh_off"], results["tp4"])
    emit(stage="tp4_vs_mesh_off", **sharded)
    used = [b for b in memory["tp4"] if b]
    mesh_tp4 = mesh_stats["tp4"]
    checks = {
        "served_on_four_tpus": (device.get("platform") == "tpu"
                                and device.get("count") == 4),
        "tp4_agrees_with_mesh_off": sharded["agree"],
        "all_four_devices_hold_state": len(used) == 4
        and max(used) / min(used) <= 1.5,
        "params_sharded": 0 < mesh_tp4.get("param_bytes_per_device", 0)
        <= 0.3 * mesh_tp4.get("param_bytes_total", 0),
        "kv_sharded": 0 < mesh_tp4.get("kv_bytes_per_device", 0)
        <= 0.3 * mesh_tp4.get("kv_bytes_replicated", 0),
    }
    return checks, device


# -- entry --------------------------------------------------------------------


def cleanup() -> None:
    """Stop anything still deployed and drop what must not outlive the run:
    parameter files and bundles (multi-GB). The recipes and serve logs stay
    for diagnosis; the directory is git-ignored."""
    from lambdipy_tpu.runtime.deploy import LocalRuntime

    state = WORK / "deployments.json"
    if state.exists():
        rt = LocalRuntime(state)
        for dep in rt.list():
            try:
                rt.stop(dep.name)
            except Exception as e:  # noqa: BLE001 — keep cleaning
                print(f"cleanup: stop {dep.name}: {e}", file=sys.stderr)
    for path in WORK.glob("*.fpk"):
        path.unlink(missing_ok=True)
    for path in WORK.glob("bundle-*"):
        shutil.rmtree(path, ignore_errors=True)


def report(checks: dict, device: dict, error: str | None = None) -> int:
    """The verdict lines; returns the process exit code."""
    failed = sorted(k for k, v in checks.items() if not v)
    ok = not error and not failed and bool(checks) \
        and device.get("platform") == "tpu"
    emit(stage="verdict", checks=checks, failed=failed, error=error)
    emit(ok=ok, device={k: device.get(k)
                        for k in ("platform", "kind", "count")})
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated parameters")
    args = ap.parse_args(argv)

    try:
        import jax

        import lambdipy_tpu  # noqa: F401 — the program under test
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repo: {e}",
              file=sys.stderr)
        return 2

    # this process computes the float32 reference and nothing else: keep
    # it on the CPU IN CODE, not in the environment its children inherit
    jax.config.update("jax_platforms", "cpu")

    WORK.mkdir(exist_ok=True)
    checks, device, error = {}, {}, None
    try:
        from lambdipy_tpu.utils.platform import probe_device

        # asked in a CHILD: this process must never start a TPU backend
        device = probe_device()
        emit(stage="probe", device=device)
        if device["platform"] != "tpu" or device["count"] < args.chips:
            error = (f"need {args.chips} TPU chip(s); jax found "
                     f"{device['count']} x {device['platform']}")
        elif args.chips == 4:
            checks, device = smoke_four_chips(args.seed)
        else:
            checks, device = smoke_one_chip(args.seed)
    except Exception as e:  # noqa: BLE001 — every failure becomes a verdict
        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"
    finally:
        cleanup()
    return report(checks, device, error)


if __name__ == "__main__":
    sys.exit(main())
