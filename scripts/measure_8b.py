"""Measure the REAL Llama-3-8B dims on the chip (VERDICT r3 missing #1).

Every published decode number so far was the 768x6x16384 micro exemplar;
this script builds the actual 4096x32x128256 int8 model — ~7.5 GB of
matmul weights, which fit a single v5e-1's 16 GB HBM with room for a
1k-context KV cache — and measures, through the same LlamaServer serving
machinery the bundle handler uses:

- batch-1 and batch-8 decode tok/s on the host clock (``generate`` returns
  host arrays, so each timed call ends with the tokens fetched), with
  roofline/HBM-utilization accounting against the measured device's
  published peaks (utils/roofline.py; none off the TPU);
- prefill latency at a 512-token prompt;
- the cold-start decomposition at 8B scale: flatpack load, host->device
  weight transfer, and first-program compile.

Params are seeded random int8 — FLOPs and HBM bytes do not care what the
weights are — generated ONCE into the framework cache as a flatpack file
(~8 GB; models/registry.py save_random_params, which starts no jax
backend) and reused by later runs and by bench.py's decode8b stage.

One process per chip: every mode but ``--cold-start`` measures in this
process. ``--cold-start`` is a PARENT — it builds and deploys, and its
children (warm step, server) need the chip — so it never starts a backend
itself.

Usage: python scripts/measure_8b.py [--batch 1,8] [--n-new 64]
       [--publish]   # writes BASELINE.json published.config5
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from bench import _timed  # noqa: E402 — the one host-clock timer

# the exemplar-scale knobs shared with recipes/builtin/jax-llama3-8b.toml:
# real model dims, context capped so prompt+decode KV fits comfortably
# beside 8 GB of weights on one chip
DIMS = dict(vocab_size=128256, hidden=4096, layers=32, heads=32,
            kv_heads=8, mlp=14336, max_len=1024)


def params_path() -> Path:
    cache = Path(os.environ.get("LAMBDIPY_CACHE_DIR",
                                os.path.expanduser("~/.lambdipy-tpu/cache")))
    return cache / "llama3-8b-int8-random.fpk"


def ensure_params(path: Path) -> float:
    """Generate the seeded int8 8B flatpack once; returns seconds spent
    (0.0 when the cached file already exists). Starts no jax backend."""
    if path.is_file():
        return 0.0
    from lambdipy_tpu.models import registry

    t0 = time.monotonic()
    registry.save_random_params("llama3-8b", path, dtype="bfloat16",
                                quant="int8", extra=dict(DIMS), seed=0)
    return time.monotonic() - t0


def _peaks():
    """Published peaks of the device this process measures on (None off
    the TPU — utils/roofline.py peaks_of)."""
    import jax

    from lambdipy_tpu.utils import roofline

    return roofline.peaks_of(jax.devices()[0])


def measure(batches=(1, 8), n_new: int = 64, prompt_len: int = 8,
            prefill_len: int = 512, do_prefill: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.bundle import flatpack
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import LlamaConfig
    from lambdipy_tpu.utils import roofline

    record: dict = {"dims": f"{DIMS['hidden']}x{DIMS['layers']}"
                            f"x{DIMS['vocab_size']}",
                    "quant": "int8", "n_new": n_new,
                    "measured_at": time.strftime("%Y-%m-%d")}
    gen_s = ensure_params(params_path())
    if gen_s:
        record["param_gen_s"] = round(gen_s, 1)

    devices = jax.devices()
    record["platform"] = devices[0].platform
    record["device_kind"] = devices[0].device_kind
    peaks = _peaks()
    t0 = time.monotonic()
    # bulk grouped upload + device-side unpack (flatpack.device_load)
    params = jax.block_until_ready(flatpack.device_load(params_path()))
    record["weight_upload_s"] = round(time.monotonic() - t0, 2)
    record["weight_bytes"] = int(roofline.param_bytes(params))

    cfg = LlamaConfig(**DIMS, quant="int8", dtype=jnp.bfloat16)
    adapter = registry.get("llama3-8b").build(
        dtype="bfloat16", quant="int8", extra=dict(DIMS))
    server = adapter.make_server(params)

    prompt = list(range(1, prompt_len + 1))
    for b in batches:
        rows = [prompt] * b
        t0 = time.monotonic()
        server.generate(rows, max_new_tokens=n_new)  # compile + warm
        key = f"b{b}"
        record[f"{key}_first_call_s"] = round(time.monotonic() - t0, 1)
        times = [_timed(lambda: server.generate(rows, max_new_tokens=n_new))
                 for _ in range(5)]
        net_ms = max(0.1, statistics.median(times))
        tok_s = b * n_new / (net_ms / 1e3)
        record.update({
            f"{key}_decode_tok_s": round(tok_s, 1),
            f"{key}_decode_net_ms": round(net_ms, 1),
        })
        if peaks is not None:
            cost = roofline.llama_decode_step_cost(
                cfg, batch=b, cache_len=prompt_len + n_new // 2)
            util = cost.utilization(net_ms / n_new / 1e3, peaks)
            bound = roofline.llama_decode_tok_s_bound(
                cfg, batch=b, cache_len=prompt_len + n_new // 2, peaks=peaks)
            record.update({
                f"{key}_decode_hbm_util": util["hbm_util"],
                f"{key}_decode_mfu": util["mfu"],
                f"{key}_roofline_tok_s": round(bound, 1),
            })
        print(json.dumps({k: v for k, v in record.items()
                          if k.startswith(key)}), file=sys.stderr)

    if not do_prefill:
        return record
    # prefill: long-prompt first-token latency (compute-bound regime).
    # A max_new_tokens=1 call still runs a bucketed decode scan after
    # the prefill (min_bucket steps = ~16 weight reads = ~180 ms at
    # 8B); drop the server to a ONE-step scan and subtract that step's
    # cost (the already-measured b1 per-step decode time) so the
    # published number is the prefill itself.
    server.min_bucket = 1
    long_prompt = list(range(1, prefill_len + 1))
    t0 = time.monotonic()
    server.generate(long_prompt, max_new_tokens=1)  # compile
    record["prefill_compile_s"] = round(time.monotonic() - t0, 1)
    times = [_timed(lambda: server.generate(long_prompt, max_new_tokens=1))
             for _ in range(5)]
    # b1-derived step cost slightly overcounts (it amortizes the tiny
    # prompt prefill into the divisor, ~1.6% at n_new=64); a run that
    # skipped b1 publishes uncorrected and SAYS so
    record["prefill_step_corrected"] = "b1_decode_net_ms" in record
    step_ms = (record["b1_decode_net_ms"] / n_new
               if record["prefill_step_corrected"] else 0.0)
    net_ms = max(0.1, statistics.median(times) - step_ms)
    record["prefill_512_net_ms"] = round(net_ms, 1)
    if peaks is not None:
        pcost = roofline.llama_prefill_cost(cfg, batch=1, seq_len=prefill_len)
        record["prefill_512_mfu"] = pcost.utilization(net_ms / 1e3,
                                                      peaks)["mfu"]
    return record


RECIPE_TMPL = """\
# generated by scripts/measure_8b.py --cold-start: the real 8B dims at
# tp=1 with pre-built weights (payload.params = checkpoint path), so the
# measured cold start is weights-load + boot, not build-time init
schema = 1
name = "jax-llama3-8b-local"
version = "1.0.0"
description = "Llama-3-8B int8 single-chip bundle from pre-built weights"
python = ["3.12"]
device = "tpu-v5e-1"
base_layer = "jax-tpu"
requires = []

[payload]
model = "llama3-8b"
handler = "lambdipy_tpu.runtime.handlers:generate_handler"
params = "{params}"
dtype = "bfloat16"
quant = "int8"
batch_size = 1

[payload.extra]
vocab_size = {vocab_size}
hidden = {hidden}
layers = {layers}
heads = {heads}
kv_heads = {kv_heads}
mlp = {mlp}
max_len = {max_len}
max_new_tokens = 32
# match the production recipe defaults (VERDICT r5 #6) so the measured
# cold start covers the engine's programs too
batch_mode = "continuous"
batch_max = 8
"""


def measure_cold_start(n_invokes: int = 5) -> dict:
    """The 8B cold start through the REAL path: build a bundle from the
    pre-built fpk (hardlinked), deploy it (subprocess server + readiness),
    and time build / boot stages / first invokes. The decomposition (from
    /healthz) separates weight upload from program acquisition.

    This function is a PARENT: the warm step and the server are its
    children and need the chip, so nothing here may start a jax backend
    (parameter generation does not). The bundle is built at a fixed path
    in the checkout, where its warm compile cache is found again."""
    import statistics
    import subprocess

    from lambdipy_tpu.runtime.deploy import LocalRuntime
    from lambdipy_tpu.utils.platform import child_env

    record: dict = {"dims": f"{DIMS['hidden']}x{DIMS['layers']}"
                            f"x{DIMS['vocab_size']}",
                    "measured_at": time.strftime("%Y-%m-%d")}
    gen_s = ensure_params(params_path())
    if gen_s:
        record["param_gen_s"] = round(gen_s, 1)
    import shutil

    work = REPO / ".lambdipy_cache" / "coldstart-8b"
    shutil.rmtree(work, ignore_errors=True)
    rdir = work / "recipes"
    rdir.mkdir(parents=True)
    (rdir / "jax-llama3-8b-local.toml").write_text(
        RECIPE_TMPL.format(params=params_path(), **DIMS))
    bundle = work / "bundle"
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "lambdipy_tpu", "build",
         "jax-llama3-8b-local", "--recipe-dir", str(rdir),
         "--out", str(bundle)],
        capture_output=True, text=True, cwd=str(REPO), timeout=1800,
        env=child_env({"LAMBDIPY_WARM_TIMEOUT": "1500"}))
    if proc.returncode != 0:
        raise RuntimeError(f"build failed: {proc.stderr[-800:]}")
    record["build_s"] = round(time.monotonic() - t0, 1)

    rt = LocalRuntime(work / "deployments.json")
    t0 = time.monotonic()
    rt.deploy("c8b", bundle, ready_timeout=1800.0)
    record["deploy_wall_s"] = round(time.monotonic() - t0, 1)
    try:
        health = rt.health("c8b")
        cs = health["cold_start"]
        record["cold_start_s"] = round(cs.get("total", 0.0), 1)
        record["cold_start_stages"] = {k: round(v, 2)
                                       for k, v in cs.items()}
        # overlap diagnostics (VERDICT r5 #5): how many serving programs
        # the boot deserialized CONCURRENTLY with the weight upload, how
        # long that preload ran, and the AOT hit count — distinguishes
        # "overlap engaged and hid program loads" from "aot/ was empty
        # and warmup paid fresh compiles"
        try:
            h = rt.metrics("c8b").get("handler", {})
            record["aot_preload"] = h.get("aot_preload")
            record["aot_hits"] = h.get("aot_hits")
            record["warmup_compile_count"] = h.get("compile_count")
        except Exception as e:  # diagnostics must not fail the mode
            record["aot_preload"] = f"unavailable: {e}"
        times = []
        for _ in range(n_invokes):
            t = time.monotonic()
            out = rt.invoke("c8b", {"tokens": [[1, 2, 3, 4, 5, 6, 7, 8]],
                                    "max_new_tokens": 32}, timeout=300.0)
            assert out.get("ok"), out
            times.append((time.monotonic() - t) * 1e3)
        record["invoke_p50_ms"] = round(statistics.median(times), 1)
        record["invoke_decode_tok_s"] = round(
            32 / (statistics.median(times) / 1e3), 1)
    finally:
        rt.stop("c8b")
    # the bundle can hold a full COPY of the ~8.5 GB fpk (the hardlink
    # falls back to copy across filesystems). Reached only on success,
    # so failure keeps the serve log for diagnosis.
    shutil.rmtree(work, ignore_errors=True)
    return record


def measure_speculative(n_new: int = 64, k: int = 8) -> dict:
    """Speculative decode at 8B on a cyclic continuation (the workload
    class lookup-drafting exists for): tokens-per-weight-read and
    effective tok/s vs the plain path and the 1-token-per-read roofline."""
    import statistics

    import jax.numpy as jnp
    import numpy as np

    from lambdipy_tpu.models import registry

    params = _load_params()
    adapter = registry.get("llama3-8b").build(
        dtype="bfloat16", quant="int8", extra=dict(DIMS))
    server = adapter.make_server(params)
    import jax

    rec = {"dims": f"{DIMS['hidden']}x{DIMS['layers']}x{DIMS['vocab_size']}",
           "k": k, "n_new": n_new,
           "platform": jax.devices()[0].platform,
           "measured_at": time.strftime("%Y-%m-%d")}
    prompt = [17, 23, 5, 99, 41, 7, 123, 64] * 4

    server.generate(prompt, max_new_tokens=n_new)  # compile + warm
    times = [_timed(lambda: server.generate(prompt, max_new_tokens=n_new))
             for _ in range(5)]
    plain_ms = max(0.1, statistics.median(times))
    rec["plain_tok_s"] = round(n_new / (plain_ms / 1e3), 1)

    spec0, stats = server.generate_speculative(
        prompt, max_new_tokens=n_new, k=k, return_stats=True)
    ref = server.generate(prompt, max_new_tokens=n_new)
    rec["greedy_agreement"] = f"{int(np.sum(spec0[0] == ref[0]))}/{n_new}"
    times = [_timed(lambda: server.generate_speculative(
        prompt, max_new_tokens=n_new, k=k)) for _ in range(5)]
    spec_ms = max(0.1, statistics.median(times))
    rec["spec_tok_s"] = round(n_new / (spec_ms / 1e3), 1)
    rec["spec_stats"] = stats
    from lambdipy_tpu.models.llama import LlamaConfig
    from lambdipy_tpu.utils import roofline

    peaks = _peaks()
    if peaks is not None:
        cfg = LlamaConfig(**DIMS, quant="int8", dtype=jnp.bfloat16)
        rec["roofline_plain_b1_tok_s"] = round(
            roofline.llama_decode_tok_s_bound(
                cfg, batch=1, cache_len=len(prompt) + n_new // 2,
                peaks=peaks), 1)
    rec["speedup_vs_plain"] = round(rec["spec_tok_s"] / rec["plain_tok_s"],
                                    2)
    return rec


def measure_concurrent(n_requests: int = 8, n_new: int = 64) -> dict:
    """Continuous-batching throughput at 8B (VERDICT r5 #6): N staggered
    concurrent requests through the engine vs serving them one after
    another. Decode is weight-bytes-bound, so the engine's shared
    segment steps should put the concurrent wall close to ONE request's
    time, not N of them.

    Parity accounting: the CPU f32 tests assert BITWISE solo parity
    (same program widths, exact arithmetic). This on-chip mode instead
    reports per-request token agreement: at 8B random-init dims the
    logit argmax gaps sit at bf16 resolution, and a solo join prefills
    through the 1-row program while staggered concurrent joins
    group-prefill as one ragged b-row call — programs of different
    width legally differ in bf16 reduction order, so near-tied first
    tokens can flip (the spec mode's greedy_agreement shows the same
    physics; segment steps themselves are always slots-wide and
    identical). A loose agreement floor still catches real packing
    bugs, which corrupt rows wholesale rather than flipping
    occasional near-ties."""
    import threading

    import numpy as np

    from lambdipy_tpu.models import registry
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher

    params = _load_params()
    adapter = registry.get("llama3-8b").build(
        dtype="bfloat16", quant="int8", extra=dict(DIMS))
    server = adapter.make_server(params)
    cb = ContinuousBatcher(server, slots=n_requests, segment=16)
    rec = {"dims": f"{DIMS['hidden']}x{DIMS['layers']}x{DIMS['vocab_size']}",
           "n_requests": n_requests,
           "n_new": n_new, "measured_at": time.strftime("%Y-%m-%d")}
    prompts = [[11 + i, 23, 5, 99, 41, 7, 123, 64] for i in range(n_requests)]

    # warm every program (prefill bucket, pack, B-slot segment) and
    # capture the solo baselines through the SAME engine
    solo = [cb.generate(p, max_new_tokens=n_new) for p in prompts]
    t0 = time.monotonic()
    for p in prompts:
        cb.generate(p, max_new_tokens=n_new)
    rec["serial_wall_s"] = round(time.monotonic() - t0, 2)

    results: list = [None] * n_requests
    errors: list = []

    def fire(i):
        time.sleep(0.01 * i)  # staggered arrivals: mid-flight joins
        try:
            results[i] = cb.generate(prompts[i], max_new_tokens=n_new)
        except Exception as e:  # surfaced after join — a thread's
            errors.append((i, e))  # traceback otherwise only hits stderr

    # UNTIMED staggered bursts first: a concurrent burst exercises
    # programs the solo path never compiles (the b-row group-prefill
    # and mid-flight pack buckets) — the first burst pays tens of
    # seconds of compiles and would read as a slowdown when what was
    # measured was compilation.
    # Two bursts: joiner grouping is timing-dependent, so a second pass
    # catches power-of-two group buckets the first happened to miss.
    for _ in range(2):
        warm_threads = [threading.Thread(target=fire, args=(i,))
                        for i in range(n_requests)]
        for t in warm_threads:
            t.start()
        for t in warm_threads:
            t.join()
        if errors or any(r is None for r in results):
            # a failed warm burst means the timed burst would re-pay
            # first-burst compiles (the artifact this warmup exists to
            # remove) or run against a degraded engine — refuse
            raise RuntimeError(f"warm burst failed: {errors or results}")
    results = [None] * n_requests

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(n_requests)]
    before = cb.stats()  # counters are lifetime-cumulative: publish the
    t0 = time.monotonic()  # concurrent run's DELTA, not warm+serial too
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    for i, r in enumerate(results):  # a crashed thread must not read as
        assert r is not None, f"request {i} returned no result"
        assert np.asarray(r).shape == np.asarray(solo[i]).shape, \
            f"request {i} shape {np.asarray(r).shape}"  # a parity stat
    agree = [float(np.mean(np.asarray(results[i]) == np.asarray(solo[i])))
             for i in range(n_requests)]
    exact = sum(bool(np.array_equal(results[i], solo[i]))
                for i in range(n_requests))
    rec["rows_bitwise_equal"] = f"{exact}/{n_requests}"
    rec["solo_agreement_min"] = round(min(agree), 3)
    rec["solo_agreement_mean"] = round(sum(agree) / len(agree), 3)
    # gross-corruption backstop, deliberately loose: ONE flipped
    # near-tie early in a row legitimately de-correlates that row's
    # whole continuation, so positional agreement can be low for a
    # correct engine at random-init weights — but a packing bug is
    # systematic (every row corrupt, nothing bitwise-equal)
    if exact == 0 and rec["solo_agreement_mean"] < 0.2:
        raise AssertionError(
            f"no row matches solo and agreement is near zero — "
            f"engine corruption, not tie-flipping: {rec}")
    rec["concurrent_wall_s"] = round(wall, 2)
    rec["speedup_vs_serial"] = round(rec["serial_wall_s"] / wall, 2)
    rec["concurrent_tok_s"] = round(n_requests * n_new / wall, 1)
    after = cb.stats()
    rec["engine"] = {k: after[k] - before[k]
                     for k in ("segments_run", "rows_in_segments",
                               "requests_served")}
    return rec


def _load_params():
    """Shared measurement preamble: bulk-load the 8B params onto the device
    and wait for the upload to finish."""
    import jax

    from lambdipy_tpu.bundle import flatpack

    ensure_params(params_path())
    return jax.block_until_ready(flatpack.device_load(params_path()))


def measure_kv_quant(n_new: int = 64, context: int = 1024) -> dict:
    """kv_quant='int8' at real 8B dims and ~1k context (VERDICT r5 #7):
    DECODE throughput vs the bf16-KV record at the same context — the
    KV read is material in the b8 roofline there — plus the max
    logprob deviation over the emitted tokens as the 32-layer error
    bound (the toy-dims bound was only extrapolated).

    Differencing design (v2 — the first on-chip run published numbers
    ~30% over the roofline bound and taught two traps):

    - decode steps are BUCKETED: ``generate(max_new_tokens=1)`` runs a
      ``min_bucket``(=16)-step scan, so differencing full(64) against
      it spans 48 steps, not 63. Both differenced calls now use
      power-of-two ``max_new`` (64 and 32) whose step counts are exact.
    - the prompt bucket is clamped by ``max_len - steps``, so at
      max_len=1024 the two calls prefill through DIFFERENT-width
      programs and the difference is contaminated by prefill. The
      measurement dims raise max_len to 2048 (capacity only — the live
      cache array is sized prompt_bucket + steps, so the decoded
      window stays ~1k) and both calls share the identical 1024-wide
      prefill program; their difference is exactly
      ``n_new - n_new//2`` decode steps over a ~1.06k-token cache."""
    import statistics

    import numpy as np
    import jax.numpy as jnp

    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import LlamaConfig
    from lambdipy_tpu.utils import roofline

    params = _load_params()
    peaks = _peaks()
    rec: dict = {"dims": f"{DIMS['hidden']}x{DIMS['layers']}"
                         f"x{DIMS['vocab_size']}",
                 "context": context, "n_new": n_new,
                 "measured_at": time.strftime("%Y-%m-%d")}
    half = n_new // 2
    assert n_new >= 32 and n_new & (n_new - 1) == 0, \
        "n_new must be a power of two >= 32 so both step counts are exact"
    prompt = list(range(1, context - n_new + 1))  # prefill bucket = context
    mdims = dict(DIMS, max_len=max(2 * context, DIMS["max_len"]))
    variants = {
        "bf16_kv": dict(mdims),
        "int8_kv": dict(mdims, kv_quant="int8"),
    }
    outs = {}
    for name, extra in variants.items():
        adapter = registry.get("llama3-8b").build(
            dtype="bfloat16", quant="int8", extra=extra)
        server = adapter.make_server(params)
        cfg = LlamaConfig(**mdims, kv_quant=extra.get("kv_quant"),
                          quant="int8", dtype=jnp.bfloat16)
        for b in (1, 8):
            rows = [prompt] * b

            def full():
                return server.generate(rows, max_new_tokens=n_new)

            def half_call():
                return server.generate(rows, max_new_tokens=half)

            full()          # compile + warm both programs
            half_call()
            # decode-only: identical prefill program in both calls, so
            # each PAIRED difference is exactly (n_new - half) decode
            # steps. Pairing full/half back-to-back makes slow drift in
            # the prefill-dominated call time cancel within a pair
            # instead of landing in the subtraction; the pair spread is
            # published so a noisy host shows up in the record.
            diffs = sorted(_timed(full) - _timed(half_call)
                           for _ in range(7))
            net_ms = max(0.1, statistics.median(diffs))
            rec[f"{name}_b{b}_pair_spread_ms"] = round(
                diffs[-2] - diffs[1], 1)
            rec[f"{name}_b{b}_tok_s"] = round(
                b * (n_new - half) / (net_ms / 1e3), 1)
            if peaks is not None:
                rec[f"{name}_b{b}_roofline_tok_s"] = round(
                    roofline.llama_decode_tok_s_bound(
                        cfg, batch=b, peaks=peaks,
                        cache_len=context + (n_new + half) // 2), 1)
        toks, lps = server.generate(prompt, max_new_tokens=n_new,
                                    return_logprobs=True)
        outs[name] = (np.asarray(toks), np.asarray(lps))
    agree = int(np.sum(outs["bf16_kv"][0] == outs["int8_kv"][0]))
    rec["greedy_agreement"] = f"{agree}/{n_new}"
    # logprob deviation over the agreeing prefix — past the first
    # divergence the sequences differ and the comparison is moot. A
    # token-0 divergence records null rather than silently omitting
    # the bound the record exists to publish.
    same = outs["bf16_kv"][0][0] == outs["int8_kv"][0][0]
    upto = int(np.argmin(same)) if not same.all() else n_new
    if upto:
        delta = np.abs(outs["bf16_kv"][1][0][:upto]
                       - outs["int8_kv"][1][0][:upto])
        rec["max_logprob_delta"] = round(float(delta.max()), 4)
    else:
        rec["max_logprob_delta"] = None
    rec["agreeing_prefix"] = upto
    return rec


def measure_prefill(lens=(512, 1024, 2048, 4096), flash_len: int = 8192,
                    batch_len: int = 512, batch: int = 4) -> dict:
    """The prefill table (VERDICT r5 #4 + #9): dense prefill
    latency/MFU at 512/1k/2k/4k, a BATCHED 512 prefill (does MFU scale
    with rows?), and the long-context paths at 8k — flash attention
    (dense would materialize an 8.6 GB score tensor per layer) and
    chunked prefill — all at real 8B dims with an 8192 window.

    Decode-scan exclusion (v2): ``generate(max_new_tokens=1)`` runs a
    bucketed ``min_bucket``-step decode scan after the prefill — at 8B
    that's ~16 weight reads, ~180 ms, swamping short-prefill rows (the
    first published table undercalled 512-token MFU ~4x). The servers
    here run with ``min_bucket = 1`` so the scan is ONE step, and each
    row reports ``net_ms`` with that step's separately-differenced cost
    subtracted (raw timing kept as ``raw_ms``)."""
    import statistics

    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import LlamaConfig
    from lambdipy_tpu.utils import roofline

    dims = dict(DIMS, max_len=max(flash_len, 8192))
    params = _load_params()
    peaks = _peaks()
    cfg = LlamaConfig(**dims, quant="int8", dtype=jnp.bfloat16)

    def mfu(net_ms, **shape):
        if peaks is None:
            return "not measured (no TPU)"
        cost = roofline.llama_prefill_cost(cfg, **shape)
        return cost.utilization(net_ms / 1e3, peaks)["mfu"]

    rec: dict = {"dims": f"{dims['hidden']}x{dims['layers']}"
                         f"x{dims['vocab_size']}",
                 "max_len": dims["max_len"],
                 "measured_at": time.strftime("%Y-%m-%d"),
                 "rows": []}

    step_ms = 0.0  # set once below; the one-step scan cost to subtract

    def time_prefill(server, L, b=1, label="dense"):
        rows = [list(range(1, L + 1))] * b
        t0 = time.monotonic()
        server.generate(rows, max_new_tokens=1)
        compile_s = time.monotonic() - t0
        times = [_timed(lambda: server.generate(rows, max_new_tokens=1))
                 for _ in range(3)]
        raw_ms = max(0.1, statistics.median(times))
        net_ms = max(0.1, raw_ms - step_ms)
        row = {"backend": label, "len": L, "batch": b,
               "net_ms": round(net_ms, 1), "raw_ms": round(raw_ms, 1),
               "mfu": mfu(net_ms, batch=b, seq_len=L),
               "compile_s": round(compile_s, 1)}
        rec["rows"].append(row)
        print(json.dumps(row), file=sys.stderr)

    adapter = registry.get("llama3-8b").build(
        dtype="bfloat16", quant="int8", extra=dims)
    server = adapter.make_server(params)
    # exact step counts for the correction differencing AND the one-step
    # scan after each timed prefill: power-of-two max_new is exact for
    # any min_bucket <= it, and min_bucket=1 makes max_new=1 exact too
    server.min_bucket = 1
    # difference the step cost at the LARGEST dense table length
    # (ADVICE r5): per-token KV at these dims is ~128 KB, so a step
    # against an 8k-deep cache reads ~12% more than one against 512 —
    # differencing at the small end under-subtracted from exactly the
    # long rows where the step is largest, inflating their net_ms.
    # Differencing at max(lens) is exact for the deepest dense row; the
    # residual biases are bounded by that same ~12%-of-one-step: short
    # rows are OVER-subtracted (their published MFU reads slightly
    # HIGH — step_ms is ~2% of a 512 prefill, so the bias is <1% of
    # MFU), and the flash row at flash_len > max(lens) is still
    # slightly under-subtracted (its dense-server step can't be
    # measured at 8k depth — that's the score tensor flash exists to
    # avoid).
    L0 = max(lens)
    rows0 = [list(range(1, L0 + 1))]
    server.generate(rows0, max_new_tokens=32)  # compile + warm
    server.generate(rows0, max_new_tokens=1)
    t32 = statistics.median(
        _timed(lambda: server.generate(rows0, max_new_tokens=32))
        for _ in range(5))
    t1 = statistics.median(
        _timed(lambda: server.generate(rows0, max_new_tokens=1))
        for _ in range(5))
    # 31 decode steps separate the two calls (identical prefill program)
    step_ms = max(0.0, (t32 - t1) / 31.0)
    rec["decode_step_ms"] = round(step_ms, 2)
    print(json.dumps({"decode_step_ms": rec["decode_step_ms"]}),
          file=sys.stderr)
    for L in lens:
        time_prefill(server, L)
    time_prefill(server, batch_len, b=batch)  # batched prefill
    # flash attention at 8k (the O(S)-memory fallback's reason to exist)
    fl = registry.get("llama3-8b").build(
        dtype="bfloat16", quant="int8",
        extra=dict(dims, attn_backend="flash"))
    fl_server = fl.make_server(params)
    fl_server.min_bucket = 1
    time_prefill(fl_server, flash_len, label="flash")
    # chunked prefill at 8k via the prefix machinery (512-token chunks)
    ck_server = adapter.make_server(params, prefill_chunk=512)
    long_tokens = list(range(1, flash_len + 1))
    ck_server.cache_prefix(long_tokens[:1024])  # compile first+ext

    def chunked_once():
        key = ck_server.cache_prefix(long_tokens)
        # cache_prefix only SUBMITS the chunk walk: wait for the cache
        # it produced so the timed region ends with the device done
        with ck_server._prefix_lock:
            cache, _ = ck_server._prefixes.pop(key)  # pop: re-time fresh
        jax.block_until_ready(cache)

    t0 = time.monotonic()
    chunked_once()
    net_ms = max(0.1, (time.monotonic() - t0) * 1e3)
    row = {"backend": "chunked512", "len": flash_len, "batch": 1,
           "net_ms": round(net_ms, 1),
           "mfu": mfu(net_ms, batch=1, seq_len=flash_len)}
    rec["rows"].append(row)
    print(json.dumps(row), file=sys.stderr)
    # scaling decomposition (the "where do the missing MFU go" analysis,
    # VERDICT r5 #4): fit t(s) = c0 + c1*s + c2*s^2 over the dense b=1
    # points. The linear term is the weight-read + per-token matmul
    # work, the quadratic term is attention score/AV work, the constant
    # is dispatch/lm_head/fixed overhead — their shares at each length
    # say whether low prefill MFU is an attention problem (quadratic
    # share high) or an overhead problem (constant share high).
    dense = [r for r in rec["rows"] if r["backend"] == "dense"
             and r["batch"] == 1]
    # >= 4 points: with exactly 3 the quadratic fit degenerates to
    # interpolation and sample jitter maps straight into the published
    # coefficients (the decomposition needs a residual DOF to mean
    # anything)
    if len(dense) >= 4:
        import numpy as np

        s_arr = np.array([r["len"] for r in dense], float)
        t_arr = np.array([r["net_ms"] for r in dense], float)
        c2, c1, c0 = (float(c) for c in np.polyfit(s_arr, t_arr, 2))
        rec["scaling_fit"] = {
            "const_ms": round(c0, 2), "linear_ms_per_tok": round(c1, 4),
            "quad_ms_per_tok2": round(c2, 8),
            "shares_at": {
                str(int(s)): {
                    "const": round(c0 / t, 2),
                    "linear": round(c1 * s / t, 2),
                    "quad": round(c2 * s * s / t, 2)}
                for s, t in zip(s_arr, t_arr)},
        }
        print(json.dumps({"scaling_fit": rec["scaling_fit"]}),
              file=sys.stderr)
    return rec


def _publish(update) -> None:
    """Apply ``update(published, config5)`` to BASELINE.json atomically
    enough for this single-writer script (one read-modify-write)."""
    from publish_util import write_doc

    path = REPO / "BASELINE.json"
    doc = json.loads(path.read_text())
    pub = doc.setdefault("published", {})
    update(pub, pub.setdefault("config5", {}))
    write_doc(doc, path)
    print(f"published -> {path}", file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", default="1,8")
    # None = "flag omitted": modes pick their own default (64, except
    # kv-quant's 128) and an EXPLICIT --n-new always wins — keying the
    # kv-quant override on the default value made --n-new 64 unreachable
    ap.add_argument("--n-new", type=int, default=None)
    ap.add_argument("--cold-start", action="store_true",
                    help="measure the build->deploy->invoke cold start "
                         "instead of decode throughput")
    ap.add_argument("--speculative", action="store_true",
                    help="measure speculative vs plain b1 decode")
    ap.add_argument("--k", type=int, default=8,
                    help="draft length for --speculative")
    ap.add_argument("--concurrent", action="store_true",
                    help="measure N staggered requests through the "
                         "continuous-batching engine vs serial")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--prefill-table", action="store_true",
                    help="measure the prefill table: dense 512/1k/4k, "
                         "batched 512, flash + chunked at 8k")
    ap.add_argument("--kv-quant", action="store_true",
                    help="measure int8-KV vs bf16-KV decode at 1k "
                         "context + the 32-layer logprob error bound")
    ap.add_argument("--publish", action="store_true",
                    help="record into BASELINE.json published.config5")
    args = ap.parse_args()
    n_new = 64 if args.n_new is None else args.n_new
    if args.prefill_table:
        record = measure_prefill()
        print(json.dumps(record, indent=2))
        if args.publish:
            _publish(lambda pub, c5: c5.__setitem__("prefill", record))
        return 0
    if args.kv_quant:
        # the differenced signal is (n_new/2) decode steps; 128 doubles
        # it vs the shared 64 default without moving the ~1k window much
        # — but only when --n-new was OMITTED (an explicit value wins)
        record = measure_kv_quant(
            n_new=128 if args.n_new is None else args.n_new)
        print(json.dumps(record, indent=2))
        if args.publish:
            _publish(lambda pub, c5: c5.__setitem__("kv_int8", record))
        return 0
    if args.concurrent:
        record = measure_concurrent(n_requests=args.n_requests,
                                    n_new=n_new)
        print(json.dumps(record, indent=2))
        if args.publish:
            _publish(lambda pub, c5: c5.__setitem__("concurrent", record))
        return 0
    if args.speculative:
        record = measure_speculative(n_new=n_new, k=args.k)
        print(json.dumps(record, indent=2))
        if args.publish:
            _publish(lambda pub, c5: c5.__setitem__("speculative", record))
        return 0
    if args.cold_start:
        record = measure_cold_start()
        print(json.dumps(record, indent=2))
        if args.publish:
            _publish(lambda pub, c5: c5.update(
                {f"cold_{k}" if k in ("build_s",) else k: v
                 for k, v in record.items()
                 if k not in ("dims", "measured_at")}))
        return 0
    batches = tuple(int(b) for b in args.batch.split(","))
    record = measure(batches=batches, n_new=n_new)
    print(json.dumps(record, indent=2))
    if args.publish:
        def replace(pub, c5):
            from publish_util import MICRO_RECIPE, RECIPE_8B

            # keep the micro exemplar visible beside the real-dims record,
            # but any dict-valued sub-records in config5 are 8B-mode
            # output (speculative/concurrent/kv_int8/prefill/cold stages)
            # and stay with config5 rather than moving under the micro key
            if c5.get("recipe") == MICRO_RECIPE:
                pub["config5_micro"] = {
                    k: v for k, v in c5.items() if not isinstance(v, dict)}
                c5 = pub["config5"] = {
                    k: v for k, v in c5.items() if isinstance(v, dict)}
            # refresh semantics for the decode-owned scalars (incl. the
            # conditional param_gen_s): drop them first so a partial run
            # (e.g. --batch 1, or one hitting the flatpack cache) can't
            # leave stale metrics stamped with the new measured_at — then
            # merge, preserving the other modes' sub-records
            import re

            for k in [k for k in c5
                      if re.match(r"b\d+_|prefill_|param_gen_s", k)]:
                del c5[k]
            record["recipe"] = RECIPE_8B
            c5.update(record)

        _publish(replace)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
