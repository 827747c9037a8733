"""Handler builders for the builtin model recipes.

A handler is what a bundle's generated ``handler.py`` delegates to: a
builder ``(spec, ctx) -> state`` where the returned state exposes
``invoke(request: dict) -> dict``. Requests/responses are JSON dicts (the
Lambda handler shape the reference's users write by hand — SURVEY.md §4 B
"user zips build/ + handler.py"; here handlers are generated and TPU-aware).

Every JAX handler jits once at init (cold start), accepts
``{"warmup": true}``, and supports ``{"random": true}`` for benchmarking
without a real payload.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable

from lambdipy_tpu.runtime import spans


@dataclass
class HandlerState:
    invoke_fn: Callable[[dict], dict]
    meta: dict
    # optional live-stats provider merged into /metrics (e.g. the decode
    # server's bucket/compile counters); must be cheap and non-blocking
    stats_fn: Callable[[], dict] | None = None
    # optional streaming invoke: request -> iterator of chunk dicts,
    # last one carrying {"done": true}. None = handler can't stream.
    invoke_stream_fn: Callable[[dict], Any] | None = None
    # optional host-only probe: prompt token ids -> tokens the automatic
    # prefix cache would reuse. The HTTP scheduler prices admission on
    # the SUFFIX a request will actually prefill (runtime/server.py) —
    # without this, deadline shedding over-rejects cache-hit requests.
    prefix_probe: Callable[[Any], int] | None = None
    # optional O(1) readiness probe: True while a background warm
    # (bucket / group-prefill compiles) is in flight. /healthz reads
    # THIS — not the full stats() document — once per fleet probe
    # interval, so it must stay a bare flag read, no locks or
    # serialization.
    warming_fn: Callable[[], bool] | None = None
    # optional O(1) engine-fault probe: {"wedged", "restarting",
    # "degrade_level"} from the continuous engine's fault-isolation
    # layer. /healthz flips ready:false (and reports wedged:true) on it
    # so the fleet router ejects a wedged replica at probe speed, and
    # server admission 503s instead of queueing requests into a dead
    # engine. Same cost contract as warming_fn: bare attribute reads.
    engine_fault_fn: Callable[[], dict] | None = None
    # optional hook of the continuous engine's slot handover: called with
    # the scheduler's grant_ahead, which the engine then calls (with the
    # longest prompt its barrier prefills) whenever a row is about to
    # release its run slot
    row_ending_hook: Callable[[Callable[[int], Any]], None] | None = None
    # optional disaggregated-serving KV ship surface (runtime/kvwire.py
    # framing over the prefix store): kv_export_fn serves a request's
    # whole-block head as a wire frame (prefilling missing blocks — on
    # a prefill-class replica this IS the request's prefill phase);
    # kv_import_fn registers a shipped frame in the radix tree. None =
    # no prefix store, /v1/kv/* answers 404.
    kv_export_fn: Callable[[dict], Any] | None = None
    kv_import_fn: Callable[[bytes], dict] | None = None
    # CHUNKED (pipelined-ship) twins: kv_export_stream_fn returns a
    # generator of wire frames (LKVS header first, then LKVC chunks —
    # each flushed as soon as the prefix-store walk produces its block
    # group, so wire transfer overlaps the remaining prefill);
    # kv_import_stream_fn consumes an iterator of raw byte chunks off
    # a chunked-transfer request body, staging each chunk as it lands
    # and attaching to the radix tree only on a complete stream (a
    # truncated/garbage stream rolls back, touching nothing).
    kv_export_stream_fn: Callable[[dict], Any] | None = None
    kv_import_stream_fn: Callable[[Any], dict] | None = None
    # optional host-only KV presence probe ({"tokens": [...]} ->
    # {"matched": n}): the router's import-miss PULL checks it before
    # trusting a ship-dedup entry (an arena reset may have flushed the
    # blocks the dedup cache still claims are there). O(depth) tree
    # walk, no device work.
    kv_probe_fn: Callable[[dict], dict] | None = None
    # optional session close (DELETE /v1/sessions/{id} -> release the
    # session's prefix-store pins now instead of waiting out the lease)
    session_end_fn: Callable[[str], dict] | None = None
    # optional host-only invariant sweep (GET /v1/debug/invariants):
    # pagepool conservation + prefix-store pin/content accounting as
    # {"ok", "checks"} — the chaos checker's quiesce probe. Cheap and
    # lock-bounded; never device work.
    debug_invariants_fn: Callable[[], dict] | None = None
    # optional host-only fault control (POST /v1/debug/faults): arm a
    # runtime/faults.py spec on the replica's live plan or clear it —
    # the chaos soak's nemesis arms composed faults on a timeline
    # through this instead of restarting the process per spec.
    faults_admin_fn: Callable[[dict], dict] | None = None
    # optional host-only live-knob control (POST /v1/debug/knobs): the
    # elastic fleet controller retunes a serving replica's
    # pipeline_depth / spec_k from its own published signals. Both
    # knobs are read per-dispatch by the continuous engine, so a live
    # write is race-free; the handler clamps/buckets and refuses what
    # the boot config never enabled.
    knobs_admin_fn: Callable[[dict], dict] | None = None

    def invoke(self, request: dict) -> dict:
        t0 = time.monotonic()
        out = self.invoke_fn(dict(request or {}))
        out.setdefault("latency_ms", round((time.monotonic() - t0) * 1e3, 3))
        return out

    def invoke_stream(self, request: dict):
        if self.invoke_stream_fn is None:
            raise ValueError("handler does not support streaming")
        return self.invoke_stream_fn(dict(request or {}))

    def stats(self) -> dict:
        if self.stats_fn is None:
            return {}
        try:
            return self.stats_fn()
        except Exception:  # stats must never break the metrics endpoint
            return {}


# --------------------------------------------------------------------------


def hello_handler(spec: dict, ctx) -> HandlerState:
    """Config 1: numpy+scipy hello world — a small deterministic linalg op
    proving the vendored native stack works inside the bundle."""
    import numpy as np
    from scipy import linalg

    def invoke(req: dict) -> dict:
        n = int(req.get("n", 64))
        rng = np.random.default_rng(int(req.get("seed", 0)))
        a = rng.normal(size=(n, n))
        sign, logdet = np.linalg.slogdet(a @ a.T + n * np.eye(n))
        lu = linalg.lu_factor(a + n * np.eye(n))[0]
        return {
            "ok": True,
            "n": n,
            "logdet": float(logdet * sign),
            "lu_trace": float(np.trace(lu)),
            "numpy": np.__version__,
        }

    return HandlerState(invoke_fn=invoke, meta={"model": "hello"})


def tabular_handler(spec: dict, ctx) -> HandlerState:
    """Config 2: sklearn (+xgboost when vendored) tabular inference."""
    import numpy as np

    from lambdipy_tpu.models import registry

    clf = registry.load_params("tabular", ctx.params_dir)
    n_features = getattr(clf, "n_features_in_", 16)
    degraded = ctx.degraded()

    def invoke(req: dict) -> dict:
        if req.get("warmup") or req.get("random"):
            x = np.zeros((1, n_features))
        else:
            x = np.asarray(req["instances"], dtype=float)
            if x.ndim == 1:
                x = x[None, :]
        proba = clf.predict_proba(x)
        return {
            "ok": True,
            "predictions": proba.argmax(1).tolist(),
            "probabilities": proba.tolist(),
            "degraded": degraded,  # e.g. ["xgboost"] in this offline env
        }

    return HandlerState(invoke_fn=invoke,
                        meta={"model": "tabular", "n_features": n_features})


# --------------------------------------------------------------------------


def _jax_adapter_and_params(spec: dict, ctx):
    from lambdipy_tpu.models import registry

    extra = dict(spec.get("extra") or {})
    # HF-imported bundles record the converted architecture in the
    # manifest; it overrides the builder defaults so the module matches
    # the checkpoint exactly (models/convert.py save_hf_params)
    info = (getattr(ctx, "manifest", None) or {}).get("payload", {}) or {}
    extra.update((info.get("params_info") or {}).get("config") or {})
    adapter = registry.get(spec["model"]).build(
        dtype=spec.get("dtype", "bfloat16"), quant=spec.get("quant"),
        extra=extra)
    if ctx.params_dir is not None:
        # single-device payloads take the bulk-transfer device load; a
        # mesh payload loads host-side so the sharder can place it
        single = not any(v > 1 for v in (spec.get("mesh") or {}).values())
        # file -> device as far as this call goes: the first transfer
        # waits for the backend (the loader's pjrt-init thread), the
        # transfers are queued, and the first program that takes the
        # weights waits for them on the device (a meshed payload's are
        # placed by _maybe_shard, under the same name)
        with spans.span("boot.params"):
            params = registry.load_params(spec["model"], ctx.params_dir,
                                          device=single)
    else:
        params = adapter.init_params(seed=0)
    return adapter, params


def _aot_or_jit(ctx, fn, example_args, mesh):
    """Boot from the bundle's AOT store (runtime/aot.py). Single-chip
    payloads get both tiers; meshed payloads get the StableHLO tier keyed
    by (topology, mesh shape), so a multi-device boot stops re-tracing
    once any boot on the same topology has saved it.

    AOT artifacts are shape-specialized to the spec's example batch, so a
    hit is wrapped with a shape dispatch: example-shaped requests (the hot
    serving path) run the AOT program, anything else re-traces through a
    plain jit fallback exactly as before AOT existed.
    """
    import jax

    from lambdipy_tpu.runtime.aot import cached_jit

    cached, src = cached_jit(ctx, "forward", fn, example_args, mesh=mesh)
    if src == "jit":
        return cached, src
    fallback = jax.jit(fn)
    shapes = tuple(getattr(a, "shape", None) for a in example_args[1:])

    def dispatch(params, *args):
        if tuple(getattr(a, "shape", None) for a in args) == shapes:
            return cached(params, *args)
        return fallback(params, *args)

    return dispatch, src


def _maybe_shard(adapter, params, spec: dict):
    """Place params on the payload mesh when it needs more than one device;
    single-chip serving device-puts them once instead.

    The single-chip device_put is load-bearing, not cosmetic: checkpoint
    restore yields HOST arrays, and jit copies a host array to the device
    again on EVERY call. A declared mesh that needs more devices than are
    visible is an error: serving it replicated on one device would hide
    the missing chips behind a slower, differently-sized deployment (on
    CPU, force host devices with
    XLA_FLAGS=--xla_force_host_platform_device_count=N)."""
    import jax

    mesh_shape = {k: v for k, v in (spec.get("mesh") or {}).items() if v > 1}
    if not mesh_shape:
        return jax.device_put(params), None
    import math

    from lambdipy_tpu.parallel.mesh import make_mesh
    from lambdipy_tpu.parallel.sharding import shard_params

    needed = math.prod(mesh_shape.values())
    if len(jax.devices()) < needed:
        raise ValueError(
            f"mesh {mesh_shape} needs {needed} devices but only "
            f"{len(jax.devices())} {jax.devices()[0].platform} device(s) "
            "are visible")
    # the first `needed` devices, not all of them: a host with more
    # chips than the declared mesh (or a CPU with forced host devices)
    # must still honor the bundle's shape instead of erroring on the
    # device-count mismatch
    mesh = make_mesh(mesh_shape, devices=jax.devices()[:needed])
    with spans.span("boot.params"):
        return shard_params(params, mesh, adapter.tp_rules), mesh


def image_classify_handler(spec: dict, ctx) -> HandlerState:
    """Config 3 / north star: ResNet-50 image classification on v5e."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    adapter, params = _jax_adapter_and_params(spec, ctx)
    params, mesh = _maybe_shard(adapter, params, spec)
    batch = int(spec.get("batch_size", 1))
    example = adapter.example_batch(batch)[0]
    fwd, aot_src = _aot_or_jit(ctx, adapter.forward, (params, example), mesh)

    def run(x):
        if mesh is not None:
            from lambdipy_tpu.parallel.mesh import use_mesh

            with use_mesh(mesh):
                return fwd(params, x)
        return fwd(params, x)

    def invoke(req: dict) -> dict:
        if req.get("warmup") or req.get("random"):
            x = example
        else:
            x = jnp.asarray(np.asarray(req["image"], dtype=np.float32),
                            example.dtype)
            if x.ndim == 3:
                x = x[None, ...]
        logits = np.asarray(jax.device_get(run(x)), dtype=np.float32)
        top = np.argsort(-logits, axis=-1)[:, :5]
        return {
            "ok": True,
            "top5": top.tolist(),
            "top1": top[:, 0].tolist(),
            "logit_max": float(logits.max()),
        }

    return HandlerState(invoke_fn=invoke, meta={
        "model": spec["model"], "batch": batch,
        "sharded": mesh is not None, "aot": aot_src,
        "platform": jax.devices()[0].platform,
    })


def text_classify_handler(spec: dict, ctx) -> HandlerState:
    """Config 4 (jax path): BERT text classification."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    adapter, params = _jax_adapter_and_params(spec, ctx)
    params, mesh = _maybe_shard(adapter, params, spec)
    cfg = adapter.config
    example_ids, example_mask = adapter.example_batch(int(spec.get("batch_size", 1)))
    fwd, aot_src = _aot_or_jit(
        ctx, adapter.forward, (params, example_ids, example_mask), mesh)

    def run(ids, mask):
        if mesh is not None:
            from lambdipy_tpu.parallel.mesh import use_mesh

            with use_mesh(mesh):
                return fwd(params, ids, mask)
        return fwd(params, ids, mask)

    def invoke(req: dict) -> dict:
        if req.get("warmup") or req.get("random"):
            ids, mask = example_ids, example_mask
        else:
            raw = np.asarray(req["input_ids"], dtype=np.int32)
            if raw.ndim == 1:
                raw = raw[None, :]
            ids = np.zeros((raw.shape[0], cfg.max_len), np.int32)
            mask = np.zeros((raw.shape[0], cfg.max_len), np.int32)
            n = min(cfg.max_len, raw.shape[1])
            ids[:, :n] = raw[:, :n]
            mask[:, :n] = 1
            ids, mask = jnp.asarray(ids), jnp.asarray(mask)
        logits = np.asarray(jax.device_get(run(ids, mask)), dtype=np.float32)
        return {
            "ok": True,
            "labels": logits.argmax(-1).tolist(),
            "logits": logits.tolist(),
        }

    return HandlerState(invoke_fn=invoke, meta={
        "model": spec["model"], "max_len": cfg.max_len,
        "sharded": mesh is not None, "aot": aot_src,
    })


def generate_handler(spec: dict, ctx) -> HandlerState:
    """Config 5: Llama TP int8 generation (greedy by default; requests may
    set temperature / top_k / top_p / seed / eos_id for sampled decode)."""
    import threading as _threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    extra = spec.get("extra") or {}
    # tensor-parallel sharded serving (ROADMAP direction 3): the `mesh`
    # bundle extra ("tp=2", "2x2", "tp=2,sp=1"...) — or LAMBDIPY_MESH,
    # the `lambdipy serve --mesh` bridge; an explicit extra wins over
    # the env like every other knob — resolves into the spec-level mesh
    # shape `_maybe_shard` places params by. The whole serve stack then
    # runs SPMD over the mesh: attention heads / MLP hidden shard over
    # tp, the KV cache over kv_heads, host-side engine logic unchanged.
    # CPU testing: XLA_FLAGS=--xla_force_host_platform_device_count=N.
    import os as _os_env

    raw_mesh = extra.get("mesh", _os_env.environ.get("LAMBDIPY_MESH"))
    if raw_mesh is not None:
        from lambdipy_tpu.parallel.mesh import parse_mesh_spec

        # an explicit knob REPLACES any spec-level [payload.mesh] —
        # including replacing it with nothing: `--mesh off` (parse ->
        # {}) must actually serve single-device, not silently keep the
        # bundle's declared mesh
        spec = {**spec, "mesh": parse_mesh_spec(str(raw_mesh))}
    # Cold-start overlap (VERDICT r5 #5): deserializing and loading the
    # AOT executables needs no weights, and the bulk weight upload needs
    # no programs — run them CONCURRENTLY instead of serially. The store
    # is created before the params load and its preload thread joins
    # right after; LlamaServer then consumes the preloaded executables
    # with only the probe left to pay.
    serve_aot_store = None
    preload_state: dict = {}
    preload_thread = None
    single_spec = not any(v > 1 for v in (spec.get("mesh") or {}).values())
    if single_spec and getattr(ctx, "bundle_dir", None) is not None \
            and str(extra.get("serve_aot", "1")) != "0":
        from lambdipy_tpu.runtime.aot import AotStore

        serve_aot_store = AotStore(ctx.bundle_dir)
        # preload only the CURRENT generation's artifacts: an upgraded
        # bundle's aot/ dir keeps the previous generation's orphans, and
        # loading those onto the device would pay for executables load()
        # never reads
        from lambdipy_tpu.models.llama import LlamaServer as _LS

        preload_thread = _threading.Thread(
            target=lambda: preload_state.update(
                serve_aot_store.preload(prefix=_LS.aot_prefix())),
            daemon=True, name="aot-preload")
        preload_thread.start()

    adapter, params = _jax_adapter_and_params(spec, ctx)
    params, mesh = _maybe_shard(adapter, params, spec)
    if preload_thread is not None:
        preload_thread.join()
    default_new = int(extra.get("max_new_tokens", 16))
    # compile-once serving: prompt-length bucketing + runtime sampling
    # knobs, one compiled program per shape bucket (llama.LlamaServer)
    server = None
    batcher = None
    continuous = None  # set when batcher is the ContinuousBatcher
    if adapter.make_server is not None:
        cap = extra.get("decode_cap")  # None = full context window
        server_caps = {"decode_cap": int(cap) if cap else None}
        if extra.get("prefix_cache_max") is not None:
            # operators serving many (or deliberately few) prefixes; an
            # explicit 0 means "smallest" (the server clamps to 1)
            server_caps["prefix_cache_max"] = int(extra["prefix_cache_max"])
        if extra.get("program_cache_max") is not None:
            # LRU bound on compiled programs; size to the workload's
            # bucket diversity (rising program_evictions in /metrics
            # means it is too small)
            server_caps["program_cache_max"] = int(extra["program_cache_max"])
        if extra.get("prefill_chunk") is not None:
            # long prefixes prefill in fixed-width chunks: dense-attention
            # memory O(chunk x s) instead of O(s^2), O(1) programs
            server_caps["prefill_chunk"] = int(extra["prefill_chunk"])
        if extra.get("min_bucket") is not None:
            # smallest prompt/decode bucket. The default 16 makes a
            # max_new_tokens=1 request run a 16-step scan — ~16 wasted
            # weight reads (~165 ms at 8B): scoring/logprob workloads
            # dominated by tiny decodes should set 1, trading a few
            # more compiled program variants per distinct length
            server_caps["min_bucket"] = int(extra["min_bucket"])
        if serve_aot_store is not None:
            # serving programs ride the bundle's AOT exec tier (single-
            # device payloads only): a loaded executable skips trace,
            # lower and compile; its preload overlapped the weight upload
            server_caps["aot"] = serve_aot_store
        server = adapter.make_server(params, mesh=mesh, **server_caps)
        window_ms = float(extra.get("batch_window_ms", 0) or 0)
        batch_mode = str(extra.get("batch_mode", "") or "").lower()
        # batch formation dequeues by the bundle's scheduling policy
        # (the same [payload.extra] sched_policy the HTTP scheduler
        # uses), so request class survives INTO the batchers.
        # LAMBDIPY_SCHED_POLICY is the serve-process override (set by
        # `lambdipy serve --sched-policy`): the handler is built inside
        # load_bundle, before the server's scheduler exists, so the CLI
        # choice reaches batch formation through the environment.
        import os as _os

        # default matches the HTTP scheduler's default ("fair"), so batch
        # formation honors class fairness even when nothing is configured
        # — /metrics reporting policy "fair" while batches board FIFO
        # would be a lie
        pol_name = (_os.environ.get("LAMBDIPY_SCHED_POLICY")
                    or extra.get("sched_policy") or "fair")
        from lambdipy_tpu.sched.policy import make_policy

        sched_policy = make_policy(str(pol_name))
        # ONE resolution of the prefix block width, shared by the page
        # pool (page width) and the prefix store (radix block) below —
        # they must agree by construction, not by parallel parsing
        raw_block = _os.environ.get("LAMBDIPY_PREFIX_BLOCK")
        if raw_block in (None, ""):
            raw_block = extra.get("prefix_block")
        prefix_block = (int(raw_block) if raw_block not in (None, "")
                        else 32)
        # one deterministic fault plan shared by the engine's sites AND
        # the prefix store's prefix_walk site (chaos specs arm a
        # replica's whole serve path through one LAMBDIPY_FAULT)
        engine_faults = None
        if batch_mode == "continuous":
            from lambdipy_tpu.runtime.continuous import ContinuousBatcher

            # requests join an in-flight decode at segment boundaries.
            # batch_cache_len bounds the B-slot KV allocation (B full-
            # window caches otherwise — at 8B dims that is HBM that the
            # operator must be able to cap per bundle)
            bcl = extra.get("batch_cache_len")
            # length-aware window bucketing (on by default): a pow-2
            # window program variant enters at the FIRST USE of its
            # bucket: compiled (10-13 s a persistent-cache hit at 7B
            # widths, PR 37) and snapshotted the first time a writable
            # bundle sees it, loaded from the bundle's AOT exec tier
            # (2-7 s) on every later boot. A read-only bundle whose
            # build never ran that bucket pays the compile each boot; a
            # latency-critical one can opt out via
            # `batch_window_bucketing = "0"` (or the
            # LAMBDIPY_WINDOW_BUCKETING env default) and keep the
            # single AOT-warmed full-window segment program. Same
            # precedence as LAMBDIPY_ATTN_BACKEND: an explicit bundle
            # extra wins over the environment.
            wb = extra.get(
                "batch_window_bucketing",
                _os.environ.get("LAMBDIPY_WINDOW_BUCKETING", "1"))
            # pipelined dispatch/collect: segments kept in flight on the
            # device before the host fetches the oldest. 1 restores the
            # synchronous loop; the default 2 overlaps device compute
            # with the per-segment fetch RTT + host bookkeeping. Same
            # precedence as the window-bucketing knob: an explicit
            # bundle extra wins over the environment (set by
            # `lambdipy serve --pipeline-depth`).
            pd = extra.get(
                "pipeline_depth",
                _os.environ.get("LAMBDIPY_PIPELINE_DEPTH", "2"))
            # fault isolation knobs (runtime/faults.py): the watchdog
            # bounds device-side waits (0 = off — size it above the
            # compile a first dispatch legitimately includes),
            # max_replays caps transparent replays of
            # rows that delivered no bytes, and a fault spec arms the
            # deterministic injection sites for chaos tests. Extra wins
            # over env, like the pipeline-depth knob (the env vars are
            # the CLI bridge: `lambdipy serve --engine-watchdog`).
            wd = extra.get(
                "engine_watchdog_s",
                _os.environ.get("LAMBDIPY_ENGINE_WATCHDOG_S", "0"))
            mr = extra.get(
                "max_replays",
                _os.environ.get("LAMBDIPY_MAX_REPLAYS", "1"))
            fspec = extra.get("fault_spec",
                              _os.environ.get("LAMBDIPY_FAULT", ""))
            # engine-level speculative decoding (DEFAULT OFF this
            # release): spec_k >= 2 turns every engine segment into
            # draft -> batched verify -> accept/rollback with bitwise
            # outputs (continuous.py docstring). `spec_k` extra wins
            # over the LAMBDIPY_SPEC_K env (the `lambdipy serve
            # --spec-k` bridge), like the knobs above. Distinct from
            # the per-REQUEST `"speculative": k` field, which still
            # serves solo through generate_speculative.
            sk = extra.get("spec_k",
                           _os.environ.get("LAMBDIPY_SPEC_K", "0"))
            # draft tier for the engine's spec path (ROADMAP direction
            # 4): draft_mode picks the provider rows start on — lookup
            # (PR 9 behavior, default), model (self-drafting
            # shallow-exit head, per-row adaptive k + fallback), off.
            # draft_exit sets how many layers the shallow-exit draft
            # runs (clamped to the model's depth). Extra wins over env
            # (`lambdipy serve --draft-mode/--draft-exit` bridge).
            dmode = extra.get("draft_mode",
                              _os.environ.get("LAMBDIPY_DRAFT_MODE",
                                              "lookup"))
            dexit = extra.get("draft_exit",
                              _os.environ.get("LAMBDIPY_DRAFT_EXIT", "1"))
            # long-context tier (runtime/longctx.py, DEFAULT OFF):
            # max_logical_ctx > cache_len serves prompts past the
            # compiled window through a sliding logical window whose
            # evicted pages spill to a host offload arena (needs
            # --kv-paged); long_prefill opts the tier's prefill side
            # into the ring-attention path on sp meshes. Extra wins
            # over env (`lambdipy serve --max-logical-ctx` bridge).
            mlc = extra.get("max_logical_ctx",
                            _os.environ.get("LAMBDIPY_MAX_LOGICAL_CTX",
                                            "0"))
            lpf = extra.get("long_prefill",
                            _os.environ.get("LAMBDIPY_LONG_PREFILL", "0"))
            # whole-prompt sequence-parallel prefill (models/llama.py
            # sp_prefill family, DEFAULT "chunked"): "sp" runs every
            # cold prefill as ONE sharded program per round over the
            # mesh's sp axis — long-context rounds, the engine's group
            # prefill, and the prefix store's cold walk all route
            # through it. Requesting it without an sp mesh axis stands
            # down counted. Extra wins over env (`lambdipy serve
            # --prefill-mode` bridge).
            pfm = extra.get("prefill_mode",
                            _os.environ.get("LAMBDIPY_PREFILL_MODE",
                                            "chunked"))
            from lambdipy_tpu.runtime.faults import FaultPlan

            # paged KV memory (runtime/pagepool.py, DEFAULT OFF): one
            # refcounted page arena replaces the engine's B full-window
            # caches — admission charges actual tokens, prefix hits
            # share pages zero-copy, capacity rows scale with the
            # workload's real lengths. `kv_paged` extra wins over the
            # LAMBDIPY_KV_PAGED env (the `lambdipy serve --kv-paged`
            # bridge); `kv_pages` sizes the arena (default: the same
            # HBM the dense engine would allocate, slots x window).
            page_pool = None
            kvp = extra.get("kv_paged",
                            _os.environ.get("LAMBDIPY_KV_PAGED", "0"))
            if str(kvp).lower() not in ("", "0", "false", "off"):
                from lambdipy_tpu.models.llama import (init_page_arena,
                                                       page_kv_bytes)
                from lambdipy_tpu.runtime.pagepool import (PagePool,
                                                           page_width)

                cfg_m = server.model.cfg
                eng_len = min(int(bcl) if bcl else cfg_m.max_len,
                              cfg_m.max_len)
                page = page_width(eng_len, prefix_block)
                window_pages = eng_len // page
                raw_np = extra.get(
                    "kv_pages", _os.environ.get("LAMBDIPY_KV_PAGES"))
                n_pages = max(2, (int(raw_np)
                                  if raw_np not in (None, "") else
                                  int(extra.get("batch_max", 8))
                                  * window_pages + 1))
                page_pool = PagePool(
                    n_pages=n_pages, page=page,
                    page_bytes=page_kv_bytes(cfg_m, page),
                    # a meshed payload's arena is born kv-head-sharded
                    # (per-device arena HBM ~1/tp); page_bytes stays the
                    # LOGICAL page size — the pool's capacity accounting
                    # is mesh-agnostic by design
                    make_arena=(lambda n=n_pages, p=page, m=mesh:
                                init_page_arena(cfg_m, n, p, mesh=m)),
                    window_pages=window_pages)
            engine_faults = (FaultPlan.from_spec(str(fspec))
                             if str(fspec).strip() else None)
            batcher = continuous = ContinuousBatcher(
                server, slots=int(extra.get("batch_max", 8)),
                segment=int(extra.get("batch_segment", 16)),
                cache_len=int(bcl) if bcl else None,
                policy=sched_policy,
                window_bucketing=str(wb).lower() not in ("0", "false",
                                                         "off"),
                pipeline_depth=int(pd),
                watchdog_s=float(wd or 0),
                max_replays=int(mr),
                faults=engine_faults,
                page_pool=page_pool,
                spec_k=int(sk or 0),
                draft_mode=str(dmode or "lookup"),
                draft_exit=int(dexit or 1),
                max_logical_ctx=int(mlc or 0),
                long_prefill=str(lpf).lower() not in ("", "0", "false",
                                                      "off"),
                prefill_mode=str(pfm or "chunked").lower())
        elif window_ms > 0:
            from lambdipy_tpu.runtime.batching import MicroBatcher

            # concurrent same-knob requests share one ragged device call
            batcher = MicroBatcher(server, window_ms=window_ms,
                                   max_batch=int(extra.get("batch_max", 8)),
                                   policy=sched_policy)

    # automatic cross-request prefix KV cache (runtime/prefixstore.py):
    # the DEFAULT path for all single-row generate requests — the prompt
    # is longest-prefix-matched against a radix tree of cached KV blocks
    # and only the suffix prefills. `prefix_cache_mb` (bundle extra, or
    # `lambdipy serve --prefix-cache-mb` via the env bridge) budgets the
    # store's HBM; 0 disables. kv_quant bundles keep it OPT-IN: the
    # cached prefix reads back quantized, so on/off parity drops from
    # bitwise to quantization tolerance — the operator must choose that.
    prefix_store = None
    # configurations where routing permanently stands down must not
    # build (or advertise) a store at all: meta would claim the cache is
    # on, /metrics would export counters that can never move, and every
    # admission would probe a permanently empty tree
    routable = (batcher is None
                or (continuous is not None
                    and server is not None
                    and continuous.cache_len == server.model.cfg.max_len))
    if server is not None and routable:
        import os as _os_px

        raw_mb = _os_px.environ.get("LAMBDIPY_PREFIX_CACHE_MB")
        if raw_mb in (None, ""):
            raw_mb = extra.get("prefix_cache_mb")
        explicit_mb = raw_mb not in (None, "")
        mb = float(raw_mb) if explicit_mb else 512.0
        if mb > 0 and (server.model.cfg.kv_quant is None or explicit_mb):
            from lambdipy_tpu.runtime.prefixstore import PrefixStore

            # a paged engine's store shares the engine's page arena:
            # blocks live as refcounted pages and a hit is a refcount
            # bump through acquire_pages (zero-copy). `prefix_block`
            # is the ONE resolved block width the page pool sized by.
            paged_pool = (continuous.pool if continuous is not None
                          else None)
            # session-pin knobs (multi-turn chat): the pin budget caps
            # total bytes open sessions may hold out of eviction's
            # reach; ttl/idle are the lease (renewed every turn). Env
            # first like the cache-mb knob — `lambdipy serve
            # --session-pin-budget/--session-ttl` bridge through it.
            raw_pb = _os_px.environ.get("LAMBDIPY_SESSION_PIN_BUDGET_MB")
            if raw_pb in (None, ""):
                raw_pb = extra.get("session_pin_budget_mb")
            raw_ttl = _os_px.environ.get("LAMBDIPY_SESSION_TTL_S")
            if raw_ttl in (None, ""):
                raw_ttl = extra.get("session_ttl_s")
            raw_idle = _os_px.environ.get("LAMBDIPY_SESSION_IDLE_S")
            if raw_idle in (None, ""):
                raw_idle = extra.get("session_idle_s")
            prefix_store = PrefixStore(
                server, block=prefix_block, budget_mb=mb,
                pool=paged_pool,
                faults=(continuous.faults if continuous is not None
                        else None),
                pin_budget_mb=(float(raw_pb)
                               if raw_pb not in (None, "") else None),
                session_ttl_s=(float(raw_ttl)
                               if raw_ttl not in (None, "") else 3600.0),
                session_idle_s=(float(raw_idle)
                                if raw_idle not in (None, "") else 600.0),
                # the store's cold walk shares the engine's prefill
                # schedule + the ONE batching.prefill stats block
                prefill_mode=(continuous.prefill_mode
                              if continuous is not None else "chunked"),
                prefill_stats=(continuous.prefill_stats
                               if continuous is not None else None))
            if paged_pool is not None:
                continuous.prefix_pages_fn = prefix_store.acquire_pages
                # host KV offload tier (runtime/offload.py, DEFAULT
                # OFF): swept-cold store pages spill their kvwire bytes
                # to host RAM and re-online on demand instead of
                # re-prefilling. kv_offload.* gauges ride
                # batching.page_pool into /metrics via
                # pool.attach_offload; kv_offload_mb budgets the host
                # arena. Extra wins over env (`lambdipy serve
                # --kv-offload` bridge).
                kvo = extra.get(
                    "kv_offload",
                    _os_px.environ.get("LAMBDIPY_KV_OFFLOAD", "0"))
                if str(kvo).lower() not in ("", "0", "false", "off"):
                    from lambdipy_tpu.runtime.offload import OffloadArena

                    raw_omb = _os_px.environ.get("LAMBDIPY_KV_OFFLOAD_MB")
                    if raw_omb in (None, ""):
                        raw_omb = extra.get("kv_offload_mb")
                    prefix_store.attach_offload(OffloadArena(
                        page=paged_pool.page,
                        layers=server.model.cfg.layers,
                        budget_mb=(float(raw_omb)
                                   if raw_omb not in (None, "")
                                   else 256.0),
                        faults=continuous.faults))

    # disaggregated-serving KV ship surface (ROADMAP direction 4): a
    # prefill-class replica exports a prompt head's KV blocks as a wire
    # frame (runtime/kvwire.py), the router ships it, and the decode
    # replica's import is a radix insert — zero-copy into arena pages
    # under --kv-paged. Rides the prefix store, so it exists exactly
    # when automatic prefix caching does.
    kv_ship_stats = None
    kv_export = kv_import = kv_probe = None
    kv_export_stream = kv_import_stream = None
    if prefix_store is not None:
        from lambdipy_tpu.runtime.kvwire import (
            StreamDecoder,
            decode_frame,
            encode_chunk,
            encode_frame,
            encode_stream_header,
        )
        from lambdipy_tpu.runtime.metrics import KvShipStats
        from lambdipy_tpu.runtime.pagepool import PagesExhausted

        kv_ship_stats = KvShipStats()

        def kv_export(req: dict):
            """{"tokens": [...]} -> wire frame bytes, or an error dict
            (the server maps dicts to 400s)."""
            raw = req.get("tokens")
            if not isinstance(raw, (list, tuple)) or not raw or \
                    not all(isinstance(t, int) for t in raw):
                return {"ok": False,
                        "error": "kv export wants a flat token id list"}
            out = prefix_store.export_blocks(list(raw))
            if out is None:
                return {"ok": False,
                        "error": "no whole-block prefix to export"}
            head, blocks = out
            frame = encode_frame(head, prefix_store.block, blocks)
            kv_ship_stats.record_export(tokens=len(head),
                                        nbytes=len(frame))
            return frame

        def kv_import(data: bytes) -> dict:
            """Wire frame -> radix insert; ValueError on garbage frames
            (server maps to 400), PagesExhausted on a full arena
            (server maps to the priced-shed 503)."""
            try:
                tokens, block, blocks = decode_frame(data)
                if block != prefix_store.block:
                    raise ValueError(
                        f"frame block width {block} != this replica's "
                        f"prefix block {prefix_store.block}")
                res = prefix_store.import_blocks(tokens, blocks)
            except PagesExhausted:
                kv_ship_stats.record_backpressure()
                raise
            except ValueError:
                kv_ship_stats.record_rejected()
                raise
            kv_ship_stats.record_import(
                tokens=len(tokens), nbytes=len(data),
                inserted=res["inserted"], present=res["present"],
                mode=res["mode"])
            return {"ok": True, **res}

        def kv_probe(req: dict) -> dict:
            """{"tokens": [...]} -> how many head tokens are actually
            PRESENT in the radix tree (host-only; no device work). The
            router's import-miss pull calls this before trusting its
            ship-dedup cache."""
            raw = req.get("tokens")
            if not isinstance(raw, (list, tuple)) or not raw or \
                    not all(isinstance(t, int) for t in raw):
                return {"ok": False,
                        "error": "kv probe wants a flat token id list"}
            return {"ok": True,
                    "matched": prefix_store.present_len(list(raw)),
                    "block": prefix_store.block}

        def kv_export_stream(req: dict):
            """Chunked export twin: {"tokens": [...], "stream": true}
            -> generator of wire frames (LKVS header, then one LKVC
            per block group, flushed as the walk produces it). Returns
            an error dict (the server maps dicts to 400s) when the
            prompt has no whole block."""
            raw = req.get("tokens")
            if not isinstance(raw, (list, tuple)) or not raw or \
                    not all(isinstance(t, int) for t in raw):
                return {"ok": False,
                        "error": "kv export wants a flat token id list"}
            out = prefix_store.export_stream(list(raw))
            if out is None:
                return {"ok": False,
                        "error": "no whole-block prefix to export"}
            head, groups = out
            cfg = prefix_store.server.model.cfg
            leaves = [[name, dt.name, list(shape)]
                      for name, (shape, dt)
                      in sorted(prefix_store._leaf_template().items())]

            def gen():
                nbytes = sent = 0
                header = encode_stream_header(head, prefix_store.block,
                                              cfg.layers, leaves)
                nbytes += len(header)
                yield header
                chunks = 0
                for group in groups:
                    frame = encode_chunk(sent, group)
                    sent += len(group)
                    nbytes += len(frame)
                    chunks += 1
                    yield frame
                # recorded only on a COMPLETE stream: a truncated
                # export is the relay's mid-stream-failure signal, not
                # a served export
                kv_ship_stats.record_export(tokens=len(head),
                                            nbytes=nbytes,
                                            chunks=chunks)

            return gen()

        def kv_import_stream(chunks_iter, commit_gate=None) -> dict:
            """Chunked import twin: raw byte chunks off the wire ->
            strict per-chunk validation (kvwire.StreamDecoder) ->
            per-chunk staging -> one atomic radix attach at stream end.
            ValueError on garbage/out-of-order/truncated streams and
            PagesExhausted on a full arena propagate AFTER the staged
            pages are rolled back — a failed stream touches nothing.

            ``commit_gate`` (a context manager) brackets ONLY the
            commit: the stream's staging must not hold a run slot,
            because the body arrives over the lifetime of the exporting
            replica's prefill — a slot held across that wait would
            serialize the decode batch behind every in-flight ship,
            the very stall the phase split removes. Anything the gate
            raises aborts the staged pages like any other failure."""
            dec = StreamDecoder()
            imp = None
            nbytes = chunks = 0
            try:
                for data in chunks_iter:
                    nbytes += len(data)
                    for kind, payload in dec.feed(data):
                        if kind == "header":
                            if payload["block"] != prefix_store.block:
                                raise ValueError(
                                    f"stream block width "
                                    f"{payload['block']} != this "
                                    f"replica's prefix block "
                                    f"{prefix_store.block}")
                            imp = prefix_store.import_begin(
                                payload["tokens"])
                        elif imp is not None:
                            chunks += 1
                            imp.add_blocks(payload[1])
                if imp is None:
                    raise ValueError("empty KV stream (no header)")
                if not dec.complete:
                    raise ValueError(
                        f"truncated KV stream: "
                        f"{dec.blocks_received} block(s) arrived")
                if commit_gate is not None:
                    with commit_gate:
                        res = imp.commit()
                else:
                    res = imp.commit()
            except PagesExhausted:
                kv_ship_stats.record_backpressure()
                kv_ship_stats.record_stream_abort()
                if imp is not None:
                    imp.abort()
                raise
            except ValueError:
                kv_ship_stats.record_rejected()
                kv_ship_stats.record_stream_abort()
                if imp is not None:
                    imp.abort()
                raise
            except BaseException:
                kv_ship_stats.record_stream_abort()
                if imp is not None:
                    imp.abort()
                raise
            kv_ship_stats.record_import(
                tokens=len(imp.row), nbytes=nbytes,
                inserted=res["inserted"], present=res["present"],
                mode=res["mode"], chunks=chunks)
            return {"ok": True, **res, "streamed": True}

    # -- chaos/debug surfaces (runtime/faults.py + the invariant sweep) ------
    # ONE live fault plan serves the whole replica (engine sites, the
    # store's prefix_walk/session_pin, the pool's page_alloc): the
    # continuous engine always owns a plan (empty when nothing is
    # armed), so the soak's nemesis can arm/clear it at runtime over
    # POST /v1/debug/faults and /metrics can report what is armed.
    live_faults = None
    if continuous is not None:
        live_faults = continuous.faults
    elif prefix_store is not None:
        live_faults = prefix_store.faults

    def debug_invariants() -> dict:
        """Cheap host-side invariant sweep (GET /v1/debug/invariants —
        the chaos checker's quiesce probe, also a live debugging aid):
        page-pool conservation, prefix-store pin/content accounting,
        plus the engine fault state as context. ``ok`` covers the
        ACCOUNTING checks; transient serving state (wedged, degrade
        level) is reported but judged by /healthz, not here."""
        ok, checks = True, {}
        if continuous is not None and continuous.pool is not None:
            try:
                continuous.pool.check_invariants()
                checks["page_pool"] = {"ok": True}
            except AssertionError as e:
                checks["page_pool"] = {"ok": False, "error": str(e)}
            checks["page_pool"]["stats"] = continuous.pool.stats()
            ok = ok and checks["page_pool"]["ok"]
        if prefix_store is not None:
            checks["prefix_store"] = prefix_store.check_invariants()
            ok = ok and checks["prefix_store"]["ok"]
        if continuous is not None:
            checks["engine"] = continuous.fault_state()
        return {"ok": ok, "checks": checks}

    def faults_admin(req: dict) -> dict:
        """POST /v1/debug/faults (host-only): arm a fault spec on the
        live plan or clear it — the chaos soak's nemesis control
        surface, so composed faults can start and stop on a timeline
        without restarting the replica."""
        if live_faults is None:
            return {"ok": False,
                    "error": "no fault plan on this handler (neither a "
                             "continuous engine nor a prefix store)"}
        if req.get("clear"):
            return {"ok": True, "cleared": live_faults.clear(),
                    "armed": live_faults.armed()}
        spec = req.get("spec")
        if not spec:
            return {"ok": False,
                    "error": "want {\"spec\": \"site:kind@...\"} or "
                             "{\"clear\": true}"}
        try:
            added = live_faults.arm(str(spec))
        except ValueError as e:
            return {"ok": False, "error": str(e)}
        return {"ok": True, "added": added,
                "armed": live_faults.armed()}

    # whether speculative decode was ENABLED at boot (post any sp-mesh
    # stand-down): the knobs endpoint only RESIZES live speculation —
    # turning it on where the boot config (or a stand-down) left it off
    # would recreate the exact hazard the stand-down existed to avoid
    spec_boot_on = continuous is not None and continuous.spec_k >= 2

    def knobs_admin(req: dict) -> dict:
        """POST /v1/debug/knobs (host-only): live-retune the continuous
        engine's per-dispatch knobs. The elastic fleet controller's
        actuator for pipeline_depth (from overlap_ratio/fetch stall)
        and spec_k (from the live acceptance EWMA). Values are clamped
        and pow-2-bucketed here so a controller bug can never push the
        engine outside its compiled program shapes."""
        if continuous is None:
            return {"ok": False,
                    "error": "no continuous engine on this handler "
                             "(pipeline_depth/spec_k are engine knobs)"}
        known = {"pipeline_depth", "spec_k", "draft_mode",
                 "max_logical_ctx", "prefill_mode"}
        unknown = sorted(set(req) - known)
        if unknown or not (set(req) & known):
            return {"ok": False,
                    "error": f"want a subset of {sorted(known)}, got "
                             f"{sorted(req) or 'nothing'}"}
        if "pipeline_depth" in req:
            try:
                d = int(req["pipeline_depth"])
            except (TypeError, ValueError):
                return {"ok": False, "error": "pipeline_depth wants an int"}
            if not 1 <= d <= 8:
                return {"ok": False,
                        "error": f"pipeline_depth {d} out of range [1, 8]"}
            continuous.pipeline_depth = d
            continuous.pipeline_stats.depth = d
        if "spec_k" in req:
            try:
                k = int(req["spec_k"])
            except (TypeError, ValueError):
                return {"ok": False, "error": "spec_k wants an int"}
            if k != 0 and not spec_boot_on:
                return {"ok": False,
                        "error": "spec_k was off at boot (config, or an "
                                 "sp-mesh stand-down): live retune only "
                                 "resizes speculation, never enables it"}
            if k != 0:
                from lambdipy_tpu.models.llama import _next_bucket
                k = min(8, max(2, _next_bucket(k, 2)))
            continuous.spec_k = k
        if "draft_mode" in req:
            dm = str(req["draft_mode"] or "").lower()
            if dm == "auto":
                dm = "model"
            if dm not in ("model", "lookup", "aux", "off"):
                return {"ok": False,
                        "error": "draft_mode wants one of "
                                 "model|lookup|aux|off"}
            if dm in ("model", "aux") and not spec_boot_on:
                # same enablement rule as spec_k: retune only steers a
                # tier that booted on — it never turns speculation on
                # where boot config (or a stand-down) left it off
                return {"ok": False,
                        "error": "spec was off at boot: draft_mode "
                                 "retune steers a live draft tier, "
                                 "never enables one"}
            if dm == "aux" and continuous.draft_provider is None:
                return {"ok": False,
                        "error": "draft_mode=aux needs a draft_provider "
                                 "wired at boot"}
            # applies to rows admitted from here on; in-flight rows
            # keep their adapted per-row provider (the fallback chain
            # still demotes them individually)
            continuous.draft_mode = dm
        if "prefill_mode" in req:
            pm = str(req["prefill_mode"] or "").lower()
            if pm not in ("chunked", "sp"):
                return {"ok": False,
                        "error": "prefill_mode wants chunked|sp"}
            # unlike spec_k this is always retunable: "sp" without a
            # usable mesh stands down counted inside set_prefill_mode,
            # so a controller can never push prefill off a cliff
            continuous.set_prefill_mode(pm)
            if prefix_store is not None:
                prefix_store.prefill_mode = continuous.prefill_mode
            if continuous._longctx is not None:
                continuous._longctx.prefill_mode = continuous.prefill_mode
        if "max_logical_ctx" in req:
            try:
                m = int(req["max_logical_ctx"])
            except (TypeError, ValueError):
                return {"ok": False,
                        "error": "max_logical_ctx wants an int"}
            if m != 0 and continuous.pool is None:
                return {"ok": False,
                        "error": "max_logical_ctx needs paged KV "
                                 "(--kv-paged) at boot"}
            m = max(0, m)
            continuous.max_logical_ctx = m
            if continuous._longctx is not None and m:
                # a live runner re-reads its admission cap; 0 just
                # stops routing (the runner idles, already-admitted
                # runs finish)
                continuous._longctx.max_logical_ctx = m
        return {"ok": True,
                "pipeline_depth": continuous.pipeline_depth,
                "spec_k": continuous.spec_k,
                "draft_mode": continuous.draft_mode,
                "max_logical_ctx": continuous.max_logical_ctx,
                "prefill_mode": continuous.prefill_mode}

    # background bucket pre-warm: the boot warmup compiles only the
    # smallest prompt bucket; a first request in a bigger bucket pays a
    # compile at request time (~40 s per program at 8B width on the
    # v5e's host, chip_smoke.py PR 21). An
    # operator-listed `warm_buckets = "64,256"` compiles those buckets on
    # a daemon thread — started AFTER the first invoke (the boot warmup)
    # completes, never at init: a background compile racing the
    # foreground warmup serializes the cold start.
    # Progress rides /metrics (handler.warm_buckets).
    import threading

    # "in_flight" is the readiness signal /healthz exposes: True from the
    # moment the warm thread is committed until it finishes, so a fleet
    # router can hold traffic off a still-compiling replica
    warm_state = {"requested": [], "done": [], "errors": [],
                  "in_flight": False}
    raw_buckets = extra.get("warm_buckets")
    if server is not None and raw_buckets:
        warm_state["requested"] = sorted(
            {int(tok) for tok in str(raw_buckets).split(",") if tok.strip()})
    _warm_lock = threading.Lock()
    _warm_started = False

    # the continuous engine's ragged group-prefill programs are another
    # first-burst compile cliff (one program per joiner-count bucket) —
    # warm them on the same daemon
    warm_group = (continuous is not None
                  and str(extra.get("warm_group_prefill", "1")) != "0")

    def _maybe_start_bucket_warm():
        nonlocal _warm_started
        if not warm_state["requested"] and not warm_group:
            return
        with _warm_lock:  # atomic test-and-set: exactly one warm thread
            if _warm_started:
                return
            _warm_started = True
            # flipped before the thread exists: no window where warm is
            # committed but a /healthz probe still reads ready
            warm_state["in_flight"] = True

        def _warm_buckets():
            # warm traffic time-shares the one device with foreground
            # requests right after boot: early requests can see inflated
            # latency until the listed buckets finish compiling — the
            # operator opted into that trade by listing warm_buckets.
            for size in warm_state["requested"]:
                try:
                    with spans.span("boot.warm", program=f"bucket-{size}"):
                        server.generate([list(range(1, size + 1))],
                                        max_new_tokens=default_new)
                    with _warm_lock:
                        warm_state["done"].append(size)
                except Exception as e:  # background QoS, never fatal —
                    # and one bad bucket must not abandon the rest
                    with _warm_lock:
                        warm_state["errors"].append(f"bucket {size}: {e}")
            if warm_group:
                try:
                    n = continuous.warm_group_prefill()
                    with _warm_lock:
                        warm_state["done"].append(f"group_prefill:{n}")
                except Exception as e:
                    with _warm_lock:
                        warm_state["errors"].append(f"group_prefill: {e}")
            # the programs this thread just compiled should boot from
            # the AOT tier next time too; with it the boot's own programs
            # end: what is compiled from here on is the traffic's, loaded
            # at first use and not before the deploy is ready
            try:
                server.aot_save_all(boot_done=True)
            except Exception:  # noqa: BLE001 — AOT is best-effort
                pass
            with _warm_lock:
                warm_state["in_flight"] = False

        threading.Thread(target=_warm_buckets, daemon=True,
                         name="bucket-warm").start()

    tokenizer, tok_err = None, None
    tok_path = (spec.get("extra") or {}).get("tokenizer_path")
    if tok_path:
        # text-in/text-out: an HF tokenizer shipped INSIDE the bundle
        # (package.py copies it and rewrites the path bundle-relative);
        # absence degrades to the token-ids API, not an error
        from pathlib import Path as _Path

        resolved = _Path(tok_path)
        if not resolved.is_absolute():
            resolved = _Path(ctx.bundle_dir) / resolved
        try:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(
                str(resolved), local_files_only=True)
        except Exception as e:  # noqa: BLE001 - degrade, recorded in meta
            tok_err = str(e)

    def _route_prefix(prompt, prefix, sess=None):
        """Transparent radix reuse: split a single-row prompt into
        (suffix prompt, cached-prefix tokens) when the prefix store can
        match or extend a block-aligned prefix. Requests carrying an
        EXPLICIT ``prefix`` keep the client's split; multi-row and
        sub-block prompts pass through. Fail-open by construction —
        ``route`` returns 0 on any store failure.

        ``sess`` = (session_id, ttl_s | None): after routing, the
        conversation's whole-block head is PINNED in the store under
        the session's lease, so turn-2+ requests keep hitting even
        under LRU pressure. A pin-budget refusal raises
        :class:`SessionPinsExceeded` (the server maps it to the priced
        ``session_pins`` 503); any routing stand-down just renews the
        lease without pinning — sessions degrade with the cache, never
        ahead of it."""
        if prefix_store is None or prefix is not None or len(prompt) != 1:
            return prompt, prefix
        standdown = False
        if continuous is not None and continuous.degrade_level >= 3:
            # degradation ladder level 3: a repeatedly-failing engine
            # bypasses the prefix cache — full-prompt prefill through
            # the plainest path until a clean interval restores it.
            # Session pins SURVIVE the bypass untouched (only the lease
            # renews): when the ladder restores, the next turn hits the
            # still-pinned head.
            standdown = True
        if continuous is not None and \
                continuous.cache_len != server.model.cfg.max_len:
            # a capped engine can't pack full-window prefix carries
            # (continuous._admit falls back solo): auto-routing would
            # silently trade away continuous batching for KV reuse —
            # keep the engine's pre-cache behavior and skip routing
            standdown = True
        if batcher is not None and continuous is None:
            # MicroBatcher mode: prefix requests bypass the window
            # batcher entirely (it has no prefix path), so routing would
            # serialize exactly the concurrent traffic the batcher
            # fuses — same silent-trade regression, same stand-down
            standdown = True
        if standdown:
            if sess is not None and sess[0]:
                prefix_store.touch_session(str(sess[0]))
            return prompt, prefix
        row = [int(t) for t in np.asarray(prompt[0]).reshape(-1)]
        m = prefix_store.route(row)
        if sess is not None and sess[0]:
            # pin AFTER route: the head's blocks exist now (the
            # request's own prefill inserted them). SessionPinsExceeded
            # propagates — the HTTP layer sheds the session, priced.
            prefix_store.pin_session(str(sess[0]), row, ttl_s=sess[1])
        if m <= 0:
            return prompt, prefix
        return ([np.asarray(row[m:], np.int32)],
                np.asarray(row[:m], np.int32))

    def run(prompt, max_new, sample_kwargs, want_lp=False):
        # prompt stays a host numpy array until the chosen path needs it:
        # the server/batcher convert internally, only the legacy
        # adapter.generate path pays a device transfer here. logprob
        # requests ride the batchers like any other (the fused program
        # computes logprobs anyway; want_lp only adds a fetch).
        if batcher is not None and len(prompt) == 1:
            return batcher.generate(prompt[0], max_new_tokens=max_new,
                                    return_logprobs=want_lp, **sample_kwargs)
        if server is not None:
            return server.generate(prompt, max_new_tokens=max_new,
                                   return_logprobs=want_lp, **sample_kwargs)
        device_prompt = jnp.asarray(prompt)
        if mesh is not None:
            from lambdipy_tpu.parallel.mesh import use_mesh

            with use_mesh(mesh):
                return adapter.generate(params, device_prompt,
                                        max_new_tokens=max_new, **sample_kwargs)
        return adapter.generate(params, device_prompt, max_new_tokens=max_new,
                                **sample_kwargs)

    def _parse(req: dict):
        """Request -> (prompt, max_new, sample_kwargs, from_text, prefix,
        want_logprobs), or an error dict (the shared front half of
        invoke and invoke_stream)."""
        from_text = False
        if req.get("warmup") or req.get("random"):
            if req.get("warmup") and server is not None and batcher is not None:
                from lambdipy_tpu.models.llama import _next_bucket
                from lambdipy_tpu.runtime.continuous import ContinuousBatcher

                if isinstance(batcher, ContinuousBatcher):
                    # one engine pass compiles the row prefill, the pack
                    # program, and the B-slot segment program
                    batcher.generate([1, 2, 3, 4],
                                     max_new_tokens=default_new)
                else:
                    # pre-compile every batch-size bucket the micro-batcher
                    # can produce — including the bucket max_batch rounds UP
                    # to — so the first concurrent burst hits warm programs,
                    # not an inline XLA compile
                    bb, top = 2, _next_bucket(batcher.max_batch, 1)
                    while bb <= top:
                        server.generate([[1, 2, 3, 4]] * bb,
                                        max_new_tokens=default_new)
                        bb *= 2
            if req.get("warmup") and server is not None:
                # pre-compile the streaming (prefill, segment) pair for
                # the default segment size too: a first streamed request
                # otherwise pays the whole compile at time-to-first-token
                for _ in server.generate_stream([1, 2, 3, 4],
                                                max_new_tokens=default_new):
                    pass
            prompt = np.asarray([[1, 2, 3, 4]], np.int32)
        elif req.get("text") is not None:
            if tokenizer is None:
                return {"ok": False,
                        "error": "bundle has no tokenizer; send 'tokens'"}
            ids = tokenizer(req["text"])["input_ids"]
            if not ids:
                return {"ok": False,
                        "error": "prompt tokenized to zero tokens"}
            prompt = np.asarray([ids], np.int32)
            from_text = True
        else:
            raw = req["tokens"]
            if isinstance(raw, (list, tuple)) and raw and \
                    isinstance(raw[0], (list, tuple, np.ndarray)):
                # list-of-rows: may be RAGGED (different prompt lengths);
                # np.asarray would crash on inhomogeneous shape, and the
                # compile-once server decodes ragged batches natively
                rows = [np.asarray(r, dtype=np.int32).reshape(-1)
                        for r in raw]
                if any(r.size == 0 for r in rows):
                    return {"ok": False, "error": "empty prompt row"}
                if len({len(r) for r in rows}) == 1:
                    prompt = np.stack(rows)
                elif server is not None:
                    prompt = rows
                else:
                    return {"ok": False, "error":
                            "ragged prompt rows need the compile-once "
                            "server (model exposes no make_server)"}
            else:
                arr = np.asarray(raw, dtype=np.int32)
                if arr.size == 0:
                    return {"ok": False, "error": "empty prompt"}
                prompt = arr[None, :] if arr.ndim == 1 else arr
        # tolerate JSON null (= "use the default"); explicit 0 is honored
        raw_new = req.get("max_new_tokens")
        max_new = default_new if raw_new is None else int(raw_new)
        # every knob tolerates JSON null (= "use the default")
        sample_kwargs = {
            "temperature": float(req.get("temperature") or 0.0),
            "top_k": int(req["top_k"]) if req.get("top_k") is not None else None,
            "top_p": float(req["top_p"]) if req.get("top_p") is not None else None,
            "seed": int(req.get("seed") or 0),
            "eos_id": int(req["eos_id"]) if req.get("eos_id") is not None else None,
        }
        if sample_kwargs["eos_id"] is None and from_text and \
                tokenizer.eos_token_id is not None:
            sample_kwargs["eos_id"] = int(tokenizer.eos_token_id)
        prefix = req.get("prefix")
        if prefix is not None:
            prefix = np.asarray(prefix, np.int32).reshape(-1)
            if prefix.size == 0:
                return {"ok": False, "error": "empty prefix"}
            if server is None:
                return {"ok": False, "error":
                        "prefix caching needs the compile-once server"}
            if len(prompt) != 1:
                return {"ok": False,
                        "error": "prefix caching is single-row"}
        spec_k = req.get("speculative")
        if spec_k is not None:
            try:
                spec_k = int(spec_k)
            except (TypeError, ValueError):
                return {"ok": False,
                        "error": "speculative must be an integer draft "
                                 "length"}
            if server is None:
                return {"ok": False, "error":
                        "speculative decoding needs the compile-once "
                        "server"}
            if len(prompt) != 1:
                return {"ok": False, "error":
                        "speculative decoding is single-row"}
        # multi-turn session surface: `session_id` (string/number) pins
        # the conversation's prefix KV under a lease; `session_ttl_s`
        # optionally tightens this session's idle lease (clamped)
        sess = None
        sid = req.get("session_id")
        if sid is not None and str(sid):
            try:
                ttl = (float(req["session_ttl_s"])
                       if req.get("session_ttl_s") is not None else None)
            except (TypeError, ValueError):
                return {"ok": False,
                        "error": "session_ttl_s must be a number"}
            sess = (str(sid), ttl)
        return (prompt, max_new, sample_kwargs, from_text, prefix,
                bool(req.get("logprobs")), spec_k, sess)

    def invoke(req: dict) -> dict:
        parsed = _parse(req)
        if isinstance(parsed, dict):
            return parsed
        try:
            return _invoke_parsed(parsed)
        finally:
            if req.get("warmup") and server is not None:
                # the warmup invoke itself compiled the fused decode
                # program — everything compiled so far is in the bundle's
                # AOT exec tier before the boot goes on, so the NEXT boot
                # loads executables instead of recompiling (no-op for
                # programs that were themselves AOT-loaded). Where no
                # warm daemon follows, the boot's own programs end here
                try:
                    server.aot_save_all(boot_done=not (
                        warm_state["requested"] or warm_group))
                except Exception:  # noqa: BLE001 — AOT is best-effort
                    pass
            # first completed invoke (the boot warmup) releases the
            # background bucket warm
            _maybe_start_bucket_warm()

    def _invoke_parsed(parsed) -> dict:
        (prompt, max_new, sample_kwargs, from_text, prefix, want_lp,
         spec_k, sess) = parsed
        prompt, prefix = _route_prefix(prompt, prefix, sess)
        lps = None
        if want_lp and server is None:
            return {"ok": False,
                    "error": "logprobs need the compile-once server"}
        spec_stats = None
        if spec_k is not None:
            # speculative decoding: prompt-lookup drafts verified in
            # chunks — plain greedy output at temperature 0, exact
            # rejection-sampled output (seed-deterministic) above it —
            # fewer weight reads either way (models/llama.py
            # generate_speculative). Stats come back with the call:
            # instance state would race under the threaded server and
            # go stale on the fallback path.
            out_, spec_stats = server.generate_speculative(
                prompt, max_new_tokens=max_new, k=spec_k, prefix=prefix,
                return_logprobs=want_lp, return_stats=True,
                **sample_kwargs)
            toks, lps = out_ if want_lp else (out_, None)
        elif prefix is not None:
            # shared-prefix KV reuse: only the suffix prefills per
            # request — and under continuous batching the prefix row
            # joins the shared engine batch (VERDICT r5 #3c; the
            # batcher falls back solo when its cache can't hold a
            # full-window prefix carry)
            if continuous is not None and len(prompt) == 1:
                out_ = continuous.generate(
                    prompt[0], max_new_tokens=max_new, prefix=prefix,
                    return_logprobs=want_lp, **sample_kwargs)
            else:
                out_ = server.generate(prompt, max_new_tokens=max_new,
                                       prefix=prefix,
                                       return_logprobs=want_lp,
                                       **sample_kwargs)
            toks, lps = out_ if want_lp else (out_, None)
        else:
            out_ = run(prompt, max_new, sample_kwargs, want_lp)
            toks, lps = out_ if want_lp else (out_, None)
            toks = np.asarray(jax.device_get(toks))
        toks = np.asarray(toks)
        out = {"ok": True, "tokens": toks.tolist(), "n_new": int(toks.shape[-1]),
               # effective request metadata for API shims (/v1/completions):
               # the real prompt token count and the eos actually in force
               # (a text prompt inherits the tokenizer's)
               "n_prompt": int(sum(len(r) for r in prompt)
                               + (len(prefix) if prefix is not None else 0))}
        if lps is not None:
            out["logprobs"] = [[round(float(x), 5) for x in row]
                               for row in np.asarray(lps)]
        if sample_kwargs["eos_id"] is not None:
            out["eos_id"] = sample_kwargs["eos_id"]
        if prefix is not None:
            out["prefix_cached"] = True
        if spec_stats is not None:
            out["speculative"] = spec_stats
        if from_text:
            row = toks[0].tolist()
            eos = sample_kwargs["eos_id"]
            if eos is not None and eos in row:
                row = row[:row.index(eos)]
            out["completion"] = tokenizer.decode(row)
        return out

    def invoke_stream(req: dict):
        """Streaming invoke: yields chunk dicts as the decode emits them
        (LlamaServer.generate_stream), ending with a summary record.
        Concatenated chunk tokens equal the non-streamed response."""
        parsed = _parse(req)
        if isinstance(parsed, dict):
            yield parsed
            return
        (prompt, max_new, sample_kwargs, from_text, prefix, want_lp,
         spec_k, sess) = parsed
        prompt, prefix = _route_prefix(prompt, prefix, sess)
        # clamp the client's segment size to a pow-2 in [4, 64]: it is
        # part of the compiled-program key, and an arbitrary per-request
        # value would grow the program cache (and pay a compile) without
        # bound on a public endpoint
        from lambdipy_tpu.models.llama import _next_bucket

        segment = min(64, _next_bucket(max(4, int(req.get("segment") or 16)), 4))
        spec_stats = None
        if spec_k is not None:
            # speculative + stream (VERDICT r5 weak #2): each verify
            # step's accepted chunk is a stream segment — TTFT is one
            # prefill + one verify step, where speculation pays most
            spec_stats = {}
            chunks_iter = server.generate_speculative_stream(
                prompt[0], max_new_tokens=max_new, k=spec_k,
                prefix=prefix, return_logprobs=want_lp,
                stats_out=spec_stats, **sample_kwargs)
        elif continuous is not None and len(prompt) == 1:
            # under continuous batching a streamed single-row request
            # joins the shared engine batch and receives the prefill's
            # token when its row is packed, then its slice per engine
            # segment (VERDICT r5 #3b)
            chunks_iter = continuous.generate_stream(
                prompt[0], max_new_tokens=max_new, segment=segment,
                prefix=prefix, return_logprobs=want_lp, **sample_kwargs)
        else:
            chunks_iter = server.generate_stream(
                prompt, max_new_tokens=max_new, segment=segment,
                prefix=prefix, return_logprobs=want_lp, **sample_kwargs)
        all_rows = None
        text_emitted = ""
        for chunk in chunks_iter:
            chunk, lp_chunk = chunk if want_lp else (chunk, None)
            all_rows = (chunk if all_rows is None
                        else np.concatenate([all_rows, chunk], axis=1))
            rec = {"ok": True, "tokens": chunk.tolist()}
            if lp_chunk is not None:
                rec["logprobs"] = [[round(float(x), 5) for x in row]
                                   for row in lp_chunk]
            if from_text:
                # incremental text per segment so OpenAI-style clients
                # render as the stream arrives (each chunk carries the
                # DELTA since the previous one). Decode the whole row each
                # time — subword merges can only be resolved with the full
                # context — and hold back trailing replacement chars from
                # an incomplete UTF-8 sequence until the next segment
                # completes it. If a later token retroactively changes
                # ALREADY-SENT text (a non-prefix-stable tokenizer), emit
                # nothing and let the summary's tail field close the gap
                # with at most the diverged span duplicated — never the
                # whole completion.
                row = all_rows[0].tolist()
                eos = sample_kwargs["eos_id"]
                if eos is not None and eos in row:
                    row = row[:row.index(eos)]
                full = tokenizer.decode(row).rstrip("�")
                if full.startswith(text_emitted):
                    rec["text"] = full[len(text_emitted):]
                    text_emitted = full
                else:
                    rec["text"] = ""
            yield rec
        n_new = 0 if all_rows is None else int(all_rows.shape[1])
        out = {"ok": True, "done": True, "n_new": n_new,
               "n_prompt": int(sum(len(r) for r in prompt)
                               + (len(prefix) if prefix is not None else 0))}
        if spec_stats is not None:
            out["speculative"] = spec_stats
        if sample_kwargs["eos_id"] is not None:
            out["eos_id"] = sample_kwargs["eos_id"]
        if prefix is not None:
            # streamed from the cached prefix KV: TTFT and KV reuse
            # together (VERDICT r3 missing #4)
            out["prefix_cached"] = True
        if from_text and all_rows is not None:
            import os as _os

            row = all_rows[0].tolist()
            eos = sample_kwargs["eos_id"]
            if eos is not None and eos in row:
                row = row[:row.index(eos)]
            completion = tokenizer.decode(row)
            out["completion"] = completion
            # `text`: the tail a delta-concatenating client still needs.
            # Normally completion minus what was streamed; if decode
            # diverged from already-sent text, fall back to the common
            # prefix so at most the diverged span repeats — never the
            # whole completion (the handler owns this because only it
            # knows what was actually sent).
            if completion.startswith(text_emitted):
                out["text"] = completion[len(text_emitted):]
            else:
                common = _os.path.commonprefix([completion, text_emitted])
                out["text"] = completion[len(common):]
        yield out
        # a streaming-only workload must release the bucket warm too
        _maybe_start_bucket_warm()

    def stats() -> dict:
        if server is None:
            return {}
        out = {"decode_buckets": [list(b) for b in server.buckets],
               "compile_count": server.compile_count,
               "program_evictions": server.program_evictions,
               # programs taken from the bundle's AOT store this boot; of
               # them, deserialised at first use (not by the preload);
               # loaded executables whose first call failed (served from
               # jit, artifact pruned); artifacts written this boot
               **{k: getattr(server, k, 0)
                  for k in ("aot_hits", "aot_lazy_loads", "aot_fallbacks",
                            "aot_saved")}}
        if preload_state:
            # programs deserialized concurrently with the weight upload
            # (cold-start overlap): count + seconds the preload took
            out["aot_preload"] = {
                "programs": len(preload_state.get("names", ())),
                "seconds": preload_state.get("seconds")}
        if batcher is not None:
            out["batching"] = batcher.stats()
        if continuous is not None:
            # what the model's kinds count (a block each: the routed FFN's
            # dropless check and load, an attention kind's keys or states),
            # booked by the engine's collector from each segment's fetch
            # and by its prefill paths
            out.update({block: recorder.report()
                        for block, recorder in continuous.counters.items()})
        if getattr(server, "spec_metrics", None) is not None:
            # the solo `"speculative": k` path's cumulative acceptance
            # counters (the engine's batching.spec block shares this
            # same object when spec_k is on — one source of truth)
            out["spec"] = server.spec_metrics.report()
        if prefix_store is not None:
            # prefix_cache_{hits,misses,hit_tokens,evictions,bytes} +
            # hit_rate — the automatic radix reuse surface
            out["prefix_cache"] = prefix_store.stats()
        if kv_ship_stats is not None:
            # disaggregated-serving export/import counters; nested under
            # batching like the engine's other serve-path blocks (a
            # batcher-less server still reports them — the ship surface
            # rides the prefix store, not the engine)
            out.setdefault("batching", {})["disagg"] = \
                kv_ship_stats.report()
        if live_faults is not None:
            # faults.armed: the LIVE injection plan (sites, kinds,
            # remaining fire counts) — a soak run, or a stray
            # LAMBDIPY_FAULT left set in prod, is visible at the front
            # door instead of only in the process's environment
            out["faults"] = {"armed": live_faults.armed()}
        if warm_state["requested"] or warm_group:
            # gate on what was ASKED (listed buckets or the engine's
            # group-prefill warm), not on what finished: an in-flight
            # warm with empty done/errors lists must still be visible
            # in /metrics, or operators can't tell "running" from "not
            # started" (ADVICE r5). Snapshot under the lock: the warm
            # daemon appends while we serialize.
            with _warm_lock:
                out["warm_buckets"] = {
                    k: list(v) if isinstance(v, list) else v
                    for k, v in warm_state.items()}
        return out

    return HandlerState(
        invoke_fn=invoke, stats_fn=stats,
        invoke_stream_fn=invoke_stream if server is not None else None,
        prefix_probe=(prefix_store.match_len
                      if prefix_store is not None else None),
        # bare dict read — GIL-atomic, no lock: exactly what a
        # once-per-probe-interval health check may cost
        warming_fn=lambda: bool(warm_state["in_flight"]),
        engine_fault_fn=(continuous.fault_state
                         if continuous is not None else None),
        row_ending_hook=((lambda fn: setattr(continuous, "row_ending_fn", fn))
                         if continuous is not None else None),
        kv_export_fn=kv_export,
        kv_import_fn=kv_import,
        kv_export_stream_fn=kv_export_stream,
        kv_import_stream_fn=kv_import_stream,
        kv_probe_fn=kv_probe,
        session_end_fn=(prefix_store.end_session
                        if prefix_store is not None else None),
        debug_invariants_fn=debug_invariants,
        faults_admin_fn=faults_admin,
        knobs_admin_fn=knobs_admin,
        meta={
            "model": spec["model"], "quant": spec.get("quant"),
            "sharded": mesh is not None,
            "mesh": ({a: int(n) for a, n in dict(mesh.shape).items()}
                     if mesh is not None else None),
            "tokenizer": tokenizer is not None,
            "compile_once": server is not None,
            "streaming": server is not None,
            "prefix_cache": prefix_store is not None,
            "sessions": prefix_store is not None,
            "kv_ship": prefix_store is not None,
            "kv_paged": (continuous is not None
                         and continuous.pool is not None),
            **({"tokenizer_error": tok_err} if tok_err else {}),
        })


def torch_text_classify_handler(spec: dict, ctx) -> HandlerState:
    """Config 4 (torch path): torch-xla when available, CPU-torch smoke
    otherwise (SURVEY.md §9.7) — the degradation is reported per-invoke."""
    import numpy as np
    import torch

    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.torch_bert import TorchBertClassifier, xla_device_or_cpu

    extra = spec.get("extra") or {}
    model = TorchBertClassifier(
        vocab_size=int(extra.get("vocab_size", 30522)),
        hidden=int(extra.get("hidden", 768)),
        layers=int(extra.get("layers", 12)),
        heads=int(extra.get("heads", 12)),
        max_len=int(extra.get("max_len", 128)),
        num_classes=int(extra.get("num_classes", 2)),
    )
    if ctx.params_dir is not None:
        model.load_state_dict(registry.load_params("bert-base-torch", ctx.params_dir))
    model.eval()
    device, device_kind = xla_device_or_cpu()
    model = model.to(device)
    max_len = model.max_len

    def invoke(req: dict) -> dict:
        if req.get("warmup") or req.get("random"):
            ids = torch.zeros(1, max_len, dtype=torch.long)
            mask = torch.ones(1, max_len, dtype=torch.long)
        else:
            raw = np.asarray(req["input_ids"], dtype=np.int64)
            if raw.ndim == 1:
                raw = raw[None, :]
            ids = torch.zeros(raw.shape[0], max_len, dtype=torch.long)
            mask = torch.zeros(raw.shape[0], max_len, dtype=torch.long)
            n = min(max_len, raw.shape[1])
            ids[:, :n] = torch.from_numpy(raw[:, :n])
            mask[:, :n] = 1
        with torch.no_grad():
            logits = model(ids.to(device), mask.to(device)).cpu().numpy()
        return {
            "ok": True,
            "labels": logits.argmax(-1).tolist(),
            "device": device_kind,  # "cpu" = the documented degraded path
        }

    meta = {"model": spec["model"], "device": device_kind}
    if device_kind == "cpu":
        # say it LOUDLY in /healthz meta, not just per-invoke: any number
        # measured against this deployment is the documented CPU-torch
        # degradation (torch-xla unavailable), not a TPU number
        meta["degraded"] = ("torch-xla unavailable: serving on CPU torch; "
                            "measured latencies are NOT TPU numbers "
                            "(SURVEY.md §9.7)")
    return HandlerState(invoke_fn=invoke, meta=meta)
