"""Continuous (in-flight) batching for the generate handler.

The MicroBatcher (runtime/batching.py) fuses requests that arrive within
one collection window; a request arriving mid-decode still waits for the
whole previous decode. This module removes that wait: a persistent
batched decode advances in SEGMENTS (the same compiled segment program
streaming uses — the carry goes in and comes out every ``segment``
tokens), and new requests join at the next segment boundary by being
packed into a free batch slot. This is the serving-throughput feature
that separates a demo server from a serving framework (VERDICT r3
missing #3): decode is weight-bytes-bound on TPU, so B in-flight rows
decode in nearly the time of one.

Design (all device work rides LlamaServer's compiled-program cache):

- The engine owns a B-slot decode carry ``(tok[B], lp[B], cache(B, L),
  pos[B], done[B], rng)`` over a fixed ``cache_len`` L. Slots are a HOST
  concept: the device program always steps all B rows; inactive slots
  compute garbage that is never read (that padding is the price of a
  single compiled shape).
- A request prefills ALONE (single-row bucketed prefill — the streaming
  prefill program) producing a 1-row carry, then waits for the engine to
  pack it into a free slot with a jitted per-leaf
  ``dynamic_update_slice`` at the slot index (one compile total: the
  slot is a traced operand).
- The engine thread is PIPELINED (``pipeline_depth``, default 2):
  dispatch is async in JAX and the carry threads device-side, so the
  loop dispatches segment N+1 immediately after segment N's dispatch
  returns and a COLLECTOR stage drains completed segments behind the
  dispatch frontier — fetch the [B, segment] token block (one
  device-to-host synchronization), deliver each active row's slice, mark
  rows that finished (max_new reached, or eos seen in the newly appended
  block). Device compute therefore overlaps the host fetch + bookkeeping
  window instead of idling through it. Slot retirement and joiner
  packing happen only at pipeline-drain BARRIERS (pipeline empty): a row
  that finishes mid-pipeline keeps its slot as a garbage row until the
  next barrier and the blocks dispatched past its finish are discarded
  host-side (counted as ``wasted_overdecode_tokens``), so outputs stay
  bitwise identical to the synchronous ``pipeline_depth=1`` loop; a
  pending joiner forces a bounded drain (at most ``pipeline_depth - 1``
  in-flight segments) so packing sees host-truth slots and a
  host-materialized carry. The engine exits when idle and restarts on
  the next request.
- SLOT HANDOVER at a row's known end. A BATCH slot is a row of the
  device carry; a RUN slot is the admission layer's (``sched``: at most
  ``max_concurrency`` requests past the HTTP gate, floored at the
  batcher's width), so with every batch slot taken the next request
  waits in front of the gate, not in ``_joiners``, until a response has
  been written. Two things keep a freed slot from decoding garbage
  while that happens, both read off what the host knows at dispatch
  time (``disp``, ``n``, no verify step of the row in flight: ``disp``
  is exact then). (a) The dispatch that leaves a row at most one
  segment of quota calls ``row_ending_fn`` once (the server sets it to
  ``Scheduler.grant_ahead``: one queued ticket is granted on a credit
  the next ``finish()`` repays), so the next request is a joiner by the
  time the row's last segment is dispatched. (b) With a joiner waiting
  and every slot taken, a live row whose whole output is dispatched
  counts like a finished one: the loop drains (cause ``handover``)
  instead of dispatching a segment that would step it as a garbage
  row; the collector books its last block, the barrier frees the slot,
  the joiner packs. That is what the depth-1 loop does anyway, so
  tokens are unchanged; a row that ends at an eos is seen at collect
  only and keeps the over-decode path. Both halves hold for requests
  the BARRIER prefills (prompts up to ``group_prefill_max``: the grant
  ahead passes that bound and a longer ticket waits for the release
  itself; the drain wants a joiner without a carry of its own). A long
  prompt prefills on its request thread the moment it is granted:
  ahead of a release that prefill would run in front of the ending
  row's last segments, and a slot handed to a prefilled joiner a
  segment sooner puts the NEXT client's prefill behind that joiner's
  first token instead of in front of it: no token later, but a whole
  prefill inside somebody's TPOT (measured: PERF.md section 6, PR 40).
- A row's FIRST token does not wait for a segment. The prefill selects
  it and the decode scan re-emits the token it consumes, so column 0 of
  a row's first block is the token the pack wrote into the batch
  carry, unchanged. The engine reads the packed carry's ``tok`` leaf
  right after the dispatch that follows the pack (``deliver_first``:
  phase ``eng.first``, fault site ``first_fetch``) and hands the token
  over; the collector books the first block from its second column. A
  stream's chunks are therefore 1 token, then ``segment - 1``, then
  whole segments, and a stream stops being replayable when that one
  token has been yielded. ``stats()`` ``first_tokens_early`` counts the
  rows; beside ``requests_served`` it is a share, 1.0 expected.
- Per-row independence makes this exact: each row's attention reads only
  its own cache row and position (models/llama.py ragged decode), so a
  row's greedy tokens are identical whether it decodes solo or packed
  next to arbitrary traffic — asserted bitwise in tests.
- eos is handled HOST-side: the device decodes with eos latching
  disabled and the engine truncates a row at its own eos, padding with
  eos exactly like the fused path's filler. This removes eos from any
  fuse key — rows with different eos ids share the batch — at the cost
  of at most one wasted segment per early-stopping row.
- SAMPLED rows batch too (VERDICT r5 #2): the segment program's
  sampling knobs are per-row operands and each row's PRNG chain derives
  from its own seed alone (llama._knob_operands), so a sampled row's
  tokens are identical solo or packed — ``seed`` keeps its
  reproducibility promise under arbitrary concurrent traffic. The
  per-slot knob vectors are assembled host-side before each segment.

- FAULT ISOLATION (runtime/faults.py has the injection layer): every
  device-side wait the engine thread makes (dispatch, per-segment fetch,
  group prefill) is registered with a WATCHDOG monitor; a wait exceeding
  ``watchdog_s`` marks the engine **wedged**, aborts every waiter, and
  bumps the engine GENERATION so the stuck thread can never touch
  restarted state (it observes the stale generation and exits at its
  next step). On any engine failure — exception or watchdog trip — rows
  that have delivered NO bytes to their client (non-streamed, or
  streamed before the first chunk) are requeued and transparently
  REPLAYED through a restarted engine (seeded per-row PRNG chains make
  the replay bitwise the first attempt), bounded by ``max_replays``;
  only partially-streamed rows surface the error. Repeated failures
  inside ``degrade_window_s`` step a DEGRADATION LADDER down — pipeline
  depth 1, then window bucketing off, then prefix-cache bypass — which
  auto-restores after ``degrade_clean_s`` without a failure; everything
  is published as ``EngineFaultStats`` under ``batching.faults``. Rows
  whose waiter went away (closed stream socket) or whose
  ``x-deadline-ms`` expired are CANCELLED at the next drain barrier
  instead of decoding to completion.

- SPECULATIVE DECODING (``spec_k``, default off): each segment becomes
  draft -> batched-verify -> accept/rollback. The host drafts up to
  ``spec_k - 1`` tokens per row by prompt lookup (llama._lookup_draft;
  rows with no n-gram match fall back to repeat-last drafts whose
  rejection makes the step emit exactly 1 token — today's path), ONE
  multi-token verify program scores every row's proposals per dispatch
  (llama._spec_seg_fn, paged twin _spec_pseg_fn), and the collector
  books each row's accepted prefix — the rejected tail is discarded
  exactly like its over-decode discard, its KV already stranded in
  garbage positions behind the device-side index (dense) or absorbed
  by the null page (paged). Acceptance is CHAIN-deterministic
  (llama._spec_chain_verify): a draft is accepted iff it equals the
  token the row's seeded select chain would emit, so outputs are
  BITWISE the non-speculative engine's — greedy and seeded-sampled
  alike — and replay after an engine failure stays exact. Pipelining
  composes through dispatch-time draft state: at depth >= 2 the host
  drafts the next step assuming the in-flight one accepts everything
  (the only regime where speculation pays anyway) and the collector
  reconciles against fetched truth, resetting the optimistic chain on
  divergence. Variable per-row advancement is bounded host-side: disp
  books the worst-case k advance per dispatch and the collector
  refunds rejected tails, so window bucketing (sized by post-accept
  max position upper bounds), joiner drains, and quota checks stay
  exact. Acceptance counters ride ``batching.spec`` on ``/metrics``
  (runtime/metrics.SpecDecodeStats, shared with the solo spec path).

Opt-in per bundle: ``[payload.extra] batch_mode = "continuous"``
(default keeps the window MicroBatcher when ``batch_window_ms`` is set).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any

from lambdipy_tpu.runtime import spans
from lambdipy_tpu.runtime.faults import EngineWatchdogTimeout, FaultPlan
from lambdipy_tpu.utils.logs import get_logger

log = get_logger("lambdipy.continuous")

_entry_seq = itertools.count()


class _StaleEngine(Exception):
    """Raised inside an engine thread whose generation was superseded by
    the watchdog (or a concurrent failure handler): the replacement
    engine owns the batch state now, so the stale thread must unwind
    without touching it."""


class RequestCancelled(RuntimeError):
    """A row cancelled at a drain barrier: its waiter disappeared
    (closed stream socket) or its deadline expired mid-decode."""


class _StaleArena(Exception):
    """The page arena was reset (engine failure) between a prefix
    acquisition and its continuation: the shared pages the continuation
    would read are zeroed now. The admission falls back to the dense
    solo path instead of serving wrong KV."""


class DraftProvider:
    """Host-side draft source behind the engine's provider seam
    (``draft_mode="aux"``): given a row's confirmed context, propose up
    to ``k`` continuation tokens for the chain verify to score. A
    provider is ONLY ever a proposal source — acceptance is decided by
    :func:`lambdipy_tpu.models.llama._spec_chain_verify` against the
    target's own select walk, so a wrong (or short, padded with ``-1``)
    proposal costs wasted verify positions, never a wrong token. The
    in-program shallow-exit head (``draft_mode="model"``) does NOT go
    through this interface: it drafts on-device inside the verify
    program, which is what keeps it fresh under pipelining."""

    def propose(self, context, k: int) -> list:
        raise NotImplementedError


class AuxModelDraft(DraftProvider):
    """A separate small draft model behind :class:`DraftProvider`: any
    ``generate``-shaped server (e.g. a TP-replicated registry twin built
    by :func:`lambdipy_tpu.models.registry.draft_twin`) greedily
    continues the context by ``k`` tokens. Reference implementation for
    the two-model draft tier — it re-prefills the context every call, so
    at CPU bench scale the self-drafting shallow-exit head is the one
    that pays; this seam is what a cached-KV draft server would slot
    into."""

    def __init__(self, server: Any):
        self.server = server

    def propose(self, context, k: int) -> list:
        import numpy as np

        ctx = [int(t) for t in np.asarray(context).reshape(-1)]
        if not ctx or k <= 0:
            return []
        out = self.server.generate(ctx, max_new_tokens=int(k))
        return [int(t) for t in np.asarray(out).reshape(-1)[:k]]


class ContinuousBatcher:
    """Segment-boundary continuous batching over a LlamaServer."""

    def __init__(self, server: Any, *, slots: int = 8, segment: int = 16,
                 cache_len: int | None = None,
                 group_prefill_max: int = 256, policy: Any = None,
                 window_bucketing: bool = True, pipeline_depth: int = 2,
                 watchdog_s: float = 0.0, max_replays: int = 1,
                 faults: FaultPlan | None = None,
                 degrade_window_s: float = 60.0,
                 degrade_clean_s: float = 30.0,
                 page_pool: Any = None,
                 spec_k: int = 0, spec_ngram: int = 3,
                 draft_mode: str = "lookup", draft_exit: int = 1,
                 draft_provider: Any = None,
                 max_logical_ctx: int = 0,
                 long_prefill: bool = False,
                 prefill_mode: str = "chunked"):
        import jax

        from lambdipy_tpu.runtime.metrics import (DecodeWindowStats,
                                                  EngineFaultStats,
                                                  KindCounters,
                                                  PipelineStats,
                                                  PrefillStats,
                                                  SpecDecodeStats)

        self.server = server
        cfg = server.model.cfg
        self.slots = max(1, slots)
        self.segment = max(1, segment)
        # length-aware decode dispatch: each segment runs through a pow-2
        # WINDOW-bucketed program variant sized to the live batch's max
        # active context (LlamaServer._windowed_seg_fn), so XLA decode
        # KV reads scale with what rows actually hold instead of the
        # full engine cache — the decode-side twin of prefill
        # bucketing. Tokens are bitwise the full-window program's; the
        # plain segment program still serves windows at the cache cap.
        self.window_bucketing = bool(window_bucketing)
        self.window_stats = DecodeWindowStats()
        # what the model's kinds count (models/llama.py Counters: an
        # attention kind's or the routed FFN's own declaration): the
        # segment programs return, behind their tokens, the sum of every
        # collection the declarations name, in their order; the collector
        # books them, and the prefill paths what a kind counts of a prefill
        # from shapes, into one recorder a /metrics block (handler.<block>)
        kinds = getattr(cfg, "counters", tuple)()
        self.counters: dict = {}
        for kind in kinds:
            self.counters.setdefault(kind.block, KindCounters()).add(kind)
        self._sown = [name for kind in kinds for name in kind.sown]
        # segments kept in flight on the device before the host fetches
        # the oldest: 1 = the fully synchronous loop (dispatch, fetch,
        # book, repeat — the device idles through every fetch RTT +
        # host window), >= 2 overlaps device compute with the collector
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.pipeline_stats = PipelineStats(depth=self.pipeline_depth)
        # -- speculative decoding (default OFF) ------------------------------
        # spec_k >= 2 turns every engine segment into draft -> batched
        # multi-token verify -> accept/rollback: the host drafts up to
        # kb - 1 tokens per row via prompt lookup, ONE kb-wide device
        # dispatch (models/llama.py _spec_seg_fn / _spec_pseg_fn) scores
        # all rows' proposals, and the collector keeps each row's
        # accepted prefix — rolling back the rejected tail exactly like
        # its over-decode discard. Acceptance is CHAIN-deterministic
        # (_spec_chain_verify): emitted tokens are bitwise the
        # non-speculative engine's for greedy and seeded-sampled rows
        # alike, so spec only changes tokens-per-weight-read.
        # spec_k <= 1 is plain decode (k = 1 IS today's exact path);
        # k bucketizes to a pow-2 like the solo path so program count
        # stays bounded.
        self.spec_k = 0
        if spec_k and int(spec_k) >= 2:
            from lambdipy_tpu.models.llama import (_next_bucket,
                                                   require_row_a_token)

            self.spec_k = max(2, _next_bucket(int(spec_k), 2))
            require_row_a_token(
                cfg, "spec_k (a verify chunk is several positions wide and "
                "a rejected tail is rolled back; no such engine)")
            for kind in kinds:
                if kind.sown:
                    raise NotImplementedError(
                        f"spec_k on {kind.what}: the verify segments "
                        f"return none of what it counts, so handler."
                        f"{kind.block} would stop counting in silence")
        # spec verify chunks are multi-token steps, which the
        # sequence-parallel decode path cannot serve (spdecode is a
        # one-token formulation): under an sp mesh every verify would
        # silently replicate the sequence-sharded cache. Stand DOWN the
        # spec knob instead — observable through the same per-reason
        # counter the other sp stand-downs use, never silent.
        srv_mesh = getattr(server, "mesh", None)
        if self.spec_k and srv_mesh is not None \
                and dict(getattr(srv_mesh, "shape", {})).get("sp", 1) > 1 \
                and getattr(cfg, "attn_backend", "dense") == "ring":
            from lambdipy_tpu.parallel.spdecode import note_standdown

            note_standdown("spec_k_under_sp_mesh")
            if str(draft_mode or "").lower() in ("model", "auto", "aux"):
                # the draft tier rides the spec verify chunk, so it
                # stands down with it — counted under its own reason so
                # a fleet can tell "spec off under sp" from "draft tier
                # requested but unservable"
                note_standdown("draft_tier_under_sp_mesh")
            log.warning(
                "engine spec_k=%d stands down: the mesh's sp axis serves "
                "decode through sequence-parallel one-token steps, and a "
                "multi-token verify chunk would replicate the sharded KV "
                "cache (reason=spec_k_under_sp_mesh on /metrics)",
                self.spec_k)
            self.spec_k = 0
        self.spec_ngram = max(1, int(spec_ngram))
        # -- model draft tier (ROADMAP direction 4) --------------------------
        # draft_mode picks the engine-default DRAFT PROVIDER for rows
        # admitted while it holds (live-retunable via /v1/debug/knobs):
        #   "lookup" — PR 9's host prompt-lookup drafting, fixed k
        #              (today's exact behavior, still the default);
        #   "model"  — the self-drafting shallow-exit head
        #              (models/llama.py _shallow_draft through the
        #              _mspec_* program families): per-row ADAPTIVE k
        #              slow-starts at 2, grows on a high acceptance EWMA
        #              and collapses model -> lookup -> off per row, so
        #              an adversarial row stops paying the draft forward
        #              while its neighbors keep speculating;
        #   "aux"    — a separate small draft model behind the same
        #              seam: a host-side DraftProvider (draft_provider=,
        #              e.g. AuxModelDraft over a registry twin) proposes
        #              the tokens, adaptivity identical to "model";
        #   "off"    — spec verify stays available but rows draft
        #              nothing (plain decode until retuned).
        # Whatever the provider proposes, acceptance is the SAME
        # chain-deterministic verify — outputs stay bitwise spec-off.
        dm = str(draft_mode or "lookup").lower()
        if dm == "auto":
            dm = "model"
        if dm not in ("model", "lookup", "aux", "off"):
            log.warning("unknown draft_mode %r; using lookup", draft_mode)
            dm = "lookup"
        self.draft_provider = draft_provider
        if dm == "aux" and draft_provider is None:
            log.warning("draft_mode=aux needs draft_provider=; "
                        "using lookup")
            dm = "lookup"
        self.draft_mode = dm
        layers = int(getattr(cfg, "layers", 1) or 1)
        self.draft_exit = max(1, min(int(draft_exit or 1), layers))
        # per-row adaptive-k controller constants: EWMA weight on the
        # newest step's accepted fraction, and the grow/shrink bands
        # (hysteresis — the gap keeps k from flapping at a steady
        # mid-range acceptance)
        self.spec_ewma_alpha = 0.3
        self.spec_grow = 0.75
        self.spec_shrink = 0.35
        # -- tensor-parallel sharded serving (ROADMAP direction 3) -----------
        # a server with a multi-device mesh runs every engine program
        # SPMD: params and the KV carry are tp-sharded, the host-side
        # logic above the dispatch boundary (slots, block tables, window
        # buckets, joiners) is unchanged. batching.mesh publishes the
        # layout + live per-device HBM split.
        self.mesh_stats = None
        if srv_mesh is not None and getattr(srv_mesh, "devices", None) is not None \
                and srv_mesh.devices.size > 1:
            from lambdipy_tpu.runtime.metrics import MeshStats

            shape = {a: int(n) for a, n in dict(srv_mesh.shape).items()
                     if int(n) > 1}
            tp = shape.get("tp", 1)
            self.mesh_stats = MeshStats()
            self.mesh_stats.set_layout(
                shape=shape, devices=int(srv_mesh.devices.size),
                # Megatron layout: per decoded token, one all-reduce
                # for the vocab-sharded embedding lookup, one after
                # o_proj + one after down_proj per layer, plus the
                # lm_head logits all-gather per select (analytic count;
                # 0 without a tp axis)
                collectives_per_segment=(
                    self.segment * (2 * cfg.layers + 2) if tp > 1 else 0))
            try:
                from lambdipy_tpu.parallel.sharding import device_bytes

                per_dev, total = device_bytes(server.params)
                self.mesh_stats.set_param_bytes(per_dev, total)
            except Exception:  # noqa: BLE001 — observability only
                pass
        # ONE SpecDecodeStats serves the solo spec path and this engine
        # (the server owns it); a server without one (stub adapters in
        # tests) gets a private instance
        self.spec_metrics = getattr(server, "spec_metrics", None)
        if self.spec_metrics is None:
            self.spec_metrics = SpecDecodeStats()
        # sched policy: when slots are scarce, waiting joiners are packed
        # in POLICY order (priority / fair-share by request class from
        # the scheduler's context) instead of arrival order; None = FIFO
        self.policy = policy
        # slot handover, the admission layer's half: called (from the
        # engine thread, outside its lock, with group_prefill_max: the
        # longest prompt the barrier prefills itself) once for each row
        # when the dispatch that leaves it at most one segment of quota
        # is made, i.e. a run slot is about to be released. The HTTP
        # server sets it to Scheduler.grant_ahead; None = nobody gates
        # admission in front of this engine
        self.row_ending_fn = None
        self.cache_len = min(cache_len or cfg.max_len, cfg.max_len)
        # prompts up to this length enqueue RAW and the engine prefills
        # them together in one ragged b-row call (prefill MFU at short
        # prompts scales with rows — 8 x 16-token prefills are one
        # 128-row-equivalent matmul instead of eight skinny ones);
        # longer prompts prefill on their request thread (chunked when
        # the server has prefill_chunk), whose chunk dispatches
        # interleave with engine segments on the device queue instead
        # of stalling in-flight decode behind one wide program
        self.group_prefill_max = max(0, group_prefill_max)
        # -- paged KV (runtime/pagepool.py) ----------------------------------
        # a PagePool turns the engine's KV residency from B full windows
        # into refcounted pages over one arena: admission charges
        # ceil(actual tokens / page) pages, prefix hits share pages by
        # refcount bump, and the decode segments gather/scatter each
        # row's pages through its block table (models/llama.py paged
        # program family) — tokens stay bitwise the dense engine's.
        self.pool = page_pool
        # paged prefix hits resolve prefix tokens -> (page ids, length)
        # through this hook (the handler wires the radix store's
        # acquire_pages); None = prefix rows fall back solo
        self.prefix_pages_fn = None
        if self.pool is not None:
            if self.cache_len % self.pool.page:
                raise ValueError(
                    f"page {self.pool.page} does not divide engine "
                    f"cache_len {self.cache_len}")
            self.pool.window_pages = self.cache_len // self.pool.page
        self._pack5_fn = None  # scalar-leaf pack for paged prefix carries
        # -- long-context tier (runtime/longctx.py) --------------------------
        # max_logical_ctx > cache_len routes a request whose prompt +
        # budget exceeds the engine cache — today's solo-fallback seam,
        # where the solo path would REJECT it — to a LongContextRunner:
        # a sliding logical window over the compiled one, evicted pages
        # spilled to a host offload arena and re-onlined under the
        # decode's device time. 0 disables (the exact prior behavior).
        # Needs a page pool (the runner rides the shared arena); without
        # one the knob stands down loudly at construction, not at the
        # first routed request.
        self.max_logical_ctx = max(0, int(max_logical_ctx or 0))
        # the compiled window is the retune FLOOR for the fleet
        # controller's max_logical_ctx rule; the boot value is its
        # restore CEILING — both published under batching.long_context
        self.max_logical_ctx_boot = self.max_logical_ctx
        self.long_prefill = bool(long_prefill)
        # -- whole-prompt sequence-parallel prefill (prefill_mode knob) ------
        # "chunked" keeps every cold prefill the serial chunk chain;
        # "sp" collapses it to rounds of sp chunk-widths, each ONE
        # sharded program (models/llama.py sp_prefill family). Resolved
        # against the server's mesh here and re-resolved on live retune
        # (/v1/debug/knobs); sp without an sp mesh axis stands down with
        # a counted reason, exactly like spec_k_under_sp_mesh.
        self.prefill_stats = PrefillStats()
        self.prefill_mode = "chunked"
        self.prefill_sp = 0
        self.set_prefill_mode(prefill_mode)
        self._longctx: Any = None     # built lazily on first routed row
        self._longctx_lock = threading.Lock()
        if self.max_logical_ctx and page_pool is None:
            log.warning(
                "max_logical_ctx=%d needs paged KV (--kv-paged); the "
                "long-context tier stands down", self.max_logical_ctx)
            self.max_logical_ctx = 0
        # -- fault isolation -------------------------------------------------
        # watchdog_s bounds every device-side wait the ENGINE thread
        # makes (dispatch, per-segment fetch, group prefill) plus the
        # request-thread prefix assembly; 0 disables — the default,
        # because a first dispatch legitimately includes a compile (~40 s
        # per program at 8B width on the v5e's host) and the operator
        # must size the timeout above it (env
        # LAMBDIPY_ENGINE_WATCHDOG_S / bundle extra
        # engine_watchdog_s / `lambdipy serve --engine-watchdog`)
        self.watchdog_s = max(0.0, float(watchdog_s or 0.0))
        # rows with no bytes delivered are transparently replayed through
        # a restarted engine at most this many times before erroring
        self.max_replays = max(0, int(max_replays))
        self.faults = faults if faults is not None else FaultPlan.empty()
        self.fault_stats = EngineFaultStats()
        if self.pool is not None and self.pool.faults is None:
            # the engine's armed plan drives the page_alloc site too, so
            # one LAMBDIPY_FAULT spec covers allocator chaos
            self.pool.faults = self.faults
        # degradation ladder: >= 2 failures inside degrade_window_s step
        # the level (1: pipeline depth -> 1, 2: + window bucketing off,
        # 3: + prefix cache bypassed); degrade_clean_s without a failure
        # restores level 0
        self.degrade_window_s = max(0.1, float(degrade_window_s))
        self.degrade_clean_s = max(0.1, float(degrade_clean_s))
        self._fail_times: list[float] = []
        self._last_failure_t: float | None = None
        self._had_failure = False        # recovery pending a clean fetch
        # generation stamp: bumped on every engine failure so a stuck
        # thread (hung device_get) can never mutate restarted state
        self._gen = 0
        self._waits: dict[int, dict] = {}   # watchdog-registered waits
        self._wait_seq = itertools.count()
        self._monitor: threading.Thread | None = None
        # wedged-idle self-probe bookkeeping (_recovery_probe)
        self._probe_t = 0.0
        self._probe_live = False
        self._probe_misses = 0   # consecutive failed probes -> backoff
        del jax  # imported for device presence; carry is built lazily
        self._lock = threading.Condition()
        self._joiners: list[dict] = []   # prefilled rows awaiting a slot
        self._active: list[dict | None] = [None] * self.slots
        self._engine_running = False
        self._carry = None               # lazily built B-slot device carry
        self._pack_fn = None
        # observability (stats()): how much fusing actually happened
        self.segments_run = 0
        self.rows_in_segments = 0
        self.requests_served = 0
        self.prefill_groups = 0      # engine-side grouped prefill calls
        self.rows_group_prefilled = 0
        # rows that joined the engine FROM a cached prefix KV (explicit
        # prefix= or the automatic radix store): suffix-only
        # continuation carries packed into the shared batch
        self.prefix_joins = 0
        # rows whose first token (the prefill's, read from the packed
        # carry) went out before their first segment was collected;
        # beside requests_served a share, 1.0 when every row took that
        # path
        self.first_tokens_early = 0

    def set_prefill_mode(self, mode) -> str:
        """Resolve + apply the ``prefill_mode`` knob (``chunked`` |
        ``sp``). Live-retunable: the next cold prefill picks up the new
        schedule (program families are cached per (width, sp), so
        flipping back and forth costs nothing after first compile).
        ``sp`` without a usable sp mesh axis stands down to chunked with
        the counted ``sp_prefill_without_sp_mesh`` reason."""
        from lambdipy_tpu.models.llama import resolve_sp_prefill

        mode = str(mode or "chunked").lower()
        if mode not in ("chunked", "sp"):
            raise ValueError(
                f"prefill_mode must be 'chunked' or 'sp', got {mode!r}")
        sp = resolve_sp_prefill(mode, getattr(self.server, "mesh", None))
        self.prefill_mode = mode
        self.prefill_sp = sp
        if mode == "sp" and not sp:
            self.prefill_stats.record_standdown("sp_prefill_without_sp_mesh")
        self.prefill_stats.configure(mode, sp)
        return mode

    # -- device helpers ------------------------------------------------------

    def _init_carry(self):
        """Fresh all-inactive B-slot carry (device). Paged engines carry
        only the scalar leaves — the KV lives in the pool's arena, which
        PERSISTS across engine restarts (replayed rows re-scatter their
        pages; frozen prefix pages survive untouched)."""
        import jax.numpy as jnp

        from lambdipy_tpu.models.llama import init_decode_cache

        cfg = self.server.model.cfg
        b = self.slots
        scalars = (jnp.zeros((b,), jnp.int32),      # tok
                   jnp.zeros((b,), jnp.float32),    # lp
                   jnp.zeros((b,), jnp.int32),      # pos
                   jnp.zeros((b,), jnp.bool_),      # done (never latches)
                   jnp.zeros((b, 2), jnp.uint32))   # per-row PRNG keys
        if self.pool is not None:
            self.pool.ensure_arena()
            return scalars
        cache = init_decode_cache(cfg, b, self.cache_len)
        for entry in cache:
            entry["index"] = jnp.zeros((b,), jnp.int32)
        mesh = getattr(self.server, "mesh", None)
        if mesh is not None and self.mesh_stats is not None:
            # place the B-slot cache kv-head-sharded from birth: the
            # engine's dominant HBM object costs 1/tp per device, and
            # the segment programs' in-program hints keep the layout
            # across every carry update (no per-segment reshard)
            from lambdipy_tpu.models.llama import shard_kv_cache

            cache = shard_kv_cache(cache, mesh)
        tok, lp, pos, done, keys = scalars
        return (tok, lp, cache, pos, done, keys)

    def _pack(self, carry, group_carry, src: int, slot: int):
        """Write row ``src`` of a (1..b)-row carry into batch slot
        ``slot`` (one compiled program per source-carry batch size: the
        row and slot indices are traced operands)."""
        import jax

        if self._pack_fn is None:
            def pack(batch_carry, group_carry, src, slot):
                def upd(b_leaf, g_leaf):
                    row = jax.lax.dynamic_slice_in_dim(g_leaf, src, 1, 0)
                    return jax.lax.dynamic_update_slice_in_dim(
                        b_leaf, row.astype(b_leaf.dtype), slot, 0)

                tok, lp, cache, pos, done, keys = batch_carry
                gtok, glp, gcache, gpos, gdone, gkeys = group_carry
                new_cache = [{k: upd(c[k], gc[k]) for k in c}
                             for c, gc in zip(cache, gcache)]
                # the row's PRNG chain packs too: its post-prefill key
                # continues exactly where solo decode would be
                return (upd(tok, gtok), upd(lp, glp), new_cache,
                        upd(pos, gpos), upd(done, gdone), upd(keys, gkeys))

            placement = {}
            params = jax.tree.leaves(getattr(self.server, "params", None))
            if getattr(self.server, "mesh", None) is None and params \
                    and hasattr(params[0], "devices"):
                # one device: NAME it. jit otherwise keys the compiled
                # program on the sharding each carry arrives with, which
                # depends on what produced the carry (a jit, an executable
                # loaded from the AOT store, a fresh scratch): on the chip
                # the warm's pack and the first burst's compiled
                # separately, the second inside the request window (PR 21).
                from jax.sharding import SingleDeviceSharding

                where = SingleDeviceSharding(next(iter(params[0].devices())))
                placement = {"in_shardings": where, "out_shardings": where}
            self._pack_fn = jax.jit(pack, **placement)
        import jax.numpy as jnp

        return self._pack_fn(carry, group_carry, jnp.int32(src),
                             jnp.int32(slot))

    # -- paged-KV helpers ----------------------------------------------------

    def _table_row(self, entry: dict, nb: int):
        """Entry's block table as ``nb`` int32 page ids, null-padded —
        the host-truth view the paged programs index by."""
        import numpy as np

        pids = entry.get("pages") or []
        row = np.zeros((nb,), np.int32)
        take = min(nb, len(pids))
        row[:take] = pids[:take]
        return row

    def _release_pages(self, entry: dict) -> None:
        """Idempotently return an entry's pages to the pool (refcount
        drop; shared prefix pages stay live under the store's ref)."""
        pids = entry.pop("pages", None)
        if pids and self.pool is not None:
            try:
                self.pool.release(pids)
            except Exception as e:  # noqa: BLE001 — accounting must not
                # take the engine down; the invariant tests catch bugs
                log.error("page release failed: %s", e)

    def _charge_pages(self, entry: dict, tokens: int,
                      shared: list | None = None) -> None:
        """Admission charges pages for the row's ACTUAL tokens (prompt +
        prefix + requested decode). Shared prefix pages ride in already
        refcount-bumped; only the remainder allocates. PagesExhausted
        propagates priced; any other allocator failure (an armed
        ``page_alloc`` fault, an accounting bug) sheds THIS row as
        backpressure instead of failing the engine."""
        from lambdipy_tpu.runtime.pagepool import PagesExhausted

        shared = shared or []
        page = self.pool.page
        need = -(-tokens // page) - len(shared)
        try:
            fresh = self.pool.alloc(max(0, need),
                                    tokens=tokens - len(shared) * page)
        except PagesExhausted:
            if shared:
                self.pool.release(shared)
            raise
        except Exception as e:  # noqa: BLE001 — injected fault / bug
            if shared:
                self.pool.release(shared)
            self.fault_stats.record_failure(
                getattr(e, "fault_site", "page_alloc"))
            raise PagesExhausted(
                max(0, need), self.pool.free_count(),
                self.pool.retry_after_s(max(1, need))) from e
        entry["pages"] = list(shared) + fresh

    def _pack5(self, carry5, row_carry5, slot: int):
        """Pack a 1-row scalar carry (a paged prefix continuation, whose
        KV is already in the arena) into batch slot ``slot``."""
        import jax

        if self._pack5_fn is None:
            def pack(batch, row, slot):
                def upd(b_leaf, g_leaf):
                    r = jax.lax.dynamic_slice_in_dim(g_leaf, 0, 1, 0)
                    return jax.lax.dynamic_update_slice_in_dim(
                        b_leaf, r.astype(b_leaf.dtype), slot, 0)

                return tuple(upd(b, g) for b, g in zip(batch, row))

            self._pack5_fn = jax.jit(pack)
        import jax.numpy as jnp

        return self._pack5_fn(carry5, row_carry5, jnp.int32(slot))

    def _pack_paged(self, carry5, group_carry, src: int, joiner: dict):
        """Pack row ``src`` of a contiguous prefill carry into the paged
        batch: scalars into the 5-leaf carry, the KV row scattered into
        the joiner's pages (under the arena chain lock)."""
        import jax.numpy as jnp

        from lambdipy_tpu.models.llama import cache_width

        pool = self.pool
        width = cache_width(group_carry[2])
        gb = group_carry[0].shape[0]
        fn = self.server._paged_pack_fn(gb, pool.n_pages, pool.page, width)
        table = jnp.asarray(self._table_row(joiner, width // pool.page))
        with pool.arena_lock:
            new5, new_arena = fn(*carry5, group_carry, jnp.int32(src),
                                 jnp.int32(joiner["slot"]), pool.arena,
                                 table)
            pool.arena = new_arena
        return new5

    def _paged_continue_row(self, entry: dict):
        """Suffix continue-prefill for a paged prefix hit: the matched
        pages are read IN PLACE through the block table and only the
        suffix writes (into the entry's fresh pages) — the zero-copy
        twin of ``_prefill_prefix_row``. Returns the 5-leaf row carry;
        the arena chain advances under the pool lock."""
        import jax.numpy as jnp

        from lambdipy_tpu.models.llama import _next_bucket

        server = self.server
        pool = self.pool
        plen, s = entry["plen"], entry["s"]
        server._validate(plen + s, entry["n"])
        # clamped to the ENGINE window (== max_len on every routed
        # configuration, so the padded width — and with it the traced
        # shapes — matches the dense prefix path exactly): a wider
        # bucket would let the suffix write clamp back onto real KV
        # inside the gathered window
        sbs = min(_next_bucket(s, server.min_bucket),
                  self.cache_len - plen)
        # gather at the full engine window: the continuation then traces
        # at exactly the shapes the dense prefix path uses, keeping the
        # bitwise argument a shape identity rather than a reduction-
        # order proof
        window = self.cache_len
        cont = server._paged_continue_fn(sbs, pool.n_pages, pool.page,
                                         window)
        table = jnp.asarray(
            self._table_row(entry, window // pool.page))[None, :]
        suffix_op, _ = server._pad_rows([entry["row"]], [s], 1, sbs)
        knobs = server._knob_operands(
            entry["temperature"], entry["top_k"], entry["top_p"],
            entry["seed"], None, b=1)
        with pool.arena_lock:
            if entry.get("arena_gen") is not None \
                    and entry["arena_gen"] != pool.arena_generation:
                # the arena reset between the acquire and here: the
                # shared prefix pages are zeroed — do NOT read them
                raise _StaleArena()
            pool.ensure_arena()
            with server._mesh_ctx():
                first, lp0, new_arena, start, done0, keys = cont(
                    server.params, pool.arena, table, jnp.int32(plen),
                    suffix_op, jnp.int32(s), *knobs)
            pool.arena = new_arena
        return (first, lp0, start, done0, keys)

    def _book_prefill(self, lengths: list, rows: int, sb: int) -> None:
        """What the model's kinds count of one dispatched prefill of
        ``rows`` rows padded to ``sb`` positions."""
        for recorder in self.counters.values():
            recorder.record_prefill(lengths, rows, sb)

    def _prefill_row(self, row, s: int, entry: dict):
        """Single-row bucketed prefill -> 1-row carry over the engine's
        cache_len (reuses the streaming prefill program family, so a
        joiner costs one prefill compile per prompt bucket, shared with
        the streaming path). The row's OWN sampling knobs and seed drive
        the first-token select, so the carry continues exactly the
        chain solo decode would walk; eos stays disabled (host-side)."""
        server = self.server
        sb = max(s, min(server.model.cfg.prompt_bucket(s, server.min_bucket),
                        self.cache_len))
        sp = self.prefill_sp if (self.prefill_sp >= 2
                                 and sb % self.prefill_sp == 0) else 0
        prefill, _ = server._stream_fns(1, sb, self.cache_len, self.segment,
                                        sp_prefill=sp)
        if sp:
            self.prefill_stats.record_round(
                1, sp, ring_hops=server.model.cfg.layers * sp)
        prompt_op, length_op = server._pad_rows([row], [s], 1, sb)
        knobs = server._knob_operands(
            entry["temperature"], entry["top_k"], entry["top_p"],
            entry["seed"], None, b=1)
        self._book_prefill([s], 1, sb)
        with server._mesh_ctx():
            return prefill(server.params, prompt_op, length_op, *knobs)

    def _prefill_group(self, entries: list):
        """ONE ragged b-row prefill for all waiting short-prompt joiners
        (VERDICT r5 #4: prefill is compute-bound and short prompts run
        it at tiny row counts — 8 joiners' 16-token prefills are one
        128-row-equivalent matmul instead of eight skinny ones). Each
        row prefills under its own knobs/seed; row-exactness of the
        ragged prefill keeps solo parity. Returns the group carry;
        entry i packs from row i."""
        from lambdipy_tpu.models.llama import _next_bucket

        server = self.server
        rows = [e["row"] for e in entries]
        lens = [e["s"] for e in entries]
        bb = _next_bucket(len(rows), 1)
        sb = max(max(lens), min(
            server.model.cfg.prompt_bucket(max(lens), server.min_bucket),
            self.cache_len))
        # sharded group prefill: the ONE ragged b-row program ring-shards
        # its prompt attention over the sp axis — same program count,
        # 1/sp the attention critical path per group
        sp = self.prefill_sp if (self.prefill_sp >= 2
                                 and sb % self.prefill_sp == 0) else 0
        prefill, _ = server._stream_fns(bb, sb, self.cache_len,
                                        self.segment, sp_prefill=sp)
        if sp:
            self.prefill_stats.record_round(
                1, sp, ring_hops=server.model.cfg.layers * sp)
        prompt_op, length_op = server._pad_rows(rows, lens, bb, sb)
        knobs = server._knob_operands(
            [e["temperature"] for e in entries],
            [e["top_k"] for e in entries],
            [e["top_p"] for e in entries],
            [e["seed"] for e in entries],
            None, b=bb)
        self._book_prefill(lens, bb, sb)
        with server._mesh_ctx():
            return prefill(server.params, prompt_op, length_op, *knobs)

    def warm_group_prefill(self) -> int:
        """Compile (or AOT-load) the ragged group-prefill programs a
        FIRST concurrent burst would otherwise pay one at a time at
        request latency (a compile of seconds to tens of seconds each,
        against a burst that decodes in about one). One program per
        power-of-two joiner
        count 2..slots at the short-prompt bucket (the min bucket is
        the dominant family), PLUS one program at the longest prompt
        bucket group prefill can see (the ``group_prefill_max`` bucket,
        clamped to what the engine cache admits) at the full-burst
        joiner count — without it a burst of long-ish prompts paid the
        cliff the warm exists to remove (ADVICE r5). The prompt buckets
        BETWEEN the min and the max family (e.g. 32/64/128 under a 256
        cap) are not warmed here — every (count, bucket) pair is
        quadratic in programs and in warm wall-time, and a deploy would
        wait for pairs its traffic never sends. They cost their compile
        ONCE in a writable bundle's life: a pair is snapshotted into the
        server's AOT store where it first compiles (this warm or the
        first burst that needs it) and loaded at its first use in every
        later boot; only a read-only bundle whose build never ran the
        pair pays the cliff each boot. Later boots preload the pairs
        warmed here instead of compiling at all. Returns programs
        touched; meant for the handler's background warm daemon, never
        the boot path."""
        from lambdipy_tpu.models.llama import _next_bucket

        counts = []
        bb = 2
        while bb <= self.slots:
            counts.append(bb)
            bb *= 2
        if self.slots > 1 and self.slots not in counts:
            # non-power-of-two slots: a full burst buckets UP past slots
            # (_next_bucket(6) = 8), a program the loop above never saw
            counts.append(self.slots)
        def warm(entries):
            group_carry = self._prefill_group(entries)
            if self.pool is not None:
                return
            # the pack program is compiled per group-carry batch size
            # too: run it once into a SCRATCH carry (never the live one —
            # the engine thread owns that), or the first burst compiles
            # it at request time
            self._pack(self._init_carry(), group_carry, 0, 0)

        seen = set()
        for count in counts:
            if (key := _next_bucket(count, 1)) in seen:
                continue
            seen.add(key)
            with spans.span("boot.warm", program=f"group-prefill-{key}x3"):
                warm([dict(row=[1, 2, 3], s=3, temperature=None,
                           top_k=None, top_p=None, seed=None)
                      for _ in range(count)])
        n = len(seen)
        # the long-prompt family: one warm at the largest joiner bucket.
        # Rows must still be engine-admittable (s + max_new <= cache_len)
        # so a realistic long group prompt tops out near half the cache.
        s_warm = min(self.group_prefill_max, max(1, self.cache_len // 2))
        min_sb = _next_bucket(3, self.server.min_bucket)
        warm_sb = _next_bucket(s_warm, self.server.min_bucket)
        if counts and warm_sb != min_sb:
            row = list(range(1, s_warm + 1))
            with spans.span("boot.warm",
                            program=f"group-prefill-{max(counts)}x{s_warm}"):
                warm([dict(row=row, s=s_warm, temperature=None,
                           top_k=None, top_p=None, seed=None)
                      for _ in range(max(counts))])
            n += 1
        return n

    def _prefill_row_chunked(self, row, s: int, entry: dict):
        """Long-prompt joiner prefill through fixed-width chunks: each
        chunk is its own device dispatch, so ENGINE SEGMENTS INTERLEAVE
        with the prefill on the device queue instead of in-flight decode
        stalling behind one wide prefill program (VERDICT r5 #4), and
        dense-attention memory stays O(chunk x s). Reuses the server's
        chunked-prefix program families; the final sub-chunk tail runs
        the carry-producing continuation. Parity class matches chunked
        prefix prefill: exact with the float KV cache (asserted in f32
        tests), quantization tolerance under kv_quant."""
        import jax.numpy as jnp

        from lambdipy_tpu.models.llama import _next_bucket

        server = self.server
        ck = server.prefill_chunk
        split = ((s - 1) // ck) * ck  # >= 1 token left for continuation
        if split == 0:
            return self._prefill_row(row, s, entry)
        tail = row[split:]
        with server._mesh_ctx():
            t0 = time.monotonic()
            cache = server._chunked_prefill_cache(
                row, split, self.cache_len, sp=self.prefill_sp,
                stats=self.prefill_stats)
            sp = self.prefill_sp
            n_chunks = -(-split // ck)
            n_rounds = -(-split // (ck * sp)) if sp >= 2 else n_chunks
            self.prefill_stats.record_walk(
                time.monotonic() - t0, n_chunks, n_rounds)
            sbs = min(_next_bucket(len(tail), server.min_bucket),
                      self.cache_len - split)
            # a full-window engine shares the prefix path's continuation
            # program (and its AOT executable); a capped one keys its own
            full = self.cache_len == server.model.cfg.max_len
            cont = server._stream_prefix_fn(
                sbs, cache_len=None if full else self.cache_len)
            suffix_op, _ = server._pad_rows([tail], [len(tail)], 1, sbs)
            knobs = server._knob_operands(
                entry["temperature"], entry["top_k"], entry["top_p"],
                entry["seed"], None, b=1)
            return cont(server.params, cache, suffix_op,
                        jnp.int32(len(tail)), *knobs)

    def _segment_fn(self):
        """The B-slot segment program (shared with streaming's family —
        keyed under the server's LRU program cache)."""
        _, seg = self.server._stream_fns(self.slots, self.server.min_bucket,
                                         self.cache_len, self.segment)
        return seg

    def _spec_draft(self, entry: dict, kb: int, q: int | None = None,
                    k: int | None = None, provider: str = "lookup"):
        """Host-side prompt-lookup draft for ONE verify step of a live
        row. The draft always EXTRAPOLATES FROM FETCHED TRUTH: the
        confirmed context (prompt — cached prefix included, a shared
        system prompt is prime n-gram material — plus booked tokens and
        the last fetched pending token), extended by lookup itself
        across the ``q`` still-in-flight verify steps, each assumed to
        advance its full kb tokens. That accept-all assumption is the
        pipelined-drafting trick ("dispatch-time draft state"): at
        depth >= 2 the host drafts step N+1 before step N's results
        land, and on the repetitive workloads where speculation pays
        the extrapolation is exactly what the device will emit, so the
        chain stays hot across the pipeline. When it breaks, the
        drafts merely miss (every step still emits >= 1 exact chain
        token — the verify compares against the device's own carry,
        never this guess) and the very next dispatch re-extrapolates
        from newer truth.

        ``k`` is the ROW's draft width this step (per-row adaptive k;
        defaults to the dispatch width ``kb``): the in-flight
        extrapolation strides by ``k`` because that is the most this
        row's pending steps can have advanced. ``provider`` routes
        between prompt lookup and the engine's host-side
        :class:`DraftProvider` (``"aux"``). Returns
        ``(d_verify [k-1], hit)``."""
        from lambdipy_tpu.models.llama import _lookup_draft_hit

        k = kb if k is None else max(2, min(int(k), kb))
        toks, pend = entry["toks"], entry.get("spec_pend")
        if entry.get("early"):
            # the prefill's token went out ahead of the row's first
            # verify block, which is not collected yet: it is that
            # step's pending, fetched already
            toks, pend = toks[:-1], toks[-1]
        base = (entry.get("prefix_toks") or []) + entry["row"] + toks
        if q is None:
            q = entry["spec_inflight"]
        if provider == "aux" and self.draft_provider is not None:
            # the aux draft model extrapolates the same way lookup
            # does: it proposes across the q assumed-accepted in-flight
            # steps too, and this step takes its slice. A short or
            # failing proposal pads RAW -1 — never accepted, so a
            # misbehaving provider degrades to plain decode, not to a
            # wrong token.
            need = (q + 1) * k - (1 if pend is not None else 0)
            try:
                ext = [int(t) for t in
                       self.draft_provider.propose(
                           base + ([pend] if pend is not None else []),
                           need)]
            except Exception:  # noqa: BLE001 — a proposal, not a result
                ext = []
            hit = len(ext) >= need
            ext += [-1] * (need - len(ext))
            if pend is not None:
                return ext[q * k: q * k + k - 1], hit
            return ext[q * k + 1: (q + 1) * k], hit
        if pend is not None:
            # ext[i] predicts chain position len(base) + 1 + i; the new
            # step's chunk starts q*k positions past the pending
            ext, hit = _lookup_draft_hit(base + [pend],
                                         (q + 1) * k - 1,
                                         ngram_max=self.spec_ngram)
            return ext[q * k: q * k + k - 1], hit
        # the device holds the true pending token but the host has not
        # fetched one yet (freshly packed row): extrapolate from the
        # prompt alone — ext[0] guesses the pending itself
        ext, hit = _lookup_draft_hit(base, (q + 1) * k,
                                     ngram_max=self.spec_ngram)
        return ext[q * k + 1: (q + 1) * k], hit

    def _spec_row_init(self) -> tuple:
        """(provider, k_row) a freshly admitted row starts with, from
        the engine's CURRENT draft_mode (so a live knob retune applies
        to new rows while in-flight rows keep their adapted state).
        Legacy lookup mode keeps the fixed-k behavior (k_row pinned at
        spec_k, no adaptivity); the model/aux tiers SLOW-START at the
        k=2 minimum bucket — an adversarial row's first steps pay one
        draft token, not spec_k - 1, which is what keeps its tok/s
        within noise of spec-off while the EWMA decides."""
        if not self.spec_k or self.draft_mode == "off":
            return "off", 1
        if self.draft_mode == "lookup":
            return "lookup", self.spec_k
        return self.draft_mode, 2

    def _spec_adapt(self, entry: dict, provider: str, k_used: int,
                    accepted_c: int) -> None:
        """Per-row adaptive k, run by the collector (engine lock held)
        after each verify step lands: fold the step's accepted fraction
        into the row's acceptance EWMA, then grow k (pow-2, up to
        spec_k) while the row stays above the grow band, shrink it
        below the shrink band, and on collapse AT the k=2 minimum
        bucket demote the row's provider down the fallback chain
        model/aux -> lookup -> off (sticky, counted under
        ``batching.spec.draft.fallbacks``). Inert in legacy lookup
        mode."""
        if self.draft_mode in ("lookup", "off"):
            return
        if provider == "off" or k_used < 2:
            return
        frac = (accepted_c - 1) / float(k_used - 1)
        ew = entry.get("accept_ewma")
        a = self.spec_ewma_alpha
        ew = frac if ew is None else ((1.0 - a) * ew + a * frac)
        entry["accept_ewma"] = ew
        if entry["draft_mode"] != provider:
            # the row was demoted between this step's dispatch and its
            # collect (depth >= 2): the stale step still feeds the
            # EWMA above, but must not re-tune k for the new provider
            return
        if ew >= self.spec_grow and entry["k_row"] < self.spec_k:
            entry["k_row"] = min(self.spec_k, max(2, entry["k_row"] * 2))
        elif ew <= self.spec_shrink:
            if entry["k_row"] > 2:
                entry["k_row"] = max(2, entry["k_row"] // 2)
            else:
                nxt = "lookup" if provider in ("model", "aux") else "off"
                entry["draft_mode"] = nxt
                entry["k_row"] = 2 if nxt != "off" else 1
                entry["accept_ewma"] = None
                self.spec_metrics.record_draft_fallback(
                    f"{provider}->{nxt}")

    # -- fault isolation -----------------------------------------------------

    @property
    def wedged(self) -> bool:
        return self.fault_stats.wedged

    @property
    def degrade_level(self) -> int:
        return self.fault_stats.degrade_level

    def fault_state(self) -> dict:
        """O(1) health snapshot for ``/healthz`` and the admission gate:
        bare attribute reads, no locks — this runs once per probe
        interval and once per accepted request."""
        return {"wedged": self.fault_stats.wedged,
                "degrade_level": self.fault_stats.degrade_level,
                "restarting": (self.fault_stats.wedged
                               and self._engine_running)}

    def _device_wait(self, site: str, gen: int | None, fn=None, *args,
                     kind: str = "engine"):
        """Run a device-side wait under the watchdog: the wait is
        registered so the monitor can bound it, the fault layer's site
        hook fires first (so injected exceptions/delays/hangs land
        exactly here), and a superseded engine generation aborts instead
        of touching restarted state. ``kind='request'`` marks waits on
        request threads (prefix assembly): the watchdog aborts their
        injected hangs and counts the trip, but only engine-kind waits
        wedge the whole engine."""
        if self.watchdog_s <= 0 and not self.faults.rules:
            # production default (no watchdog, empty fault plan): the
            # register/monitor machinery can never fire, so skip its
            # per-wait Event + two contended lock acquisitions — only
            # the site stamp (failure attribution) and the stale-
            # generation guard remain on the hot decode path
            try:
                out = fn(*args) if fn is not None else None
            except Exception as e:  # noqa: BLE001 — stamp for attribution
                if not hasattr(e, "fault_site"):
                    e.fault_site = site
                raise
            if gen is not None and gen != self._gen:
                raise _StaleEngine()
            return out
        wid = next(self._wait_seq)
        abort = threading.Event()
        rec = {"site": site, "t0": time.monotonic(), "gen": gen,
               "kind": kind, "abort": abort, "tripped": False}
        with self._lock:
            self._waits[wid] = rec
            self._ensure_monitor_locked()
        try:
            self.faults.check(site, interrupt=abort)
            out = fn(*args) if fn is not None else None
        except Exception as e:  # noqa: BLE001 — stamp for attribution
            if not hasattr(e, "fault_site"):
                e.fault_site = site
            raise
        finally:
            with self._lock:
                self._waits.pop(wid, None)
        if abort.is_set():
            raise EngineWatchdogTimeout(site, self.watchdog_s)
        if gen is not None and gen != self._gen:
            raise _StaleEngine()
        return out

    def _ensure_monitor_locked(self) -> None:
        if self.watchdog_s <= 0:
            return
        if self._monitor is not None and self._monitor.is_alive():
            return
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         daemon=True,
                                         name="engine-watchdog")
        self._monitor.start()

    def _monitor_loop(self) -> None:
        tick = max(0.01, min(0.2, self.watchdog_s / 4))
        while True:
            time.sleep(tick)
            now = time.monotonic()
            expired: list[dict] = []
            with self._lock:
                # tripped waits are DISOWNED: a real (non-injected) hang
                # never returns, so its record lingers in _waits forever
                # — counting it as live would block the idle branch (and
                # the wedged self-probe) permanently
                live = any(not rec["tripped"]
                           for rec in self._waits.values())
                if not live and not self._engine_running:
                    if not self.fault_stats.wedged:
                        self._monitor = None  # idle: next wait restarts us
                        return
                    # wedged with no work queued: behind a fleet the pool
                    # EJECTS a wedged replica, so the clear-on-successful-
                    # serve path can never run — no request will arrive to
                    # prove the transport recovered. Self-probe instead.
                    if (not self._probe_live
                            and now - self._probe_t
                            >= min(600.0, max(1.0, 2 * self.watchdog_s)
                                   * (1 << self._probe_misses))):
                        self._probe_live = True
                        self._probe_t = now
                        threading.Thread(target=self._recovery_probe,
                                         daemon=True,
                                         name="engine-recovery-probe"
                                         ).start()
                else:
                    expired = [rec for rec in self._waits.values()
                               if not rec["tripped"]
                               and now - rec["t0"] > self.watchdog_s]
                    for rec in expired:
                        rec["tripped"] = True
            for rec in expired:
                # aborts an injected hang immediately; a REAL hung
                # device call stays stuck, but its thread is already
                # disowned by the generation bump below
                rec["abort"].set()
                if rec["kind"] == "engine":
                    self._fail_engine(
                        EngineWatchdogTimeout(rec["site"], self.watchdog_s),
                        site=f"watchdog:{rec['site']}", gen=rec["gen"],
                        wedged=True)
                else:
                    # request-thread wait (prefix assembly): the guard
                    # raises to its own caller; record the trip only
                    self.fault_stats.record_failure(
                        f"watchdog:{rec['site']}", watchdog=True)

    def _recovery_probe(self) -> None:
        """Self-directed recovery for a wedged engine with nothing left
        to serve: round-trip a trivial device op under the watchdog —
        success proves the transport is answering again, clears the
        wedge so ``/healthz`` goes ready, and the fleet pool readmits
        through its normal consecutive-passes path. The probe runs
        through the ``transport`` fault site, so a chaos plan with a
        permanent transport fault keeps the engine deterministically
        wedged. The device op runs on a DISPOSABLE inner thread with a
        bounded join: a transport that is still truly hung swallows
        that thread (nothing can unblock a real hang), but the probe
        itself always terminates — future probes keep firing, at an
        exponentially backed-off cadence so the leaked-thread rate
        against a long-dead transport stays bounded."""
        done = threading.Event()
        ok: list = []

        def op():
            try:
                import jax

                self._device_wait(
                    "transport", None,
                    lambda: jax.device_get(jax.device_put(0)),
                    kind="request")
                ok.append(True)
            except Exception:  # noqa: BLE001 — still wedged
                pass
            finally:
                done.set()

        threading.Thread(target=op, daemon=True,
                         name="engine-recovery-probe-op").start()
        # injected hangs resolve via the watchdog abort; a REAL hang
        # just never sets done and the wait below times out
        finished = done.wait(timeout=2 * self.watchdog_s + 1.0)
        self._probe_live = False
        if not (finished and ok):
            self._probe_misses = min(self._probe_misses + 1, 9)
            return
        self._probe_misses = 0
        with self._lock:
            if self.fault_stats.wedged and not self._engine_running:
                self.fault_stats.set_wedged(False)
                self._had_failure = False
                self.fault_stats.record_recovery()
                log.info("engine recovery probe succeeded: wedge cleared")

    def _cancel_due(self, entry: dict, now: float) -> bool:
        return bool(entry.get("abandoned")) or (
            entry.get("deadline_at") is not None
            and now > entry["deadline_at"])

    def _cancel_expired_locked(self, now: float) -> None:
        """Drain-barrier cancellation: free slots (and the joiner queue)
        of rows whose waiter is gone or whose deadline expired — decoding
        them to completion would burn device time nobody reads."""
        for slot, e in enumerate(self._active):
            if e is not None and not e["done"] and self._cancel_due(e, now):
                e["error"] = RequestCancelled(
                    "cancelled at drain barrier: "
                    + ("waiter gone" if e.get("abandoned")
                       else "deadline expired"))
                e["done"] = True
                self._active[slot] = None
                self._release_pages(e)
                self.fault_stats.record_cancelled()
        for j in [j for j in self._joiners if self._cancel_due(j, now)]:
            j["error"] = RequestCancelled(
                "cancelled while queued: "
                + ("waiter gone" if j.get("abandoned")
                   else "deadline expired"))
            j["done"] = True
            self._joiners.remove(j)
            self._release_pages(j)
            self.fault_stats.record_cancelled()

    def _fail_engine(self, error: Exception, *, site: str,
                     gen: int | None, wedged: bool = False) -> None:
        """One engine failure, handled surgically instead of erroring the
        world: done-but-undrained rows keep their bitwise results, rows
        with no bytes delivered requeue for transparent replay (bounded
        by ``max_replays``), everything else gets the error; the ladder
        and wedged flag update; a replacement engine thread starts when
        anything was requeued."""
        with self._lock:
            if gen is not None and gen != self._gen:
                return  # a newer generation already handled this
            self._gen += 1
            now = time.monotonic()
            self.fault_stats.record_failure(site, watchdog=wedged)
            if wedged:
                self.fault_stats.set_wedged(True)
                self._probe_t = now  # first self-probe a full interval out
                self._probe_misses = 0  # fresh wedge: base probe cadence
            self._had_failure = True
            self._last_failure_t = now
            self._fail_times = [t for t in self._fail_times
                                if now - t <= self.degrade_window_s]
            self._fail_times.append(now)
            if len(self._fail_times) >= 2 and \
                    self.fault_stats.degrade_level < 3:
                self.fault_stats.record_degrade(
                    self.fault_stats.degrade_level + 1, site)
            requeued = 0
            survivors: list[dict] = []
            for entry in self._joiners + [a for a in self._active if a]:
                if entry["done"]:
                    # completed mid-pipeline (slot held as garbage until
                    # the next barrier): its bitwise-valid result is
                    # already readable — never overwrite it. Its pages
                    # release here: the barrier that would have freed
                    # them dies with this engine.
                    self._release_pages(entry)
                    continue
                if (not entry["streamed"] and not entry["abandoned"]
                        and entry["replays"] < self.max_replays):
                    # no bytes have reached this row's client: reset to
                    # its admitted state and replay. Seeded per-row PRNG
                    # chains make the replay bitwise the first attempt.
                    entry["replays"] += 1
                    entry["toks"], entry["lps"] = [], []
                    entry["disp"] = 0
                    entry["early"] = 0
                    entry["eos_at"] = None
                    entry["slot"] = None
                    entry["packed"] = False
                    entry["carry"] = None  # re-prefills in the engine
                    # replayed rows re-draft from scratch; parity holds
                    # because acceptance is chain-deterministic — the
                    # replay re-derives the same per-row PRNG walk, so
                    # the emitted tokens are bitwise the first attempt
                    # whatever the new drafts propose
                    entry["spec_pend"] = None
                    entry["spec_inflight"] = 0
                    if self.pool is not None \
                            and entry.get("prefix_toks"):
                        # the arena reset below zeroes the shared pages
                        # a zero-copy continuation would read: replay as
                        # a FULL cold row through the row's own (kept)
                        # pages — the prefill recomputes exactly the KV
                        # they held, so the replay stays bitwise
                        entry["row"] = entry["prefix_toks"] + entry["row"]
                        entry["s"] = len(entry["row"])
                        entry["pos0"] = entry["s"]
                        entry["prefix_toks"] = None
                        entry.pop("plen", None)
                        entry.pop("arena_gen", None)
                    survivors.append(entry)
                    requeued += 1
                else:
                    entry["error"] = error
                    entry["done"] = True
                    self._release_pages(entry)
            if requeued:
                self.fault_stats.record_replays(attempted=requeued)
            self._joiners = survivors
            self._active = [None] * self.slots
            self._carry = None  # rebuilt clean on restart
            if self.pool is not None:
                # on an async backend the published arena may be the
                # OUTPUT of the failed computation — every program
                # consuming it would re-raise. Discard it (the paged
                # twin of dropping the carry): replays re-prefill and
                # re-scatter into their kept pages, and the prefix
                # store flushes its now-stale tree on the generation
                # bump. Page ACCOUNTING (host truth) is unaffected.
                self.pool.reset_arena()
            if survivors:
                self._engine_running = True
                threading.Thread(target=self._engine_loop,
                                 args=(self._gen,), daemon=True,
                                 name="continuous-batch").start()
            else:
                self._engine_running = False
            self._lock.notify_all()
        log.error("continuous-batch engine failed at %s: %s "
                  "(replaying %d row(s), degrade level %d%s)",
                  site, error, requeued, self.fault_stats.degrade_level,
                  ", wedged" if wedged else "")

    # -- engine --------------------------------------------------------------

    def _book_locked(self, entry: dict, base: int) -> None:
        """The eos / ``n`` bookkeeping of the tokens a row has just been
        handed (``entry["toks"][base:]``: a collected block, or the one
        token deliver_first sent ahead of it); the engine lock is held."""
        eos = entry["eos_id"]
        if eos is not None and entry["eos_at"] is None:
            # scan only the newly appended tokens (the old
            # `eos in entry["toks"]` rescan was O(n^2) over a long
            # decode) and record the first-hit index so truncation needs
            # no second scan — an eos INSIDE an accepted draft block
            # lands here like any other token
            new = entry["toks"][base:]
            if eos in new:
                entry["eos_at"] = base + new.index(eos)
        if entry["eos_at"] is not None or len(entry["toks"]) >= entry["n"]:
            entry["done"] = True
            self.requests_served += 1
            if entry["replays"]:
                # a requeued row completed through the restarted
                # engine — the replay delivered
                self.fault_stats.record_replays(succeeded=1)

    def _engine_loop(self, gen: int):
        try:
            self._engine_body(gen)
        except _StaleEngine:
            log.debug("stale engine generation exited")
        except Exception as e:  # noqa: BLE001 — waiters must never hang
            self._fail_engine(e, site=getattr(e, "fault_site", "engine"),
                              gen=gen)

    def _engine_body(self, gen: int):
        from collections import deque

        import jax
        import jax.numpy as jnp
        import numpy as np

        server = self.server
        from lambdipy_tpu.models.llama import _next_bucket

        pool = self.pool
        # paged engines never touch the dense B-slot segment program (the
        # KV lives in the pool's arena, not a batch cache) — building it
        # would compile a program family this engine can't dispatch
        seg_full = self._segment_fn() if pool is None else None
        # eos stays disabled on device (host-side truncation); the
        # sampling knobs are PER-SLOT vectors rebuilt before each
        # segment from the active rows' own requests
        eos_op = jnp.full((self.slots,), -1, jnp.int32)
        pstats = self.pipeline_stats
        # dispatched-but-not-fetched segments, oldest first; each record
        # snapshots what the host needs to book the result later: the
        # slot -> entry mapping and the window accounting AT DISPATCH
        # time (the window was chosen then — recording it at collect
        # keeps DecodeWindowStats truthful about queued segments)
        inflight: deque = deque()
        # the loop's leaf phases (eng.barrier, eng.prefill, eng.pack,
        # eng.dispatch, eng.first, eng.wait, eng.fetch, eng.book): one is
        # open at any moment from here to the loop's exit, none encloses
        # another
        phase = spans.phases()
        # rows packed since the last dispatch: their first tokens go out
        # after it (deliver_first)
        firsts: list = []

        def rids(entries) -> str:
            """The requests a phase works for, as its ``rids`` argument."""
            return spans.rids_arg(e["rid"] for e in entries)

        ep_t0 = time.monotonic()
        # mark the episode open so report()'s wall (and overlap_ratio)
        # includes the in-progress episode: under sustained traffic the
        # engine may never go idle, and a /metrics scrape mid-episode
        # must not divide device_busy_s by only the COMPLETED episodes'
        # wall (0.0 on the first, > 1.0 ratios later)
        pstats.begin_episode(ep_t0)

        def deliver_first(tok, lp):
            """Eager delivery of the prefill's token. The prefill selects
            a row's first token and the scan re-emits the token it
            consumes, so column 0 of the row's first block IS the carry's
            token, carried through a whole segment untouched. It goes out
            here instead, read from the PACKED batch carry (``tok`` and
            ``lp``, its ``[B]`` leaves as the first segment takes them)
            right after that segment's dispatch: the device has prefill,
            pack and segment queued and never waits for this read, and
            the read returns when the device has run the pack, so the
            row's first segment is what runs next and nothing but that
            segment lies between a stream's first two chunks. One
            ``device_get`` of whole leaves, indexed on the host: no
            program is added. The collector books that first block from
            its second column."""
            rows = firsts[:]
            firsts.clear()
            phase.enter("eng.first", rids=rids(rows))
            tok_h, lp_h = self._device_wait(
                "first_fetch", gen, jax.device_get,
                (tok, lp if any(e["want_lp"] for e in rows) else None))
            with self._lock:
                if gen != self._gen:
                    # a failure handler reset these entries meanwhile: a
                    # token booked now would lead the replay's own
                    raise _StaleEngine()
                for entry in rows:
                    if entry["done"]:
                        continue  # cancelled at a barrier since its pack
                    slot = entry["slot"]
                    entry["toks"].append(int(tok_h[slot]))
                    if entry["want_lp"]:
                        entry["lps"].append(float(lp_h[slot]))
                    entry["early"] = 1
                    self.first_tokens_early += 1
                    # an eos here, or max_new_tokens 1, finishes the
                    # request: its first block is over-decode
                    self._book_locked(entry, 0)
                self._lock.notify_all()

        def collect_one():
            """The collector stage: fetch the OLDEST in-flight segment
            and do its host bookkeeping — token append, incremental eos
            scan, done marking. Runs behind the dispatch frontier, so
            on pipeline_depth >= 2 the device is computing the next
            segment during this fetch + bookkeeping window."""
            rec = inflight.popleft()
            served = rids(e for _, e in rec["rows"])
            phase.enter("eng.wait", rids=served)
            # compute-ready marker for the overlap ratio: the device is
            # done with this segment here; whatever the fetch costs past
            # this point only keeps the device busy if another segment
            # is queued behind it. Both device waits run under the
            # watchdog: a device that never answers trips it instead of
            # blocking the engine forever.
            self._device_wait("transport", gen,
                              jax.block_until_ready, rec["toks"])
            t_ready = time.monotonic()
            phase.enter("eng.fetch", rids=served)
            # one host fetch per segment: every device_get is a
            # synchronization with the device, so the logprob block rides
            # the same fetch — and only when some active request
            # actually asked for it. A
            # speculative record additionally carries the per-row accept
            # COUNTS (how much of the block is real) and the new PENDING
            # token (the next step's draft anchor) on the same fetch.
            kb_rec = rec.get("spec", 0)

            def fetch():
                want = [rec["toks"]]
                if rec["need_lp"]:
                    want.append(rec["lps"])
                if kb_rec:
                    want += [rec["counts"], rec["pending"]]
                want += rec["sown"]
                got = [np.asarray(x)
                       for x in jax.device_get(tuple(want))]
                blk = got.pop(0)
                lp = got.pop(0) if rec["need_lp"] else None
                cnt = got.pop(0) if kb_rec else None
                pend = got.pop(0) if kb_rec else None
                return blk, lp, cnt, pend, dict(zip(self._sown, got))

            block, lp_block, counts_h, pending_h, sown_h = \
                self._device_wait("segment_fetch", gen, fetch)
            t_end = time.monotonic()
            phase.enter("eng.book", rids=served)
            if self._had_failure:
                # first successful fetch after a failure: the engine is
                # demonstrably serving again — clear the wedge and count
                # the recovery (the ladder restores separately, after a
                # clean interval)
                self._had_failure = False
                self.fault_stats.record_recovery()
                if self.fault_stats.wedged:
                    self.fault_stats.set_wedged(False)
            self.window_stats.record_segment(
                attended=rec["attended"], window_read=rec["window_read"],
                full_window=rec["full_window"], window=rec["window"])
            wasted = 0
            with self._lock:
                if gen != self._gen:
                    # a failure handler requeued these entries while we
                    # were fetching: booking this block against their
                    # RESET state would corrupt the replay
                    raise _StaleEngine()
                self.segments_run += 1
                if self.mesh_stats is not None:
                    self.mesh_stats.record_segment()
                booked = [slot for slot, e in rec["rows"] if not e["done"]]
                if not kb_rec:
                    # (a verify segment returns none of it: no model that
                    # counts is served with spec_k)
                    for recorder in self.counters.values():
                        recorder.record_segment(sown_h, booked,
                                                block.shape[1])
                for slot, entry in rec["rows"]:
                    # per-row accepted width: everything for a plain
                    # segment; counts_h[slot] (1..kb) for a verify step
                    # — the COLLECTOR-SIDE ROLLBACK: the rejected tail
                    # is simply never booked, structurally the same
                    # discard as the over-decode branch below (its KV
                    # already sits in garbage positions behind the
                    # device-side index)
                    c = int(counts_h[slot]) if kb_rec else block.shape[1]
                    info = rec["assumed"].pop(slot, None) if kb_rec \
                        else None
                    if info is not None:
                        # this row's step left the pipeline (the row
                        # may have finished meanwhile — still count it)
                        entry["spec_inflight"] -= 1
                    if entry["done"]:
                        # over-decode: this block was dispatched before
                        # the row's finish became host-visible — discard
                        # the tail so output stays bitwise the depth-1
                        # engine's
                        wasted += c
                        if kb_rec and pool is not None:
                            entry["disp"] -= (kb_rec - c)
                        continue
                    self.rows_in_segments += 1
                    # a row's FIRST block begins with the prefill's
                    # token, which deliver_first sent when the row was
                    # packed: book it from its second column
                    early, entry["early"] = entry["early"], 0
                    base = len(entry["toks"])
                    entry["toks"].extend(block[slot][early:c].tolist())
                    if lp_block is not None:
                        entry["lps"].extend(
                            lp_block[slot][early:c].tolist())
                    if kb_rec:
                        # reconcile the optimistic dispatch accounting:
                        # disp assumed the full kb advance; the step
                        # really moved c — later window sizing and the
                        # dispatch quota see truth again. The fetched
                        # pending becomes the next draft anchor
                        # (collects are FIFO, so this is always the
                        # most advanced truth).
                        entry["disp"] -= (kb_rec - c)
                        entry["spec_pend"] = int(pending_h[slot])
                        if info is not None:
                            # per-provider accounting uses the ROW's
                            # dispatched width (adaptive k snapshot),
                            # not the batch bucket — a k=2 row in a
                            # kb=8 dispatch proposed 1 token, and the
                            # EWMA must see its real accepted fraction
                            prov, hit, k_used = info
                            self.spec_metrics.record_step(
                                proposed=k_used - 1, accepted=c - 1,
                                emitted=c, hit=bool(hit),
                                provider=prov, k=k_used)
                            self._spec_adapt(entry, prov, k_used, c)
                    self._book_locked(entry, base)
                self._lock.notify_all()
            # fetch clock starts AFTER block_until_ready so fetch_block_s
            # measures only the device_get transport window, not the
            # device-compute wait the collector pays when it outruns the
            # device
            pstats.record_collect(rec["t_dispatch"], t_ready,
                                  fetch_s=t_end - t_ready, wasted=wasted)

        try:
            while True:
                # ---- barrier: the pipeline is EMPTY here. Slot
                # retirement and joiner packing only happen at these
                # drain barriers, so in-flight segments never see their
                # slot repurposed under them. ----
                barrier = phase.enter("eng.barrier")
                with self._lock:
                    if gen != self._gen:
                        raise _StaleEngine()
                    now = time.monotonic()
                    # a clean interval since the last failure restores
                    # the degradation ladder to full service
                    if self.fault_stats.degrade_level \
                            and self._last_failure_t is not None \
                            and now - self._last_failure_t \
                            > self.degrade_clean_s:
                        self.fault_stats.record_restore()
                        self._fail_times.clear()
                    # rows whose waiter went away or whose deadline
                    # expired cancel here, before they take (or keep)
                    # a slot
                    self._cancel_expired_locked(now)
                    for slot, e in enumerate(self._active):
                        if e is not None and e["done"]:
                            # finished mid-pipeline: the slot decoded as
                            # a garbage row until this barrier; free it
                            # (a paged row's pages go back to the pool —
                            # shared prefix pages only drop one ref)
                            self._active[slot] = None
                            self._release_pages(e)
                    free = [i for i, a in enumerate(self._active)
                            if a is None]
                    if self._joiners and free:
                        # slot handoff dequeues by policy: under slot
                        # contention the scheduling class (not arrival
                        # order) decides who joins the in-flight batch
                        ordered = (self.policy.order(list(self._joiners))
                                   if self.policy is not None
                                   else list(self._joiners))
                        for joiner in ordered:
                            if not free:
                                break
                            self._joiners.remove(joiner)
                            joiner["slot"] = free.pop(0)
                            self._active[joiner["slot"]] = joiner
                            spans.mark(joiner["rid"], "req.join")
                    packing = [a for a in self._active
                               if a is not None and not a.get("packed")]
                    if packing:
                        barrier.set(rids=rids(packing))
                    if not any(self._active):
                        # idle: engine exits; next request restarts it
                        self._engine_running = False
                        self._lock.notify_all()
                        return
                if self._carry is None:
                    self._carry = self._init_carry()
                raw = [a for a in packing if a.get("carry") is None
                       and a.get("prefix_toks") is None
                       and a["s"] <= self.group_prefill_max]
                # replayed LONG-prompt rows (admitted via the request
                # thread's chunked prefill) never belong in the ragged
                # group program: their s buckets past group_prefill_max
                # into a shape the warm never compiled — under a
                # watchdog the fresh compile would trip mid-recovery
                # and burn the replay budget. Re-run the chunked path
                # instead: same programs as admission, bitwise.
                long_replay = [a for a in packing
                               if a.get("carry") is None
                               and a.get("prefix_toks") is None
                               and a["s"] > self.group_prefill_max]
                carried = [a for a in packing
                           if a.get("carry") is not None]
                # replayed prefix rows lost their continuation carry
                # with the failed engine: re-assemble from the cached
                # prefix KV here (same program, same tokens — bitwise),
                # erroring only the row whose prefix has meanwhile been
                # evicted
                for j in [a for a in packing if a.get("carry") is None
                          and a.get("prefix_toks") is not None]:
                    phase.enter("eng.prefill", rids=rids([j]),
                                bucket=j["s"], kind="prefix")
                    try:
                        if pool is not None:
                            # a replayed PAGED prefix row kept its pages
                            # (shared prefix + own suffix) through the
                            # failure: re-run the same zero-copy
                            # continuation — bitwise the first attempt
                            j["carry"] = self._device_wait(
                                "prefix_assemble", gen,
                                self._paged_continue_row, j)
                        else:
                            j["carry"] = self._device_wait(
                                "prefix_assemble", gen,
                                self._prefill_prefix_row, j["prefix_toks"],
                                j["row"], j["s"], j)
                        carried.append(j)
                    except (_StaleEngine, EngineWatchdogTimeout):
                        raise
                    except Exception as e:  # noqa: BLE001
                        with self._lock:
                            if gen != self._gen:
                                # a failure handler (watchdog) already
                                # requeued this entry under a new
                                # generation — touching it here would
                                # error a row the replay is about to
                                # serve
                                raise _StaleEngine() from None
                            log.error("prefix re-assembly failed: %s", e)
                            self.fault_stats.record_failure(
                                "prefix_assemble")
                            j["error"], j["done"] = e, True
                            self._active[j["slot"]] = None
                            self._release_pages(j)
                            self._lock.notify_all()
                for j in long_replay:
                    ck = self.server.prefill_chunk
                    chunked = (ck and j["s"] > ck
                               and self.cache_len % ck == 0)
                    phase.enter("eng.prefill", rids=rids([j]),
                                bucket=j["s"], kind="long")
                    try:
                        j["carry"] = self._device_wait(
                            "group_prefill", gen,
                            (self._prefill_row_chunked if chunked
                             else self._prefill_row),
                            j["row"], j["s"], j)
                        carried.append(j)
                    except (_StaleEngine, EngineWatchdogTimeout):
                        raise
                    except Exception as e:  # noqa: BLE001
                        with self._lock:
                            if gen != self._gen:
                                raise _StaleEngine() from None
                            log.error("long-row replay prefill "
                                      "failed: %s", e)
                            self.fault_stats.record_failure(
                                getattr(e, "fault_site",
                                        "group_prefill"))
                            j["error"], j["done"] = e, True
                            self._active[j["slot"]] = None
                            self._release_pages(j)
                            self._lock.notify_all()
                group_carry = None
                if raw:
                    phase.enter(
                        "eng.prefill", rids=rids(raw), kind="group",
                        bucket=_next_bucket(max(j["s"] for j in raw),
                                            server.min_bucket))
                    try:
                        group_carry = self._device_wait(
                            "group_prefill", gen, self._prefill_group, raw)
                        with self._lock:
                            self.prefill_groups += 1
                            self.rows_group_prefilled += len(raw)
                    except (_StaleEngine, EngineWatchdogTimeout):
                        # the watchdog already failed the engine (and
                        # requeued these entries) — unwind, don't touch
                        raise
                    except Exception as e:  # noqa: BLE001
                        # a group-prefill failure (injected fault,
                        # fresh-bucket compile OOM, transient device
                        # error) stays scoped to the raw joiners —
                        # in-flight decode and carried joiners keep
                        # running. Joiners under their replay budget
                        # requeue for the next barrier's group call
                        # (fault gone -> bitwise the first attempt);
                        # the rest error explicitly.
                        with self._lock:
                            if gen != self._gen:
                                # the failure handler already requeued
                                # these entries under a new generation
                                # (their slot is gone and their replay
                                # budget spent on OUR failure): erroring
                                # them here would race the replay that
                                # is about to serve them
                                raise _StaleEngine() from None
                            log.error("group prefill failed: %s", e)
                            self.fault_stats.record_failure(
                                getattr(e, "fault_site", "group_prefill"))
                            retried = 0
                            for j in raw:
                                self._active[j["slot"]] = None
                                if j["replays"] < self.max_replays:
                                    j["replays"] += 1
                                    j["slot"] = None
                                    self._joiners.append(j)
                                    retried += 1
                                else:
                                    j["error"], j["done"] = e, True
                                    self._release_pages(j)
                            if retried:
                                self.fault_stats.record_replays(
                                    attempted=retried)
                            self._lock.notify_all()
                        raw = []
                phase.enter("eng.pack", rids=rids(raw + carried))
                firsts.extend(raw + carried)
                for src, joiner in enumerate(raw):
                    if pool is not None:
                        # scalars into the 5-leaf carry, the KV row
                        # scattered into the joiner's pages
                        self._carry = self._pack_paged(
                            self._carry, group_carry, src, joiner)
                    else:
                        self._carry = self._pack(self._carry, group_carry,
                                                 src, joiner["slot"])
                    joiner["packed"] = True
                    spans.mark(joiner["rid"], "req.prefill")
                group_carry = None  # free the group cache
                for joiner in carried:
                    if pool is not None and len(joiner["carry"]) == 5:
                        # paged prefix continuation: the row's KV is
                        # already in the arena — only scalars pack
                        self._carry = self._pack5(
                            self._carry, joiner["carry"], joiner["slot"])
                    elif pool is not None:
                        # a dense 1-row prefill carry (solo / chunked
                        # long-prompt path): scatter its cache row into
                        # the joiner's pages on the way in
                        self._carry = self._pack_paged(
                            self._carry, joiner["carry"], 0, joiner)
                    else:
                        self._carry = self._pack(self._carry,
                                                 joiner["carry"], 0,
                                                 joiner["slot"])
                    joiner["carry"] = None  # free the 1-row cache
                    joiner["packed"] = True
                    spans.mark(joiner["rid"], "req.prefill")
                if pool is not None:
                    # the per-slot block tables the paged segment
                    # programs index by — host truth, rebuilt once per
                    # barrier (slot membership only changes here)
                    nb_full = self.cache_len // pool.page
                    tbl_host = np.stack(
                        [self._table_row(e, nb_full) if e is not None
                         else np.zeros((nb_full,), np.int32)
                         for e in self._active])
                # ---- pipelined dispatch: keep up to pipeline_depth
                # segments in flight; once the frontier is full, each
                # dispatch is followed by collecting the OLDEST segment,
                # so the fetch overlaps the next segment's compute ----
                cause = None
                while True:
                    # ladder level >= 1 forces the synchronous depth-1
                    # loop: a failing device gets one outstanding wait
                    # at a time, the easiest shape to recover
                    dispatching = phase.enter("eng.dispatch")
                    eff_depth = (1 if self.fault_stats.degrade_level >= 1
                                 else self.pipeline_depth)
                    # speculative verify width for THIS dispatch: ladder
                    # level >= 2 pins the plain full-window program (no
                    # first-use spec/window-variant compiles while the
                    # device misbehaves) — plain and spec dispatches
                    # interleave freely because both advance the same
                    # carry and emit the same deterministic chain
                    spec_on = bool(self.spec_k
                                   and self.fault_stats.degrade_level < 2)
                    with self._lock:
                        if gen != self._gen:
                            raise _StaleEngine()
                        live = [(slot, e)
                                for slot, e in enumerate(self._active)
                                if e is not None]
                        if not any(not e["done"]
                                   and e["disp"] < e["n"]
                                   for _, e in live):
                            # every live row has its full output
                            # dispatched — drain to observe the tails
                            cause = "complete"
                            break
                        if self._joiners and (
                                len(live) < self.slots
                                or any(e["done"] for _, e in live)):
                            # a joiner can take (or is about to take) a
                            # slot: stop dispatching so the bounded
                            # drain below (at most pipeline_depth - 1
                            # segments) reaches the packing barrier
                            cause = "joiner"
                            break
                        if any(j.get("carry") is None
                               for j in self._joiners) and any(
                                e["disp"] >= e["n"]
                                and not e["spec_inflight"]
                                for _, e in live):
                            # slot handover (module docstring): a
                            # row's whole output is dispatched (disp is
                            # exact: no verify step of it in flight) and
                            # a joiner that the barrier itself prefills
                            # waits for its slot, so the next segment
                            # would only step the row as garbage: drain
                            # instead. A row that ends at an eos is seen
                            # at collect only and takes the branch
                            # above, as does everything while only
                            # joiners with a carry of their own wait.
                            cause = "handover"
                            break
                        # per-dispatch verify width: the pow-2 bucket of
                        # the live rows' ADAPTIVE k (legacy lookup mode
                        # pins every row at spec_k, reproducing the
                        # fixed-width dispatch exactly). When every live
                        # row's draft tier is off or collapsed, kb = 0
                        # and this dispatch IS the plain segment program
                        # — an adversarial batch pays zero speculation
                        # overhead, the mechanism behind the >= 0.95x
                        # fallback gate.
                        kb = 0
                        if spec_on:
                            kmax = max((e["k_row"] for _, e in live
                                        if not e["done"]
                                        and e["draft_mode"] != "off"),
                                       default=1)
                            if kmax >= 2:
                                kb = min(self.spec_k,
                                         _next_bucket(int(kmax), 2))
                        # optimistic per-dispatch advance: a verify step
                        # moves a row 1..kb tokens; disp books the
                        # maximum and the collector refunds the
                        # shortfall
                        adv = kb or self.segment
                        t_host = np.zeros((self.slots,), np.float32)
                        k_host = np.zeros((self.slots,), np.int32)
                        p_host = np.ones((self.slots,), np.float32)
                        positions = []  # live rows' dispatch positions
                        win_pos = []    # every occupied slot's position:
                        # a paged window must cover DONE garbage rows
                        # too — a clamped out-of-window write would
                        # scatter through the row's block table into a
                        # real (possibly shared) page, where the dense
                        # engine's private cache rows shrugged it off
                        need_lp = False
                        # masked draft positions stay RAW -1: a chain
                        # token is always in [0, vocab), so a row
                        # drafting fewer than kb - 1 tokens (adaptive
                        # k_row < kb, provider off, empty slot) can
                        # never have its padding accepted — the
                        # embedding path clamps a copy, as ever
                        d_host = (np.full((self.slots, kb - 1), -1,
                                          np.int32) if kb else None)
                        m_host = (np.zeros((self.slots, kb - 1),
                                           np.int32) if kb else None)
                        use_model = False
                        assumed: dict = {}
                        to_draft: list = []
                        ending = 0  # rows this dispatch leaves <= 1 segment
                        for slot, e in live:
                            if e["done"]:
                                # finished mid-pipeline: still stepped
                                # by the device (garbage) but its knobs,
                                # window need and fetch wants are dead
                                if pool is not None:
                                    win_pos.append(e["pos0"] + e["disp"])
                                    e["disp"] += adv
                                continue
                            t_host[slot] = e["temperature"] or 0.0
                            k_host[slot] = e["top_k"] or 0
                            p_host[slot] = (1.0 if e["top_p"] is None
                                            else e["top_p"])
                            # the DEVICE-side position: tokens already
                            # dispatched, not yet necessarily fetched
                            # (an UPPER BOUND under speculation — the
                            # collector refunds rejected tails)
                            positions.append(e["pos0"] + e["disp"])
                            win_pos.append(e["pos0"] + e["disp"])
                            need_lp = need_lp or e["want_lp"]
                            if kb and e["draft_mode"] != "off" \
                                    and e["k_row"] >= 2:
                                # snapshot the in-flight depth now;
                                # the O(context) lookup itself runs
                                # AFTER the lock drops (below) — only
                                # this engine thread mutates toks/spec
                                # state, so the post-lock read is safe,
                                # and a concurrent failure handler's
                                # reset is caught by the generation
                                # check at dispatch. The row's provider
                                # + adaptive width snapshot rides along
                                # so a mid-flight retune can't skew
                                # this step's accounting.
                                to_draft.append(
                                    (slot, e, e["spec_inflight"],
                                     e["draft_mode"],
                                     min(int(e["k_row"]), kb)))
                                e["spec_inflight"] += 1
                            e["disp"] += adv
                            if not kb and not e["ending"] \
                                    and not e["spec_inflight"] \
                                    and e["n"] - e["disp"] <= self.segment:
                                # the row ends with the NEXT segment at
                                # the latest (exact: a plain dispatch,
                                # no verify step of the row in flight)
                                e["ending"] = True
                                ending += 1
                    # a run slot is about to be released for each such
                    # row: the admission layer may send its next request
                    # now, so that it waits in _joiners when the row's
                    # last block is dispatched (the handover drain above)
                    if ending and self.row_ending_fn is not None:
                        try:
                            for _ in range(ending):
                                self.row_ending_fn(self.group_prefill_max)
                        except Exception:  # noqa: BLE001 — a hint only
                            log.exception("row_ending_fn failed")
                    # host-side drafting OUTSIDE the lock: the n-gram
                    # scan is O(context) per row, and admit/stream
                    # waiters must not queue behind it
                    for slot, e, q, prov, krow in to_draft:
                        if prov == "model":
                            # drafted IN-PROGRAM (shallow-exit chain off
                            # the device-true carry token): nothing to
                            # extrapolate host-side, just mark which
                            # positions take the model chain
                            m_host[slot, :krow - 1] = 1
                            assumed[slot] = ("model", True, krow)
                            use_model = True
                            continue
                        dv, hit = self._spec_draft(e, kb, q, k=krow,
                                                   provider=prov)
                        d_host[slot, :krow - 1] = \
                            np.asarray(dv, np.int64)[:krow - 1]
                        assumed[slot] = (prov, hit, krow)
                    # window bucketing: the segment's furthest write
                    # lands at max(pos) + segment - 1, so a pow-2 window
                    # >= max(pos) + segment keeps every live row's
                    # reads/writes in bounds and the output bitwise the
                    # full-window program's. Retired/finished slots'
                    # garbage rows may hold larger stale positions;
                    # their out-of-window scatters drop harmlessly
                    # (nothing reads them).
                    window = self.cache_len
                    wpos = win_pos if pool is not None else positions
                    if self.window_bucketing and wpos \
                            and self.fault_stats.degrade_level < 2:
                        # ladder level >= 2 pins the full-window program
                        # (no first-use window-variant compiles while
                        # the device is misbehaving). Under speculation
                        # the positions are POST-ACCEPT upper bounds, so
                        # the bucket covers the chunk's furthest write
                        # whatever the rows accept.
                        needed = max(wpos) + adv
                        window = min(_next_bucket(needed, 16),
                                     self.cache_len)
                    if pool is not None:
                        # window and page are both pow-2: clamping the
                        # window up to one page keeps the gather width a
                        # whole number of table entries
                        window = max(window, pool.page)
                        if kb and use_model:
                            seg = server._mspec_pseg_fn(
                                self.slots, pool.n_pages, pool.page,
                                window, kb, self.draft_exit)
                        elif kb:
                            seg = server._spec_pseg_fn(
                                self.slots, pool.n_pages, pool.page,
                                window, kb)
                        else:
                            seg = server._paged_seg_fn(
                                self.slots, pool.n_pages, pool.page,
                                window, self.segment)
                        tbl_op = jnp.asarray(
                            tbl_host[:, :window // pool.page])
                    elif kb and use_model:
                        seg = server._mspec_seg_fn(
                            self.slots, self.cache_len, window, kb,
                            self.draft_exit)
                    elif kb:
                        seg = server._spec_seg_fn(
                            self.slots, self.cache_len, window, kb)
                    elif window < self.cache_len:
                        seg = server._windowed_seg_fn(
                            self.slots, self.cache_len, window,
                            self.segment)
                    else:
                        seg = seg_full
                    t_disp = time.monotonic()
                    dispatching.set(rids=rids(e for _, e in live),
                                    window=window, rows=len(positions))

                    def dispatch():
                        knob_ops = (jnp.asarray(t_host),
                                    jnp.asarray(k_host),
                                    jnp.asarray(p_host))
                        draft_ops = ()
                        if kb and use_model:
                            draft_ops = (jnp.asarray(d_host),
                                         jnp.asarray(m_host))
                        elif kb:
                            draft_ops = (jnp.asarray(d_host),)
                        if pool is None:
                            with server._mesh_ctx():
                                return seg(server.params, *knob_ops,
                                           *draft_ops, *self._carry,
                                           eos_op)
                        # paged dispatch advances the arena chain: the
                        # lock holds for enqueue time only (dispatch is
                        # async), but the next arena reader must see
                        # this segment's scatter
                        tok_c, lp_c, pos_c, done_c, keys_c = self._carry
                        with pool.arena_lock:
                            with server._mesh_ctx():
                                out, (f2, lp2, new_arena, pos2, done2,
                                      rng2) = seg(
                                    server.params, *knob_ops,
                                    *draft_ops, tok_c,
                                    lp_c, pool.arena, tbl_op, pos_c,
                                    done_c, keys_c, eos_op)
                            pool.arena = new_arena
                        return out, (f2, lp2, pos2, done2, rng2)

                    # the batch carry as this segment takes it: its tok
                    # and lp leaves hold the first tokens of the rows
                    # packed since the last dispatch (deliver_first)
                    tok, lp = self._carry[:2]
                    outs, self._carry = self._device_wait(
                        "segment_dispatch", gen, dispatch)
                    sown = []
                    if kb:
                        toks, lps, counts_op, pending_op = outs
                    else:
                        # a plain segment also returns the sums of what
                        # the model's kinds sow (self._sown, in order)
                        toks, lps, *sown = outs
                    # attended = per-row sum of positions each step's
                    # attention actually covered (pos + 1 keys at write
                    # index pos); a verify chunk computes all kb
                    # positions whatever it accepts, so adv is the
                    # honest width either way
                    rec = {
                        "toks": toks, "lps": lps, "need_lp": need_lp,
                        "sown": sown,
                        "rows": live, "window": window,
                        "t_dispatch": t_disp,
                        "attended": sum(adv * p + adv * (adv + 1) // 2
                                        for p in positions),
                        "window_read": (len(positions) * adv * window),
                        "full_window": (len(positions) * adv
                                        * self.cache_len)}
                    if kb:
                        rec.update({"spec": kb, "counts": counts_op,
                                    "pending": pending_op,
                                    "assumed": assumed})
                    inflight.append(rec)
                    pstats.record_dispatch(len(inflight))
                    if firsts:
                        deliver_first(tok, lp)
                    if len(inflight) >= eff_depth:
                        collect_one()
                # ---- drain: collect everything behind the frontier so
                # the barrier above sees host-truth slots and a
                # host-materialized carry ----
                if inflight:
                    pstats.record_drain(cause)
                    while inflight:
                        collect_one()
        finally:
            phase.exit()
            pstats.record_wall(time.monotonic() - ep_t0)

    def _prefill_prefix_row(self, prefix_tokens, row, s: int, entry: dict,
                            pentry=None):
        """Continue-prefill from a cached prefix KV -> 1-row carry over
        the FULL context window (the prefix cache's size). The same
        continuation program streaming-with-prefix uses, so packing a
        prefix row into the engine adds zero new program families."""
        import jax.numpy as jnp

        from lambdipy_tpu.models.llama import _next_bucket

        server = self.server
        cfg = server.model.cfg
        cache, plen = (pentry if pentry is not None
                       else server._prefix_entry(prefix_tokens))
        server._validate(plen + s, entry["n"])
        sbs = min(_next_bucket(s, server.min_bucket), cfg.max_len - plen)
        cont = server._stream_prefix_fn(sbs)
        suffix_op, _ = server._pad_rows([row], [s], 1, sbs)
        knobs = server._knob_operands(
            entry["temperature"], entry["top_k"], entry["top_p"],
            entry["seed"], None, b=1)
        with server._mesh_ctx():
            return cont(server.params, cache, suffix_op, jnp.int32(s),
                        *knobs)

    # -- API -----------------------------------------------------------------

    def _admit(self, prompt_row, max_new_tokens, temperature, top_k, top_p,
               seed, eos_id, return_logprobs, prefix):
        """Shared admission: validate, prefill (plain or from a cached
        prefix), enqueue as a joiner and start the engine. Returns the
        live entry dict, or None when the request must run solo (over
        the engine's cache cap, or a prefix row when the engine cache is
        smaller than the prefix cache's full window)."""
        import numpy as np

        from lambdipy_tpu.sched import (current_request_class,
                                        current_request_deadline_ms,
                                        current_request_rid)

        if max_new_tokens <= 0:
            return None
        row = np.asarray(prompt_row, np.int32).reshape(-1).tolist()
        s = len(row)
        deadline_ms = current_request_deadline_ms()
        entry = {"n": max_new_tokens, "eos_id": eos_id,
                 "temperature": temperature, "top_k": top_k, "top_p": top_p,
                 "seed": seed, "toks": [], "lps": [],
                 "want_lp": return_logprobs,
                 "done": False, "error": None, "slot": None, "packed": False,
                 # tokens DISPATCHED for this row (>= len(toks) while
                 # segments are in flight) — the device-side decode
                 # position the pipelined loop windows and quotas by
                 "disp": 0,
                 # 1 from the moment the prefill's token went out ahead of
                 # the row's first block (deliver_first) until the
                 # collector has booked that block from its second column
                 "early": 0,
                 # absolute index of the row's first eos token, recorded
                 # by the collector's incremental block scan; None until
                 # (unless) one appears
                 "eos_at": None,
                 # decode position at join time (prompt end; prefix rows
                 # include the cached prefix) — the window bucketing's
                 # host-side view of how far this row's cache reaches
                 "pos0": s,
                 # fault isolation: replay budget consumed so far, and
                 # the delivery markers that decide replay-vs-error (a
                 # row with bytes on the wire can only error); the
                 # prompt row/prefix persist so a replayed entry can
                 # re-prefill from its admitted state
                 "replays": 0, "streamed": False, "abandoned": False,
                 # set once, when the dispatch that leaves the row at most
                 # one segment of quota has told row_ending_fn
                 "ending": False,
                 # speculative draft state: the last FETCHED pending
                 # token (None = the device knows it, the host has not
                 # collected one yet) and the count of
                 # dispatched-uncollected verify steps the next draft
                 # must extrapolate across
                 "spec_pend": None, "spec_inflight": 0,
                 "row": row, "s": s, "prefix_toks": None,
                 "deadline_at": (time.monotonic() + deadline_ms / 1e3
                                 if deadline_ms else None),
                 "cls": current_request_class(), "seq": next(_entry_seq),
                 # the request's span id: the engine stamps its tiles
                 # (req.join, req.prefill) and names its phases by it
                 "rid": current_request_rid()}
        # per-row draft-tier state (inert when spec is off): the row's
        # CURRENT provider along the fallback chain, its adaptive draft
        # width, and the acceptance EWMA the collector folds each
        # landed verify step into
        entry["draft_mode"], entry["k_row"] = self._spec_row_init()
        entry["accept_ewma"] = None
        if prefix is not None:
            if self.pool is not None:
                # paged prefix hit: resolve the prefix to SHARED arena
                # pages (refcount bump — the zero-copy path) and charge
                # only the suffix + decode remainder; an unknown prefix
                # (explicit client prefix= that never routed through
                # the radix store, or a hit evicted meanwhile) serves
                # solo through the dense server path
                from lambdipy_tpu.runtime.pagepool import PagesExhausted

                # generation read BEFORE the acquire: a reset between
                # them is caught by _paged_continue_row's check (the
                # store's flush makes post-reset acquires miss anyway)
                arena_gen = self.pool.arena_generation
                acq = (self.prefix_pages_fn(prefix)
                       if self.prefix_pages_fn is not None else None)
                if acq is None:
                    return None
                pids, plen = acq
                need_total = -(-(plen + s + max_new_tokens)
                               // self.pool.page)
                if plen + s + max_new_tokens > self.cache_len \
                        or need_total > self.pool.capacity_pages:
                    # a row no engine window (or arena) could EVER hold
                    # serves solo — only a TRANSIENTLY full arena sheds
                    self.pool.release(pids)
                    return None
                entry["plen"] = plen
                entry["pos0"] = plen + s
                entry["arena_gen"] = arena_gen
                entry["prefix_toks"] = \
                    np.asarray(prefix, np.int32).reshape(-1).tolist()
                self._charge_pages(entry, plen + s + max_new_tokens,
                                   shared=pids)
                try:
                    entry["carry"] = self._device_wait(
                        "prefix_assemble", None, self._paged_continue_row,
                        entry, kind="request")
                except _StaleArena:
                    self._release_pages(entry)
                    return None
                except BaseException:
                    self._release_pages(entry)
                    raise
            else:
                # a prefix carry can only pack into an engine whose
                # slots match its cache width — gate on the ENTRY's
                # actual shape (today always the full context window,
                # but the stored cache is the source of truth, not the
                # config constant). The fetched entry rides into the
                # prefill so the gate and the continuation use the SAME
                # cache (no second lookup, no eviction window between
                # them).
                from lambdipy_tpu.models.llama import cache_width

                pentry = self.server._prefix_entry(prefix)
                if self.cache_len != cache_width(pentry[0]):
                    return None
                entry["pos0"] = pentry[1] + s
                entry["prefix_toks"] = \
                    np.asarray(prefix, np.int32).reshape(-1).tolist()
                # guarded as a request-kind wait: the watchdog bounds an
                # injected prefix-assembly hang (the abort raises here,
                # to this caller) without wedging the shared engine
                entry["carry"] = self._device_wait(
                    "prefix_assemble", None, self._prefill_prefix_row,
                    prefix, row, s, entry, pentry, kind="request")
            with self._lock:
                self.prefix_joins += 1
        else:
            if s + max_new_tokens > self.cache_len:
                # a request over the engine's (operator-capped)
                # cache_len is still servable solo — the same bundle
                # served it before continuous mode existed, so don't
                # turn the cap into a client-visible error (ADVICE r4);
                # server._validate still rejects what the model itself
                # can't hold
                return None
            self.server._validate(s, max_new_tokens)
            if self.pool is not None:
                # token-bounded admission: the row charges pages for
                # what it will actually hold, not a window. A row no
                # arena could EVER hold serves solo; a transiently full
                # arena sheds priced (PagesExhausted -> 503 +
                # Retry-After at the HTTP layer).
                need = -(-(s + max_new_tokens) // self.pool.page)
                if need > self.pool.capacity_pages:
                    return None
                self._charge_pages(entry, s + max_new_tokens)
            # Short prompts enqueue RAW and the engine prefills waiting
            # joiners together in one ragged call; long prompts prefill
            # here on the request thread — in chunks when the server
            # has prefill_chunk, so engine segments interleave instead
            # of stalling. Either way the prefill's token goes out when
            # the ENGINE has packed the row and the device has run the
            # pack (deliver_first), not from here: one sent before the
            # row's first segment is next on the device would put the
            # wait for a slot, or for another request's prefill queued
            # ahead of the pack, between a stream's first two tokens.
            try:
                if s <= self.group_prefill_max:
                    entry["carry"] = None
                else:
                    ck = self.server.prefill_chunk
                    if ck and s > ck and self.cache_len % ck == 0:
                        entry["carry"] = self._prefill_row_chunked(row, s,
                                                                   entry)
                    else:
                        entry["carry"] = self._prefill_row(row, s, entry)
            except BaseException:
                self._release_pages(entry)
                raise
        # everything since the scheduler's grant — the handler's own work
        # and a long prompt's prefill on this thread — is req.admit; the
        # wait for a slot (req.join) starts here
        spans.mark(entry["rid"], "req.admit")
        with self._lock:
            self._joiners.append(entry)
            if not self._engine_running:
                self._engine_running = True
                threading.Thread(target=self._engine_loop,
                                 args=(self._gen,), daemon=True,
                                 name="continuous-batch").start()
        return entry

    def _longctx_runner(self):
        """The lazily built long-context tier (one per engine — it
        serializes its own runs). A construction failure stands the
        knob down permanently and loudly; it never takes the serve
        path with it."""
        if self.pool is None or not self.max_logical_ctx:
            return None
        with self._longctx_lock:
            if self._longctx is None:
                from lambdipy_tpu.runtime.longctx import LongContextRunner

                try:
                    self._longctx = LongContextRunner(
                        self.server, self.pool,
                        window=self.cache_len,
                        segment=self.segment,
                        max_logical_ctx=self.max_logical_ctx,
                        long_prefill=self.long_prefill,
                        faults=self.faults,
                        max_replays=max(1, self.max_replays),
                        prefill_mode=self.prefill_mode,
                        prefill_stats=self.prefill_stats)
                except Exception as e:  # noqa: BLE001 — stand down, keep serving
                    log.error("long-context runner unavailable (knob "
                              "stands down): %s", e)
                    self.max_logical_ctx = 0
                    return None
            return self._longctx

    def _route_longctx(self, prompt_row, max_new_tokens: int, prefix):
        """Route an engine-refused request to the long-context tier —
        only when the refusal was the WINDOW (prompt + budget past
        cache_len, which the solo fallback would reject outright) and
        the logical cap holds it. Everything else keeps its existing
        fallback."""
        import numpy as np

        if prefix is not None or not self.max_logical_ctx:
            return None
        try:
            s = int(np.asarray(prompt_row).reshape(-1).shape[0])
        except Exception:  # noqa: BLE001 — malformed rows fail where they did
            return None
        if s + int(max_new_tokens) <= self.cache_len:
            return None
        runner = self._longctx_runner()
        if runner is None or not runner.fits(s, int(max_new_tokens)):
            return None
        return runner

    def generate(self, prompt_row, *, max_new_tokens: int,
                 temperature: float = 0.0, top_k=None, top_p=None,
                 seed: int = 0, eos_id=None, prefix=None,
                 return_logprobs: bool = False):
        """One request row -> [1, max_new_tokens] (the ``server.generate``
        single-prompt contract, logprobs included). Sampled requests
        batch like greedy ones — per-row knob operands and seed-derived
        per-row PRNG chains make a row's output independent of what
        shares the engine (VERDICT r5 #2) — and ``prefix=`` rows join
        the shared batch from their cached prefix KV (VERDICT r5 #3c)."""
        import numpy as np

        entry = self._admit(prompt_row, max_new_tokens, temperature, top_k,
                            top_p, seed, eos_id, return_logprobs, prefix)
        if entry is None:
            runner = self._route_longctx(prompt_row, max_new_tokens, prefix)
            if runner is not None:
                return runner.generate(
                    prompt_row, max_new_tokens=max_new_tokens,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    seed=seed, eos_id=eos_id,
                    return_logprobs=return_logprobs)
            return self.server.generate(
                prompt_row, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=seed, eos_id=eos_id, prefix=prefix,
                return_logprobs=return_logprobs)
        with self._lock:
            while not entry["done"]:
                self._lock.wait(timeout=1.0)
        if entry["error"] is not None:
            raise entry["error"]
        toks, lps = entry["toks"], entry["lps"]
        # solo-parity post-processing: truncate at the row's own eos and
        # pad with the eos filler, exactly like the fused path's latch.
        # The collector recorded the first-hit index (entry["eos_at"])
        # while scanning each newly appended block, so no rescan here;
        # an eos landing at or past max_new_tokens is out of the
        # delivered window and latches nothing.
        eos_at = entry["eos_at"]
        if eos_id is not None and eos_at is not None \
                and eos_at < max_new_tokens:
            cut = eos_at + 1
            toks = toks[:cut] + [eos_id] * (max_new_tokens - cut)
            lps = lps[:cut] + [0.0] * (max_new_tokens - cut)
        out = np.asarray([toks[:max_new_tokens]], np.int32)
        if return_logprobs:
            return out, np.asarray([lps[:max_new_tokens]], np.float32)
        return out

    def generate_stream(self, prompt_row, *, max_new_tokens: int,
                        temperature: float = 0.0, top_k=None, top_p=None,
                        seed: int = 0, eos_id=None, segment: int = 16,
                        prefix=None, return_logprobs: bool = False):
        """Streaming over the SHARED engine batch (VERDICT r5 #3b): the
        row joins in-flight decode like any other request and what the
        engine hands it is yielded as it lands, so streamed requests no
        longer bypass continuous batching. Yields ``[1, k]`` chunks
        ((tokens, logprobs) pairs when asked): the first holds ONE
        token, the prefill's, sent when the engine has packed the row
        (``deliver_first``); the second the other ``segment - 1`` of the
        row's first segment; every later one a whole segment (the
        ENGINE's size: the per-request ``segment`` knob applies only to
        the solo fallback; a verify step's accepted tokens under
        ``spec_k``). Concatenated chunks equal the non-streamed
        ``generate`` output up to the chunk containing eos, whose rest
        is eos filler, like ``LlamaServer.generate_stream``'s."""
        import numpy as np

        entry = self._admit(prompt_row, max_new_tokens, temperature, top_k,
                            top_p, seed, eos_id, return_logprobs, prefix)
        if entry is None:
            runner = self._route_longctx(prompt_row, max_new_tokens, prefix)
            if runner is not None:
                # the runner decodes whole rows (no incremental joiner);
                # deliver its output at the engine's segment cadence so
                # stream consumers see the same chunk contract. Tokens
                # are the runner's verbatim — eos padding included.
                res = runner.generate(
                    prompt_row, max_new_tokens=max_new_tokens,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    seed=seed, eos_id=eos_id,
                    return_logprobs=return_logprobs)
                toks, lps = res if return_logprobs else (res, None)
                step = max(1, self.segment)
                for c0 in range(0, toks.shape[1], step):
                    if return_logprobs:
                        yield (toks[:, c0:c0 + step], lps[:, c0:c0 + step])
                    else:
                        yield toks[:, c0:c0 + step]
                    if eos_id is not None \
                            and eos_id in toks[0, c0:c0 + step]:
                        return
                return
            yield from self.server.generate_stream(
                prompt_row, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=seed, eos_id=eos_id, segment=segment, prefix=prefix,
                return_logprobs=return_logprobs)
            return
        try:
            delivered = 0
            latched = False
            while not latched:
                with self._lock:
                    while (not entry["done"]
                           and len(entry["toks"]) <= delivered):
                        self._lock.wait(timeout=1.0)
                    if entry["error"] is not None:
                        raise entry["error"]
                    if entry["done"] and len(entry["toks"]) <= delivered:
                        return
                    toks = list(entry["toks"])
                    lps = list(entry["lps"])
                    take = min(len(toks), max_new_tokens)
                    if take > delivered:
                        # bytes are about to reach the client: from here
                        # on an engine failure can only surface as an
                        # error (a terminal stream event), never as a
                        # transparent replay — marked under the SAME
                        # lock the failure handler takes, so there is no
                        # window where a replay could splice a restarted
                        # decode onto an already-started stream
                        entry["streamed"] = True
                chunk = toks[delivered:take]
                lp_chunk = lps[delivered:take] if entry["want_lp"] else None
                if not chunk:
                    return
                # eos latch parity with the fused path: fill the rest of
                # the delivering chunk with eos (the device latch would
                # have), then stop the stream at this segment boundary
                if eos_id is not None and eos_id in chunk:
                    cut = chunk.index(eos_id) + 1
                    chunk = chunk[:cut] + [eos_id] * (len(chunk) - cut)
                    if lp_chunk is not None:
                        lp_chunk = lp_chunk[:cut] \
                            + [0.0] * (len(chunk) - cut)
                    latched = True
                delivered = take
                arr = np.asarray([chunk], np.int32)
                if entry["want_lp"]:
                    yield arr, np.asarray([lp_chunk], np.float32)
                else:
                    yield arr
                if delivered >= max_new_tokens:
                    return
        finally:
            # a closed generator (client went away mid-stream) leaves the
            # row with no waiter: flag it so the engine cancels the slot
            # at its next drain barrier instead of decoding to completion
            with self._lock:
                if not entry["done"]:
                    entry["abandoned"] = True

    def stats(self) -> dict:
        with self._lock:
            active = sum(1 for a in self._active if a is not None)
            return {"mode": "continuous", "slots": self.slots,
                    "segment": self.segment, "cache_len": self.cache_len,
                    "window_bucketing": self.window_bucketing,
                    "pipeline_depth": self.pipeline_depth,
                    "watchdog_s": self.watchdog_s,
                    "max_replays": self.max_replays,
                    "faults": self.fault_stats.report(),
                    **({"fault_plan": self.faults.describe()}
                       if self.faults.active() else {}),
                    "pipeline": self.pipeline_stats.report(),
                    "decode_window": self.window_stats.report(),
                    "prefill": self.prefill_stats.report(),
                    **({"spec": {"k": self.spec_k,
                                 "draft_mode": self.draft_mode,
                                 "draft_exit": self.draft_exit,
                                 **self.spec_metrics.report()}}
                       if self.spec_k else {}),
                    "segments_run": self.segments_run,
                    "rows_in_segments": self.rows_in_segments,
                    "requests_served": self.requests_served,
                    "first_tokens_early": self.first_tokens_early,
                    "prefill_groups": self.prefill_groups,
                    "rows_group_prefilled": self.rows_group_prefilled,
                    "prefix_joins": self.prefix_joins,
                    "active_rows": active,
                    "waiting_joiners": len(self._joiners),
                    **({"mesh": self._mesh_report_locked()}
                       if self.mesh_stats is not None else {}),
                    **({"long_context": self._longctx.report()}
                       if self._longctx is not None else {}),
                    **({"page_pool": self.pool.stats()}
                       if self.pool is not None else {})}

    def _mesh_report_locked(self) -> dict:
        """``batching.mesh``: refresh the KV byte gauges from the LIVE
        engine state (the current carry's cache, or the paged arena)
        before reporting — shard metadata reads only, no device data.
        Caller holds the engine lock, so the carry can't swap under
        the read."""
        try:
            from lambdipy_tpu.parallel.sharding import device_bytes

            if self.pool is not None:
                arena = getattr(self.pool, "arena", None)
                if arena is not None:
                    self.mesh_stats.set_kv_bytes(*device_bytes(arena))
            elif self._carry is not None:
                self.mesh_stats.set_kv_bytes(*device_bytes(self._carry[2]))
        except Exception:  # noqa: BLE001 — observability only
            pass
        return self.mesh_stats.report()
