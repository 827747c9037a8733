"""Serve metrics: invoke latency percentiles + cold-start breakdown.

SURVEY.md §6 metrics row: the reference has stdout echo only; the rebuild
keeps p50/p99 and cold-start stage timings as first-class, exported on
``/metrics`` as JSON. :class:`PrefixCacheStats` is the counter block the
automatic prefix KV cache (runtime/prefixstore.py) publishes under
``handler.prefix_cache``.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field


@dataclass
class LatencyStats:
    """Bounded reservoir of recent latencies (ms) with percentile report."""

    capacity: int = 2048
    samples: list[float] = field(default_factory=list)
    count: int = 0
    errors: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record(self, ms: float) -> None:
        with self._lock:
            # ring position is the PRE-increment count: sample N lands at
            # index N % capacity, so the first wraparound overwrite hits
            # slot 0 (incrementing first skewed the ring by one and made
            # slot 0 immortal)
            if len(self.samples) >= self.capacity:
                self.samples[self.count % self.capacity] = ms
            else:
                self.samples.append(ms)
            self.count += 1

    def record_error(self) -> None:
        with self._lock:
            self.errors += 1

    @staticmethod
    def _percentile(samples: list[float], q: float) -> float | None:
        if not samples:
            return None
        s = sorted(samples)
        idx = min(len(s) - 1, max(0, round(q / 100.0 * (len(s) - 1))))
        return s[idx]

    def percentile(self, q: float) -> float | None:
        with self._lock:
            samples = list(self.samples)
        return self._percentile(samples, q)

    def report(self) -> dict:
        # one consistent snapshot: count/errors/samples move together, so
        # read them all under the lock and compute percentiles outside it
        with self._lock:
            count, errors = self.count, self.errors
            samples = list(self.samples)
        return {
            "count": count,
            "errors": errors,
            "p50_ms": self._percentile(samples, 50),
            "p90_ms": self._percentile(samples, 90),
            "p99_ms": self._percentile(samples, 99),
        }


@dataclass
class DecodeWindowStats:
    """Counters for length-aware decode (the ``decode.window`` block on
    ``/metrics``): how many KV positions each decode step actually
    ATTENDED vs how many the dispatched program READ vs what the full
    static window would have read. ``savings_ratio`` = read / full —
    < 1 means the window bucketing (or the blocked kernel) cut decode
    KV traffic; 1.0 means every step paid the whole allocated window.
    ``buckets`` histograms the pow-2 windows segments dispatched at."""

    attended_tokens: int = 0   # sum over rows x steps of positions attended
    window_tokens: int = 0     # sum of positions the program actually read
    full_tokens: int = 0       # what the full static window would have read
    segments: int = 0
    buckets: dict = field(default_factory=dict)  # window -> segment count
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record_segment(self, *, attended: int, window_read: int,
                       full_window: int, window: int) -> None:
        with self._lock:
            self.attended_tokens += int(attended)
            self.window_tokens += int(window_read)
            self.full_tokens += int(full_window)
            self.segments += 1
            self.buckets[int(window)] = self.buckets.get(int(window), 0) + 1

    def report(self) -> dict:
        with self._lock:
            full = self.full_tokens
            return {
                "attended_tokens": self.attended_tokens,
                "window_tokens": self.window_tokens,
                "full_tokens": full,
                "savings_ratio": (round(self.window_tokens / full, 4)
                                  if full else 1.0),
                "attended_ratio": (round(self.attended_tokens / full, 4)
                                   if full else 1.0),
                "segments": self.segments,
                "buckets": {str(w): n
                            for w, n in sorted(self.buckets.items())},
            }


class KindCounters:
    """One ``handler.<block>`` block of ``/metrics``: the recorder of what a
    model's kinds declare for it (``models/llama.py Counters``; what each
    key means is written there, beside the code that counts it). Sums that
    only grow, under one lock; kinds that share a block report their fields
    in the kinds' order."""

    def __init__(self):
        self._kinds: list = []
        self._sums: dict = {}
        self._lock = threading.Lock()

    def add(self, kind) -> None:
        self._kinds.append(kind)
        self._sums.update({name: list(zero) if isinstance(zero, list)
                           else zero for name, zero in kind.fields.items()})

    def _grow(self, adds: dict) -> None:
        with self._lock:
            for name, add in adds.items():
                old = self._sums[name]
                if isinstance(old, list):   # a vector, empty until first fed
                    add = [int(x) for x in add]
                    self._sums[name] = [a + b for a, b in zip(old, add)] \
                        if old else add
                else:
                    self._sums[name] = old + int(add)

    def record_segment(self, sown: dict, booked: list, steps: int) -> None:
        """One fetched plain segment of ``steps`` steps. ``sown``: what the
        program summed of every collection, as host arrays; ``booked``: the
        slots of the rows the collector books: only theirs are counted of
        whatever has a row axis."""
        for kind in self._kinds:
            if kind.segment is not None:
                self._grow(kind.segment(
                    {name: sown[name][booked] if sown[name].ndim
                     else int(sown[name]) for name in kind.sown},
                    len(booked), steps))

    def record_prefill(self, lengths, rows: int, s: int) -> None:
        """One dispatched prefill of ``rows`` rows padded to ``s``
        positions, the real rows ``lengths`` long."""
        for kind in self._kinds:
            if kind.prefill is not None:
                self._grow(kind.prefill(lengths, rows, s))

    def report(self) -> dict:
        with self._lock:
            return {name: list(val) if isinstance(val, list) else val
                    for name, val in self._sums.items()}


@dataclass
class MeshStats:
    """Gauges + counters for tensor-parallel sharded serving (the
    ``batching.mesh`` block on ``/metrics``). ``shape`` is the serving
    mesh ({axis: size}, size-1 axes omitted) over ``devices`` chips.
    The byte gauges are refreshed from the LIVE engine state at scrape
    time (host-only shard metadata, no device reads):
    ``kv_bytes_per_device`` is the busiest device's share of the
    engine's KV residency (B-slot carry, or the paged arena) vs
    ``kv_bytes_replicated`` — the same object's single-device
    footprint; ``hbm_savings`` is their ratio (~1/tp when the head
    sharding holds, 1.0 means the mesh is paying collectives for
    nothing). ``param_bytes_per_device`` / ``param_bytes_total`` track
    the weights the same way. ``collectives_per_segment`` is the
    analytic Megatron-layout count for one engine segment — per decoded
    token, one all-reduce for the vocab-sharded embedding lookup, one
    after the row-parallel o_proj and one after down_proj per layer,
    plus one lm_head logits all-gather per select — i.e.
    ``segment * (2 * layers + 2)``; 0 on a tp-less mesh.
    ``segments_sharded`` counts segments dispatched over the mesh."""

    shape: dict = field(default_factory=dict)
    devices: int = 1
    kv_bytes_per_device: int = 0
    kv_bytes_replicated: int = 0
    param_bytes_per_device: int = 0
    param_bytes_total: int = 0
    collectives_per_segment: int = 0
    segments_sharded: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def set_layout(self, *, shape: dict, devices: int,
                   collectives_per_segment: int) -> None:
        with self._lock:
            self.shape = {str(a): int(n) for a, n in shape.items()}
            self.devices = int(devices)
            self.collectives_per_segment = int(collectives_per_segment)

    def set_kv_bytes(self, per_device: int, replicated: int) -> None:
        with self._lock:
            self.kv_bytes_per_device = int(per_device)
            self.kv_bytes_replicated = int(replicated)

    def set_param_bytes(self, per_device: int, total: int) -> None:
        with self._lock:
            self.param_bytes_per_device = int(per_device)
            self.param_bytes_total = int(total)

    def record_segment(self, n: int = 1) -> None:
        with self._lock:
            self.segments_sharded += int(n)

    def report(self) -> dict:
        with self._lock:
            rep = self.kv_bytes_replicated
            return {
                "shape": dict(self.shape),
                "devices": self.devices,
                "kv_bytes_per_device": self.kv_bytes_per_device,
                "kv_bytes_replicated": rep,
                "hbm_savings": (round(self.kv_bytes_per_device / rep, 4)
                                if rep else 1.0),
                "param_bytes_per_device": self.param_bytes_per_device,
                "param_bytes_total": self.param_bytes_total,
                "param_savings": (
                    round(self.param_bytes_per_device
                          / self.param_bytes_total, 4)
                    if self.param_bytes_total else 1.0),
                "collectives_per_segment": self.collectives_per_segment,
                "segments_sharded": self.segments_sharded,
            }


@dataclass
class PipelineStats:
    """Counters for the continuous engine's pipelined dispatch/collect
    loop (the ``batching.pipeline`` block on ``/metrics``). ``in_flight``
    histograms the pipeline depth at each dispatch (how many segments
    were queued on the device, this one included); ``drains`` counts the
    barrier causes (``joiner`` = a pending joiner forced a bounded drain
    so packing sees host-truth slots, ``handover`` = a live row's whole
    output was dispatched while a joiner waited for its slot, so the
    drain came before the segment that would have stepped it as a
    garbage row, ``complete`` = every live row reached its dispatch
    quota). ``wasted_overdecode_tokens`` are tokens
    fetched for rows that had already finished (EOS observed behind the
    dispatch frontier) and were discarded host-side. ``overlap_ratio`` =
    device-busy / wall: device-busy is the union of each segment's
    [dispatch, fetch-complete] interval, so 1.0 means the device always
    had a segment in flight while the host fetched and booked results —
    the overlap the pipeline exists to create."""

    depth: int = 1             # configured pipeline_depth
    segments: int = 0          # segments collected (host-fetched)
    dispatches: int = 0        # segments dispatched
    wasted_tokens: int = 0     # over-decoded tokens discarded host-side
    inflight: dict = field(default_factory=dict)  # depth -> dispatches
    drains: dict = field(default_factory=dict)    # cause -> count
    device_busy_s: float = 0.0
    fetch_block_s: float = 0.0  # host wall spent blocked in device_get
    wall_s: float = 0.0         # engine-busy wall (idle time excluded)
    _cover_end: float = field(default=0.0, repr=False)
    # monotonic start of the episode currently running, or None when the
    # engine is idle — report() folds the open episode into wall so a
    # mid-episode scrape never divides device_busy_s by a stale wall
    _ep_t0: float | None = field(default=None, repr=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record_dispatch(self, inflight_depth: int) -> None:
        with self._lock:
            self.dispatches += 1
            self.inflight[int(inflight_depth)] = \
                self.inflight.get(int(inflight_depth), 0) + 1

    def record_collect(self, dispatch_t: float, ready_t: float, *,
                       fetch_s: float, wasted: int) -> None:
        with self._lock:
            self.segments += 1
            self.wasted_tokens += int(wasted)
            self.fetch_block_s += max(0.0, fetch_s)
            # union of [dispatch, compute-ready] intervals (ready is
            # when block_until_ready returned — BEFORE the fetch RTT,
            # which the device spends idle unless another segment is
            # queued behind it), accumulated incrementally: both
            # endpoints are monotone across segments, so the uncovered
            # part of this interval starts at the later of its own
            # dispatch and the previous cover's end
            self.device_busy_s += max(
                0.0, ready_t - max(dispatch_t, self._cover_end))
            self._cover_end = max(self._cover_end, ready_t)

    def record_drain(self, cause: str) -> None:
        with self._lock:
            self.drains[cause] = self.drains.get(cause, 0) + 1

    def begin_episode(self, t: float) -> None:
        """Mark an engine episode open at monotonic time ``t``."""
        with self._lock:
            self._ep_t0 = t

    def record_wall(self, seconds: float) -> None:
        """Close the open episode, folding its wall into ``wall_s``."""
        with self._lock:
            self.wall_s += max(0.0, seconds)
            self._ep_t0 = None

    def report(self) -> dict:
        with self._lock:
            wall = self.wall_s
            if self._ep_t0 is not None:
                wall += max(0.0, time.monotonic() - self._ep_t0)
            return {
                "depth": self.depth,
                "segments": self.segments,
                "dispatches": self.dispatches,
                "wasted_overdecode_tokens": self.wasted_tokens,
                "in_flight": {str(d): n
                              for d, n in sorted(self.inflight.items())},
                "drains": dict(self.drains),
                "device_busy_s": round(self.device_busy_s, 4),
                "fetch_block_s": round(self.fetch_block_s, 4),
                "wall_s": round(wall, 4),
                "overlap_ratio": (round(self.device_busy_s / wall, 4)
                                  if wall else 0.0),
            }


@dataclass
class EngineFaultStats:
    """Counters + gauges for the continuous engine's fault-isolation
    layer (the ``batching.faults`` block on ``/metrics``). ``failures``
    keys engine failures by site (a ``watchdog:`` prefix marks waits the
    monitor gave up on); ``replays`` track rows transparently requeued
    through a restarted engine and how many of those completed;
    ``cancelled`` counts rows dropped at a drain barrier because their
    waiter went away (closed stream) or their deadline expired.
    ``degrade_level`` is the ladder position (0 = full service, 1 =
    pipeline depth forced to 1, 2 = + window bucketing off, 3 = + prefix
    cache bypassed); ``degrade_steps`` counts entries into each level
    with the site that caused the last step. ``recoveries`` counts the
    first successful device fetch after a failure (the engine is
    demonstrably serving again), ``restores`` the ladder resetting to 0
    after a clean interval. ``wedged`` mirrors what ``/healthz``
    reports."""

    failures: dict = field(default_factory=dict)   # site -> count
    watchdog_trips: int = 0
    replays_attempted: int = 0
    replays_succeeded: int = 0
    cancelled: int = 0
    degrade_level: int = 0                          # gauge
    degrade_steps: dict = field(default_factory=dict)  # level -> entries
    last_degrade_cause: str | None = None
    recoveries: int = 0
    restores: int = 0
    wedged: bool = False                            # gauge
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record_failure(self, site: str, *, watchdog: bool = False) -> None:
        with self._lock:
            self.failures[site] = self.failures.get(site, 0) + 1
            if watchdog:
                self.watchdog_trips += 1

    def record_replays(self, *, attempted: int = 0, succeeded: int = 0
                       ) -> None:
        with self._lock:
            self.replays_attempted += int(attempted)
            self.replays_succeeded += int(succeeded)

    def record_cancelled(self, n: int = 1) -> None:
        with self._lock:
            self.cancelled += int(n)

    def record_degrade(self, level: int, cause: str) -> None:
        with self._lock:
            self.degrade_level = int(level)
            self.degrade_steps[str(level)] = \
                self.degrade_steps.get(str(level), 0) + 1
            self.last_degrade_cause = cause

    def record_restore(self) -> None:
        with self._lock:
            if self.degrade_level:
                self.restores += 1
            self.degrade_level = 0

    def record_recovery(self) -> None:
        with self._lock:
            self.recoveries += 1

    def set_wedged(self, wedged: bool) -> None:
        with self._lock:
            self.wedged = bool(wedged)

    def report(self) -> dict:
        with self._lock:
            return {
                "failures": dict(self.failures),
                "watchdog_trips": self.watchdog_trips,
                "replays": {"attempted": self.replays_attempted,
                            "succeeded": self.replays_succeeded},
                "cancelled": self.cancelled,
                "degrade_level": self.degrade_level,
                "degrade_steps": dict(self.degrade_steps),
                "last_degrade_cause": self.last_degrade_cause,
                "recoveries": self.recoveries,
                "restores": self.restores,
                "wedged": self.wedged,
            }


@dataclass
class SpecDecodeStats:
    """Counters for speculative decoding — the ``batching.spec`` block on
    ``/metrics`` when the continuous engine runs with ``spec_k``, and the
    ``spec`` block for the solo ``"speculative": k`` request path. ONE
    object serves both (``LlamaServer.spec_metrics``; the engine shares
    the server's instance), so operators read acceptance through one
    surface regardless of which path a request took.

    A *step* is one verify dispatch: ``proposed`` draft tokens offered
    (``kb - 1`` per step), ``accepted`` of them matched the target
    chain, ``emitted`` tokens delivered (accepted + the always-correct
    corrected/pending token). ``acceptance_rate`` = accepted/proposed;
    ``tokens_per_step`` = emitted/steps — the speedup's direct proxy
    (decode is weight-bytes-bound, so tokens/step ~ tok/s multiplier).
    ``wasted_verify_tokens`` are proposed-but-rejected positions: the
    verify FLOPs burned for nothing (each rejected position still paid
    its slice of the chunk forward). ``draft_hits``/``draft_misses``
    split steps by whether prompt-lookup found an n-gram match or fell
    back (repeat-last-token / unknown pending); ``hist`` buckets steps
    by tokens emitted (1..kb — a mass at 1 means drafts never land).
    ``fallback_rows`` counts whole requests that degraded to plain
    decode (no room for a verify chunk near the context boundary).
    ``row_fallbacks`` keys those by reason. ``sp_standdown`` mirrors
    the sequence-parallel decode stand-down counter
    (:func:`lambdipy_tpu.parallel.spdecode.standdown_count`) so the
    silently-degraded long-context condition is visible next to the
    speculation counters it gates."""

    steps: int = 0
    emitted_tokens: int = 0
    proposed_tokens: int = 0
    accepted_tokens: int = 0
    wasted_verify_tokens: int = 0
    draft_hits: int = 0
    draft_misses: int = 0
    fallback_rows: int = 0
    row_fallbacks: dict = field(default_factory=dict)  # reason -> rows
    hist: dict = field(default_factory=dict)           # emitted -> steps
    # -- draft tier (batching.spec.draft) -- per-PROVIDER step counters +
    # acceptance EWMA (model / lookup / aux), the dispatched-k histogram
    # (adaptive-k convergence is readable straight off it: mass at the
    # cap means rows grew, mass at 2 means they collapsed), and the
    # per-row provider-demotion counts ("model->lookup", "lookup->off")
    providers: dict = field(default_factory=dict)      # name -> counters
    k_hist: dict = field(default_factory=dict)         # k -> steps
    draft_fallbacks: dict = field(default_factory=dict)  # edge -> rows
    draft_ewma_alpha: float = 0.2
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record_step(self, *, proposed: int, accepted: int, emitted: int,
                    hit: bool, provider: str = "lookup",
                    k: int | None = None) -> None:
        with self._lock:
            self.steps += 1
            self.proposed_tokens += int(proposed)
            self.accepted_tokens += int(accepted)
            self.emitted_tokens += int(emitted)
            self.wasted_verify_tokens += max(0, int(proposed) - int(accepted))
            if hit:
                self.draft_hits += 1
            else:
                self.draft_misses += 1
            self.hist[int(emitted)] = self.hist.get(int(emitted), 0) + 1
            p = self.providers.setdefault(
                str(provider), {"steps": 0, "proposed": 0, "accepted": 0,
                                "ewma": None})
            p["steps"] += 1
            p["proposed"] += int(proposed)
            p["accepted"] += int(accepted)
            if proposed > 0:
                frac = int(accepted) / float(proposed)
                a = self.draft_ewma_alpha
                p["ewma"] = (frac if p["ewma"] is None
                             else (1.0 - a) * p["ewma"] + a * frac)
            if k is not None:
                self.k_hist[int(k)] = self.k_hist.get(int(k), 0) + 1

    def record_fallback(self, reason: str = "plain") -> None:
        with self._lock:
            self.fallback_rows += 1
            self.row_fallbacks[str(reason)] = \
                self.row_fallbacks.get(str(reason), 0) + 1

    def record_draft_fallback(self, edge: str) -> None:
        """One row demoted along the provider chain (edge like
        ``"model->lookup"``) by the engine's per-row adaptive-k
        controller."""
        with self._lock:
            self.draft_fallbacks[str(edge)] = \
                self.draft_fallbacks.get(str(edge), 0) + 1

    def report(self) -> dict:
        try:
            from lambdipy_tpu.parallel.spdecode import standdown_stats
            sd = standdown_stats()
            standdowns, sd_reasons = sd["spec_standdown"], sd["reasons"]
        except Exception:  # pragma: no cover — observability only
            standdowns, sd_reasons = 0, {}
        with self._lock:
            steps, proposed = self.steps, self.proposed_tokens
            return {
                "steps": steps,
                "emitted_tokens": self.emitted_tokens,
                "proposed_tokens": proposed,
                "accepted_tokens": self.accepted_tokens,
                "acceptance_rate": (round(self.accepted_tokens / proposed, 4)
                                    if proposed else 0.0),
                "tokens_per_step": (round(self.emitted_tokens / steps, 3)
                                    if steps else 0.0),
                "wasted_verify_tokens": self.wasted_verify_tokens,
                "draft_hits": self.draft_hits,
                "draft_misses": self.draft_misses,
                "draft_hit_rate": (round(self.draft_hits / steps, 4)
                                   if steps else 0.0),
                "fallback_rows": self.fallback_rows,
                "row_fallbacks": dict(self.row_fallbacks),
                "tokens_per_step_hist": {str(n): c for n, c in
                                         sorted(self.hist.items())},
                # the draft-tier block the fleet controller reads:
                # per-provider acceptance EWMA (policy demotes
                # draft_mode when the model provider's collapses), the
                # adaptive-k histogram, and provider-demotion counts
                "draft": {
                    "providers": {
                        name: {"steps": p["steps"],
                               "proposed": p["proposed"],
                               "accepted": p["accepted"],
                               "acceptance_ewma": (
                                   round(p["ewma"], 4)
                                   if p["ewma"] is not None else None)}
                        for name, p in sorted(self.providers.items())},
                    "k_hist": {str(n): c for n, c in
                               sorted(self.k_hist.items())},
                    "fallbacks": dict(self.draft_fallbacks),
                },
                "sp_standdown": standdowns,
                # keyed by reason so a fleet can tell "blocked backend
                # under an sp mesh" from "spec chunk under ring" at the
                # router — the aggregated /metrics sums these per reason
                "sp_standdown_reasons": dict(sd_reasons),
            }


@dataclass
class PagePoolStats:
    """Counters for the paged KV memory manager (the
    ``batching.page_pool`` block on ``/metrics``; gauges — pages
    free/live/shared, fragmentation, refcount histogram, capacity rows —
    ride on :meth:`lambdipy_tpu.runtime.pagepool.PagePool.stats`, which
    merges this report in). ``allocs``/``alloc_pages`` count allocation
    calls and pages taken, ``releases``/``release_pages`` pages actually
    returned to the free list (a release of a still-shared page is a
    refcount drop, not a free), ``shares`` refcount bumps (each one is a
    prefix-cache hit's zero-copy page reuse), and ``sheds`` admissions
    refused with :class:`~lambdipy_tpu.runtime.pagepool.PagesExhausted`
    (priced 503s, not errors)."""

    allocs: int = 0
    alloc_pages: int = 0
    releases: int = 0
    release_pages: int = 0
    shares: int = 0
    sheds: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record_alloc(self, pages: int) -> None:
        with self._lock:
            self.allocs += 1
            self.alloc_pages += int(pages)

    def record_release(self, pages: int) -> None:
        with self._lock:
            self.releases += 1
            self.release_pages += int(pages)

    def record_share(self, pages: int = 1) -> None:
        with self._lock:
            self.shares += int(pages)

    def record_shed(self) -> None:
        with self._lock:
            self.sheds += 1

    def report(self) -> dict:
        with self._lock:
            return {
                "allocs": self.allocs,
                "alloc_pages": self.alloc_pages,
                "releases": self.releases,
                "release_pages": self.release_pages,
                "shares": self.shares,
                "sheds": self.sheds,
            }


@dataclass
class KvOffloadStats:
    """Counters for the paged-KV host-offload tier (the ``kv.offload``
    block on ``/metrics``; residency gauges ride on
    :meth:`lambdipy_tpu.runtime.offload.OffloadArena.gauges`, merged
    into the pool's stats). ``spills``/``spill_pages`` count spill calls
    and pages moved to host RAM, ``reonlines``/``reonline_pages`` the
    batched fetch-and-write round trips back into the device arena
    (``reonline_batches`` meters how well the prefetcher coalesces
    them — one frame decode per batch, not per page), and
    ``template_encodes`` every derivation of the kvwire leaf template
    from live arrays — the hot loop must keep it at its attach-time
    value (one), which ``tests/test_long_context.py`` asserts.
    ``prefetch_hits`` are pages the decode-cursor prefetcher had
    already re-onlined when attention demanded them; ``demand_misses``
    stalled the dispatch (``stall_s`` accumulates that wait).
    ``recomputes`` count failed re-onlines degraded to prefill
    recompute — counted work, never a wrong token."""

    spills: int = 0
    spill_pages: int = 0
    spill_bytes: int = 0
    reonlines: int = 0
    reonline_pages: int = 0
    reonline_batches: int = 0
    frame_decodes: int = 0
    template_encodes: int = 0
    prefetch_hits: int = 0
    demand_misses: int = 0
    stall_s: float = 0.0
    stalls: int = 0
    recomputes: int = 0
    drops: int = 0
    spill_refusals: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record_spill(self, pages: int, nbytes: int) -> None:
        with self._lock:
            self.spills += 1
            self.spill_pages += int(pages)
            self.spill_bytes += int(nbytes)

    def record_spill_refusal(self) -> None:
        with self._lock:
            self.spill_refusals += 1

    def record_reonline(self, pages: int, *, batches: int = 1,
                        decodes: int = 1) -> None:
        with self._lock:
            self.reonlines += 1
            self.reonline_pages += int(pages)
            self.reonline_batches += int(batches)
            self.frame_decodes += int(decodes)

    def record_template_encode(self) -> None:
        with self._lock:
            self.template_encodes += 1

    def record_prefetch(self, hits: int, misses: int) -> None:
        with self._lock:
            self.prefetch_hits += int(hits)
            self.demand_misses += int(misses)

    def record_stall(self, seconds: float) -> None:
        with self._lock:
            self.stalls += 1
            self.stall_s += float(seconds)

    def record_recompute(self, pages: int = 1) -> None:
        with self._lock:
            self.recomputes += int(pages)

    def record_drop(self, pages: int = 1) -> None:
        with self._lock:
            self.drops += int(pages)

    def report(self) -> dict:
        with self._lock:
            demanded = self.prefetch_hits + self.demand_misses
            return {
                "spills": self.spills,
                "spill_pages": self.spill_pages,
                "spill_bytes": self.spill_bytes,
                "reonlines": self.reonlines,
                "reonline_pages": self.reonline_pages,
                "reonline_batches": self.reonline_batches,
                "frame_decodes": self.frame_decodes,
                "template_encodes": self.template_encodes,
                "prefetch_hits": self.prefetch_hits,
                "demand_misses": self.demand_misses,
                "prefetch_hit_rate": (
                    round(self.prefetch_hits / demanded, 4)
                    if demanded else 1.0),
                "stalls": self.stalls,
                "stall_s": round(self.stall_s, 6),
                "recomputes": self.recomputes,
                "drops": self.drops,
                "spill_refusals": self.spill_refusals,
            }


@dataclass
class PrefillStats:
    """Counters for the cold-prefill tier (the ``batching.prefill``
    block on ``/metrics``), shared by the continuous engine's prefill
    paths and the prefix store's cold walks. A ROUND is one program
    dispatch on the TTFT critical path; under ``prefill_mode=sp`` a
    round carries up to ``sp`` chunk-widths of the prompt (shard
    occupancy = chunks / (rounds x sp)), under ``chunked`` every round
    is one chunk. ``ring_collectives`` counts the modeled ring hops of
    sharded first-round programs (layers x sp ppermute steps each).
    ``critical_path_s`` is host wall time over whole walks — with
    device time modeled through the ``prefix_walk`` delay site it IS
    the modeled TTFT critical path; ``serial_equiv_s`` scales each walk's wall by its
    chunks/rounds ratio, the chunked-equivalent cost the sharded
    schedule avoided. ``standdowns`` mirrors the counted reasons a
    requested sp prefill ran chunked (no sp mesh axis, pool pressure,
    window not divisible)."""

    mode: str = "chunked"
    sp: int = 0
    rounds: int = 0
    chunks: int = 0
    sharded_chunks: int = 0
    ring_collectives: int = 0
    walks: int = 0
    critical_path_s: float = 0.0
    serial_equiv_s: float = 0.0
    standdowns: dict = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def configure(self, mode: str, sp: int) -> None:
        with self._lock:
            self.mode = str(mode)
            self.sp = int(sp)

    def record_round(self, chunks: int, sp: int, *,
                     ring_hops: int = 0) -> None:
        with self._lock:
            self.rounds += 1
            self.chunks += int(chunks)
            if sp >= 2:
                self.sharded_chunks += int(chunks)
            self.ring_collectives += int(ring_hops)

    def record_walk(self, wall_s: float, chunks: int, rounds: int) -> None:
        with self._lock:
            self.walks += 1
            self.critical_path_s += float(wall_s)
            self.serial_equiv_s += float(wall_s) * (
                int(chunks) / max(1, int(rounds)))

    def record_standdown(self, reason: str) -> None:
        with self._lock:
            self.standdowns[reason] = self.standdowns.get(reason, 0) + 1

    def report(self) -> dict:
        with self._lock:
            slots = self.rounds * max(1, self.sp)
            return {
                "mode": self.mode,
                "sp": self.sp,
                "rounds": self.rounds,
                "chunks": self.chunks,
                "sharded_chunks": self.sharded_chunks,
                "shard_occupancy": (
                    round(self.chunks / slots, 4) if self.rounds else 0.0),
                "ring_collectives": self.ring_collectives,
                "walks": self.walks,
                "critical_path_s": round(self.critical_path_s, 6),
                "serial_equiv_s": round(self.serial_equiv_s, 6),
                "standdowns": dict(self.standdowns),
            }


@dataclass
class KvShipStats:
    """Replica-side counters for the disaggregated-serving KV ship
    surface (the ``batching.disagg`` block on ``/metrics``). Exports are
    ``/v1/kv/export`` frames served (a prefill-class replica's output);
    imports are ``/v1/kv/import`` frames registered in the radix tree.
    ``import_blocks_present`` counts blocks an import found already
    cached (the router's dedup missed, or two ships raced — the import
    is idempotent); ``imports_zero_copy`` vs ``imports_assembled``
    splits imports by how a later hit CONSUMES them: paged-mode imports
    land in arena pages (a hit is an ``acquire_pages`` refcount bump,
    zero copies), dense-mode imports are tree slices (a hit pays a
    ``concat_cache_blocks`` assembly). ``import_backpressure`` counts
    imports refused because the page arena was full — the priced-shed
    path the router's fallback-to-mixed rides.

    The ``*_stream``/``*_chunk`` counters cover the PIPELINED (chunked)
    ship: streamed exports/imports are the subset that rode the
    ``LKVS``/``LKVC`` frame stream, chunk counters are the wire frames
    flushed/received, and ``import_stream_aborts`` counts chunked
    imports that rolled their staged pages back (truncated stream,
    garbage chunk, dead relay) — an abort touches nothing, so it is a
    wasted transfer, never a corrupt tree."""

    exports: int = 0
    export_bytes: int = 0
    export_tokens: int = 0
    export_streams: int = 0
    export_chunks: int = 0
    imports: int = 0
    import_bytes: int = 0
    import_tokens: int = 0
    import_streams: int = 0
    import_chunks: int = 0
    import_stream_aborts: int = 0
    import_blocks_inserted: int = 0
    import_blocks_present: int = 0
    imports_zero_copy: int = 0
    imports_assembled: int = 0
    import_backpressure: int = 0
    import_rejected: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record_export(self, *, tokens: int, nbytes: int,
                      chunks: int = 0) -> None:
        with self._lock:
            self.exports += 1
            self.export_tokens += int(tokens)
            self.export_bytes += int(nbytes)
            if chunks:
                self.export_streams += 1
                self.export_chunks += int(chunks)

    def record_import(self, *, tokens: int, nbytes: int, inserted: int,
                      present: int, mode: str, chunks: int = 0) -> None:
        with self._lock:
            self.imports += 1
            self.import_tokens += int(tokens)
            self.import_bytes += int(nbytes)
            self.import_blocks_inserted += int(inserted)
            self.import_blocks_present += int(present)
            if chunks:
                self.import_streams += 1
                self.import_chunks += int(chunks)
            if mode == "paged":
                self.imports_zero_copy += 1
            else:
                self.imports_assembled += 1

    def record_backpressure(self) -> None:
        with self._lock:
            self.import_backpressure += 1

    def record_rejected(self) -> None:
        with self._lock:
            self.import_rejected += 1

    def record_stream_abort(self) -> None:
        with self._lock:
            self.import_stream_aborts += 1

    def report(self) -> dict:
        with self._lock:
            return {
                "exports": self.exports,
                "export_bytes": self.export_bytes,
                "export_tokens": self.export_tokens,
                "export_streams": self.export_streams,
                "export_chunks": self.export_chunks,
                "imports": self.imports,
                "import_bytes": self.import_bytes,
                "import_tokens": self.import_tokens,
                "import_streams": self.import_streams,
                "import_chunks": self.import_chunks,
                "import_stream_aborts": self.import_stream_aborts,
                "import_blocks": {
                    "inserted": self.import_blocks_inserted,
                    "present": self.import_blocks_present,
                },
                "imports_zero_copy": self.imports_zero_copy,
                "imports_assembled": self.imports_assembled,
                "import_backpressure": self.import_backpressure,
                "import_rejected": self.import_rejected,
            }


@dataclass
class DisaggStats:
    """Router-side counters for phase-split (disaggregated) serving —
    the ``fleet.disagg`` block on the fleet ``/metrics``.

    ``prefill_dispatches`` counts export legs that completed on a
    prefill-class replica; ``decode_dispatches`` counts full ships
    (export + import both landed, so the decode replica serves the
    request from shipped KV). ``ship_skips`` are requests whose prefix
    the router already shipped to that decode replica (the per-replica
    shipped-key LRU). ``fallbacks`` keys every path back to MIXED-mode
    local prefill by reason — a fallback is a slower request, never a
    lost one. The byte/latency EWMAs (alpha 0.2) price the transfer the
    way the page pool prices its backpressure.

    PIPELINED shipping: ``ships_pipelined`` counts ships that rode the
    chunked relay (export frames pumped to the import leg while later
    prefill chunks were still running), ``chunks_relayed`` the ``LKVC``
    frames pumped, and ``mid_stream_failures`` ships that died AFTER
    the stream opened (truncated export, dead import leg, injected
    ``kv_ship_chunk`` fault) — every one also lands in ``fallbacks``
    by reason, because a mid-stream death degrades to mixed-mode like
    any other ship failure.

    ``util`` is the per-replica-class busy-fraction EWMA (alpha 0.3)
    the router folds from pool occupancy at scrape time — the
    observability basis for sizing the prefill pool: a prefill class
    pinned near 1.0 while decode idles wants more prefill replicas
    (and vice versa)."""

    prefill_dispatches: int = 0
    decode_dispatches: int = 0
    ships: int = 0
    ships_pipelined: int = 0
    chunks_relayed: int = 0
    mid_stream_failures: int = 0
    ship_skips: int = 0
    ship_bytes_total: int = 0
    ship_bytes_ewma: float = 0.0
    ship_ms_ewma: float = 0.0
    import_blocks_inserted: int = 0
    import_blocks_present: int = 0
    imports_zero_copy: int = 0
    imports_assembled: int = 0
    fallbacks: dict = field(default_factory=dict)  # reason -> n
    util: dict = field(default_factory=dict)       # class -> busy EWMA
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def count(self, counter: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, counter, getattr(self, counter) + n)

    def record_fallback(self, reason: str) -> None:
        with self._lock:
            self.fallbacks[str(reason)] = \
                self.fallbacks.get(str(reason), 0) + 1

    def record_ship(self, *, nbytes: int, ms: float, chunks: int = 0,
                    pipelined: bool = False) -> None:
        with self._lock:
            self.ships += 1
            if chunks:
                self.chunks_relayed += int(chunks)
            if pipelined:
                # explicitly flagged, NOT inferred from chunks: the
                # blocking buffer-then-relay baseline ships chunk
                # frames too but overlaps nothing
                self.ships_pipelined += 1
            self.ship_bytes_total += int(nbytes)
            a = 0.2
            if self.ships == 1:
                self.ship_bytes_ewma = float(nbytes)
                self.ship_ms_ewma = float(ms)
            else:
                self.ship_bytes_ewma = ((1 - a) * self.ship_bytes_ewma
                                        + a * float(nbytes))
                self.ship_ms_ewma = ((1 - a) * self.ship_ms_ewma
                                     + a * float(ms))

    def record_util(self, cls: str, busy_frac: float) -> None:
        """Fold one busy-fraction sample (0..1) for a replica class
        into its EWMA — called by the router at scrape time from the
        pool's time-weighted occupancy accounting."""
        frac = min(1.0, max(0.0, float(busy_frac)))
        with self._lock:
            prev = self.util.get(str(cls))
            self.util[str(cls)] = (frac if prev is None
                                   else 0.7 * prev + 0.3 * frac)

    def record_import_result(self, *, inserted: int, present: int,
                             mode: str) -> None:
        with self._lock:
            self.import_blocks_inserted += int(inserted)
            self.import_blocks_present += int(present)
            if mode == "paged":
                self.imports_zero_copy += 1
            else:
                self.imports_assembled += 1

    def report(self) -> dict:
        with self._lock:
            return {
                "prefill_dispatches": self.prefill_dispatches,
                "decode_dispatches": self.decode_dispatches,
                "ships": self.ships,
                "ships_pipelined": self.ships_pipelined,
                "chunks_relayed": self.chunks_relayed,
                "mid_stream_failures": self.mid_stream_failures,
                "ship_skips": self.ship_skips,
                "ship_bytes_total": self.ship_bytes_total,
                "ship_bytes_ewma": round(self.ship_bytes_ewma, 1),
                "ship_ms_ewma": round(self.ship_ms_ewma, 3),
                "util": {cls: round(v, 4)
                         for cls, v in sorted(self.util.items())},
                "import_blocks": {
                    "inserted": self.import_blocks_inserted,
                    "present": self.import_blocks_present,
                },
                "imports_zero_copy": self.imports_zero_copy,
                "imports_assembled": self.imports_assembled,
                "fallbacks": dict(self.fallbacks),
            }


@dataclass
class SessionStats:
    """Router-side counters for sticky multi-turn sessions — the
    ``fleet.sessions`` block on the fleet ``/metrics``.

    ``opened`` counts session ids first seen; ``sticky_hits`` turns that
    landed on their recorded home replica, ``sticky_misses`` pick
    attempts whose preferred home was unusable at pick time — a
    saturation spill (the home past the outstanding threshold), or the
    home vanishing between the sticky check and the pick. The turn
    still serves and re-homes; under retries/spill a single turn can
    count more than one miss, so hits/misses are attempt-level, not
    turn-level.
    ``failovers`` counts re-homings off a dead/drained home; ``reships``
    the subset whose whole-block KV head was successfully re-shipped to
    the new home (export from the old home → import on the new one), and
    ``reship_fallbacks`` keys the rest by reason — the common SIGKILL
    case is ``old_home_unreachable``: the KV died with the worker, so
    the new home's counted local re-prefill IS the recovery path.
    ``deletes`` counts explicit ``DELETE /v1/sessions/{id}`` closes.
    ``drain_reships`` counts PROACTIVE re-ships fired by a home
    replica's ``begin_drain`` (the session's pinned head moves to its
    rendezvous successor BEFORE the next turn arrives, so the turn
    after a rolling restart pays a sticky hit, not a failover
    re-prefill); their failures land in ``reship_fallbacks`` like
    turn-time ones. ``record_expiries`` counts sticky records swept by
    the router's idle TTL — replica-side pin leases expire on their
    own, and without the sweep the router's session gauge drifted
    arbitrarily far from the fleet's real pinned state (a chaos-soak
    find)."""

    opened: int = 0
    sticky_hits: int = 0
    sticky_misses: int = 0
    failovers: int = 0
    reships: int = 0
    drain_reships: int = 0
    deletes: int = 0
    record_expiries: int = 0
    reship_fallbacks: dict = field(default_factory=dict)  # reason -> n
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def count(self, counter: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, counter, getattr(self, counter) + n)

    def record_fallback(self, reason: str) -> None:
        with self._lock:
            self.reship_fallbacks[str(reason)] = \
                self.reship_fallbacks.get(str(reason), 0) + 1

    def report(self) -> dict:
        with self._lock:
            return {
                "opened": self.opened,
                "sticky_hits": self.sticky_hits,
                "sticky_misses": self.sticky_misses,
                "failovers": self.failovers,
                "reships": self.reships,
                "drain_reships": self.drain_reships,
                "deletes": self.deletes,
                "record_expiries": self.record_expiries,
                "reship_fallbacks": dict(self.reship_fallbacks),
            }


@dataclass
class RouterStats:
    """Counters for the fleet front-door (fleet/router.py), exported on
    the router's ``/metrics`` under ``router``. ``retries`` counts
    re-sends after a retryable failure (connection loss or a 429/503
    shed), ``failovers`` the subset caused by a dead connection;
    ``hedges``/``hedge_wins`` track duplicate sends for slow requests
    and how often the duplicate answered first. The ``affinity_*``
    counters measure prefix-affinity routing: a hit means the request
    reached its rendezvous-hash target; fallbacks record why it did not
    (target ejected/busy). ``latency`` is the router-observed end-to-end
    distribution — the P9x basis for the hedging threshold.

    The ``spill_*`` counters track the router's fleet-wide-overload
    parking lot (fleet/spill.py): ``spilled`` = requests parked at
    least once, ``spill_drained`` = grants back into the retry loop,
    ``spill_expired``/``spill_overflow`` = the queue's own sheds (the
    live depth/wait gauges ride on the spill queue's report in the
    router ``/metrics``). ``retry_budget_denied`` counts re-sends the
    fleet-wide retry budget refused; ``warmed_prefixes`` counts hot
    radix prefixes replayed into a readmitted/attached replica's
    cache."""

    requests: int = 0
    completed: int = 0
    errors: int = 0
    retries: int = 0
    failovers: int = 0
    hedges: int = 0
    hedge_wins: int = 0
    no_replica: int = 0
    spilled: int = 0
    spill_drained: int = 0
    spill_expired: int = 0
    spill_overflow: int = 0
    retry_budget_denied: int = 0
    warmed_prefixes: int = 0
    affinity_requests: int = 0
    affinity_hits: int = 0
    affinity_fallbacks: dict = field(default_factory=dict)  # reason -> n
    latency: LatencyStats = field(default_factory=LatencyStats)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def count(self, counter: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, counter, getattr(self, counter) + n)

    def count_affinity(self, outcome: str) -> None:
        """``outcome``: 'hit', or a fallback reason ('saturated',
        'ejected', ...). Every call is one affinity-keyed request."""
        with self._lock:
            self.affinity_requests += 1
            if outcome == "hit":
                self.affinity_hits += 1
            else:
                self.affinity_fallbacks[outcome] = \
                    self.affinity_fallbacks.get(outcome, 0) + 1

    def report(self) -> dict:
        with self._lock:
            aff = dict(self.affinity_fallbacks)
            out = {
                "requests": self.requests,
                "completed": self.completed,
                "errors": self.errors,
                "retries": self.retries,
                "failovers": self.failovers,
                "hedges": self.hedges,
                "hedge_wins": self.hedge_wins,
                "no_replica": self.no_replica,
                "spill": {
                    "spilled": self.spilled,
                    "drained": self.spill_drained,
                    "expired": self.spill_expired,
                    "overflow": self.spill_overflow,
                },
                "retry_budget_denied": self.retry_budget_denied,
                "warmed_prefixes": self.warmed_prefixes,
                "affinity": {
                    "requests": self.affinity_requests,
                    "hits": self.affinity_hits,
                    "hit_rate": (round(self.affinity_hits
                                       / self.affinity_requests, 4)
                                 if self.affinity_requests else 0.0),
                    "fallbacks": aff,
                },
            }
        out["latency"] = self.latency.report()
        return out


@dataclass
class ControllerStats:
    """Counters for the elastic fleet control loop
    (fleet/controller.py) — the ``fleet.controller`` block on the
    fleet ``/metrics``.

    ``actions`` counts APPLIED actions by kind (promote/demote/spawn/
    retire/set_knob); ``intents`` counts decisions that were logged but
    NOT applied — every decision in dry-run mode, plus live decisions
    whose actuator refused (e.g. a spawn with no spawner wired).
    ``last_decision`` is the most recent non-empty decision trace
    (tick time, the signal values that drove it, the rendered
    actions) so an operator can answer "why did the fleet just
    resize" from one scrape. ``targets`` echoes the loop's current
    goal posts (SLO, bands, dry_run) — the knobs the controller is
    steering TOWARD, as opposed to the per-replica knobs it steers."""

    ticks: int = 0
    errors: int = 0
    actions: dict = field(default_factory=dict)   # kind -> applied n
    intents: dict = field(default_factory=dict)   # kind -> logged-only n
    last_decision: dict = field(default_factory=dict)
    targets: dict = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def count(self, counter: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, counter, getattr(self, counter) + n)

    def record_action(self, kind: str, *, applied: bool) -> None:
        with self._lock:
            book = self.actions if applied else self.intents
            book[str(kind)] = book.get(str(kind), 0) + 1

    def record_decision(self, trace: dict) -> None:
        with self._lock:
            self.last_decision = dict(trace)

    def set_targets(self, **targets) -> None:
        with self._lock:
            self.targets.update(targets)

    def report(self) -> dict:
        with self._lock:
            return {
                "ticks": self.ticks,
                "errors": self.errors,
                "actions": dict(sorted(self.actions.items())),
                "intents": dict(sorted(self.intents.items())),
                "last_decision": dict(self.last_decision),
                "targets": dict(sorted(self.targets.items())),
            }


@dataclass
class PrefixCacheStats:
    """Counters for the automatic cross-request prefix KV cache: a
    request whose prompt longest-prefix-matches the radix tree is a hit
    (``hit_tokens`` = prompt tokens whose prefill was skipped), one with
    cacheable length but no match is a miss. ``bytes``/``blocks`` track
    what the store currently holds against its HBM budget; ``evictions``
    counts blocks dropped by the budget's LRU sweep.
    ``assembly_bytes_peak`` is the largest single full-window cache the
    store has ASSEMBLED (``concat_cache_blocks``) for a hit — the copy +
    peak-HBM spike the paged path eliminates, reported explicitly (always
    present, 0 on the paged path) so "no assembly happened" is an
    observable fact rather than a missing key."""

    hits: int = 0
    misses: int = 0
    hit_tokens: int = 0
    evictions: int = 0
    bytes: int = 0
    blocks: int = 0
    assembly_bytes_peak: int = 0
    assemblies: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def record_request(self, matched_tokens: int) -> None:
        with self._lock:
            if matched_tokens > 0:
                self.hits += 1
                self.hit_tokens += matched_tokens
            else:
                self.misses += 1

    def record_insert(self, n_blocks: int, nbytes: int) -> None:
        with self._lock:
            self.blocks += n_blocks
            self.bytes += nbytes

    def record_evict(self, n_blocks: int, nbytes: int) -> None:
        with self._lock:
            self.blocks -= n_blocks
            self.bytes -= nbytes
            self.evictions += n_blocks

    def record_assembly(self, nbytes: int) -> None:
        with self._lock:
            self.assemblies += 1
            self.assembly_bytes_peak = max(self.assembly_bytes_peak,
                                           int(nbytes))

    def report(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": round(self.hits / total, 4) if total else 0.0,
                "hit_tokens": self.hit_tokens,
                "evictions": self.evictions,
                "bytes": self.bytes,
                "blocks": self.blocks,
                "assemblies": self.assemblies,
                "assembly_bytes_peak": self.assembly_bytes_peak,
            }
