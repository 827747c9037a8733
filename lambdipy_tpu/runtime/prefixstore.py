"""Automatic cross-request prefix KV cache: radix reuse for the serve path.

Real generate traffic is dominated by shared prompt prefixes — system
prompts, few-shot templates, multi-turn histories — and prefill is the
compute-bound axis of TPU serving (round 5 measured dense 8B prefill at
57-76% MFU). Before this module the repo only reused a prefix when the
CLIENT shipped the prefix token ids explicitly (``prefix=`` requests);
every ordinary request re-prefilled its whole prompt. :class:`PrefixStore`
makes reuse automatic and transparent, in the style of SGLang's
RadixAttention / vLLM's automatic prefix caching:

- The store keeps a RADIX TREE keyed by fixed-width token blocks. A node
  at depth d holds the KV slice (store layout — float, or int8 + scales
  under ``kv_quant``) for its own block at absolute positions
  ``[d*block, (d+1)*block)``; KV is position-dependent (RoPE is applied
  before the cache store), so depth pins position by construction.
- On arrival :meth:`route` longest-prefix-matches the prompt against the
  tree in whole blocks (capped so at least one suffix token remains for
  the continuation to select from). Matched blocks are assembled into a
  full-window decode cache (``models/llama.py concat_cache_blocks``) and
  registered in the server's prefix-entry LRU, so every EXISTING
  ``prefix=`` path — fused, streaming, continuous-engine join,
  speculative — serves the suffix-only continuation unchanged.
- Unmatched whole blocks are prefilled HERE, through the server's
  fixed-width chunk programs (the same first/ext family chunked prefill
  uses), and their slices inserted into the tree as the walk goes: the
  request's own prefill IS the insertion, so a cold prefix costs one
  prefill total and every later request extends the match for free.
  Concurrent first requests for the same target path collapse to one
  device walk (per-key inflight events, like ``cache_prefix``).
- An HBM budget bounds the tree: block bytes are accounted exactly from
  the stored leaves, and inserts beyond the budget evict
  least-recently-used LEAF nodes (evicting an interior node would orphan
  the positions after it). Counters ride
  :class:`lambdipy_tpu.runtime.metrics.PrefixCacheStats` into
  ``/metrics`` as ``handler.prefix_cache``.

Correctness bar (carried over from the continuous engine): with the
float KV cache a routed request's tokens are BITWISE the unrouted ones —
the continuation attends the same masked KV the wide prefill would have
produced — asserted for greedy and seeded-sampled decode in
tests/test_prefixstore.py. Under ``kv_quant`` the cached prefix reads
back quantized (tolerance-level parity), so the handler keeps automatic
reuse opt-in there.

PAGED mode (``pool=`` a :class:`lambdipy_tpu.runtime.pagepool.PagePool`):
the tree's nodes hold arena PAGE IDS instead of host-side KV slices — a
radix block IS a page. A full hit costs a refcount bump per page
(:meth:`PrefixStore.acquire_pages`): no ``concat_cache_blocks``
assembly, no registered full-window duplicate, no peak-HBM spike — the
``assembly_bytes_peak`` gauge stays 0 by construction. Cold walks run
the same chunk programs into a transient contiguous cache and write each
new block into its own page; eviction is refcount-aware (only leaves no
live row shares may release their page).

SESSION PINS (multi-turn chat): a session id attached to a request PINS
the conversation's radix path — pinned nodes are excluded from the LRU
budget sweep AND from the refcount-aware cold-page reclaim
(``reclaim_fn``), so an open conversation's KV cannot vanish under cache
pressure mid-conversation and every turn-2+ request longest-prefix-
matches its whole history. Pins are LEASES, not locks: each carries an
absolute TTL (from session creation) and an idle timeout renewed on
every turn, and expired sessions release lazily on the next locked store
operation (``stats()`` included, so a scrape is enough to converge
accounting to zero). Total pinned bytes are capped by
``pin_budget_mb`` — a pin that would exceed it raises
:class:`SessionPinsExceeded`, which the HTTP layer maps to a priced 503
shed (reason ``session_pins``) with Retry-After taken from the earliest
lease-expiry horizon: pins can never starve live traffic, they can only
shed new sessions. An arena-generation bump (engine failure reset)
invalidates every pin observably (``pin_invalidations``): the sessions
drop with the stale tree and the next turn re-prefills through the
normal walk — a counted, bounded re-prefill, never a wedge.

Every failure path FAILS OPEN: a store error logs and the request serves
unrouted — the cache is an optimization, never an availability risk.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any

from lambdipy_tpu.runtime.metrics import PrefixCacheStats
from lambdipy_tpu.utils.logs import get_logger

log = get_logger("lambdipy.prefixstore")


class SessionPinsExceeded(RuntimeError):
    """Pinning this session's head would push total pinned bytes past
    ``pin_budget_mb``. Mapped by the HTTP layer to a priced 503 shed
    (reason ``session_pins``); ``retry_after_s`` is the earliest
    lease-expiry horizon — when the next pinned session can lapse and
    free budget."""

    def __init__(self, needed: int, budget: int, retry_after_s: float):
        self.needed = int(needed)
        self.budget = int(budget)
        self.retry_after_s = float(retry_after_s)
        super().__init__(
            f"session pin budget exhausted: pinning needs {needed} more "
            f"bytes of a {budget}-byte budget (retry in "
            f"~{self.retry_after_s:.1f}s)")


class _Session:
    """One live conversation's pin lease: the pinned path nodes, the
    idle-renewed expiry, the absolute deadline (created + ttl), and the
    EFFECTIVE idle window (the store default, tightened by the client's
    own ``session_ttl_s`` — renewals must honor the tightened value,
    never silently expand it back to the default)."""

    __slots__ = ("nodes", "expires", "deadline", "idle", "turns")

    def __init__(self, deadline: float, idle: float):
        self.nodes: list = []
        self.expires = 0.0
        self.deadline = deadline
        self.idle = idle
        self.turns = 0


class _Node:
    """One block of a cached prefix: ``kv`` is the per-layer store-layout
    slice list for this block's absolute positions (dense mode), or
    ``page_id`` names the arena page holding them (paged mode — the
    store owns one pool ref per node). ``pins`` counts live sessions
    holding this node: a pinned node is excluded from every eviction
    sweep. ``off_key`` (paged mode with a host offload tier attached)
    names this block's kvwire bytes in the offload arena when the page
    was SPILLED instead of dropped — page_id is None then, and
    :meth:`PrefixStore.acquire_pages` re-onlines it on demand."""

    __slots__ = ("parent", "token_key", "children", "kv", "nbytes",
                 "last_used", "page_id", "pins", "off_key")

    def __init__(self, parent, token_key, kv=None, nbytes=0,
                 page_id=None):
        self.parent = parent
        self.token_key = token_key  # tuple of this block's tokens
        self.children: dict[tuple, "_Node"] = {}
        self.kv = kv
        self.nbytes = nbytes
        self.last_used = 0
        self.page_id = page_id
        self.pins = 0
        self.off_key = None


def _slices_bytes(slices) -> int:
    """Exact stored bytes of one block's per-layer slice list."""
    return sum(int(v.size) * v.dtype.itemsize
               for entry in slices for v in entry.values())


def _cache_bytes(cache) -> int:
    """Exact bytes of one assembled full-window cache (array leaves
    only — the scalar ``index`` is noise)."""
    return sum(int(v.size) * v.dtype.itemsize
               for entry in cache for v in entry.values()
               if hasattr(v, "dtype"))


class PrefixStore:
    """Radix-tree prefix KV store over a ``LlamaServer``."""

    def __init__(self, server: Any, *, block: int = 32,
                 budget_mb: float = 512.0, pool: Any = None,
                 faults: Any = None, pin_budget_mb: float | None = None,
                 session_ttl_s: float = 3600.0,
                 session_idle_s: float = 600.0,
                 prefill_mode: str = "chunked", prefill_stats: Any = None):
        from lambdipy_tpu.runtime.pagepool import page_width

        self.server = server
        # "chunked" (the serial walk) or "sp": cold walks dispatch rounds
        # of sp x walk_chunk tokens as ONE sharded program each — the
        # whole-prompt sequence-parallel prefill tier. Resolved against
        # the server's mesh per walk (_sp_factor): no sp axis stands the
        # walk down to chunked with a counted reason, never silently.
        self.prefill_mode = prefill_mode
        # shared PrefillStats (runtime/metrics.py) — the handler passes
        # the engine's instance so /metrics shows ONE batching.prefill
        # block across engine prefill and store walks
        self.prefill_stats = prefill_stats
        # FaultPlan | None; site "prefix_walk" fires once per cold-walk
        # chunk dispatch: an injected exception fails the walk OPEN
        # (route() serves the request unrouted), a delay stands for the
        # chunk's prefill device time where the real prefill is too cheap
        # to occupy a replica (tests/test_kvship.py holds the open failure)
        self.faults = faults
        cfg = server.model.cfg
        from lambdipy_tpu.models.llama import require_kv_cache

        require_kv_cache(cfg, "the radix prefix store (PrefixStore)")
        # PAGED mode (runtime/pagepool.py): a radix block IS an arena
        # page. Nodes hold page ids instead of host-side KV slices, a
        # hit hands its pages out by refcount bump (acquire_pages — zero
        # copies, no assembled full-window duplicate), and eviction is a
        # refcount-aware page release: only leaves no live row still
        # shares may return to the pool.
        self.pool = pool
        if pool is not None:
            # the pool's page width was normalized against the engine
            # window at construction; the tree must key by the same
            # width or block boundaries and page boundaries would drift
            self.block = int(pool.page)
        else:
            # pow-2 block that divides the context window: every block
            # write lands at a multiple-of-block offset and must never
            # cross max_len (dynamic_update_slice would clamp it onto
            # real KV) — the same constraint chunked prefill enforces
            # for prefill_chunk. page_width is this exact normalization
            # (one implementation, shared with the pool's page sizing).
            self.block = page_width(cfg.max_len, block)
        # cold-miss walks dispatch in WIDER chunks than the tree's block
        # (block slices are cut from the final cache either way): a
        # unique long prompt should not pay one device dispatch per 32
        # tokens. Prefer the server's existing prefill_chunk program
        # family (zero new compiles) when it block-aligns, else a
        # 256-token family; block-width remains the tail/fallback.
        ck = getattr(server, "prefill_chunk", None)
        if ck and ck % self.block == 0:
            wide = ck
        else:
            wide = max(self.block, min(256, cfg.max_len))
        while wide > self.block and cfg.max_len % wide:
            wide //= 2
        self.walk_chunk = wide
        self.budget_bytes = max(0, int(float(budget_mb) * 2**20))
        self.stats_counters = PrefixCacheStats()
        self._root = _Node(None, None)
        # RLock: in paged mode the pool's out-of-pages reclaim hook
        # (reclaim_pages) re-enters through the store's own page alloc
        self._lock = threading.RLock()
        # arena CONTENT generation this tree's pages were written
        # against: an engine failure resets the arena (zeroed, bumped),
        # making every cached page stale — the tree flushes lazily on
        # its next locked operation (_maybe_flush_stale_locked)
        self._arena_gen = pool.arena_generation if pool is not None else 0
        if pool is not None:
            # admission must never starve behind a cold cache: a short
            # pool alloc evicts this store's unshared LRU pages first
            pool.reclaim_fn = self.reclaim_pages
        # host offload tier (runtime/offload.py), wired post-init by
        # attach_offload(): swept-cold pages spill their kvwire bytes to
        # host RAM instead of vanishing, and acquire_pages re-onlines
        # them on demand through the validated page-write path
        self.offload: Any = None
        self._clock = itertools.count(1)
        # target-path key -> Event: concurrent cold requests for the same
        # prefix wait for one device walk instead of duplicating it
        self._inflight: dict[str, threading.Event] = {}
        # -- session pins (multi-turn chat) --------------------------------
        # default pin budget: half the store budget, so a fully pinned
        # session population still leaves LRU headroom for ordinary
        # shared-prefix traffic. An explicit budget is CLAMPED to the
        # cache budget: pinned bytes live inside the store's accounting,
        # and a pin budget above it would let sessions hold the whole
        # cache (or, paged, the whole arena) out of eviction's reach —
        # exactly the live-traffic starvation pins must never cause.
        self.pin_budget_bytes = int(
            min(float(pin_budget_mb) * 2**20, self.budget_bytes)
            if pin_budget_mb is not None
            else self.budget_bytes // 2)
        self.session_ttl_s = max(1.0, float(session_ttl_s))
        self.session_idle_s = max(1.0, float(session_idle_s))
        self._sessions: dict[str, _Session] = {}
        self._pinned_bytes = 0
        self._pinned_leaves = 0
        self.pin_sheds = 0          # NEW sessions refused on budget (503)
        self.pin_overflows = 0      # renewals that could not extend
        self.pin_expiries = 0       # sessions lapsed by TTL/idle lease
        self.pin_invalidations = 0  # sessions dropped by an arena reset
        self.pin_faults = 0         # injected session_pin faults (open)
        if pool is not None:
            # pinned-page gauges ride batching.page_pool too, so an
            # operator sizing the arena sees pins squeezing headroom
            # next to the refcount gauges (host-only, store lock only —
            # the pool calls this OUTSIDE its own lock)
            pool.pinned_fn = self._pool_pin_gauges

    def attach_offload(self, offload: Any) -> None:
        """Wire a host offload tier
        (:class:`lambdipy_tpu.runtime.offload.OffloadArena`) into the
        paged store: the LRU sweep SPILLS cold unshared pages to host
        RAM (kvwire frames) instead of dropping them, and
        :meth:`acquire_pages` re-onlines spilled blocks in one batched
        frame decode on demand. The leaf template is seeded HERE, once,
        from the store layout — the spill/re-online hot loop never
        re-derives it (asserted by ``template_encodes`` staying at 1)."""
        if self.pool is None:
            raise ValueError("KV offload requires paged mode (pool=)")
        template = self._leaf_template()
        offload.attach_template(
            [[name, dt.name, list(shape)]
             for name, (shape, dt) in sorted(template.items())])
        self.offload = offload
        self.pool.attach_offload(offload)

    @staticmethod
    def _node_key(node: _Node) -> tuple:
        """Offload-arena key of a node: the FULL token path from the
        root — position-unique by construction (KV is RoPE'd before
        store, so the same block tokens at two depths are two entries)."""
        parts = []
        while node is not None and node.token_key is not None:
            parts.append(node.token_key)
            node = node.parent
        return tuple(t for key in reversed(parts) for t in key)

    # -- host-side matching --------------------------------------------------

    def _target_len(self, n_tokens: int) -> int:
        """Largest cacheable block-aligned prefix of an n-token prompt:
        at least one token must remain as suffix (the continuation
        program selects the first output token from it)."""
        return ((n_tokens - 1) // self.block) * self.block

    def match_len(self, tokens) -> int:
        """Host-only longest-prefix match in whole blocks — no device
        work, no mutation beyond LRU bookkeeping. This is also the
        scheduler's cost probe: admission prices the SUFFIX a cache-hit
        request will actually prefill (runtime/server.py)."""
        try:
            row = [int(t) for t in tokens]
        except (TypeError, ValueError):
            return 0
        with self._lock:
            return self._match_locked(row)[0]

    def _maybe_flush_stale_locked(self) -> None:
        """Paged mode, under the store lock: if the pool's arena was
        RESET since this tree's pages were written (engine failure —
        their content is zeroed), drop the whole tree. Refs release
        now; pages shared with live rows return to the free list when
        those rows retire. Walks then re-prefill against the fresh
        arena — correctness over cache warmth."""
        if self.pool is None \
                or self._arena_gen == self.pool.arena_generation:
            return
        self._arena_gen = self.pool.arena_generation
        dead_keys = []
        for node in list(self._iter_nodes()):
            if node.page_id is not None:
                self.pool.release([node.page_id])
                self.stats_counters.record_evict(1, node.nbytes)
                node.page_id = None
            if node.off_key is not None:
                # host bytes survive an arena reset, but the tree drops
                # wholesale — unreachable entries must not leak budget
                dead_keys.append(node.off_key)
                node.off_key = None
            node.pins = 0
        if dead_keys and self.offload is not None:
            try:
                self.offload.drop(dead_keys)
            except Exception:  # noqa: BLE001 — cleanup must not block flush
                pass
        # session pins die with the stale tree — OBSERVABLY: the next
        # turn re-prefills its whole head through the normal walk (a
        # counted, bounded recovery) and re-pins fresh nodes
        if self._sessions:
            dropped = len(self._sessions)
            self.pin_invalidations += dropped
            self._sessions.clear()
            log.info("arena reset invalidated %d session pin lease(s)",
                     dropped)
        self._pinned_bytes = 0
        self._pinned_leaves = 0
        self._root.children = {}
        log.info("prefix store flushed: arena generation moved "
                 "(engine failure reset the page arena)")

    def _match_locked(self, row: list) -> tuple[int, list]:
        """(matched token count, path nodes) under the store lock."""
        self._maybe_flush_stale_locked()
        cap = self._target_len(len(row))
        m, node, path = 0, self._root, []
        while m < cap:
            child = node.children.get(tuple(row[m:m + self.block]))
            if child is None:
                break
            child.last_used = next(self._clock)
            path.append(child)
            node = child
            m += self.block
        return m, path

    # -- the routing entry point ---------------------------------------------

    def route(self, row) -> int:
        """Match + extend + register for one single-row prompt. Returns
        the block-aligned prefix length the request should dispatch with
        (``prefix=row[:m]``, prompt = the suffix), or 0 when the prompt
        is too short to cache or the store failed (serve unrouted).

        A cold prompt is NOT a fast no-op: the unmatched whole blocks
        prefill here (that work replaces the prefill the request would
        have paid anyway) and insert into the tree, so the first request
        for a prefix pays ~one prefill and every later request rides it.
        """
        row = [int(t) for t in row]
        cfg = self.server.model.cfg
        if len(row) > cfg.max_len:
            # the request itself is doomed (server._validate rejects it):
            # a walk here would burn up to a full window of device
            # prefill and evict hot LRU entries for nothing
            return 0
        # the clamp also keeps every block write inside the window —
        # an unclamped target would let the ext loop's writes reach
        # max_len, where dynamic_update_slice CLAMPS them back onto
        # real tail KV (the documented chunked-prefill trap)
        target = min(self._target_len(len(row)),
                     cfg.max_len - self.block)
        if target <= 0:
            return 0  # sub-block prompt: can never hit, don't count it
        with self._lock:
            matched, path = self._match_locked(row)
        self.stats_counters.record_request(matched)
        try:
            if matched >= target:
                if self.pool is None:
                    self._ensure_assembled(row,
                                           path[:target // self.block])
                # paged full hit: nothing to do here — the pages are
                # already in the arena and the engine acquires them by
                # refcount bump (acquire_pages); no assembly, no copy
            else:
                self._extend(row, target)
            return target
        except Exception as e:  # noqa: BLE001 — fail open, serve unrouted
            log.error("prefix store routing failed (serving without "
                      "reuse): %s", e)
            return 0

    def acquire_pages(self, tokens):
        """Paged-mode hit handout: resolve a block-aligned prefix to its
        arena pages with one pool ref taken PER PAGE for the caller (the
        zero-copy path — the engine's row shares the store's physical
        pages; releasing them is a refcount drop). Returns ``(page_ids,
        prefix_len)`` or None when any block is missing (evicted since
        routing, or an explicit client prefix that never walked this
        tree) — the caller then serves through the dense fallback.
        Retain happens under the store lock, so a concurrent LRU sweep
        cannot release a page between the match and the bump.

        With a host offload tier attached, blocks whose pages were
        SPILLED re-online here — one batched kvwire frame decode for all
        missing blocks, written back through the validated page-write
        path — before the handout. A failed re-online (offload fault,
        dropped entry, page famine) degrades to None: the caller's dense
        fallback recomputes the prefix via prefill — counted
        (``kv_offload.recomputes``), never a wrong token."""
        if self.pool is None:
            return None
        try:
            row = [int(t) for t in tokens]
        except (TypeError, ValueError):
            return None
        if not row or len(row) % self.block:
            return None
        with self._lock:
            self._maybe_flush_stale_locked()
            node, m, path = self._root, 0, []
            while m < len(row):
                child = node.children.get(tuple(row[m:m + self.block]))
                if child is None or (child.page_id is None
                                     and child.off_key is None):
                    return None
                child.last_used = next(self._clock)
                path.append(child)
                node = child
                m += self.block
            missing = [n for n in path if n.page_id is None]
            # retain the resident pages FIRST: the re-online alloc may
            # re-enter the reclaim sweep, and a refcount of 2 is what
            # keeps the sweep's hands off the path we are handing out
            resident = [n.page_id for n in path if n.page_id is not None]
            self.pool.retain(resident)
            if missing and not self._reonline_locked(missing):
                self.pool.release(resident)
                return None
            fresh = [n.page_id for n in missing]
            self.pool.retain(fresh)
            pids = [n.page_id for n in path]
        return pids, m

    def _reonline_locked(self, nodes: list) -> bool:
        """Bring spilled blocks back into the arena, under the store
        lock: ONE batched fetch (one frame decode for the whole batch),
        one alloc, chained page writes under the arena lock with a
        generation guard. On success every node holds a fresh page (the
        store's ref) and its offload entry is dropped. Any failure
        returns False with nothing leaked — the caller serves dense."""
        import jax.numpy as jnp
        import numpy as np

        from lambdipy_tpu.runtime.offload import OffloadMiss
        from lambdipy_tpu.runtime.pagepool import PagesExhausted

        pool = self.pool
        stats = getattr(self.offload, "stats", None)
        keys = [n.off_key for n in nodes]
        if self.offload is None or any(k is None for k in keys):
            return False
        try:
            blocks = self.offload.fetch_many(keys)
        except OffloadMiss as e:
            # the entries are GONE (dropped by a racer or an operator):
            # retrying every walk is pointless — prune from the
            # shallowest ghost down (the path is a chain, so that
            # subtree holds every deeper node) and the next request
            # prefills the range fresh
            log.error("spilled prefix blocks missing from the offload "
                      "arena (recomputing via prefill): %s", e)
            self._prune_subtree_locked(nodes[0])
            if stats is not None:
                stats.record_recompute(len(keys))
            return False
        except Exception as e:  # noqa: BLE001 — injected faults, transient IO
            log.error("page re-online failed (recomputing via "
                      "prefill): %s", e)
            if stats is not None:
                stats.record_recompute(len(keys))
            return False
        try:
            pids = pool.alloc(len(nodes), tokens=len(nodes) * self.block,
                              record_shed=False)
        except PagesExhausted:
            if stats is not None:
                stats.record_recompute(len(keys))
            return False
        write = self.server._page_write_fn(pool.n_pages, pool.page)
        try:
            with pool.arena_lock, self.server._mesh_ctx():
                if pool.arena_generation != self._arena_gen:
                    # the arena reset between walk and write: staged
                    # content would be stale — the flush sweep owns
                    # cleanup, this handout just fails dense
                    pool.release(pids)
                    return False
                arena = pool.ensure_arena()
                for pid, blk in zip(pids, blocks):
                    jblk = [{name: jnp.asarray(np.asarray(val))
                             for name, val in entry.items()}
                            for entry in blk]
                    arena = write(arena, jnp.int32(pid), jblk)
                pool.arena = arena
        except Exception as e:  # noqa: BLE001 — a failed write leaks nothing
            log.error("page re-online write failed (recomputing via "
                      "prefill): %s", e)
            pool.release(pids)
            if stats is not None:
                stats.record_recompute(len(keys))
            return False
        for node, pid in zip(nodes, pids):
            node.page_id = pid
            node.off_key = None
            self.stats_counters.record_insert(1, node.nbytes)
        self.offload.drop(keys)
        return True

    # -- session pins (multi-turn chat) ---------------------------------------

    def _unpin_locked(self, nodes) -> None:
        for n in nodes:
            if n.pins <= 0:
                continue  # already cleared by an arena flush
            n.pins -= 1
            if n.pins == 0:
                self._pinned_bytes -= n.nbytes
                self._pinned_leaves -= 1

    def _expire_sessions_locked(self, now: float) -> None:
        """Lazily lapse sessions past their idle lease or absolute TTL —
        called from every pin/stats path, so a /metrics scrape alone is
        enough to converge pin accounting after sessions go quiet."""
        for sid in [s for s, sess in self._sessions.items()
                    if now >= sess.expires or now >= sess.deadline]:
            self._unpin_locked(self._sessions.pop(sid).nodes)
            self.pin_expiries += 1
            log.info("session %s lease expired: pins released", sid[:16])

    def _lease_horizon_locked(self, now: float) -> float:
        """Seconds until the next pinned session CAN lapse — the honest
        Retry-After for a budget shed (a freed budget needs a lease to
        end, not wall-clock optimism)."""
        horizon = [min(s.expires, s.deadline) - now
                   for s in self._sessions.values()]
        return max(1.0, min(horizon)) if horizon else 1.0

    def pin_session(self, session_id: str, tokens, *,
                    ttl_s: float | None = None) -> int:
        """Pin (or renew) ``session_id`` on the whole-block head of
        ``tokens`` — call AFTER :meth:`route` so the head's blocks exist.
        Pinned nodes are excluded from the LRU budget sweep and the
        cold-page reclaim until the session ends (:meth:`end_session`),
        its lease lapses, or an arena reset invalidates the tree. Each
        turn re-pins the (longer) head and renews the idle lease;
        ``ttl_s`` optionally TIGHTENS the idle lease for this session
        (clamped to the configured ``session_idle_s`` — a client may ask
        for less retention, never more; once tightened it sticks for the
        session's lifetime). Returns the pinned token count.

        Budget overflow splits by session age: a NEW session the budget
        cannot hold raises :class:`SessionPinsExceeded` (nothing
        mutated — the HTTP layer sheds the turn 503 and the client
        retries after the lease horizon), while an EXISTING
        conversation whose head outgrew the budget keeps the pins it
        already holds, renews its lease, and serves (``pin_overflows``
        counts it) — a mid-conversation turn must never become
        permanently unservable over a retention optimization."""
        if self.faults is not None:
            try:
                self.faults.check("session_pin")
            except Exception as e:  # noqa: BLE001 — injected: fail OPEN
                with self._lock:
                    self.pin_faults += 1
                log.error("session pin failed open (turn serves "
                          "unpinned): %s", e)
                return 0
        try:
            row = [int(t) for t in tokens]
        except (TypeError, ValueError):
            return 0
        sid = str(session_id)
        cfg = self.server.model.cfg
        target = min(self._target_len(len(row)),
                     cfg.max_len - self.block)
        idle = self.session_idle_s
        if ttl_s is not None and float(ttl_s) > 0:
            idle = min(idle, float(ttl_s))
        now = time.monotonic()
        with self._lock:
            self._maybe_flush_stale_locked()
            self._expire_sessions_locked(now)
            path: list = []
            if target > 0:
                _, path = self._present_locked(row[:target])
            sess = self._sessions.get(sid)
            if sess is not None:
                # a tightened per-request lease sticks for the session's
                # lifetime (clients ask for LESS retention, never more)
                # — applied BEFORE any overflow early-return, so a
                # tightening sent while the budget is full still lands
                sess.idle = min(sess.idle, idle)
            held = set(id(n) for n in sess.nodes) if sess else set()
            fresh = [n for n in path
                     if n.pins == 0 and id(n) not in held]
            need = sum(n.nbytes for n in fresh)
            if self._pinned_bytes + need > self.pin_budget_bytes:
                if sess is None:
                    # a NEW session the budget cannot hold: the priced
                    # shed — new sessions queue behind lease turnover
                    self.pin_sheds += 1
                    raise SessionPinsExceeded(
                        self._pinned_bytes + need
                        - self.pin_budget_bytes,
                        self.pin_budget_bytes,
                        self._lease_horizon_locked(now))
                # an EXISTING conversation whose head outgrew the
                # budget: keep the pins it already holds and renew the
                # lease — the turn serves with partial (or stale-depth)
                # pinning rather than the session becoming permanently
                # unservable (a pin is retention, never admission)
                self.pin_overflows += 1
                sess.expires = now + sess.idle
                sess.turns += 1
                return len(sess.nodes) * self.block
            if sess is None:
                sess = _Session(deadline=now + self.session_ttl_s,
                                idle=idle)
                self._sessions[sid] = sess
            for n in path:
                if id(n) not in held:
                    n.pins += 1
                    if n.pins == 1:
                        self._pinned_bytes += n.nbytes
                        self._pinned_leaves += 1
            # a turn's prompt extends the previous head, so stale nodes
            # only exist when the client changed conversations under one
            # id — unpin them rather than leak the lease
            new_ids = set(id(n) for n in path)
            self._unpin_locked([n for n in sess.nodes
                                if id(n) not in new_ids])
            sess.nodes = path
            sess.expires = now + sess.idle
            sess.turns += 1
            return len(path) * self.block

    def touch_session(self, session_id: str) -> bool:
        """Renew a session's idle lease without re-walking its head
        (sub-block turns, degraded routing). Honors the session's own
        (possibly client-tightened) idle window. False = unknown or
        already lapsed."""
        now = time.monotonic()
        with self._lock:
            self._expire_sessions_locked(now)
            sess = self._sessions.get(str(session_id))
            if sess is None:
                return False
            sess.expires = now + sess.idle
            return True

    def end_session(self, session_id: str) -> dict:
        """Explicit close (``DELETE /v1/sessions/{id}``): release the
        session's pins now instead of waiting out the lease."""
        with self._lock:
            self._expire_sessions_locked(time.monotonic())
            sess = self._sessions.pop(str(session_id), None)
            if sess is None:
                return {"released": False, "pinned_leaves": 0}
            n = len(sess.nodes)
            self._unpin_locked(sess.nodes)
            return {"released": True, "pinned_leaves": n}

    def present_len(self, tokens) -> int:
        """Host-only: tokens of the whole-block head actually PRESENT
        (dense kv or live paged page) — the ``/v1/kv/probe`` surface the
        router's import-miss pull checks before trusting its ship-dedup
        cache."""
        try:
            row = [int(t) for t in tokens]
        except (TypeError, ValueError):
            return 0
        head = row[:(len(row) // self.block) * self.block]
        if not head:
            return 0
        with self._lock:
            self._maybe_flush_stale_locked()
            return self._present_locked(head)[0]

    def _pool_pin_gauges(self) -> dict:
        """batching.page_pool's view of session pins (paged mode): each
        pinned leaf holds exactly one arena page the reclaim sweep may
        not touch."""
        with self._lock:
            return {"pinned_pages": self._pinned_leaves,
                    "pinned_bytes": self._pinned_bytes,
                    "pin_budget_bytes": self.pin_budget_bytes,
                    "pin_sheds": self.pin_sheds}

    # -- KV export / import (disaggregated prefill/decode) --------------------

    def _present_locked(self, row: list) -> tuple[int, list]:
        """Longest prefix of a BLOCK-ALIGNED ``row`` whose blocks are
        all actually present (dense ``kv`` or paged ``page_id`` still
        live — ``_match_locked`` caps one block short for continuation
        routing; the ship surface needs the whole head). A SPILLED
        paged block (``off_key`` set, host bytes live) counts as
        present: probe and export both serve it, so a failover re-ship
        includes a partially-offloaded row's whole history. Returns
        ``(present token count, path nodes)``."""
        node, m, path = self._root, 0, []
        while m < len(row):
            child = node.children.get(tuple(row[m:m + self.block]))
            if child is None or ((child.page_id is None
                                  and child.off_key is None)
                                 if self.pool is not None
                                 else child.kv is None):
                break
            child.last_used = next(self._clock)
            path.append(child)
            node = child
            m += self.block
        return m, path

    def _leaf_template(self) -> dict:
        """name -> (shape, np dtype) of one block slice in THIS server's
        store layout — what an import frame must match exactly.
        ``np_dtype`` resolves the ml_dtypes extended set (bfloat16), so
        a bf16 bundle's template round-trips like its wire frames.
        Computed once (it is a constant of the server config): the
        import path must not pay device allocations per frame for
        static shape metadata."""
        tmpl = getattr(self, "_leaf_tmpl", None)
        if tmpl is None:
            from lambdipy_tpu.models.llama import _empty_cache_entry
            from lambdipy_tpu.runtime.kvwire import np_dtype

            entry = _empty_cache_entry(self.server.model.cfg, 1,
                                       self.block)
            tmpl = {name: (tuple(int(d) for d in val.shape),
                           np_dtype(val.dtype.name))
                    for name, val in entry.items()}
            self._leaf_tmpl = tmpl
        return tmpl

    def export_blocks(self, tokens):
        """Serve a KV-export: the whole-block head of ``tokens`` as
        ``(head, blocks)`` where ``blocks`` is numpy block slices (one
        list entry per block, per-layer leaf dicts — the wire shape of
        runtime/kvwire.py). Missing blocks PREFILL here, exactly like a
        cold route — on a prefill-class replica this call IS the
        request's prefill phase. Returns None when the prompt has no
        whole block. A block the tree cannot hold (arena/budget
        pressure) truncates the export to what is present — the decode
        side then prefills the tail locally, correct either way."""
        import numpy as np

        row = [int(t) for t in tokens]
        cfg = self.server.model.cfg
        bk = self.block
        m = min((len(row) // bk) * bk, cfg.max_len - bk)
        if m <= 0:
            return None
        head = row[:m]
        pids: list = []
        offs: list = []
        kvs: list = []
        for attempt in range(2):
            with self._lock:
                self._maybe_flush_stale_locked()
                present, path = self._present_locked(head)
                if present >= m or attempt:
                    if present <= 0:
                        return None
                    if self.pool is not None:
                        # pin under the validating lock: a concurrent
                        # LRU release-and-reuse must not swap page
                        # content between the walk and the host read.
                        # Spilled blocks (page_id None) ride their
                        # off_key instead — host bytes need no pin.
                        pids = [n.page_id for n in path]
                        offs = [n.off_key for n in path]
                        self.pool.retain(
                            [p for p in pids if p is not None])
                    else:
                        # python refs keep the slices alive even if the
                        # budget sweep drops the nodes meanwhile
                        kvs = [n.kv for n in path]
                    head = head[:present]
                    break
            # prefill the missing blocks through the normal walk (one
            # retry: a racer eviction mid-walk exports the shorter head)
            self._extend(head, m)
        if self.pool is not None:
            from lambdipy_tpu.models.llama import arena_page_slices

            try:
                with self.pool.arena_lock:
                    arena = self.pool.ensure_arena()
                fetched = self._fetch_offloaded(
                    [k for p, k in zip(pids, offs)
                     if p is None and k is not None])
                blocks = []
                for pid, key in zip(pids, offs):
                    if pid is not None:
                        blocks.append(arena_page_slices(
                            arena, pid, self.pool.page))
                    elif key in fetched:
                        blocks.append(fetched[key])
                    else:
                        # a racer re-onlined-and-dropped or the entry
                        # died: truncate at the first unreadable block —
                        # the decode side prefills the tail locally
                        break
                head = head[:len(blocks) * bk]
            finally:
                self.pool.release([p for p in pids if p is not None])
        else:
            blocks = [[{name: np.asarray(val)
                        for name, val in entry.items()}
                       for entry in kv] for kv in kvs]
        return head, blocks

    def _fetch_offloaded(self, keys: list) -> dict:
        """Read-only batched fetch of spilled blocks for the export
        surfaces (entries stay offloaded — an export must not churn
        residency). Returns ``{key: numpy block}``; failures return
        what could not be read as ABSENT, and the caller truncates."""
        if not keys or self.offload is None:
            return {}
        try:
            return dict(zip(keys, self.offload.fetch_many(keys)))
        except Exception as e:  # noqa: BLE001 — export truncates, never fails
            log.error("offloaded-block fetch failed during export "
                      "(truncating): %s", e)
            return {}

    def import_blocks(self, tokens, blocks) -> dict:
        """Register shipped whole-block KV under ``tokens`` — a ship
        arrival is just a radix insert. Dense mode attaches the slices
        as tree nodes; paged mode writes each new block into its own
        arena page (``strict`` alloc: :class:`PagesExhausted` propagates
        as priced backpressure for the router's fallback-to-mixed
        path). Validates the frame against this server's store layout
        before any device work — a garbage frame raises ``ValueError``
        and touches nothing. Idempotent: blocks already present count
        as ``present`` and are left alone."""
        import jax.numpy as jnp
        import numpy as np

        row = [int(t) for t in tokens]
        bk = self.block
        self._validate_import_head(row, len(blocks))
        for blk in blocks:
            self._validate_import_block(blk)
        with self._lock:
            self._maybe_flush_stale_locked()
            present, _ = self._present_locked(row)
        mode = "paged" if self.pool is not None else "dense"
        new = blocks[present // bk:]
        if not new:
            return {"present": len(blocks), "inserted": 0, "mode": mode}
        jblocks = [[{name: jnp.asarray(np.asarray(val))
                     for name, val in entry.items()}
                    for entry in blk] for blk in new]
        if self.pool is not None:
            inserted = self._insert_paged(row, present, jblocks,
                                          strict=True)
        else:
            inserted = self._insert(row, present, jblocks)
        return {"present": present // bk, "inserted": inserted,
                "mode": mode}

    def _validate_import_head(self, row: list, n_blocks: int) -> None:
        """Import geometry checks shared by the monolithic and chunked
        paths: whole-block coverage and room left to decode."""
        bk = self.block
        cfg = self.server.model.cfg
        if not row or len(row) % bk or len(row) // bk != int(n_blocks):
            raise ValueError(
                f"import tokens ({len(row)}) must cover exactly "
                f"{n_blocks} x {bk}-token blocks")
        if len(row) > cfg.max_len - bk:
            raise ValueError(
                f"shipped prefix of {len(row)} tokens leaves no room "
                f"to decode in a {cfg.max_len}-token window")

    def _validate_import_block(self, blk) -> None:
        """One block's layer/leaf layout vs this server's store
        template — the per-chunk half of import validation."""
        import numpy as np

        cfg = self.server.model.cfg
        template = self._leaf_template()
        if len(blk) != cfg.layers:
            raise ValueError(
                f"frame has {len(blk)} layers, server has "
                f"{cfg.layers}")
        for entry in blk:
            if set(entry) != set(template):
                raise ValueError(
                    f"frame leaves {sorted(entry)} do not match "
                    f"store layout {sorted(template)}")
            for name, val in entry.items():
                shape, dt = template[name]
                arr = np.asarray(val)
                if tuple(arr.shape) != shape or arr.dtype != dt:
                    raise ValueError(
                        f"leaf {name!r} is {arr.dtype}{arr.shape}, "
                        f"server stores {dt}{shape}")

    def import_begin(self, tokens) -> "KvStreamImport":
        """Open a CHUNKED import (the pipelined ship's receiving end):
        validates the stream's head geometry now, hands back a
        :class:`KvStreamImport` that stages each arriving chunk — under
        ``--kv-paged`` the whole ship's pages are reserved up front
        (:class:`PagesExhausted` propagates immediately as priced
        backpressure, before any wire time is sunk) and each chunk's
        device write runs as it arrives, overlapping the rest of the
        transfer. NOTHING touches the radix tree until
        :meth:`KvStreamImport.commit`; an abort (truncated stream,
        garbage chunk, dead connection) releases every staged page and
        leaves the tree exactly as it was."""
        return KvStreamImport(self, tokens)

    def export_stream(self, tokens):
        """Incremental export twin of :meth:`export_blocks`: returns
        ``(head, generator)`` — the generator yields GROUPS of numpy
        block slices (one group per present-prefix run or cold-walk
        chunk) as soon as each is available, so the HTTP layer can
        flush a wire chunk while the next prefill chunk is still on the
        device. Unlike the monolithic export, the head is FIXED up
        front (the stream header has already been promised to the
        wire); a mid-walk failure truncates the stream — which the
        receiver detects by construction — instead of shrinking it.
        Returns None when the prompt has no whole block."""
        row = [int(t) for t in tokens]
        cfg = self.server.model.cfg
        bk = self.block
        m = min((len(row) // bk) * bk, cfg.max_len - bk)
        if m <= 0:
            return None
        head = row[:m]
        return head, self._export_stream_gen(head)

    def _export_stream_gen(self, head: list):
        group = max(1, self.walk_chunk // self.block)
        key = self.server._prefix_key(head)
        target = len(head)
        while True:
            owner, waiter, pinned, offs, kvs = False, None, [], [], []
            with self._lock:
                self._maybe_flush_stale_locked()
                present, path = self._present_locked(head)
                if present < target and self.pool is not None:
                    # the cold-walk tail GATHERS the present prefix back
                    # into a contiguous cache — that read needs RESIDENT
                    # pages, so clamp the reusable prefix at the first
                    # spilled block (the walk re-prefills from there:
                    # correct, just less reuse)
                    res = 0
                    for n in path:
                        if n.page_id is None:
                            break
                        res += self.block
                    present, path = res, path[:res // self.block]
                if present < target:
                    waiter = self._inflight.get(key)
                    if waiter is None:
                        self._inflight[key] = threading.Event()
                        owner = True
                if present >= target or owner:
                    if self.pool is not None:
                        # pin under the validating lock (the export_blocks
                        # rule): an LRU release-and-reuse must not swap
                        # page content before the host read; spilled
                        # blocks ride their off_key, no pin needed
                        pinned = [n.page_id for n in path]
                        offs = [n.off_key for n in path]
                        self.pool.retain(
                            [p for p in pinned if p is not None])
                    else:
                        kvs = [n.kv for n in path]
            if present >= target:
                try:
                    yield from self._read_block_groups(pinned, kvs, group,
                                                       offs)
                finally:
                    if pinned:
                        self.pool.release(
                            [p for p in pinned if p is not None])
                return
            if not owner:
                # another thread owns the walk for this very prefix:
                # wait for it, then serve from the (now present) tree
                if not waiter.wait(timeout=300.0):
                    raise RuntimeError(
                        f"prefix walk for key {key[:8]}... owned by "
                        "another thread did not complete within 300s")
                continue
            try:
                yield from self._read_block_groups(pinned, kvs, group,
                                                   offs)
                yield from self._walk_stream(head, present, pinned, kvs)
            finally:
                if pinned:
                    self.pool.release(
                        [p for p in pinned if p is not None])
                with self._lock:
                    event = self._inflight.pop(key, None)
                if event is not None:
                    event.set()
            return

    def _read_block_groups(self, pinned: list, kvs: list, group: int,
                           offs: list | None = None):
        """Yield the already-present prefix as numpy block groups —
        paged reads ride the held refs in ``pinned`` (a None pin is a
        SPILLED block, read from the offload arena via the matching
        ``offs`` key — one batched fetch per group), dense reads the
        python refs in ``kvs``. An unreadable spilled block truncates
        the stream, which the receiver detects by construction."""
        import numpy as np

        if self.pool is not None:
            if not pinned:
                return
            from lambdipy_tpu.models.llama import arena_page_slices

            with self.pool.arena_lock:
                arena = self.pool.ensure_arena()
            offs = offs if offs else [None] * len(pinned)
            for i in range(0, len(pinned), group):
                g_pids = pinned[i:i + group]
                g_offs = offs[i:i + group]
                fetched = self._fetch_offloaded(
                    [k for p, k in zip(g_pids, g_offs)
                     if p is None and k is not None])
                out = []
                for pid, okey in zip(g_pids, g_offs):
                    if pid is not None:
                        out.append(arena_page_slices(
                            arena, pid, self.pool.page))
                    elif okey in fetched:
                        out.append(fetched[okey])
                    else:
                        if out:
                            yield out
                        return
                yield out
        else:
            for i in range(0, len(kvs), group):
                yield [[{name: np.asarray(val)
                         for name, val in entry.items()}
                        for entry in kv] for kv in kvs[i:i + group]]

    def _walk_stream(self, row: list, matched: int, pinned: list,
                     kvs: list):
        """The cold-walk tail of a streamed export: mirrors
        :meth:`_walk` chunk for chunk, but yields each chunk's block
        slices (as numpy, wire-ready) the moment the chunk program
        returns — and inserts them into the tree best-effort along the
        way (the export IS the prefill, exactly like the monolithic
        path; a failed insert caches less, it never fails the ship)."""
        import jax.numpy as jnp
        import numpy as np

        from lambdipy_tpu.models.llama import (
            concat_cache_blocks,
            copy_cache,
            slice_cache_blocks,
        )

        server = self.server
        cfg = server.model.cfg
        bk = self.block
        target = len(row)

        def emit(cache, lo: int, hi: int):
            jb = [slice_cache_blocks(cache, p, bk)
                  for p in range(lo, hi, bk)]
            try:
                if self.pool is not None:
                    self._insert_paged(row, lo, jb)
                else:
                    self._insert(row, lo, jb)
            except Exception as e:  # noqa: BLE001 — cache less, ship on
                log.error("streamed export insert failed (caching "
                          "less): %s", e)
            return [[{name: np.asarray(val)
                      for name, val in entry.items()}
                     for entry in blk] for blk in jb]

        sp = self._sp_factor()
        rk = self.walk_chunk * sp
        t_walk = time.monotonic()
        n_rounds = n_chunks = 0
        with server._mesh_ctx():
            if matched == 0 and sp >= 2 and target >= rk \
                    and rk <= cfg.max_len:
                # sharded export: the export IS the prefill, and one
                # round ships sp walk-chunks of KV per occupancy slot
                pf = server._sp_first_fn(rk, cfg.max_len, sp)
                prompt_op, _ = server._pad_rows([row[:rk]], [rk], 1, rk)
                self._walk_fault()
                cache = pf(server.params, prompt_op, jnp.int32(rk))
                pos = rk
                n_rounds += 1
                n_chunks += sp
                if self.prefill_stats is not None:
                    self.prefill_stats.record_round(
                        sp, sp, ring_hops=cfg.layers * sp)
                yield emit(cache, 0, rk)
            elif matched == 0:
                fw = self.walk_chunk if target >= self.walk_chunk else bk
                pf = server._prefix_first_fn(fw, cfg.max_len)
                prompt_op, _ = server._pad_rows([row[:fw]], [fw], 1, fw)
                self._walk_fault()
                cache = pf(server.params, prompt_op, jnp.int32(fw))
                pos = fw
                n_rounds += 1
                n_chunks += 1
                if self.prefill_stats is not None:
                    self.prefill_stats.record_round(1, 1)
                yield emit(cache, 0, fw)
            elif self.pool is not None:
                gather = server._paged_gather_fn(
                    self.pool.n_pages, self.pool.page, cfg.max_len)
                tbl = np.zeros((1, cfg.max_len // bk), np.int32)
                tbl[0, :len(pinned)] = pinned
                with self.pool.arena_lock:
                    arena = self.pool.ensure_arena()
                    cache = gather(arena, jnp.asarray(tbl),
                                   jnp.int32(matched))
                pos = matched
            else:
                entry = server.get_prefix(
                    server._prefix_key(row[:matched]))
                if entry is not None:
                    # the ext loop DONATES its cache argument; the LRU's
                    # copy must stay live for concurrent readers
                    cache = copy_cache(entry[0])
                else:
                    cache = concat_cache_blocks(cfg, kvs, cfg.max_len)
                    self.stats_counters.record_assembly(
                        _cache_bytes(cache))
                pos = matched
            wk = self.walk_chunk
            ext = server._prefix_ext_fn(bk)
            ext_wide = server._prefix_ext_fn(wk) if wk > bk else None
            ext_round = (server._sp_ext_fn(rk, sp)
                         if sp >= 2 and rk <= cfg.max_len else None)
            while pos < target:
                self._walk_fault()
                if (ext_round is not None and target - pos >= rk
                        and pos + rk <= cfg.max_len):
                    chunk_op, _ = server._pad_rows(
                        [row[pos:pos + rk]], [rk], 1, rk)
                    cache = ext_round(server.params, cache, chunk_op,
                                      jnp.int32(rk))
                    n_rounds += 1
                    n_chunks += sp
                    if self.prefill_stats is not None:
                        self.prefill_stats.record_round(sp, sp)
                    yield emit(cache, pos, pos + rk)
                    pos += rk
                elif (ext_wide is not None and target - pos >= wk
                        and pos + wk <= cfg.max_len):
                    chunk_op, _ = server._pad_rows(
                        [row[pos:pos + wk]], [wk], 1, wk)
                    cache = ext_wide(server.params, cache, chunk_op,
                                     jnp.int32(wk))
                    n_rounds += 1
                    n_chunks += 1
                    if self.prefill_stats is not None:
                        self.prefill_stats.record_round(1, 1)
                    yield emit(cache, pos, pos + wk)
                    pos += wk
                else:
                    chunk_op, _ = server._pad_rows(
                        [row[pos:pos + bk]], [bk], 1, bk)
                    cache = ext(server.params, cache, chunk_op,
                                jnp.int32(bk))
                    n_rounds += 1
                    n_chunks += 1
                    if self.prefill_stats is not None:
                        self.prefill_stats.record_round(1, 1)
                    yield emit(cache, pos, pos + bk)
                    pos += bk
            if self.prefill_stats is not None:
                self.prefill_stats.record_walk(
                    time.monotonic() - t_walk, n_chunks, n_rounds)
            if self.pool is None:
                # register the full cache like _walk does, so the next
                # local hit on this prefix skips reassembly
                server.register_prefix(server._prefix_key(row), cache,
                                       target)

    # -- assembly / extension ------------------------------------------------

    def _ensure_assembled(self, row: list, path: list) -> None:
        """Make sure the server's prefix LRU holds the full-window cache
        for ``row[:len(path)*block]``, assembling it from the tree's
        block slices when it was evicted."""
        from lambdipy_tpu.models.llama import concat_cache_blocks

        m = len(path) * self.block
        key = self.server._prefix_key(row[:m])
        if self.server.get_prefix(key) is not None:
            return
        cfg = self.server.model.cfg
        with self.server._mesh_ctx():
            cache = concat_cache_blocks(cfg, [n.kv for n in path],
                                        cfg.max_len)
        self.stats_counters.record_assembly(_cache_bytes(cache))
        self.server.register_prefix(key, cache, m)

    def _extend(self, row: list, target: int) -> None:
        """Prefill ``row`` up to ``target`` tokens through the server's
        block-width chunk programs, inserting each new block into the
        tree and registering the final cache as the target's prefix
        entry. Re-matches after any inflight wait — the owner usually
        inserted the very blocks this thread wanted."""
        key = self.server._prefix_key(row[:target])
        while True:
            owner, waiter, pinned = False, None, []
            with self._lock:
                matched, path = self._match_locked(row)
                if matched < target and self.pool is not None:
                    # PIN the matched pages for the walk, under the same
                    # lock that validated them: a concurrent LRU sweep
                    # could otherwise release-and-reuse a matched page
                    # between here and the walk's arena snapshot, and
                    # the gather would silently read another row's KV.
                    # An already-evicted node (page_id None) truncates
                    # the usable prefix — the walk just re-prefills it.
                    # Only the ids in ``pinned`` were retained; releasing
                    # anything else would strip the STORE's own refs
                    # (the double-free the serve drive caught).
                    keep = []
                    for n in path:
                        if n.page_id is None:
                            break
                        keep.append(n)
                    path = keep
                    matched = len(keep) * self.block
                    pinned = [n.page_id for n in keep]
                    self.pool.retain(pinned)
                if matched < target:
                    waiter = self._inflight.get(key)
                    if waiter is None:
                        self._inflight[key] = threading.Event()
                        owner = True
            if matched >= target:
                # a full match never pins (the pin block is gated on
                # matched < target) — nothing to drop here
                if self.pool is None:
                    self._ensure_assembled(row,
                                           path[:target // self.block])
                return
            if owner:
                try:
                    self._walk(row, matched, target, path)
                finally:
                    if pinned:
                        self.pool.release(pinned)
                    with self._lock:
                        event = self._inflight.pop(key, None)
                    if event is not None:
                        event.set()
                return
            if pinned:
                # not the owner: drop the pins before waiting
                self.pool.release(pinned)
            if not waiter.wait(timeout=300.0):
                raise RuntimeError(
                    f"prefix walk for key {key[:8]}... owned by another "
                    "thread did not complete within 300s")

    def _walk_fault(self) -> None:
        """``prefix_walk`` site: once per cold-walk chunk dispatch — and
        in sp-prefill mode once per ROUND: both modes price identical
        modeled per-chunk device time through this site, the sharded
        walk just stacks sp chunks onto one critical-path slot."""
        if self.faults is not None:
            self.faults.check("prefix_walk")

    def _sp_factor(self) -> int:
        """Usable whole-prompt sp-prefill factor for cold walks (0 =
        chunked; stand-down counted in resolve_sp_prefill)."""
        from lambdipy_tpu.models.llama import resolve_sp_prefill

        return resolve_sp_prefill(self.prefill_mode,
                                  getattr(self.server, "mesh", None))

    def _walk(self, row: list, matched: int, target: int,
              path: list) -> None:
        import jax.numpy as jnp

        from lambdipy_tpu.models.llama import (
            concat_cache_blocks,
            copy_cache,
            slice_cache_blocks,
        )

        server = self.server
        cfg = server.model.cfg
        bk = self.block
        sp = self._sp_factor()
        rk = self.walk_chunk * sp  # sp-round width (0 when chunked)
        t_walk = time.monotonic()
        n_rounds = n_chunks = 0
        with server._mesh_ctx():
            if matched == 0 and sp >= 2 and target >= rk \
                    and rk <= cfg.max_len:
                # whole-prompt sp first round: ONE sharded program covers
                # sp walk-chunks — for prompts that fit a round, the
                # entire cold prefill is this single dispatch
                pf = server._sp_first_fn(rk, cfg.max_len, sp)
                prompt_op, _ = server._pad_rows([row[:rk]], [rk], 1, rk)
                self._walk_fault()
                cache = pf(server.params, prompt_op, jnp.int32(rk))
                pos = rk
                n_rounds += 1
                n_chunks += sp
                if self.prefill_stats is not None:
                    self.prefill_stats.record_round(
                        sp, sp, ring_hops=cfg.layers * sp)
            elif matched == 0:
                # first chunk rides the wide family too when it fits
                fw = self.walk_chunk if target >= self.walk_chunk else bk
                pf = server._prefix_first_fn(fw, cfg.max_len)
                prompt_op, _ = server._pad_rows([row[:fw]], [fw], 1, fw)
                self._walk_fault()
                cache = pf(server.params, prompt_op, jnp.int32(fw))
                pos = fw
                n_rounds += 1
                n_chunks += 1
                if self.prefill_stats is not None:
                    self.prefill_stats.record_round(1, 1)
            elif self.pool is not None:
                # paged: the matched blocks live in arena pages — gather
                # them into the walk's contiguous working cache (a
                # transient buffer for the ext programs, never
                # registered; the hit path itself stays zero-copy)
                import numpy as np

                gather = server._paged_gather_fn(
                    self.pool.n_pages, self.pool.page, cfg.max_len)
                tbl = np.zeros((1, cfg.max_len // bk), np.int32)
                tbl[0, :len(path)] = [n.page_id for n in path]
                with self.pool.arena_lock:
                    arena = self.pool.ensure_arena()
                    cache = gather(arena, jnp.asarray(tbl),
                                   jnp.int32(matched))
                pos = matched
            else:
                key_m = server._prefix_key(row[:matched])
                entry = server.get_prefix(key_m)
                if entry is not None:
                    # the ext loop DONATES its cache argument; the LRU's
                    # copy must stay live for concurrent readers
                    cache = copy_cache(entry[0])
                else:
                    cache = concat_cache_blocks(
                        cfg, [n.kv for n in path], cfg.max_len)
                    self.stats_counters.record_assembly(
                        _cache_bytes(cache))
                pos = matched
            # full-width wide chunks where they fit, block-width tail.
            # A wide write must stay inside max_len: the ext program
            # writes its whole padded window at the cache index, and
            # dynamic_update_slice would CLAMP a crossing window back
            # onto real prefix KV (the documented chunked-prefill trap)
            wk = self.walk_chunk
            ext = server._prefix_ext_fn(bk)
            ext_wide = server._prefix_ext_fn(wk) if wk > bk else None
            ext_round = (server._sp_ext_fn(rk, sp)
                         if sp >= 2 and rk <= cfg.max_len else None)
            while pos < target:
                self._walk_fault()
                if (ext_round is not None and target - pos >= rk
                        and pos + rk <= cfg.max_len):
                    # one sharded ROUND = sp serial chunks, one
                    # critical-path slot (one fault fire above)
                    chunk_op, _ = server._pad_rows(
                        [row[pos:pos + rk]], [rk], 1, rk)
                    cache = ext_round(server.params, cache, chunk_op,
                                      jnp.int32(rk))
                    pos += rk
                    n_rounds += 1
                    n_chunks += sp
                    if self.prefill_stats is not None:
                        self.prefill_stats.record_round(sp, sp)
                elif (ext_wide is not None and target - pos >= wk
                        and pos + wk <= cfg.max_len):
                    chunk_op, _ = server._pad_rows(
                        [row[pos:pos + wk]], [wk], 1, wk)
                    cache = ext_wide(server.params, cache, chunk_op,
                                     jnp.int32(wk))
                    pos += wk
                    n_rounds += 1
                    n_chunks += 1
                    if self.prefill_stats is not None:
                        self.prefill_stats.record_round(1, 1)
                else:
                    chunk_op, _ = server._pad_rows(
                        [row[pos:pos + bk]], [bk], 1, bk)
                    cache = ext(server.params, cache, chunk_op,
                                jnp.int32(bk))
                    pos += bk
                    n_rounds += 1
                    n_chunks += 1
                    if self.prefill_stats is not None:
                        self.prefill_stats.record_round(1, 1)
            new_blocks = [slice_cache_blocks(cache, p, bk)
                          for p in range(matched, target, bk)]
        if self.prefill_stats is not None:
            self.prefill_stats.record_walk(
                time.monotonic() - t_walk, n_chunks, n_rounds)
        if self.pool is not None:
            # paged insertion: each fresh block gets its own arena page
            # (store-owned ref); the full-window walk cache is a
            # TRANSIENT buffer — nothing registers, so the store never
            # holds an assembled duplicate
            self._insert_paged(row, matched, new_blocks)
            return
        server.register_prefix(server._prefix_key(row[:target]), cache,
                               target)
        self._insert(row, matched, new_blocks)

    def _insert(self, row: list, start: int, new_blocks: list) -> int:
        """Attach the freshly computed block slices under the matched
        path (idempotent against racers), then sweep the budget.
        Returns blocks actually attached (a racer may have won some)."""
        attached = 0
        with self._lock:
            # re-walk from the root: a racer may have restructured the
            # path (or inserted some of these very blocks) meanwhile
            node, m = self._root, 0
            while m < start + len(new_blocks) * self.block:
                tok_key = tuple(row[m:m + self.block])
                child = node.children.get(tok_key)
                if child is None:
                    idx = (m - start) // self.block
                    if m < start or idx >= len(new_blocks):
                        # a racer evicted part of our base path: give up
                        # the insert — the KV is already serving
                        break
                    kv = new_blocks[idx]
                    child = _Node(node, tok_key, kv, _slices_bytes(kv))
                    node.children[tok_key] = child
                    self.stats_counters.record_insert(1, child.nbytes)
                    attached += 1
                child.last_used = next(self._clock)
                node = child
                m += self.block
            self._evict_locked()
        return attached

    def _insert_paged(self, row: list, start: int, new_blocks: list,
                      *, strict: bool = False) -> int:
        """Paged-mode insertion: write each fresh block slice into its
        own arena page (``_page_write_fn``) and attach page-carrying
        nodes under the matched path. The page writes — including the
        write program's first-use compile — are STAGED before taking
        the store lock, so concurrent route()/match_len()/
        acquire_pages() callers never stall behind a cold insert's
        device work. Out-of-pages asks the pool's reclaim hook (this
        store's cold unshared leaves) via ``alloc``; a genuinely full
        arena just caches fewer blocks — fail open, the request already
        has its KV in the walk cache. ``strict`` (the KV-IMPORT path)
        instead allocates every page up front and PROPAGATES
        :class:`PagesExhausted`: a ship the arena cannot hold must
        surface as priced backpressure to the router, not silently
        cache nothing. Returns blocks actually attached."""
        import jax.numpy as jnp

        from lambdipy_tpu.runtime.pagepool import PagesExhausted

        server, pool, bk = self.server, self.pool, self.block
        write = server._page_write_fn(pool.n_pages, pool.page)
        gen = pool.arena_generation
        staged: list[int] = []
        pre: list[int] = []
        if strict:
            # one all-or-nothing alloc: record_shed=False keeps a ship
            # refusal out of the pool's admission-shed counter (the
            # router's fallback counter owns this failure mode)
            pre = pool.alloc(len(new_blocks), tokens=len(new_blocks) * bk,
                             record_shed=False)
        try:
            for i, blk in enumerate(new_blocks):
                if strict:
                    pid = pre[i]
                else:
                    try:
                        pid = pool.alloc(1, tokens=bk,
                                         record_shed=False)[0]
                    except PagesExhausted:
                        break  # cache less; `sheds` meters admissions
                    except Exception as e:  # noqa: BLE001 — injected
                        log.error("prefix page alloc failed (caching "
                                  "less): %s", e)
                        break
                with pool.arena_lock:
                    arena = pool.ensure_arena()
                    pool.arena = write(arena, jnp.int32(pid), blk)
                staged.append(pid)
        except Exception:
            # a failed page write must not leak its un-staged pages
            pool.release([p for p in pre if p not in staged])
            pool.release(staged)
            raise
        return self._attach_paged(row, start, staged, gen)

    def _attach_paged(self, row: list, start: int, staged: list,
                      gen: int) -> int:
        """Attach already-staged (allocated + written) arena pages as
        tree nodes under the matched path — the commit half of
        :meth:`_insert_paged`, shared with the chunked KV-import path,
        whose pages stage one wire chunk at a time. Ownership of every
        page in ``staged`` transfers HERE: each either becomes a
        store-owned node or is released (racer duplicates, a vanished
        base path, an arena reset since ``gen``)."""
        pool, bk = self.pool, self.block
        attached: set[int] = set()
        with self._lock:
            self._maybe_flush_stale_locked()
            if pool.arena_generation != gen:
                # the arena reset mid-stage: the staged content is gone
                pool.release(staged)
                return 0
            node, m = self._root, 0
            while m < start + len(staged) * bk:
                tok_key = tuple(row[m:m + bk])
                child = node.children.get(tok_key)
                if child is None:
                    idx = (m - start) // bk
                    if m < start or idx >= len(staged):
                        # a racer evicted part of our base path: give up
                        # the insert — the KV is already serving
                        break
                    child = _Node(node, tok_key, None, pool.page_bytes,
                                  page_id=staged[idx])
                    node.children[tok_key] = child
                    self.stats_counters.record_insert(1, child.nbytes)
                    attached.add(idx)
                child.last_used = next(self._clock)
                node = child
                m += bk
            self._evict_locked()
        leftovers = [pid for i, pid in enumerate(staged)
                     if i not in attached]
        if leftovers:
            # a racer already held those nodes (its pages serve), or the
            # base path vanished: our staged duplicates return
            pool.release(leftovers)
        return len(attached)

    def reclaim_pages(self, n: int) -> int:
        """Pool out-of-pages hook: release up to ``n`` cold UNSHARED
        leaf pages so live admissions never shed behind a cache — a
        request's KV outranks a cached prefix nobody is using right
        now. Returns pages actually freed (shared/hot pages stay)."""
        with self._lock:
            return self._sweep_unshared_locked(n)

    def _sweep_unshared_locked(self, n: int) -> int:
        """Release up to ``n`` LRU leaves whose page only the store
        holds, in ONE tree pass with the pool refcounts snapshotted
        once — a per-page rescan (O(tree) each, a pool-lock round-trip
        per leaf) turned page pressure into admission-latency spikes.
        A parent whose whole chain became evictable frees on the next
        sweep (pressure recurs; convergence does not need cascading
        here).

        With a host offload tier attached the victim's page SPILLS —
        its kvwire bytes move to host RAM and the node stays in the
        tree as a ghost (``off_key`` set, page released), so a later
        hit re-onlines it instead of re-prefilling. A spill refusal
        (offload budget full) falls back to today's drop. LRU order
        (``last_used``) is the temperature signal: the coldest pages
        leave the arena first."""
        refs = self.pool.snapshot_refs()
        nodes = list(self._iter_nodes())
        # pinned leaves are invisible to the sweep: an open session's
        # conversation KV must survive cache pressure — that retention
        # is bounded by the PIN budget, not the LRU budget
        if self.offload is not None:
            # "leaf" relaxes to "no RESIDENT descendant": a spilled
            # child is a ghost (host bytes, no page) and must not
            # shield its parent's cold page from the sweep — that
            # would wedge reclaim behind the very pages spilling is
            # meant to free
            blocked: set[int] = set()
            for node in nodes:
                if node.page_id is not None:
                    p = node.parent
                    while p is not None and id(p) not in blocked:
                        blocked.add(id(p))
                        p = p.parent
            leaves = [node for node in nodes
                      if node.page_id is not None
                      and id(node) not in blocked and not node.pins
                      and refs.get(node.page_id, 0) == 1]
        else:
            leaves = [node for node in nodes
                      if not node.children and node.page_id is not None
                      and not node.pins
                      and refs.get(node.page_id, 0) == 1]
        leaves.sort(key=lambda node: node.last_used)
        victims = leaves[:max(0, int(n))]
        arena = None
        if self.offload is not None and victims:
            with self.pool.arena_lock:
                arena = self.pool.ensure_arena()
        freed = 0
        for victim in victims:
            spilled, key = False, None
            if arena is not None:
                from lambdipy_tpu.models.llama import arena_page_slices

                key = self._node_key(victim)
                try:
                    block = arena_page_slices(arena, victim.page_id,
                                              self.pool.page)
                    spilled = self.offload.spill(
                        key, victim.token_key, block)
                except Exception as e:  # noqa: BLE001 — drop instead
                    log.error("page spill failed (dropping page "
                              "instead): %s", e)
            if spilled:
                victim.off_key = key
                self.stats_counters.record_evict(1, victim.nbytes)
                self.pool.release([victim.page_id])
                victim.page_id = None
            else:
                # drop fallback: the whole subtree below the victim is
                # ghosts by construction (no resident descendant) and
                # becomes unreachable — prune it consistently
                self._prune_subtree_locked(victim)
            freed += 1
        return freed

    def _prune_subtree_locked(self, node: _Node) -> None:
        """Detach ``node`` and clean its WHOLE subtree: resident pages
        release (evict-counted), spilled entries drop, pin accounting
        settles. Nothing unreachable may keep a page, a host byte, or
        a counter."""
        if node.parent is not None:
            node.parent.children.pop(node.token_key, None)
        keys = []
        stack = [node]
        while stack:
            cur = stack.pop()
            if cur.page_id is not None:
                self.pool.release([cur.page_id])
                self.stats_counters.record_evict(1, cur.nbytes)
                cur.page_id = None
            if cur.off_key is not None:
                keys.append(cur.off_key)
                cur.off_key = None
            if cur.pins > 0:
                self._pinned_bytes -= cur.nbytes
                self._pinned_leaves -= 1
                cur.pins = 0
            stack.extend(cur.children.values())
            cur.children = {}
        if keys and self.offload is not None:
            try:
                self.offload.drop(keys)
            except Exception:  # noqa: BLE001 — cleanup must not fail a prune
                pass

    def _evict_locked(self) -> None:
        """LRU leaf eviction until the budget holds (leaves only: an
        interior node's KV is position-prefixed by its parents, so
        dropping it would orphan every descendant block). Paged mode is
        REFCOUNT-AWARE: a leaf whose page a live row still shares is
        skipped — it is hot by definition, and releasing it would only
        drop the store's ref without freeing a page; the sweep retries
        it once the sharing rows have retired."""
        if self.pool is not None:
            while True:
                over = self.stats_counters.report()["bytes"] \
                    - self.budget_bytes
                if over <= 0:
                    return
                need = -(-over // max(1, self.pool.page_bytes))
                if not self._sweep_unshared_locked(need):
                    return
        while self.stats_counters.report()["bytes"] > self.budget_bytes:
            leaves = [n for n in self._iter_nodes()
                      if not n.children and n.kv is not None
                      and not n.pins]
            if not leaves:
                return
            victim = min(leaves, key=lambda n: n.last_used)
            victim.parent.children.pop(victim.token_key, None)
            self.stats_counters.record_evict(1, victim.nbytes)
            victim.kv = None

    def _iter_nodes(self):
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            yield node
            stack.extend(node.children.values())

    # -- observability -------------------------------------------------------

    def check_invariants(self) -> dict:
        """Cheap host-only accounting sweep — the replica's
        ``/v1/debug/invariants`` surface and the chaos checker's quiesce
        probe. Recomputes pin and content accounting from the tree and
        cross-checks the live counters; paged mode additionally checks
        every cached node's page is still live in the pool (the store
        owns one ref per node). Returns ``{"ok", "violations", ...}``
        with gauges — never raises, so it is safe to poll mid-traffic."""
        violations: list[str] = []
        with self._lock:
            self._maybe_flush_stale_locked()
            self._expire_sessions_locked(time.monotonic())
            nodes = list(self._iter_nodes())
            pinned = [n for n in nodes if n.pins > 0]
            leaves, nbytes = len(pinned), sum(n.nbytes for n in pinned)
            if leaves != self._pinned_leaves:
                violations.append(
                    f"pinned_leaves counter {self._pinned_leaves} != "
                    f"{leaves} pinned nodes in the tree")
            if nbytes != self._pinned_bytes:
                violations.append(
                    f"pinned_bytes counter {self._pinned_bytes} != "
                    f"{nbytes} recomputed from pinned nodes")
            held: dict[int, int] = {}
            for sid, sess in self._sessions.items():
                for n in sess.nodes:
                    held[id(n)] = held.get(id(n), 0) + 1
            for n in nodes:
                if n.pins != held.get(id(n), 0):
                    violations.append(
                        f"node pins={n.pins} but {held.get(id(n), 0)} "
                        f"live session(s) hold it")
                    break  # one representative is enough detail
            content = [n for n in nodes
                       if (n.page_id is not None if self.pool is not None
                           else n.kv is not None)]
            content_bytes = sum(n.nbytes for n in content)
            rep = self.stats_counters.report()
            if len(content) != rep["blocks"]:
                violations.append(
                    f"blocks counter {rep['blocks']} != {len(content)} "
                    f"content nodes in the tree")
            if content_bytes != rep["bytes"]:
                violations.append(
                    f"bytes counter {rep['bytes']} != {content_bytes} "
                    f"recomputed from content nodes")
            if self.pool is not None:
                refs = self.pool.snapshot_refs()
                for n in content:
                    if refs.get(n.page_id, 0) < 1:
                        violations.append(
                            f"tree references page {n.page_id} with no "
                            f"live pool ref")
                        break
            ghosts = [n for n in nodes if n.off_key is not None]
            for n in ghosts:
                if n.page_id is not None:
                    violations.append(
                        f"node holds page {n.page_id} AND offload key "
                        f"{n.off_key!r} — spill/re-online must be "
                        f"exclusive")
                    break
            return {
                "ok": not violations,
                "violations": violations,
                "sessions_active": len(self._sessions),
                "pinned_leaves": leaves,
                "pinned_bytes": nbytes,
                "blocks": len(content),
                "bytes": content_bytes,
                "offloaded_blocks": len(ghosts),
                "paged": self.pool is not None,
            }

    def stats(self) -> dict:
        out = self.stats_counters.report()
        out["block"] = self.block
        out["budget_bytes"] = self.budget_bytes
        # session-pin surface: the scrape itself runs the lazy lease
        # sweep, so "pins return to zero after every session closes" is
        # observable without traffic
        with self._lock:
            self._maybe_flush_stale_locked()
            self._expire_sessions_locked(time.monotonic())
            out["sessions_active"] = len(self._sessions)
            out["pinned_leaves"] = self._pinned_leaves
            out["pinned_bytes"] = self._pinned_bytes
            out["pin_budget_bytes"] = self.pin_budget_bytes
            out["pin_sheds"] = self.pin_sheds
            out["pin_overflows"] = self.pin_overflows
            out["pin_expiries"] = self.pin_expiries
            out["pin_invalidations"] = self.pin_invalidations
            out["pin_faults"] = self.pin_faults
        if self.pool is not None:
            # paged mode: block bytes above are arena pages the store
            # holds a ref on; shares/refcounts live in the pool's own
            # batching.page_pool block
            out["paged"] = True
        # the assembled full-window caches live in the SERVER's
        # count-bounded prefix LRU (prefix_cache_max), OUTSIDE this
        # budget — surface their real footprint so an operator sizing
        # HBM sees both consumers, not just the tree
        try:
            with self.server._prefix_lock:
                entries = list(self.server._prefixes.values())
            out["assembled_entries"] = len(entries)
            out["assembled_bytes"] = sum(
                int(v.size) * v.dtype.itemsize
                for cache, _len in entries for entry in cache
                for v in entry.values() if hasattr(v, "dtype"))
        except Exception:  # noqa: BLE001 — stats must never break /metrics
            pass
        return out


class KvStreamImport:
    """One chunked KV import in flight (see
    :meth:`PrefixStore.import_begin`). Lifecycle::

        imp = store.import_begin(tokens)     # geometry + page reservation
        imp.add_blocks(blocks)               # per wire chunk: validate + stage
        res = imp.commit()                   # attach to the tree, atomically
        imp.abort()                          # any failure: release, touch nothing

    Staging is the device half (page writes / host->jnp conversion) and
    runs per chunk, overlapping the remaining wire transfer; the radix
    tree is only mutated at :meth:`commit`, so a truncated or garbage
    stream rolls back to exactly the pre-stream state — the router's
    ship-dedup LRU can never be told about blocks that half-arrived."""

    def __init__(self, store: PrefixStore, tokens):
        self.store = store
        self.row = [int(t) for t in tokens]
        bk = store.block
        self.n_blocks = len(self.row) // bk if self.row else 0
        store._validate_import_head(self.row, self.n_blocks)
        with store._lock:
            store._maybe_flush_stale_locked()
            present, _ = store._present_locked(self.row)
        self.present = present          # tokens already in the tree
        self.received = 0               # blocks fed so far (incl. present)
        self.closed = False
        self._jblocks: list = []        # dense staging
        self._pages: list[int] = []     # paged staging (pre-reserved)
        self._written = 0
        self._gen = 0
        self._write = None
        pool = store.pool
        if pool is not None:
            n_new = self.n_blocks - present // bk
            self._gen = pool.arena_generation
            self._write = store.server._page_write_fn(pool.n_pages,
                                                      pool.page)
            if n_new > 0:
                # reserve the WHOLE ship before any wire time is spent
                # on it: a full arena must surface as backpressure now
                # (PagesExhausted -> the priced 503), not as a half-
                # staged stream later. record_shed=False — the router's
                # fallback counter owns this failure mode.
                self._pages = pool.alloc(n_new, tokens=n_new * bk,
                                         record_shed=False)

    def add_blocks(self, blocks) -> None:
        """Stage one wire chunk's blocks (arriving strictly in block
        order — the stream decoder enforces it). Blocks the tree
        already held at begin are skipped; the rest stage into their
        reserved pages (paged) or convert for insertion (dense)."""
        import jax.numpy as jnp
        import numpy as np

        if self.closed:
            raise ValueError("KV stream import already closed")
        store, bk = self.store, self.store.block
        if self.received + len(blocks) > self.n_blocks:
            raise ValueError(
                f"KV stream overruns its header: {self.received} + "
                f"{len(blocks)} > {self.n_blocks} blocks")
        for blk in blocks:
            store._validate_import_block(blk)
            idx = self.received
            self.received += 1
            if idx * bk < self.present:
                continue  # already present at begin: idempotent skip
            jb = [{name: jnp.asarray(np.asarray(val))
                   for name, val in entry.items()} for entry in blk]
            pool = store.pool
            if pool is None:
                self._jblocks.append(jb)
                continue
            pid = self._pages[self._written]
            with pool.arena_lock:
                arena = pool.ensure_arena()
                pool.arena = self._write(arena, jnp.int32(pid), jb)
            self._written += 1

    @property
    def complete(self) -> bool:
        return self.received >= self.n_blocks

    def commit(self) -> dict:
        """Attach every staged block under the matched path — the same
        idempotent insert the monolithic import performs. Refuses (and
        rolls back) an incomplete stream: committing a half-arrived
        head would be exactly the silent partial insert the staged
        design exists to prevent."""
        store, bk = self.store, self.store.block
        if self.closed:
            raise ValueError("KV stream import already closed")
        if not self.complete:
            got = self.received
            self.abort()
            raise ValueError(
                f"truncated KV stream: {got} of {self.n_blocks} "
                f"block(s) arrived")
        self.closed = True
        mode = "paged" if store.pool is not None else "dense"
        try:
            if store.pool is not None:
                # ownership of the staged pages transfers to the attach
                # (store nodes or released as racer duplicates)
                inserted = store._attach_paged(self.row, self.present,
                                               self._pages, self._gen)
                self._pages = []
            elif self._jblocks:
                inserted = store._insert(self.row, self.present,
                                         self._jblocks)
            else:
                inserted = 0
        except Exception:
            self._release()
            raise
        return {"present": self.present // bk, "inserted": inserted,
                "mode": mode}

    def abort(self) -> None:
        """Release every staged page and forget the staging — the tree
        (and the pool's accounting) read as if the stream never
        started. Idempotent; safe after commit."""
        if self.closed:
            return
        self.closed = True
        self._release()

    def _release(self) -> None:
        pool = self.store.pool
        if pool is not None and self._pages:
            pool.release(self._pages)
        self._pages = []
        self._jblocks = []
