"""The long-context tier: serve 8-32x the compiled window through a
sliding block-table view + paged-KV host offload.

The compiled programs never grow: decode runs the ``models/llama.py
_lpaged_seg_fn`` family at the bundle's compiled ``window``, and the
block table maps a LOGICAL view of a far larger session — slot 0 of the
gathered window is logical token ``base``, the carry's cursor stays in
the LOCAL frame (cache writes, validity mask) while RoPE sees
``local + base``, the token's true logical position. When the cursor
reaches the view's edge the host slides the view forward by whole pages:
the evicted head pages spill to the :class:`~lambdipy_tpu.runtime
.offload.OffloadArena` (host RAM, kvwire bytes — the failover re-ship
and prefix-reuse read them back), their pool pages recycle into the
view's tail, and the device carry shifts frames with one exact int32
subtract. A 128k-token session runs over a 4k compiled window in a
FIXED page budget; with ``base = 0`` (any context that fits the window)
the programs compute bitwise what the plain paged path computes.

Attention is therefore windowed past the compiled width (each token
attends the most recent ``window``-ish logical positions — the page-
granular slide schedule is deterministic in the lengths alone), which is
the explicit contract of the tier: capacity beyond the window trades
global attention for a sliding window, never for shed.

Prefill is CHUNKED through the same view (``_lpaged_continue_fn``):
half-window chunks land at the cursor, the view sliding between chunks,
so TTFT grows linearly in prompt length instead of cliffing at the
window. With ``long_prefill=True`` and a ring-attention bundle
(``attn_backend="ring"`` over an ``sp`` mesh axis, ``parallel/ring.py``)
each chunk's attention is additionally sequence-sharded across the mesh
— the opt-in long-prefill mode; requesting it without a ring mesh stands
down counted (``note_standdown``), never silently.

``resident_cap`` is the pressure-yield mode: between segments the
runner spills the view's coldest already-full pages past the cap
(:class:`~lambdipy_tpu.runtime.offload.PageTemperature` picks victims)
and re-onlines them through the :class:`~lambdipy_tpu.runtime.offload
.Prefetcher` state machine keyed off the decode cursor — the prefetch
fetch+write is issued right after the (async) segment dispatch, so the
host frame decode hides under device compute and the next dispatch's
demand check finds the pages resident. A demand miss is a TIMED stall
(``kv.offload.stall_s``); a FAILED re-online (``offload_stall`` fault,
or a page the arena refused under budget) aborts the pass and the run
REPLAYS from scratch with yielding disabled — the schedule is
deterministic, so the replay emits identical tokens: a recompute
(counted), never a wrong token.
"""

from __future__ import annotations

import threading
import time
from typing import Any

from lambdipy_tpu.runtime.metrics import KvOffloadStats
from lambdipy_tpu.runtime.offload import (
    OffloadArena,
    OffloadMiss,
    PageTemperature,
    Prefetcher,
)
from lambdipy_tpu.utils.logs import get_logger

log = get_logger("lambdipy.longctx")


class ReonlineFailed(RuntimeError):
    """A spilled page could not come back (injected fault or budget
    drop). Carries the original cause; the runner's replay path eats
    this up to ``max_replays`` times."""

    def __init__(self, cause: BaseException, pages: int):
        super().__init__(f"re-online of {pages} page(s) failed: {cause!r}")
        self.cause = cause
        self.pages = pages


class LongContextRunner:
    """Solo long-context decode over a shared page pool.

    One request at a time per runner call (the continuous engine routes
    over-window rows here the way it routes them to ``server.generate``
    today — the runner IS the solo fallback for the long tier). All
    device work runs under ``pool.arena_lock`` for enqueue time only,
    advancing the pool's functional arena chain exactly like the engine
    and the prefix store do, so a runner coexists with both on one
    pool."""

    def __init__(self, server: Any, pool: Any, offload: OffloadArena
                 | None = None, *, window: int | None = None,
                 segment: int = 16, max_logical_ctx: int = 0,
                 resident_cap: int | None = None,
                 long_prefill: bool = False, faults: Any = None,
                 max_replays: int = 2,
                 stats: KvOffloadStats | None = None,
                 prefill_mode: str = "chunked",
                 prefill_stats: Any = None):
        import itertools

        cfg = server.model.cfg
        self.server = server
        self.pool = pool
        self.window = int(window) if window else int(cfg.max_len)
        if self.window % pool.page or self.window < 2 * pool.page:
            raise ValueError(
                f"window {self.window} must be >= 2 whole {pool.page}-"
                f"token pages")
        self.n_view = self.window // pool.page
        self.segment = max(1, int(segment))
        self.max_logical_ctx = int(max_logical_ctx) \
            if max_logical_ctx else 32 * self.window
        # the boot-time cap: a fleet controller stepping max_logical_ctx
        # down on offload stalls restores toward this, never past it
        self.boot_logical_ctx = self.max_logical_ctx
        self.resident_cap = resident_cap
        self.max_replays = max(0, int(max_replays))
        self.stats = stats if stats is not None else KvOffloadStats()
        if offload is None:
            # share the pool's attached arena (the prefix store's host
            # tier) when one exists — one host budget, one stats block
            # on /metrics; runner keys are ("lc", run, page#) tuples, so
            # they can never collide with the store's token-path keys
            offload = getattr(pool, "offload", None)
            if offload is not None:
                self.stats = getattr(offload, "stats", self.stats)
        self.offload = offload if offload is not None else OffloadArena(
            page=pool.page, layers=cfg.layers, stats=self.stats,
            faults=faults)
        # one runner, one stats stream: an injected offload arena keeps
        # its own counters wired to the same block only if the caller
        # passed a shared KvOffloadStats
        if getattr(pool, "offload", None) is None:
            # surface kv_offload gauges through batching.page_pool even
            # when only the long-context tier spills
            pool.attach_offload(self.offload)
        self.temp = PageTemperature()
        # whole-prompt sp prefill (prefill_mode="sp"): the serial
        # window/2 slide chain collapses to rounds of sp chunks, each
        # round ONE sharded program (_lsp_round_fn); sp resolves per run
        # against the live mesh so a bundle swap can't strand the knob
        self.prefill_mode = prefill_mode
        self.prefill_stats = prefill_stats
        self.long_prefill = bool(long_prefill)
        self._ring_ok = self._probe_ring() if self.long_prefill else False
        if self.long_prefill and not self._ring_ok:
            from lambdipy_tpu.parallel.spdecode import note_standdown

            note_standdown("long_prefill_without_ring_mesh")
            log.warning(
                "long_prefill requested but the bundle is not a ring-"
                "attention sp-mesh configuration; chunked prefill runs "
                "unsharded (counted stand-down)")
        self._run_ids = itertools.count(1)
        self._lock = threading.Lock()  # one run at a time per runner

    def _probe_ring(self) -> bool:
        cfg = self.server.model.cfg
        mesh = getattr(self.server, "mesh", None)
        return (getattr(cfg, "attn_backend", "dense") == "ring"
                and mesh is not None
                and dict(getattr(mesh, "shape", {})).get("sp", 1) > 1)

    def _sp_standdown(self, reason: str) -> int:
        from lambdipy_tpu.parallel.spdecode import note_standdown

        note_standdown(reason)
        if self.prefill_stats is not None:
            self.prefill_stats.record_standdown(reason)
        return 0

    def _sp_factor(self, s: int) -> int:
        """Shard count for THIS run's prefill, or 0 for the serial
        chain. Every refusal is a counted stand-down, never silent:
        prompts of one chunk or less gain nothing from sharding, an odd
        page count makes the half-window non-page-aligned (the slide
        schedule the rounds must reproduce moves ``n_view // 2`` whole
        pages), and a round needs ``(sp + 1) * n_view / 2`` free pages
        at peak (fresh round pages + the carried prior half-window)."""
        from lambdipy_tpu.models.llama import resolve_sp_prefill

        sp = resolve_sp_prefill(self.prefill_mode,
                                getattr(self.server, "mesh", None))
        if sp < 2:
            if sp != 0 or self.prefill_mode != "sp":
                return 0
            if self.prefill_stats is not None:
                self.prefill_stats.record_standdown(
                    "sp_prefill_without_sp_mesh")
            return 0
        if s <= self.window // 2:
            return 0  # one serial chunk already; not a degradation
        if self.n_view % 2:
            return self._sp_standdown("sp_prefill_window_not_divisible")
        need = (sp + 1) * (self.n_view // 2)
        if self.pool.free_count() < need:
            return self._sp_standdown("sp_prefill_pool_pressure")
        return sp

    def _spill_history(self, st: dict, pids: list, lpi0: int) -> None:
        """Spill already-attended prefill pages (logical pages ``lpi0 +
        j``) to the offload arena under the run's ``("lc", ...)`` keys
        and recycle their pool pages — the sp-round twin of the eviction
        half of :meth:`_slide`. Decode never re-reads them; the spill
        keeps the run's offload history identical to the serial
        schedule's (budget refusals land in ``st["lost"]`` the same
        way)."""
        from lambdipy_tpu.models.llama import arena_page_slices

        if not pids:
            return
        pool, page = self.pool, self.pool.page
        with pool.arena_lock:
            arena = pool.ensure_arena()
        for j, pid in enumerate(pids):
            lpi = lpi0 + j
            key = ("lc", st["run_id"], lpi)
            toks = st["tokens"][lpi * page:(lpi + 1) * page]
            block = arena_page_slices(arena, pid, page)
            if self.offload.spill(key, toks, block):
                st["off"][lpi] = key
            else:
                st["lost"].add(lpi)
        pool.release(pids)

    def _sp_prefill(self, st: dict, row, s: int, knobs, sp: int):
        """Whole-prompt sequence-parallel prefill: run the serial
        window/2 slide schedule as ``ceil(s / (sp * window/2))`` ROUNDS
        of ``sp`` chunks each, every round one sharded program
        (``server._lsp_round_fn``). The round's union view is [prior
        half-window][sp fresh chunks]; ``band = window/2`` gives every
        query exactly the keys its serial chunk would have had resident,
        so the tokens match the serial chain's. Between rounds the
        union's head retires through :meth:`_spill_history` and the last
        half-window carries forward as the next prior. Returns the final
        round's carry with the cursor already translated into the decode
        view's frame; ``st`` leaves with the table/base/local the serial
        chain would have produced."""
        import jax.numpy as jnp

        from lambdipy_tpu.runtime.pagepool import NULL_PAGE

        server, pool = self.server, self.pool
        page, window, n_view = self.pool.page, self.window, self.n_view
        w2 = window // 2
        rbs = sp * w2
        rpages = rbs // page
        ppages = w2 // page
        t_op, k_op, p_op, keys0, eos_op = knobs
        rnd = server._lsp_round_fn(sp, pool.n_pages, page, window, sp)
        n_rounds = -(-s // rbs)
        layers = int(getattr(server.model.cfg, "layers", 0))
        t0 = time.monotonic()
        prior: list = []
        prior_len = 0
        carry = None
        fresh: list = []
        live: set = set()  # alloc'd pages not yet retired or handed off
        for r in range(n_rounds):
            c0 = r * rbs
            rlen = min(rbs, s - c0)
            try:
                fresh = pool.alloc(rpages, tokens=rlen,
                                   record_shed=False)
            except BaseException:
                pool.release(sorted(live))
                raise
            live |= set(fresh)
            prior_len = w2 if r else 0
            # round 0 has no prior: the head slots point at the null
            # page, whose gathered bits sit beyond the cache index and
            # scatter back bitwise-unchanged
            tbl_list = (prior + fresh) if r else \
                (fresh + [NULL_PAGE] * ppages)
            suffix_op, _ = server._pad_rows([row[c0:c0 + rlen]], [rlen],
                                            1, rbs)
            tbl = jnp.asarray(tbl_list, jnp.int32)[None, :]
            with pool.arena_lock:
                pool.ensure_arena()
                with server._mesh_ctx():
                    first, lp0, new_arena, start_c, done_c, keys = rnd(
                        server.params, pool.arena, tbl,
                        jnp.int32(prior_len), jnp.int32(c0), suffix_op,
                        jnp.int32(rlen), t_op, k_op, p_op, keys0,
                        eos_op)
                pool.arena = new_arena
            if self.prefill_stats is not None:
                self.prefill_stats.record_round(-(-rlen // w2), sp,
                                                ring_hops=layers * sp)
            # like the serial chain: only the FINAL round's selection is
            # the request's first token (same rng operand every round)
            carry = (first, lp0, start_c, done_c, keys)
            if r < n_rounds - 1:
                gs = c0 - prior_len
                evict = prior + fresh[:-ppages]
                self._spill_history(st, evict, gs // page)
                live -= set(evict)
                prior = fresh[-ppages:]
        # -- hand off to the decode view: the exact (base, local, table)
        # the serial slide schedule ends on --------------------------------
        gs = (n_rounds - 1) * rbs - prior_len
        union = (prior + fresh) if prior_len else \
            (fresh + [NULL_PAGE] * ppages)
        base = max(0, -(-(s - window) // w2)) * w2
        local = s - base
        off0 = (base - gs) // page
        self._spill_history(st, union[:off0], gs // page)
        st["table"] = union[off0:off0 + n_view]
        assert len(st["table"]) == n_view \
            and NULL_PAGE not in st["table"]  # covered: base >= gs and
        # base + window <= gs + union tokens, both multiples of the page
        # a RAGGED last round can leave union pages past the decode view
        # (tokens >= base + window >= s: pure padding) — plain release,
        # nothing in them is history worth spilling
        tail = [p for p in union[off0 + n_view:] if p != NULL_PAGE]
        if tail:
            self.pool.release(tail)
        st["base"], st["local"] = base, local
        self.temp.touch([("lc", st["run_id"], base // page + j)
                         for j in range(local // page)])
        if self.prefill_stats is not None:
            self.prefill_stats.record_walk(time.monotonic() - t0,
                                           -(-s // w2), n_rounds)
        first, lp0, start_c, done_c, keys = carry
        # union-frame cursor (prior_len + rlen) -> decode-view frame
        start_c = start_c - jnp.int32(base - gs)
        return first, lp0, start_c, done_c, keys

    # -- public --------------------------------------------------------------

    def fits(self, s: int, max_new_tokens: int) -> bool:
        return 0 < s + max_new_tokens <= self.max_logical_ctx

    def generate(self, prompt_row, *, max_new_tokens: int,
                 temperature: float = 0.0, top_k=None, top_p=None,
                 seed: int = 0, eos_id=None, return_logprobs: bool = False):
        """``server.generate``'s single-row contract over the logical
        window: ``[1, max_new_tokens]`` tokens (+ logprobs when asked),
        eos-latched with eos filler. Deterministic in the request alone
        — a replay after a failed re-online re-emits the same stream."""
        import numpy as np

        with self._lock:
            replays = 0
            while True:
                try:
                    toks, lps = self._run(
                        prompt_row, max_new_tokens, temperature, top_k,
                        top_p, seed, eos_id,
                        resident_cap=(self.resident_cap if replays == 0
                                      else None))
                    break
                except ReonlineFailed as exc:
                    # the lost page's KV is recomputed by replaying the
                    # whole deterministic schedule with yielding OFF —
                    # under a permanently-armed fault the replay makes
                    # progress because it never fetches
                    self.stats.record_recompute(exc.pages)
                    replays += 1
                    if replays > self.max_replays:
                        raise exc.cause
                    log.warning(
                        "long-context re-online failed (%s); replaying "
                        "run from scratch (%d/%d)", exc, replays,
                        self.max_replays)
        out = np.asarray([toks[:max_new_tokens]], np.int32)
        if return_logprobs:
            return out, np.asarray([lps[:max_new_tokens]], np.float32)
        return out

    # -- internals -----------------------------------------------------------

    def _slide(self, st: dict, k_pages: int) -> int:
        """Advance the view by ``k_pages`` whole pages: spill the evicted
        head pages (full of already-attended tokens) to the offload
        arena, recycle their pool pages into the view's tail, shift the
        frame. Returns the token delta (the caller shifts the device
        carry's local cursor by exactly this, int32-exact). Spill bytes
        come off the PRE-slide arena value — the functional arena chain
        means later writes can never alter it."""
        import jax.numpy as jnp  # noqa: F401 — device libs load lazily

        from lambdipy_tpu.models.llama import arena_page_slices

        pool, page = self.pool, self.pool.page
        evict = st["table"][:k_pages]
        with pool.arena_lock:
            arena = pool.ensure_arena()
        base_page = st["base"] // page
        for j, pid in enumerate(evict):
            lpi = base_page + j
            if pid is None:
                # already spilled by the pressure-yield pass: its bytes
                # are in the offload arena under st["off"][lpi]
                continue
            key = ("lc", st["run_id"], lpi)
            toks = st["tokens"][lpi * page:(lpi + 1) * page]
            block = arena_page_slices(arena, pid, page)
            if self.offload.spill(key, toks, block):
                st["off"][lpi] = key
            else:
                # budget refusal: the page is LOST to history (failover
                # re-ship of this run will recompute it) but decode
                # never needs it again — the view has moved past it
                st["lost"].add(lpi)
        gone = [("lc", st["run_id"], base_page + j) for j in range(k_pages)]
        self.temp.forget(gone)
        st["prefetch"].forget(gone)
        pool.release([pid for pid in evict if pid is not None])
        fresh = pool.alloc(k_pages, tokens=0, record_shed=False)
        st["table"] = st["table"][k_pages:] + list(fresh)
        st["base"] += k_pages * page
        st["local"] -= k_pages * page
        return k_pages * page

    def _reonline(self, st: dict, slots: list, *, timed: bool) -> None:
        """Fetch the offloaded pages for view ``slots`` in ONE batched
        frame decode and write them into freshly allocated arena pages
        through the page-write program (the same validated-insert path
        every kvwire import takes). ``timed`` marks a demand miss — the
        wall clock it burns is the re-online stall ``record_stall`` books."""
        import jax.numpy as jnp

        if not slots:
            return
        pool, server = self.pool, self.server
        base_page = st["base"] // pool.page
        keys = [("lc", st["run_id"], base_page + j) for j in slots]
        t0 = time.monotonic() if timed else 0.0
        try:
            blocks = self.offload.fetch_many(keys)
        except (OffloadMiss, Exception) as exc:  # noqa: B014 — fault kinds vary
            raise ReonlineFailed(exc, len(keys)) from exc
        pids = pool.alloc(len(slots), tokens=0, record_shed=False)
        write = server._page_write_fn(pool.n_pages, pool.page)
        with pool.arena_lock:
            arena = pool.ensure_arena()
            with server._mesh_ctx():
                for pid, block in zip(pids, blocks):
                    arena = write(arena, jnp.int32(pid), block)
            pool.arena = arena
        for j, pid in zip(slots, pids):
            st["table"][j] = pid
            st["off"].pop(base_page + j, None)
        self.offload.drop(keys)
        if timed:
            # a demand-missed page already scored its miss; take it out
            # of the tracker so later segments don't re-score it
            st["prefetch"].forget(keys)
            self.stats.record_stall(time.monotonic() - t0)
        else:
            st["prefetch"].complete(keys)
        self.temp.touch(keys)

    def _yield_cold(self, st: dict, arena_before) -> None:
        """Pressure-yield (``resident_cap``): spill the view's coldest
        FULL pages past the cap back to host RAM and release their pool
        pages — capacity other sessions can use between this row's
        segments. Runs right after an async dispatch, reading the
        pre-dispatch arena value (bitwise the values the in-flight
        segment attends: decode only writes the cursor page, which is
        never a victim)."""
        from lambdipy_tpu.models.llama import arena_page_slices

        pool, page = self.pool, self.pool.page
        cap = self.resident_cap
        base_page = st["base"] // page
        # victims: whole pages strictly below the cursor page (full,
        # read-only for the in-flight segment), never the write region
        full = [j for j in range(self.n_view)
                if (j + 1) * page <= st["local"]
                and st["table"][j] is not None]
        excess = len([j for j in range(self.n_view)
                      if st["table"][j] is not None]) - cap
        if excess <= 0 or not full:
            return
        victims = self.temp.coldest(
            [("lc", st["run_id"], base_page + j) for j in full],
            min(excess, len(full)))
        for *_, lpi in victims:
            j = lpi - base_page
            pid = st["table"][j]
            key = ("lc", st["run_id"], lpi)
            toks = st["tokens"][lpi * page:(lpi + 1) * page]
            block = arena_page_slices(arena_before, pid, page)
            if not self.offload.spill(key, toks, block):
                continue  # refusal: keep it resident, nothing lost
            st["off"][lpi] = key
            st["prefetch"].spill([key])
            pool.release([pid])
            st["table"][j] = None

    def _view_table(self, st: dict):
        """The dispatch operand: every slot must be resident (a None
        slot here is a programming error — demand re-onlines first)."""
        import jax.numpy as jnp

        assert all(pid is not None for pid in st["table"])
        return jnp.asarray(st["table"], jnp.int32)[None, :]

    def _offloaded_slots(self, st: dict) -> list:
        return [j for j in range(self.n_view) if st["table"][j] is None]

    def _run(self, prompt_row, max_new_tokens, temperature, top_k, top_p,
             seed, eos_id, *, resident_cap):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from lambdipy_tpu.models.llama import _next_bucket

        server, pool = self.server, self.pool
        page, window, n_view = pool.page, self.window, self.n_view
        rows, lengths = server._normalize_prompts(prompt_row)
        if len(rows) != 1:
            raise ValueError("the long-context tier is single-row")
        row, s = rows[0], lengths[0]
        total = s + max_new_tokens
        if not self.fits(s, max_new_tokens):
            raise ValueError(
                f"{total} tokens exceed max_logical_ctx="
                f"{self.max_logical_ctx}")
        yield_cap = resident_cap if resident_cap \
            and resident_cap < n_view else None
        sp = self._sp_factor(s)
        st = {"run_id": next(self._run_ids), "base": 0, "local": 0,
              "tokens": list(row), "off": {}, "lost": set(),
              "table": [] if sp else list(pool.alloc(n_view, tokens=0,
                                                     record_shed=False)),
              "prefetch": Prefetcher(self.stats)}
        knobs = server._knob_operands(temperature, top_k, top_p, seed,
                                      eos_id, b=1)
        t_op, k_op, p_op, keys0, eos_op = knobs
        out_toks: list = []
        out_lps: list = []
        try:
            if sp:
                # -- whole-prompt sp prefill: sharded rounds ------------------
                first, lp0, start_c, done_c, keys = \
                    self._sp_prefill(st, row, s, knobs, sp)
            else:
                # -- chunked prefill through the sliding view -----------------
                t_pf = time.monotonic()
                chunk = window // 2
                carry = None
                for c0 in range(0, s, chunk):
                    clen = min(chunk, s - c0)
                    while st["local"] + clen > window:
                        self._slide(st, n_view // 2)
                    sbs = min(_next_bucket(clen, server.min_bucket),
                              window - st["local"])
                    cont = server._lpaged_continue_fn(sbs, pool.n_pages,
                                                      page, window)
                    suffix_op, _ = server._pad_rows([row[c0:c0 + clen]],
                                                    [clen], 1, sbs)
                    tbl = self._view_table(st)
                    with pool.arena_lock:
                        pool.ensure_arena()
                        with server._mesh_ctx():
                            first, lp0, new_arena, start_c, done_c, keys = \
                                cont(server.params, pool.arena, tbl,
                                     jnp.int32(st["local"]),
                                     jnp.int32(st["base"]), suffix_op,
                                     jnp.int32(clen), t_op, k_op, p_op,
                                     keys0, eos_op)
                        pool.arena = new_arena
                    st["local"] += clen
                    self.temp.touch(
                        [("lc", st["run_id"], st["base"] // page + j)
                         for j in range(st["local"] // page)])
                    if self.prefill_stats is not None:
                        self.prefill_stats.record_round(1, 1)
                    # only the FINAL chunk's selection is the request's
                    # first token; mid-chunk selections are discarded (the
                    # rng operand is the same each chunk, so the final
                    # split matches a single whole-prompt prefill's)
                    carry = (first, lp0, start_c, done_c, keys)
                first, lp0, start_c, done_c, keys = carry
                if self.prefill_stats is not None:
                    n_chunks = -(-s // chunk)
                    self.prefill_stats.record_walk(
                        time.monotonic() - t_pf, n_chunks, n_chunks)
            # -- segment decode over the sliding view -------------------------
            seg_len = self.segment
            seg_fn = server._lpaged_seg_fn(1, pool.n_pages, page, window,
                                           seg_len)
            eos_seen = False
            while len(out_toks) < max_new_tokens and not eos_seen:
                while st["local"] + seg_len > window:
                    delta = self._slide(st, n_view // 2)
                    start_c = start_c - jnp.int32(delta)
                # demand: every view slot must be resident at dispatch.
                # The check covers ALL view pages so a page the prefetch
                # already brought home is COUNTED as a hit (only pages
                # with spill history score; always-resident ones don't);
                # stragglers re-online now — a timed stall
                base_page = st["base"] // page
                miss = st["prefetch"].demand(
                    [("lc", st["run_id"], base_page + j) for j in range(n_view)])
                self._reonline(st, sorted(k[2] - base_page for k in miss),
                               timed=True)
                tbl = self._view_table(st)
                base_op = jnp.broadcast_to(jnp.int32(st["base"]), (1,))
                with pool.arena_lock:
                    arena_before = pool.ensure_arena()
                    with server._mesh_ctx():
                        (toks, lps), (first, lp0, new_arena, start_c,
                                      done_c, keys) = seg_fn(
                            server.params, t_op, k_op, p_op, first, lp0,
                            pool.arena, tbl, start_c, base_op, done_c,
                            keys, eos_op)
                    pool.arena = new_arena
                # dispatch is async: the yield + prefetch below run on
                # the host while the device chews the segment, so the
                # re-online frame decode hides under the previous step
                if yield_cap is not None:
                    self._yield_cold(st, arena_before)
                    planned = st["prefetch"].plan(
                        [("lc", st["run_id"], st["base"] // page + j)
                         for j in self._offloaded_slots(st)])
                    if planned:
                        base_page = st["base"] // page
                        self._reonline(
                            st, [k[2] - base_page for k in planned],
                            timed=False)
                chunk_t = np.asarray(jax.device_get(toks))[0]
                chunk_l = np.asarray(jax.device_get(lps))[0]
                take = min(seg_len, max_new_tokens - len(out_toks))
                for i in range(take):
                    tok = int(chunk_t[i])
                    out_toks.append(tok)
                    out_lps.append(float(chunk_l[i]))
                    st["tokens"].append(tok)
                    if eos_id is not None and tok == int(eos_id):
                        eos_seen = True
                        break
                st["local"] += seg_len
                self.temp.touch([("lc", st["run_id"], st["base"] // page + j)
                                 for j in range(min(st["local"], window)
                                                // page)])
            if eos_id is not None and eos_seen:
                pad = max_new_tokens - len(out_toks)
                out_toks += [int(eos_id)] * pad
                out_lps += [0.0] * pad
            else:
                out_toks = out_toks[:max_new_tokens]
                out_lps = out_lps[:max_new_tokens]
            return out_toks, out_lps
        finally:
            pool.release([pid for pid in st["table"] if pid is not None])
            self.offload.drop(list(st["off"].values()))
            self.temp.forget(list(st["off"].values()))

    def report(self) -> dict:
        return {"window": self.window, "segment": self.segment,
                "max_logical_ctx": self.max_logical_ctx,
                "boot_logical_ctx": self.boot_logical_ctx,
                "resident_cap": self.resident_cap,
                "long_prefill": self.long_prefill,
                "ring_active": self._ring_ok,
                "prefill_mode": self.prefill_mode,
                **self.offload.gauges(), **self.stats.report()}
