"""Paged KV page allocator (runtime/pagepool.py): refcounts, free-list
reuse, exact accounting, and out-of-pages backpressure.

The allocator is the trust anchor of the paged engine — a silent
refcount bug corrupts KV shared between requests — so these tests lean
on invariants (every page free XOR live exactly once, bytes conserve)
under randomized alloc/share/release interleavings, not just happy
paths. The HTTP-facing contract is exercised too: exhaustion surfaces
as a PRICED shed (503 + Retry-After through runtime/server.py), never
an unhandled exception."""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from lambdipy_tpu.runtime.pagepool import (
    NULL_PAGE,
    PagePool,
    PagesExhausted,
    page_width,
)


def mkpool(n_pages=17, page=16, page_bytes=1024, **kw):
    return PagePool(n_pages=n_pages, page=page, page_bytes=page_bytes,
                    **kw)


# -- page width normalization -------------------------------------------------


def test_page_width_pow2_dividing_window():
    assert page_width(256, 32) == 32
    assert page_width(256, 48) == 64     # pow-2 bucket of 48
    assert page_width(1072, 64) == 16    # largest pow-2 dividing 1072
    assert page_width(128, 1024) == 128  # clamped to the window
    assert page_width(8, 0) == 1


# -- basic alloc/share/release ------------------------------------------------


def test_alloc_release_roundtrip_and_reuse():
    pool = mkpool(n_pages=5)
    a = pool.alloc(2, tokens=20)
    assert len(a) == 2 and NULL_PAGE not in a
    assert pool.free_count() == 2
    pool.release(a)
    assert pool.free_count() == 4
    # LIFO reuse: the pages just released come back first
    b = pool.alloc(2)
    assert set(b) & set(a)
    pool.check_invariants()


def test_share_is_refcount_not_copy():
    pool = mkpool()
    pids = pool.alloc(3)
    pool.retain(pids)
    pool.release(pids)           # first owner gone
    assert pool.free_count() == pool.capacity_pages - 3  # still live
    assert all(pool.refcount(p) == 1 for p in pids)
    pool.release(pids)           # second owner gone -> free
    assert pool.free_count() == pool.capacity_pages
    pool.check_invariants()


def test_double_free_and_bad_retain_raise():
    pool = mkpool()
    (pid,) = pool.alloc(1)
    pool.release([pid])
    with pytest.raises(ValueError, match="double free"):
        pool.release([pid])
    with pytest.raises(ValueError, match="retain"):
        pool.retain([pid])


def test_null_page_is_inert():
    pool = mkpool()
    pool.retain([NULL_PAGE])
    pool.release([NULL_PAGE])    # never frees, never double-frees
    pool.release([NULL_PAGE])
    assert pool.refcount(NULL_PAGE) == 1
    pool.check_invariants()


def test_exhaustion_is_priced_backpressure():
    pool = mkpool(n_pages=4)
    pool.alloc(3)
    with pytest.raises(PagesExhausted) as exc:
        pool.alloc(2)
    assert exc.value.needed == 2 and exc.value.free == 0
    assert exc.value.retry_after_s > 0
    assert pool.stats()["sheds"] == 1
    # a failed alloc leaks nothing
    pool.check_invariants()


def test_alloc_zero_and_negative_are_noops():
    pool = mkpool()
    assert pool.alloc(0) == []
    assert pool.alloc(-3) == []
    assert pool.free_count() == pool.capacity_pages


# -- stats / accounting -------------------------------------------------------


def test_stats_exact_bytes_and_fragmentation():
    pool = mkpool(n_pages=9, page=16, page_bytes=100)
    pool.alloc(2, tokens=20)     # second page holds 4/16 tokens
    st = pool.stats()
    assert st["bytes_total"] == 8 * 100
    assert st["bytes_live"] == 200 and st["bytes_free"] == 600
    assert st["bytes_live"] + st["bytes_free"] == st["bytes_total"]
    # 32 allocated token slots, 20 used -> 12/32 wasted
    assert st["internal_fragmentation"] == pytest.approx(12 / 32)
    assert st["pages_shared"] == 0 and st["max_refcount"] == 1
    assert st["allocs"] == 1 and st["alloc_pages"] == 2


def test_stats_refcount_histogram_and_capacity_rows():
    pool = mkpool(n_pages=9, window_pages=4)
    a = pool.alloc(2)
    pool.retain(a)
    pool.alloc(1)
    st = pool.stats()
    assert st["refcount_histogram"] == {"2": 2, "1": 1}
    assert st["max_refcount"] == 2 and st["pages_shared"] == 2
    # 5 free pages / 4-page windows -> 1 more full-window row now;
    # window-bound could only ever hold 2
    assert st["capacity_rows_now"] == 1
    assert st["window_bound_rows"] == 2


def test_token_accounting_admits_more_mixed_length_rows_than_windows():
    """What paged KV is for: in the budget a window-bound allocator spends
    on ``slots`` rows (a full window each), admission by the rows' ACTUAL
    tokens holds strictly more rows of mixed lengths, and never leaks."""
    slots, window, page = 4, 128, 16
    pool = mkpool(n_pages=slots * (window // page) + 1, page=page,
                  window_pages=window // page)
    assert pool.stats()["window_bound_rows"] == slots
    rng = np.random.default_rng(7)
    admitted = 0
    with pytest.raises(PagesExhausted):
        while True:
            tokens = int(rng.integers(page, window // 2))
            pool.alloc(-(-tokens // page), tokens=tokens)
            admitted += 1
    pool.check_invariants()
    assert admitted > slots, (admitted, slots)
    # rows of under half a window: at least two to a window's pages
    assert admitted >= 2 * slots


# -- randomized invariant fuzz ------------------------------------------------


def test_fuzz_alloc_share_release_invariants():
    """Random interleavings against a shadow refcount model: the pool's
    refcounts always match the model, no page is ever free and live at
    once, and free + live bytes always cover the arena exactly."""
    rng = np.random.default_rng(42)
    pool = mkpool(n_pages=33, page=8, page_bytes=64)
    shadow: dict[int, int] = {}      # pid -> model refcount
    for step in range(2000):
        op = rng.integers(0, 3)
        if op == 0:                  # alloc
            n = int(rng.integers(1, 5))
            try:
                pids = pool.alloc(n, tokens=int(rng.integers(0, n * 8 + 1)))
            except PagesExhausted:
                assert pool.free_count() < n
            else:
                for p in pids:
                    assert shadow.get(p, 0) == 0, "allocated a live page"
                    shadow[p] = 1
        elif op == 1 and shadow:     # share a random live subset
            live = [p for p, r in shadow.items() if r > 0]
            take = list(rng.choice(live,
                                   size=min(len(live),
                                            int(rng.integers(1, 4))),
                                   replace=False))
            pool.retain(take)
            for p in take:
                shadow[p] += 1
        elif op == 2 and shadow:     # release one ref on a subset
            live = [p for p, r in shadow.items() if r > 0]
            take = list(rng.choice(live,
                                   size=min(len(live),
                                            int(rng.integers(1, 4))),
                                   replace=False))
            pool.release(take)
            for p in take:
                shadow[p] -= 1
                if shadow[p] == 0:
                    del shadow[p]
        pool.check_invariants()
        for p, r in shadow.items():
            assert pool.refcount(p) == r
    st = pool.stats()
    assert st["pages_live"] == len(shadow)
    assert st["release_pages"] + st["pages_live"] == st["alloc_pages"]


def test_fuzz_pin_shadow_model_sweep_never_frees_pinned():
    """The session-pin extension of the fuzz: pages carry a PINNED flag
    (the store's session pins, modeled as pure bookkeeping) and a
    store-style sweep op releases only UNPINNED refcount-1 pages — the
    exact contract the prefix store's reclaim/eviction sweeps honor.
    Invariants hold through pin/unpin churn and the shadow model stays
    exact: a pinned page is never freed by a sweep, only by its own
    unpin + release."""
    rng = np.random.default_rng(7)
    pool = mkpool(n_pages=33, page=8, page_bytes=64)
    shadow: dict[int, int] = {}      # pid -> model refcount
    pinned: set[int] = set()         # the store's pinned leaves
    for step in range(2000):
        op = rng.integers(0, 5)
        if op == 0:                  # alloc (a cold insert)
            try:
                pids = pool.alloc(int(rng.integers(1, 4)))
            except PagesExhausted:
                pass
            else:
                for p in pids:
                    assert shadow.get(p, 0) == 0
                    shadow[p] = 1
        elif op == 1 and shadow:     # pin a live page (a session turn)
            live = [p for p, r in shadow.items() if r > 0]
            pinned.add(int(rng.choice(live)))
        elif op == 2 and pinned:     # unpin (session end / lease lapse)
            pinned.discard(int(rng.choice(sorted(pinned))))
        elif op == 3 and shadow:     # a row shares/releases a page
            live = [p for p, r in shadow.items() if r > 0]
            p = int(rng.choice(live))
            if rng.integers(0, 2) and shadow[p] > 1:
                pool.release([p])
                shadow[p] -= 1
            else:
                pool.retain([p])
                shadow[p] += 1
        else:                        # the store's cold-page sweep
            victims = [p for p, r in shadow.items()
                       if r == 1 and p not in pinned]
            take = victims[:int(rng.integers(0, 4))]
            pool.release(take)
            for p in take:
                del shadow[p]
        pool.check_invariants()
        for p in pinned:             # a pinned page is always live
            assert pool.refcount(p) == shadow[p] > 0
    # end every "session", then sweep: the pool drains to exactly the
    # still-shared pages — pins never leaked a page
    pinned.clear()
    stuck = [p for p, r in shadow.items() if r == 1]
    pool.release(stuck)
    for p in stuck:
        del shadow[p]
    pool.check_invariants()
    assert pool.stats()["pages_live"] == len(shadow)


def test_concurrent_alloc_release_conserves_pages():
    pool = mkpool(n_pages=65, page=8, page_bytes=8)
    errs: list = []

    def churn(seed):
        rng = np.random.default_rng(seed)
        held: list = []
        try:
            for _ in range(300):
                if held and rng.integers(0, 2):
                    pool.release(held.pop())
                else:
                    try:
                        held.append(pool.alloc(int(rng.integers(1, 4))))
                    except PagesExhausted:
                        pass
            for h in held:
                pool.release(h)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=churn, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert pool.free_count() == pool.capacity_pages
    pool.check_invariants()


# -- engine + HTTP backpressure ----------------------------------------------


def test_engine_sheds_priced_when_arena_full(tiny_server):
    """A transiently full arena sheds the admission with PagesExhausted
    (priced: retry_after_s rides the exception) and serves again once
    pages release — never an engine failure, never a lost in-flight
    row."""
    from lambdipy_tpu.models.llama import init_page_arena, page_kv_bytes
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher

    cfg = tiny_server.model.cfg
    page = page_width(cfg.max_len, 16)
    pool = PagePool(n_pages=3, page=page,
                    page_bytes=page_kv_bytes(cfg, page),
                    make_arena=lambda: init_page_arena(cfg, 3, page))
    eng = ContinuousBatcher(tiny_server, slots=2, segment=8,
                            page_pool=pool)
    row = [1, 2, 3]
    solo = tiny_server.generate(row, max_new_tokens=8)
    held = pool.alloc(2)
    with pytest.raises(PagesExhausted) as exc:
        eng.generate(row, max_new_tokens=8)
    assert exc.value.retry_after_s > 0
    pool.release(held)
    np.testing.assert_array_equal(eng.generate(row, max_new_tokens=8),
                                  solo)
    pool.check_invariants()


def test_server_maps_pages_exhausted_to_shed_503(monkeypatch, tmp_path):
    """PagesExhausted escaping handler.invoke answers shed-style: 503 +
    integer Retry-After from the pool's own estimate, shed reason
    ``kv_pages``, no error counted — backpressure, not a fault."""
    from pathlib import Path
    from types import SimpleNamespace

    import lambdipy_tpu.runtime.server as server_mod
    from lambdipy_tpu.runtime.loader import BootReport

    def invoke(st, request):
        raise PagesExhausted(4, 1, retry_after_s=2.5)

    def stub_boot(bundle_dir, warmup=True):
        return BootReport(
            bundle_dir=Path(bundle_dir),
            handler=SimpleNamespace(invoke=invoke),
            state=SimpleNamespace(meta={"model": "stub"},
                                  stats=lambda: {"stub": True}),
            stages={"init": 0.0}, manifest={"payload": {"extra": {}}})

    monkeypatch.setattr(server_mod, "load_bundle", stub_boot)
    srv = server_mod.BundleServer(tmp_path, port=0,
                                  warmup=False).start_background()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/invoke",
            data=json.dumps({"tokens": [1, 2]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 503
        assert int(exc.value.headers["Retry-After"]) == 3  # ceil(2.5)
        body = json.loads(exc.value.read())
        assert not body["ok"] and body["retry_after_s"] == 2.5
        shed = srv.sched.admission.shed_report()
        assert shed["by_reason"].get("kv_pages") == 1
        assert srv.stats.report()["errors"] == 0
    finally:
        threading.Thread(target=srv.stop, daemon=True).start()
