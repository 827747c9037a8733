"""LatencyStats: percentile edge cases + reservoir wraparound (the seed
overwrote with the post-increment count, skewing the ring by one and
making slot 0 immortal). Plus the prefix-cache counter block and the
decode-window (length-aware decode) counter block."""

import threading

import pytest

from lambdipy_tpu.runtime.metrics import (DecodeWindowStats, LatencyStats,
                                          PipelineStats, PrefixCacheStats)


def test_empty_reservoir_reports_none():
    stats = LatencyStats()
    report = stats.report()
    assert report["count"] == 0 and report["errors"] == 0
    assert report["p50_ms"] is None
    assert report["p90_ms"] is None
    assert report["p99_ms"] is None
    assert stats.percentile(50) is None


def test_single_sample_every_percentile():
    stats = LatencyStats()
    stats.record(42.0)
    report = stats.report()
    assert report["count"] == 1
    assert report["p50_ms"] == report["p90_ms"] == report["p99_ms"] == 42.0


def test_wraparound_overwrites_oldest_first():
    """After capacity, sample N lands at ring slot N % capacity: the
    FIRST overwrite must hit slot 0 (the oldest sample), not slot 1."""
    stats = LatencyStats(capacity=4)
    for v in (1.0, 2.0, 3.0, 4.0):
        stats.record(v)
    assert stats.samples == [1.0, 2.0, 3.0, 4.0]
    stats.record(5.0)  # 5th sample -> slot 4 % 4 == 0
    assert stats.samples == [5.0, 2.0, 3.0, 4.0]
    stats.record(6.0)
    assert stats.samples == [5.0, 6.0, 3.0, 4.0]
    # a full extra lap replaces everything — no immortal slot
    for v in (7.0, 8.0, 9.0, 10.0):
        stats.record(v)
    assert sorted(stats.samples) == [7.0, 8.0, 9.0, 10.0]
    assert stats.count == 10


def test_percentiles_after_wraparound():
    stats = LatencyStats(capacity=8)
    for v in range(100):
        stats.record(float(v))
    report = stats.report()
    # reservoir holds exactly the last 8 samples: 92..99
    assert report["count"] == 100
    assert report["p50_ms"] >= 92.0
    assert report["p99_ms"] == 99.0


def test_report_under_concurrent_recording():
    """report() snapshots count/errors/samples under the lock; hammer it
    concurrently and require internally consistent output."""
    stats = LatencyStats(capacity=32)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            stats.record(float(i % 50))
            if i % 7 == 0:
                stats.record_error()
            i += 1

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            report = stats.report()
            if report["count"]:
                assert report["p50_ms"] is not None
                assert 0.0 <= report["p50_ms"] <= 49.0
    finally:
        stop.set()
        for t in threads:
            t.join()
    final = stats.report()
    assert final["count"] > 0 and final["errors"] > 0


def test_decode_window_stats_counters():
    """The ``decode.window`` block the continuous engine publishes:
    attended / read / full token accounting, the savings ratio (< 1
    means windowed decode cut KV traffic), the pow-2 bucket histogram,
    and safe empty-state reporting."""
    st = DecodeWindowStats()
    assert st.report() == {"attended_tokens": 0, "window_tokens": 0,
                           "full_tokens": 0, "savings_ratio": 1.0,
                           "attended_ratio": 1.0, "segments": 0,
                           "buckets": {}}
    # 2 rows x 4 steps at a 64-window inside a 256 cache
    st.record_segment(attended=300, window_read=2 * 4 * 64,
                      full_window=2 * 4 * 256, window=64)
    # 1 row x 4 steps at the full window
    st.record_segment(attended=900, window_read=4 * 256,
                      full_window=4 * 256, window=256)
    rep = st.report()
    assert rep["segments"] == 2
    assert rep["attended_tokens"] == 1200
    assert rep["window_tokens"] == 512 + 1024
    assert rep["full_tokens"] == 2048 + 1024
    assert rep["savings_ratio"] == round(1536 / 3072, 4)
    assert rep["attended_ratio"] == round(1200 / 3072, 4)
    assert rep["buckets"] == {"64": 1, "256": 1}


def test_decode_window_stats_concurrent():
    st = DecodeWindowStats()

    def write():
        for _ in range(200):
            st.record_segment(attended=10, window_read=32, full_window=64,
                              window=32)

    threads = [threading.Thread(target=write) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rep = st.report()
    assert rep["segments"] == 800
    assert rep["window_tokens"] == 800 * 32
    assert rep["savings_ratio"] == 0.5


def test_mesh_stats_counters():
    """The ``batching.mesh`` block a tensor-parallel engine publishes:
    layout, live per-device vs replicated byte gauges with their
    savings ratios, the analytic collective count, and safe
    empty-state reporting (savings 1.0 = no mesh benefit claimed)."""
    from lambdipy_tpu.runtime.metrics import MeshStats

    st = MeshStats()
    rep = st.report()
    assert rep["shape"] == {} and rep["devices"] == 1
    assert rep["hbm_savings"] == 1.0 and rep["param_savings"] == 1.0
    assert rep["segments_sharded"] == 0

    st.set_layout(shape={"tp": 2}, devices=2,
                  collectives_per_segment=16 * (2 * 32 + 1))
    st.set_kv_bytes(512, 1024)
    st.set_param_bytes(300, 500)
    st.record_segment()
    st.record_segment(2)
    rep = st.report()
    assert rep["shape"] == {"tp": 2} and rep["devices"] == 2
    assert rep["kv_bytes_per_device"] == 512
    assert rep["kv_bytes_replicated"] == 1024
    assert rep["hbm_savings"] == 0.5
    assert rep["param_savings"] == 0.6
    assert rep["collectives_per_segment"] == 16 * 65
    assert rep["segments_sharded"] == 3


def test_mesh_stats_concurrent():
    from lambdipy_tpu.runtime.metrics import MeshStats

    st = MeshStats()

    def write():
        for _ in range(200):
            st.record_segment()
            st.set_kv_bytes(1, 2)

    threads = [threading.Thread(target=write) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert st.report()["segments_sharded"] == 800


def test_pipeline_stats_empty_report():
    st = PipelineStats(depth=2)
    assert st.report() == {"depth": 2, "segments": 0, "dispatches": 0,
                           "wasted_overdecode_tokens": 0, "in_flight": {},
                           "drains": {}, "device_busy_s": 0.0,
                           "fetch_block_s": 0.0, "wall_s": 0.0,
                           "overlap_ratio": 0.0}


def test_pipeline_stats_counters_and_overlap_union():
    """The ``batching.pipeline`` block: in-flight histogram, drain
    causes, wasted over-decode tokens, and the overlap ratio — device
    busy is the UNION of per-segment [dispatch, compute-ready]
    intervals, so two overlapping in-flight segments count their shared
    window once."""
    st = PipelineStats(depth=2)
    st.record_dispatch(1)
    st.record_dispatch(2)
    st.record_dispatch(2)
    # seg A: dispatched t=0, ready t=1. seg B: dispatched t=0.5 (while A
    # in flight), ready t=2 -> union busy = [0, 2] = 2.0, not 2.5
    st.record_collect(0.0, 1.0, fetch_s=0.2, wasted=0)
    st.record_collect(0.5, 2.0, fetch_s=0.3, wasted=4)
    st.record_drain("joiner")
    st.record_drain("complete")
    st.record_drain("complete")
    st.record_wall(4.0)
    rep = st.report()
    assert rep["dispatches"] == 3 and rep["segments"] == 2
    assert rep["in_flight"] == {"1": 1, "2": 2}
    assert rep["drains"] == {"joiner": 1, "complete": 2}
    assert rep["wasted_overdecode_tokens"] == 4
    assert rep["device_busy_s"] == 2.0
    assert rep["fetch_block_s"] == 0.5
    assert rep["wall_s"] == 4.0
    assert rep["overlap_ratio"] == 0.5


@pytest.mark.parametrize("cause", ["joiner", "handover", "complete"])
def test_pipeline_stats_reports_each_drain_cause(cause):
    """``drains`` is keyed by the barrier's cause, each counted on its
    own: ``handover`` (a row's whole output dispatched with a joiner
    waiting for its slot) beside ``joiner`` and ``complete``."""
    st = PipelineStats(depth=2)
    for other in ("joiner", "handover", "complete"):
        st.record_drain(other)
    st.record_drain(cause)
    want = {"joiner": 1, "handover": 1, "complete": 1}
    want[cause] = 2
    assert st.report()["drains"] == want


def test_scheduler_report_counts_grants_ahead():
    """``/metrics`` ``sched.granted_ahead``: tickets granted against a run
    slot about to be released; 0 in the report until one is, and a call
    that finds nobody queued is not counted."""
    from lambdipy_tpu.sched import SchedConfig, Scheduler

    sched = Scheduler(SchedConfig(max_concurrency=1))
    assert sched.report()["granted_ahead"] == 0
    holder = sched.admit()
    assert sched.grant_ahead() is False
    assert sched.report()["granted_ahead"] == 0
    queued = sched.admit()
    assert sched.grant_ahead() is True
    rep = sched.report()
    assert rep["granted_ahead"] == 1 and rep["running"] == 2
    assert rep["max_concurrency"] == 1
    for t in (holder, queued):
        sched.finish(t, service_ms=1.0)
    rep = sched.report()
    assert rep["granted_ahead"] == 1 and rep["running"] == 0


def test_pipeline_stats_disjoint_intervals_sum():
    """Non-overlapping segments (the depth-1 synchronous loop) sum their
    individual compute windows — the ratio then reads the device's real
    duty cycle."""
    st = PipelineStats(depth=1)
    st.record_collect(0.0, 1.0, fetch_s=0.5, wasted=0)
    st.record_collect(2.0, 2.5, fetch_s=0.5, wasted=0)  # idle gap 1..2
    st.record_wall(2.5)
    rep = st.report()
    assert rep["device_busy_s"] == 1.5
    assert rep["overlap_ratio"] == 0.6


def test_pipeline_stats_open_episode_wall():
    """A /metrics scrape mid-episode folds the OPEN episode into wall:
    under sustained traffic the engine never goes idle, so overlap_ratio
    would otherwise read 0.0 forever (first episode) or divide by only
    the completed episodes' wall (> 1.0 ratios later)."""
    import time

    st = PipelineStats(depth=2)
    st.begin_episode(time.monotonic() - 2.0)
    st.record_collect(0.0, 1.0, fetch_s=0.1, wasted=0)
    rep = st.report()
    assert rep["wall_s"] >= 2.0
    assert 0.0 < rep["overlap_ratio"] <= 1.0
    st.record_wall(2.0)  # closes the episode
    assert st.report()["wall_s"] == 2.0


def test_pipeline_stats_concurrent():
    st = PipelineStats()

    def write():
        for i in range(200):
            st.record_dispatch(1 + i % 2)
            st.record_collect(float(i), float(i) + 0.5, fetch_s=0.1,
                              wasted=1)

    threads = [threading.Thread(target=write) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rep = st.report()
    assert rep["dispatches"] == 800 and rep["segments"] == 800
    assert rep["wasted_overdecode_tokens"] == 800
    assert rep["in_flight"] == {"1": 400, "2": 400}


def test_prefix_cache_stats_counters():
    """The /metrics counter block the radix prefix store publishes:
    hit/miss/hit_tokens accounting, byte/block bookkeeping through
    insert + evict, and a rate that never divides by zero."""
    st = PrefixCacheStats()
    assert st.report() == {"hits": 0, "misses": 0, "hit_rate": 0.0,
                           "hit_tokens": 0, "evictions": 0, "bytes": 0,
                           "blocks": 0, "assemblies": 0,
                           "assembly_bytes_peak": 0}
    st.record_request(0)        # miss
    st.record_request(64)       # hit, 64 reused tokens
    st.record_request(32)
    st.record_insert(2, 8192)
    st.record_insert(1, 4096)
    st.record_evict(1, 4096)
    rep = st.report()
    assert rep["hits"] == 2 and rep["misses"] == 1
    assert rep["hit_rate"] == round(2 / 3, 4)
    assert rep["hit_tokens"] == 96
    assert rep["blocks"] == 2 and rep["bytes"] == 8192
    assert rep["evictions"] == 1


def test_prefix_cache_assembly_peak_gauge():
    """``assembly_bytes_peak`` is ALWAYS reported — 0 until an assembly
    happens, so the paged path's zero-copy claim is an observable fact
    rather than a missing key — and tracks the LARGEST single assembled
    cache, not a running sum."""
    st = PrefixCacheStats()
    rep = st.report()
    assert rep["assembly_bytes_peak"] == 0 and rep["assemblies"] == 0
    st.record_assembly(1 << 20)
    st.record_assembly(1 << 18)          # smaller: peak must not move
    rep = st.report()
    assert rep["assemblies"] == 2
    assert rep["assembly_bytes_peak"] == 1 << 20
    st.record_assembly(1 << 21)
    assert st.report()["assembly_bytes_peak"] == 1 << 21


def test_page_pool_stats_counters():
    """The paged-KV allocator's counter block (``batching.page_pool``):
    alloc/release count calls AND pages, shares count refcount bumps
    (each one a zero-copy prefix-hit page), sheds count priced
    PagesExhausted refusals."""
    from lambdipy_tpu.runtime.metrics import PagePoolStats

    st = PagePoolStats()
    assert st.report() == {"allocs": 0, "alloc_pages": 0, "releases": 0,
                           "release_pages": 0, "shares": 0, "sheds": 0}
    st.record_alloc(3)
    st.record_alloc(1)
    st.record_release(2)
    st.record_share(4)
    st.record_shed()
    rep = st.report()
    assert rep["allocs"] == 2 and rep["alloc_pages"] == 4
    assert rep["releases"] == 1 and rep["release_pages"] == 2
    assert rep["shares"] == 4 and rep["sheds"] == 1


def test_page_pool_stats_concurrent():
    import threading

    from lambdipy_tpu.runtime.metrics import PagePoolStats

    st = PagePoolStats()

    def work():
        for _ in range(200):
            st.record_alloc(2)
            st.record_share()
            st.record_release(2)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rep = st.report()
    assert rep["alloc_pages"] == rep["release_pages"] == 1600
    assert rep["shares"] == 800


# -- disaggregated-serving counters ------------------------------------------


def test_kv_ship_stats_report_shape():
    from lambdipy_tpu.runtime.metrics import KvShipStats

    st = KvShipStats()
    rep = st.report()
    assert rep["exports"] == rep["imports"] == 0
    assert rep["import_blocks"] == {"inserted": 0, "present": 0}
    st.record_export(tokens=32, nbytes=1000)
    st.record_import(tokens=32, nbytes=1000, inserted=2, present=0,
                     mode="paged")
    st.record_import(tokens=16, nbytes=600, inserted=0, present=1,
                     mode="dense")
    st.record_backpressure()
    st.record_rejected()
    rep = st.report()
    assert rep["exports"] == 1 and rep["export_bytes"] == 1000
    assert rep["imports"] == 2 and rep["import_bytes"] == 1600
    assert rep["import_blocks"] == {"inserted": 2, "present": 1}
    assert rep["imports_zero_copy"] == 1
    assert rep["imports_assembled"] == 1
    assert rep["import_backpressure"] == 1
    assert rep["import_rejected"] == 1


def test_disagg_stats_ewma_and_fallbacks():
    from lambdipy_tpu.runtime.metrics import DisaggStats

    st = DisaggStats()
    assert st.report()["ships"] == 0
    # first ship seeds the EWMAs exactly; later ships smooth (alpha .2)
    st.record_ship(nbytes=1000, ms=10.0)
    rep = st.report()
    assert rep["ship_bytes_ewma"] == 1000.0 and rep["ship_ms_ewma"] == 10.0
    st.record_ship(nbytes=2000, ms=20.0)
    rep = st.report()
    assert rep["ship_bytes_ewma"] == 1200.0
    assert rep["ship_ms_ewma"] == 12.0
    assert rep["ships"] == 2 and rep["ship_bytes_total"] == 3000
    st.count("prefill_dispatches")
    st.count("decode_dispatches")
    st.count("ship_skips", 3)
    st.record_fallback("export_failed")
    st.record_fallback("export_failed")
    st.record_fallback("no_prefill_replica")
    st.record_import_result(inserted=2, present=1, mode="paged")
    rep = st.report()
    assert rep["prefill_dispatches"] == 1
    assert rep["decode_dispatches"] == 1
    assert rep["ship_skips"] == 3
    assert rep["fallbacks"] == {"export_failed": 2,
                                "no_prefill_replica": 1}
    assert rep["import_blocks"] == {"inserted": 2, "present": 1}
    assert rep["imports_zero_copy"] == 1


def test_disagg_stats_threaded_counts():
    from lambdipy_tpu.runtime.metrics import DisaggStats

    st = DisaggStats()

    def worker():
        for _ in range(200):
            st.count("ship_skips")
            st.record_fallback("x")
            st.record_ship(nbytes=10, ms=1.0)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rep = st.report()
    assert rep["ship_skips"] == 800
    assert rep["fallbacks"]["x"] == 800
    assert rep["ships"] == 800 and rep["ship_bytes_total"] == 8000


def test_session_stats_report_shape():
    from lambdipy_tpu.runtime.metrics import SessionStats

    st = SessionStats()
    st.count("opened")
    st.count("sticky_hits", 3)
    st.count("failovers")
    st.count("reships")
    st.count("deletes")
    st.record_fallback("old_home_unreachable")
    st.record_fallback("old_home_unreachable")
    st.record_fallback("import_backpressure")
    rep = st.report()
    assert rep["opened"] == 1 and rep["sticky_hits"] == 3
    assert rep["sticky_misses"] == 0
    assert rep["failovers"] == 1 and rep["reships"] == 1
    assert rep["deletes"] == 1
    assert rep["reship_fallbacks"] == {"old_home_unreachable": 2,
                                       "import_backpressure": 1}


def test_prefix_store_stats_pin_surface():
    """The session-pin gauges ride prefixstore.stats() even on an empty
    tree — an operator watching pins squeeze cache headroom must see
    zeros, not missing keys."""
    from types import SimpleNamespace

    from lambdipy_tpu.runtime.prefixstore import PrefixStore

    server = SimpleNamespace(
        model=SimpleNamespace(cfg=SimpleNamespace(max_len=128)))
    store = PrefixStore(server, block=16, budget_mb=1,
                        pin_budget_mb=0.5)
    st = store.stats()
    for key in ("sessions_active", "pinned_leaves", "pinned_bytes",
                "pin_budget_bytes", "pin_sheds", "pin_overflows",
                "pin_expiries", "pin_invalidations", "pin_faults"):
        assert key in st, key
    assert st["pin_budget_bytes"] == int(0.5 * 2**20)
    assert st["pinned_leaves"] == 0 and st["sessions_active"] == 0
    # a session on a sub-block prompt still opens (lease + DELETE work)
    store.pin_session("s", [1, 2, 3])
    st = store.stats()
    assert st["sessions_active"] == 1 and st["pinned_leaves"] == 0
    assert store.end_session("s")["released"]


def test_page_pool_merges_pinned_gauges():
    """batching.page_pool surfaces the store's pinned-page gauges via
    the pinned_fn hook (merged OUTSIDE the pool lock), and a broken
    provider never breaks the stats document."""
    from lambdipy_tpu.runtime.pagepool import PagePool

    pool = PagePool(n_pages=9, page=16, page_bytes=1024)
    pool.pinned_fn = lambda: {"pinned_pages": 3, "pinned_bytes": 3072,
                              "pin_budget_bytes": 8192, "pin_sheds": 1}
    st = pool.stats()
    assert st["pinned_pages"] == 3 and st["pinned_bytes"] == 3072
    assert st["pin_budget_bytes"] == 8192 and st["pin_sheds"] == 1

    def broken():
        raise RuntimeError("boom")

    pool.pinned_fn = broken
    assert "pages_total" in pool.stats()  # still serves


def test_kv_ship_stats_stream_counters():
    from lambdipy_tpu.runtime.metrics import KvShipStats

    st = KvShipStats()
    rep = st.report()
    assert rep["export_streams"] == rep["import_streams"] == 0
    assert rep["import_stream_aborts"] == 0
    # a monolithic export/import never bumps the stream counters
    st.record_export(tokens=32, nbytes=1000)
    st.record_import(tokens=32, nbytes=1000, inserted=2, present=0,
                     mode="dense")
    rep = st.report()
    assert rep["export_streams"] == 0 and rep["import_streams"] == 0
    # chunked ones do, and aborts are their own row
    st.record_export(tokens=64, nbytes=2000, chunks=4)
    st.record_import(tokens=64, nbytes=2000, inserted=4, present=0,
                     mode="paged", chunks=4)
    st.record_stream_abort()
    rep = st.report()
    assert rep["exports"] == 2 and rep["export_streams"] == 1
    assert rep["export_chunks"] == 4
    assert rep["imports"] == 2 and rep["import_streams"] == 1
    assert rep["import_chunks"] == 4
    assert rep["import_stream_aborts"] == 1


def test_disagg_stats_pipelined_and_util():
    from lambdipy_tpu.runtime.metrics import DisaggStats

    st = DisaggStats()
    rep = st.report()
    assert rep["ships_pipelined"] == 0 and rep["chunks_relayed"] == 0
    assert rep["mid_stream_failures"] == 0 and rep["util"] == {}
    st.record_ship(nbytes=1000, ms=10.0)            # monolithic
    st.record_ship(nbytes=2000, ms=20.0, chunks=4)  # chunked, BLOCKING
    st.record_ship(nbytes=3000, ms=30.0, chunks=5,
                   pipelined=True)                  # chunked, pipelined
    st.count("mid_stream_failures")
    rep = st.report()
    # pipelined is an explicit flag: the buffer-then-relay baseline
    # ships chunk frames too but must not count as overlapped
    assert rep["ships"] == 3 and rep["ships_pipelined"] == 1
    assert rep["chunks_relayed"] == 9
    assert rep["mid_stream_failures"] == 1
    # util EWMA: first sample seeds, later samples smooth (alpha .3),
    # and out-of-range samples clamp
    st.record_util("prefill", 0.5)
    assert st.report()["util"] == {"prefill": 0.5}
    st.record_util("prefill", 1.0)
    assert abs(st.report()["util"]["prefill"] - 0.65) < 1e-9
    st.record_util("decode", 7.0)   # clamps to 1.0
    st.record_util("mixed", -1.0)   # clamps to 0.0
    util = st.report()["util"]
    assert util["decode"] == 1.0 and util["mixed"] == 0.0


def test_session_stats_drain_reships():
    from lambdipy_tpu.runtime.metrics import SessionStats

    st = SessionStats()
    assert st.report()["drain_reships"] == 0
    st.count("drain_reships", 2)
    rep = st.report()
    assert rep["drain_reships"] == 2 and rep["reships"] == 0


# -- faults.armed (the chaos-soak observability satellite) -------------------


def test_fault_plan_armed_report_shape_and_remaining():
    """faults.armed: sites/kinds/remaining fire counts for the live
    plan — remaining decrements as rules fire, hang rules report inf,
    and per-site call counters ride along."""
    from lambdipy_tpu.runtime.faults import FaultPlan, InjectedFault

    plan = FaultPlan.from_spec(
        "transport:delay@ms=7,n=2;segment_fetch:hang")
    armed = plan.armed()
    assert armed["active"]
    assert armed["sites"] == ["segment_fetch", "transport"]
    by_site = {r["site"]: r for r in armed["rules"]}
    assert by_site["transport"]["kind"] == "delay"
    assert by_site["transport"]["ms"] == 7.0
    assert by_site["transport"]["remaining"] == 2
    assert by_site["segment_fetch"]["n"] == "inf"
    assert by_site["segment_fetch"]["remaining"] == "inf"
    plan.check("transport")  # fires the delay once
    armed = plan.armed()
    by_site = {r["site"]: r for r in armed["rules"]}
    assert by_site["transport"]["fired"] == 1
    assert by_site["transport"]["remaining"] == 1
    assert armed["counts"] == {"transport": 1}
    assert not FaultPlan.empty().armed()["active"]


def test_router_metrics_exposes_armed_faults():
    """The fleet /metrics document carries the router process's live
    plan under faults.armed — a soak run (or a stray
    LAMBDIPY_FLEET_FAULT) is visible at the front door; a distinct
    pool plan reports alongside."""
    from lambdipy_tpu.fleet import FleetRouter, ReplicaPool
    from lambdipy_tpu.runtime.faults import FaultPlan

    plan = FaultPlan.from_spec("route_connect:exception@n=3")
    pool = ReplicaPool(faults=plan)
    router = FleetRouter(pool, faults=plan)
    try:
        armed = router.metrics()["faults"]
        assert armed["armed"]["active"]
        assert armed["armed"]["sites"] == ["route_connect"]
        assert "pool_armed" not in armed  # shared plan: one report
    finally:
        router._httpd.server_close()
        pool.close()
    probe_plan = FaultPlan.from_spec("probe:exception@n=1")
    pool2 = ReplicaPool(faults=probe_plan)
    router2 = FleetRouter(pool2, faults=FaultPlan.empty())
    try:
        armed = router2.metrics()["faults"]
        assert not armed["armed"]["active"]
        assert armed["pool_armed"]["sites"] == ["probe"]
    finally:
        router2._httpd.server_close()
        pool2.close()
