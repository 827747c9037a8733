"""Automatic cross-request prefix KV cache (radix reuse): bitwise
on/off parity — greedy and seeded-sampled, solo, streamed and under
concurrent continuous-batching traffic — plus budget eviction and the
scheduler's suffix pricing."""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from lambdipy_tpu.runtime.prefixstore import PrefixStore


@pytest.fixture(scope="module")
def tiny_server():
    from lambdipy_tpu.models import registry

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    return adapter.make_server(params)


def test_radix_match_extend_and_counters(tiny_server):
    """Cold prompt inserts its whole blocks (miss), a sharing prompt
    hits, a longer one extends the match — counters track each."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    row = list(range(1, 41)) + [7, 8, 9]  # 43 tokens -> 32 cacheable
    assert store.route(row) == 32
    st = store.stats()
    assert (st["misses"], st["hits"], st["blocks"]) == (1, 0, 2)
    # shares both blocks -> hit, no new insertion
    row2 = row[:32] + [5, 5, 5, 5, 5]
    assert store.route(row2) == 32
    st = store.stats()
    assert (st["hits"], st["hit_tokens"], st["blocks"]) == (1, 32, 2)
    # extends one block past the match
    row3 = row[:43] + list(range(50, 60))  # 53 tokens -> 48 cacheable
    assert store.route(row3) == 48
    st = store.stats()
    assert (st["hits"], st["hit_tokens"], st["blocks"]) == (2, 64, 3)
    # sub-block prompts can never cache and are not counted
    assert store.route([1, 2, 3]) == 0
    assert store.stats()["misses"] == 1
    # a prompt the model can never serve must not walk (or pollute the
    # LRU / burn a window of prefill) — it stands down untouched
    before = store.stats()
    assert store.route(list(range(1, 300))) == 0  # > max_len (128)
    assert store.stats() == before
    assert store.match_len(row3) == 48 and store.match_len([9, 9]) == 0


def test_bitwise_parity_greedy_sampled_and_reassembly(tiny_server):
    """Routed output is BITWISE the unrouted output for greedy and
    seeded-sampled decode — including after the assembled full-window
    cache is dropped and must reassemble from the tree's block
    slices."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    row = list(range(3, 45))  # 42 tokens -> 32 cacheable
    for kw in ({}, dict(temperature=0.9, seed=7, top_k=5, top_p=0.95)):
        off = tiny_server.generate(row, max_new_tokens=8, **kw)
        m = store.route(row)
        assert m == 32
        on = tiny_server.generate(row[m:], prefix=row[:m],
                                  max_new_tokens=8, **kw)
        np.testing.assert_array_equal(on, off, err_msg=str(kw))
    # drop the assembled entries: the next route must reassemble the
    # full-window cache from stored blocks, with identical output
    with tiny_server._prefix_lock:
        tiny_server._prefixes.clear()
    off = tiny_server.generate(row, max_new_tokens=8)
    m = store.route(row)
    on = tiny_server.generate(row[m:], prefix=row[:m], max_new_tokens=8)
    np.testing.assert_array_equal(on, off)


def test_streamed_parity_from_routed_prefix(tiny_server):
    """Streaming from a radix-matched prefix concatenates to the fused
    unrouted output."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    row = list(range(2, 40))  # 38 tokens -> 32 cacheable
    off = tiny_server.generate(row, max_new_tokens=11)
    m = store.route(row)
    chunks = list(tiny_server.generate_stream(
        row[m:], prefix=row[:m], max_new_tokens=11, segment=4))
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), off)


def test_parity_under_concurrent_continuous_traffic(tiny_server):
    """The acceptance bar: routed requests join the continuous engine
    next to unrouted traffic and every row's tokens are bitwise its
    solo output — greedy and seeded-sampled, cold and hot."""
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher

    cb = ContinuousBatcher(tiny_server, slots=4, segment=4)
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    shared = list(range(1, 34))  # 33 tokens of shared material
    reqs = [
        dict(row=shared + [40, 41], kw={}),
        dict(row=shared + [50, 51, 52], kw=dict(temperature=0.9, seed=7)),
        dict(row=[9, 8, 7], kw={}),  # unrouted neighbor
        dict(row=shared + [60], kw=dict(temperature=1.2, top_k=3, seed=3)),
    ]
    solo = [tiny_server.generate(r["row"], max_new_tokens=8, **r["kw"])
            for r in reqs]
    # seed the tree once so the concurrent burst actually HITS (a fully
    # concurrent cold burst counts as misses — each arrives before any
    # insertion lands; the inflight dedup still collapses the walk)
    store.route(reqs[0]["row"])

    def run(r):
        row = r["row"]
        m = store.route(row)
        if m > 0:
            return cb.generate(row[m:], max_new_tokens=8, prefix=row[:m],
                               **r["kw"])
        return cb.generate(row, max_new_tokens=8, **r["kw"])

    with ThreadPoolExecutor(max_workers=4) as ex:
        futs = [ex.submit(run, r) for r in reqs]
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(f.result(), solo[i],
                                          err_msg=f"request {i} diverged")
    stats = cb.stats()
    assert stats["prefix_joins"] >= 2, stats
    assert store.stats()["hits"] >= 2, store.stats()


def test_budget_evicts_lru_leaf_blocks(tiny_server):
    """Inserts beyond the HBM budget evict least-recently-used leaf
    blocks; bytes stay within budget and the counters say so."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    # measure a block's bytes from a first insert
    store.route(list(range(1, 20)))  # 1 block
    per_block = store.stats()["bytes"]
    small = PrefixStore(tiny_server, block=16,
                        budget_mb=1.5 * per_block / 2**20)
    small.route(list(range(1, 40)))   # 2 blocks -> evicts down to 1
    st = small.stats()
    assert st["evictions"] >= 1, st
    assert st["bytes"] <= small.budget_bytes, st
    # the surviving tree still serves correct (possibly shorter) matches
    row = list(range(1, 40))
    off = tiny_server.generate(row, max_new_tokens=8)
    m = small.route(row)
    if m > 0:
        on = tiny_server.generate(row[m:], prefix=row[:m],
                                  max_new_tokens=8)
        np.testing.assert_array_equal(on, off)


def test_wide_chunk_cold_walk_matches_block_walk(tiny_server, monkeypatch):
    """Cold walks dispatch in wide chunks (here the server's
    prefill_chunk family) with a block-width tail: bitwise the same
    output and the same stored blocks as pure block-width walking."""
    monkeypatch.setattr(tiny_server, "prefill_chunk", 32, raising=False)
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    assert store.walk_chunk == 32
    row = list(range(1, 92))  # 91 tokens -> target 80: 32-wide x2 + 16
    off = tiny_server.generate(row, max_new_tokens=8)
    m = store.route(row)
    assert m == 80
    on = tiny_server.generate(row[m:], prefix=row[:m], max_new_tokens=8)
    np.testing.assert_array_equal(on, off)
    st = store.stats()
    assert st["blocks"] == 5 and st["assembled_entries"] >= 1
    assert st["assembled_bytes"] > 0


def test_concurrent_cold_requests_collapse_to_one_walk(tiny_server):
    """A thundering herd of first requests for the SAME prefix performs
    one extension walk (inflight dedup), and all of them match."""
    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    row = list(range(5, 60))  # 55 tokens -> 48 cacheable

    with ThreadPoolExecutor(max_workers=4) as ex:
        ms = list(ex.map(lambda _: store.route(list(row)), range(4)))
    assert ms == [48] * 4
    st = store.stats()
    assert st["blocks"] == 3, st  # inserted exactly once


def test_sched_prices_suffix_not_full_prompt(tiny_server):
    """runtime/server.py admission subtracts the prefix probe's matched
    tokens — deadline shedding must price what the device will actually
    prefill."""
    from lambdipy_tpu.runtime.server import _request_token_counts

    store = PrefixStore(tiny_server, block=16, budget_mb=8)
    row = list(range(1, 49))  # 48 tokens -> 32 cacheable (one must stay)
    store.route(row)
    req = {"tokens": row, "max_new_tokens": 8}
    prefill, decode = _request_token_counts(req, prefix_probe=store.match_len)
    assert (prefill, decode) == (len(row) - 32, 8)
    # no probe -> full prompt; explicit prefix -> client's split priced
    assert _request_token_counts(req)[0] == len(row)
    with_prefix = {"tokens": [1, 2], "prefix": row, "max_new_tokens": 4}
    assert _request_token_counts(
        with_prefix, prefix_probe=store.match_len)[0] == len(row) + 2
    # a failing probe is advisory: fall back to the full count
    def boom(_):
        raise RuntimeError("probe down")
    assert _request_token_counts(req, prefix_probe=boom)[0] == len(row)


@pytest.mark.slow  # bundle build + boot (~25 s); the routing logic and
# parity are covered non-slow above — this is the handler wiring proof
def test_handler_routes_automatically(tmp_path):
    """End-to-end through the generate handler: plain token requests
    ride the radix cache by default — the response says so, /metrics
    counters move, and output is bitwise the unrouted multi-row path
    (multi-row requests skip routing, giving an in-bundle reference)."""
    from tests.test_runtime import make_model_bundle
    from lambdipy_tpu.runtime.loader import load_bundle

    bundle = make_model_bundle(
        tmp_path, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "8", "prefix_block": "16",
               "prefix_cache_mb": "8"})
    r = load_bundle(bundle, warmup=True)
    assert r.state.meta["prefix_cache"] is True
    row = list(range(1, 44))
    # multi-row requests skip auto-routing: an unrouted reference
    ref = r.state.invoke({"tokens": [row, row]})
    assert ref["ok"], ref
    first = r.state.invoke({"tokens": row})
    second = r.state.invoke({"tokens": row})
    assert first["ok"] and second["ok"]
    assert first["prefix_cached"] and second["prefix_cached"]
    assert first["tokens"][0] == ref["tokens"][0]
    assert second["tokens"] == first["tokens"]
    assert first["n_prompt"] == len(row)
    pc = r.state.stats()["prefix_cache"]
    assert pc["hits"] >= 1 and pc["misses"] >= 1 and pc["bytes"] > 0
    assert r.state.prefix_probe(row) > 0
