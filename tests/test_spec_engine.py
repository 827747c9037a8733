"""Speculative decoding inside the continuous engine (spec_k): batched
draft/verify with collector rollback, bitwise the plain engine for
greedy AND seeded-sampled rows (chain-deterministic acceptance).

Wall-clock discipline: every non-slow test shares ONE engine shape
(slots=2, segment=4, kb=4) over the session tiny_server so the
("spec_seg", ...) program family compiles once for the module; the
paged twin and pipeline depth 2 are ``test_spec_engine_paged_parity``
and ``test_spec_engine_pipeline_depth2``."""

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from lambdipy_tpu.runtime.continuous import ContinuousBatcher


def _mk(tiny_server, **kw):
    args = dict(slots=2, segment=4, spec_k=4)
    args.update(kw)
    return ContinuousBatcher(tiny_server, **args)


def _fresh_metrics(cb):
    """Engines share the server's SpecDecodeStats by default (one
    /metrics surface); tests that assert counters isolate them."""
    from lambdipy_tpu.runtime.metrics import SpecDecodeStats

    cb.spec_metrics = SpecDecodeStats()
    return cb.spec_metrics


def test_spec_engine_matches_solo_greedy(tiny_server):
    """The bitwise contract: concurrent staggered rows through a
    spec_k engine emit exactly their solo greedy outputs — speculation
    changes tokens-per-weight-read, never the tokens."""
    cb = _mk(tiny_server)
    prompts = [[1, 2, 3, 5], [9, 8, 7]]
    n = 12
    solo = [tiny_server.generate(p, max_new_tokens=n) for p in prompts]
    results = [None] * 2

    def run(i):
        time.sleep(0.01 * i)  # staggered arrivals, mid-flight joins
        results[i] = cb.generate(prompts[i], max_new_tokens=n)

    with ThreadPoolExecutor(max_workers=2) as ex:
        list(ex.map(run, range(2)))
    for i in range(2):
        np.testing.assert_array_equal(results[i], solo[i],
                                      err_msg=f"request {i} diverged")
    stats = cb.stats()
    assert stats["spec"]["k"] == 4
    assert stats["spec"]["steps"] > 0


def test_spec_engine_sampled_rows_bitwise(tiny_server):
    """Seeded-sampled rows keep their reproducibility promise through
    the verify chunks: acceptance re-derives the row's own PRNG chain,
    so the engine output equals solo sampling bitwise — the property
    rejection-sampling verification cannot offer."""
    cb = _mk(tiny_server)
    prompts = [[5, 6, 7], [1, 2, 3, 4]]
    kws = [dict(temperature=0.9, seed=7),
           dict(temperature=0.7, top_k=16, top_p=0.9, seed=3)]
    solo = [tiny_server.generate(p, max_new_tokens=10, **kw)
            for p, kw in zip(prompts, kws)]
    with ThreadPoolExecutor(max_workers=2) as ex:
        outs = list(ex.map(
            lambda a: cb.generate(a[0], max_new_tokens=10, **a[1]),
            zip(prompts, kws)))
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o, solo[i], err_msg=f"row {i}")


def test_spec_engine_accepts_on_repetitive_decode(tiny_server):
    """A prompt whose greedy decode cycles verifies >1 token per weight
    read through the engine, and the counters ride stats()['spec']."""
    cb = _mk(tiny_server)
    metrics = _fresh_metrics(cb)
    ref = tiny_server.generate([5, 6, 7, 8], max_new_tokens=32)
    out = cb.generate([5, 6, 7, 8], max_new_tokens=32)
    np.testing.assert_array_equal(out, ref)
    rep = metrics.report()
    assert rep["tokens_per_step"] > 1.0, rep
    assert rep["emitted_tokens"] >= 32, rep
    assert rep["acceptance_rate"] > 0.0, rep
    assert rep["tokens_per_step_hist"], rep


def test_spec_engine_eos_inside_accepted_block(tiny_server):
    """EOS emitted mid-draft-block latches exactly like the plain
    engine: host-side truncation + filler parity with the fused path."""
    cb = _mk(tiny_server)
    free = tiny_server.generate([5, 6, 7, 8], max_new_tokens=10)[0]
    eos = int(free[3])
    ref = tiny_server.generate([5, 6, 7, 8], max_new_tokens=10,
                               eos_id=eos)
    out = cb.generate([5, 6, 7, 8], max_new_tokens=10, eos_id=eos)
    np.testing.assert_array_equal(out, ref)


def test_spec_engine_stream_and_logprobs(tiny_server):
    """Streamed chunks (per-segment slices of accepted tokens)
    concatenate to the fused output, and logprobs ride the same fetch."""
    cb = _mk(tiny_server)
    ref_t, ref_l = tiny_server.generate([1, 2, 3], max_new_tokens=12,
                                        return_logprobs=True)
    got = list(cb.generate_stream([1, 2, 3], max_new_tokens=12,
                                  return_logprobs=True))
    st = np.concatenate([c for c, _ in got], axis=1)
    sl = np.concatenate([lp for _, lp in got], axis=1)
    np.testing.assert_array_equal(st[:, :12], ref_t)
    np.testing.assert_allclose(sl[:, :12], ref_l, rtol=1e-5, atol=1e-6)


def test_spec_engine_pipeline_depth2(tiny_server):
    """Depth-2 pipelining composes: in-flight records carry
    dispatch-time draft state (lookup extrapolated across in-flight
    steps), the collector reconciles from fetched truth, and outputs
    stay bitwise depth-1's (== solo's) for greedy and sampled rows.
    Same engine shape as the rest of the module — depth is host-side,
    so no new programs compile."""
    prompts = [[5, 6, 7, 8], [2, 4, 6]]
    solo = [tiny_server.generate(p, max_new_tokens=16) for p in prompts]
    solo_s = tiny_server.generate([5, 6, 7, 8], max_new_tokens=16,
                                  temperature=0.8, seed=5)
    cb = _mk(tiny_server, pipeline_depth=2)
    with ThreadPoolExecutor(max_workers=2) as ex:
        outs = list(ex.map(
            lambda p: cb.generate(p, max_new_tokens=16), prompts))
    for o, r in zip(outs, solo):
        np.testing.assert_array_equal(o, r)
    np.testing.assert_array_equal(
        cb.generate([5, 6, 7, 8], max_new_tokens=16, temperature=0.8,
                    seed=5), solo_s)


def test_spec_engine_keeps_todays_drains(tiny_server):
    """The slot handover needs a row's EXACT dispatched count, and a
    verify step books an upper bound the collector refunds: a row of a
    speculative engine (lookup mode: every dispatch a verify step) is
    never announced as ending and never handed over ahead of its
    collect. With a joiner waiting for a slot from the start the drains
    are today's causes and the outputs bitwise solo's."""
    from tests.test_continuous_pipeline import drained, hold_first_prefill

    # two lengths: the short row's booked count passes its n (with its
    # verify steps still in flight) while the long one keeps the engine
    # dispatching, which is where a handover would come too early
    reqs = [([5, 6, 7, 8], 16), ([2, 4, 6], 32), ([9, 8, 7], 16)]
    solo = [tiny_server.generate(p, max_new_tokens=n) for p, n in reqs]
    cb = _mk(tiny_server, pipeline_depth=2)
    told = []
    cb.row_ending_fn = lambda limit: told.append(1)
    # the third request is enqueued before the first two decode
    hold_first_prefill(cb, lambda: sum(
        e is not None for e in cb._active) + len(cb._joiners) == 3)
    with ThreadPoolExecutor(max_workers=3) as ex:
        outs = list(ex.map(
            lambda r: cb.generate(r[0], max_new_tokens=r[1]), reqs))
    for o, r in zip(outs, solo):
        np.testing.assert_array_equal(o, r)
    pipe = drained(cb)
    assert told == []
    assert pipe["drains"] and set(pipe["drains"]) <= {"joiner", "complete"}, \
        pipe
    assert cb.stats()["spec"]["steps"] > 0


def test_spec_engine_prefix_rows_join(tiny_server):
    """A prefix= row joins the speculative engine from its cached KV;
    the prefix tokens feed the drafts and output parity holds."""
    cb = _mk(tiny_server)
    prefix, suffix = list(range(1, 20)), [4, 5]
    ref = tiny_server.generate(prefix + suffix, max_new_tokens=12)
    out = cb.generate(suffix, max_new_tokens=12, prefix=prefix)
    np.testing.assert_array_equal(out, ref)
    assert cb.prefix_joins == 1


def test_spec_k_normalization(tiny_server):
    """spec_k <= 1 disables (k=1 IS the plain path); k bucketizes to a
    pow-2 so the program count stays bounded."""
    assert ContinuousBatcher(tiny_server, spec_k=0).spec_k == 0
    assert ContinuousBatcher(tiny_server, spec_k=1).spec_k == 0
    assert ContinuousBatcher(tiny_server, spec_k=3).spec_k == 4
    assert ContinuousBatcher(tiny_server, spec_k=8).spec_k == 8


def test_spec_engine_replay_after_failure(tiny_server, monkeypatch):
    """An engine failure mid-spec-decode replays no-bytes rows through a
    restarted engine bitwise (chain-deterministic acceptance makes the
    replay independent of what the new drafts propose)."""
    ref = tiny_server.generate([5, 6, 7, 8], max_new_tokens=12,
                               temperature=0.8, seed=9)
    cb = _mk(tiny_server, max_replays=1)
    real = cb._spec_draft
    state = {"n": 0}

    def flaky(entry, kb, q=None, **kw):
        state["n"] += 1
        if state["n"] == 2:
            raise RuntimeError("injected draft-time failure")
        return real(entry, kb, q, **kw)

    monkeypatch.setattr(cb, "_spec_draft", flaky)
    out = cb.generate([5, 6, 7, 8], max_new_tokens=12, temperature=0.8,
                      seed=9)
    np.testing.assert_array_equal(out, ref)
    assert cb.fault_stats.replays_attempted >= 1


def test_spec_engine_paged_parity():
    """The paged twin (_spec_pseg_fn): gather/verify/scatter through
    block tables, rejected tails absorbed by the null page — cold,
    prefix-hit (zero-copy pages) and sampled rows all bitwise solo."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import init_page_arena, page_kv_bytes
    from lambdipy_tpu.runtime.pagepool import PagePool, page_width
    from lambdipy_tpu.runtime.prefixstore import PrefixStore

    adapter = registry.get("llama-tiny").build()
    cfg = adapter.config
    server = adapter.make_server(adapter.init_params(seed=0))
    block = 16
    page = page_width(cfg.max_len, block)
    n_pages = 2 * (cfg.max_len // page) + 1
    pool = PagePool(n_pages=n_pages, page=page,
                    page_bytes=page_kv_bytes(cfg, page),
                    make_arena=lambda n=n_pages: init_page_arena(
                        cfg, n, page))
    cb = ContinuousBatcher(server, slots=2, segment=4, page_pool=pool,
                           spec_k=4)
    store = PrefixStore(server, block=block, budget_mb=16, pool=pool)
    cb.prefix_pages_fn = store.acquire_pages

    ref = server.generate([5, 6, 7, 8], max_new_tokens=12)
    np.testing.assert_array_equal(
        cb.generate([5, 6, 7, 8], max_new_tokens=12), ref)
    row = list(range(1, 33)) + [4, 5]
    refp = server.generate(row, max_new_tokens=12)
    for _ in range(2):  # cold walk, then the zero-copy page hit
        m = store.route(row)
        out = (cb.generate(np.asarray(row[m:], np.int32),
                           max_new_tokens=12,
                           prefix=np.asarray(row[:m], np.int32))
               if m > 0 else cb.generate(row, max_new_tokens=12))
        np.testing.assert_array_equal(out, refp)
    refs = server.generate([9, 8, 7], max_new_tokens=12,
                           temperature=0.9, seed=4)
    np.testing.assert_array_equal(
        cb.generate([9, 8, 7], max_new_tokens=12, temperature=0.9,
                    seed=4), refs)
    with cb._lock:
        while cb._engine_running:
            cb._lock.wait(0.05)
    pool.check_invariants()


@pytest.mark.slow  # two bundle loads; the spec_k extra is one int cast
# away from the tested ContinuousBatcher wiring
def test_handler_spec_k_extra(tmp_path):
    """Bundle extra spec_k reaches the engine; batching.spec appears on
    the stats surface; tokens match the spec-off bundle's."""
    from lambdipy_tpu.runtime.loader import load_bundle
    from tests.test_runtime import make_model_bundle

    plain_bundle = make_model_bundle(
        tmp_path / "plain", model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "16", "batch_mode": "continuous",
               "batch_max": "2", "batch_segment": "4"})
    plain = load_bundle(plain_bundle, warmup=False)
    ref = plain.handler.invoke(plain.state, {"tokens": [5, 6, 7, 8]})

    bundle = make_model_bundle(
        tmp_path / "spec", model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "16", "batch_mode": "continuous",
               "batch_max": "2", "batch_segment": "4", "spec_k": "4"})
    report = load_bundle(bundle, warmup=False)
    out = report.handler.invoke(report.state, {"tokens": [5, 6, 7, 8]})
    assert out["ok"] and out["tokens"] == ref["tokens"]
    stats = report.state.stats()
    spec = stats["batching"]["spec"]
    assert spec["k"] == 4 and spec["steps"] > 0
    assert "acceptance_rate" in spec and "tokens_per_step" in spec
    # the solo-path surface reports through the same shared object
    assert stats["spec"]["steps"] == spec["steps"]
