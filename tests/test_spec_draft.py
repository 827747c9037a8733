"""The model-draft speculative tier: shallow-exit self-drafting,
per-row adaptive k with the provider fallback chain, the DraftProvider
seam (aux twin models), and the knob/policy plumbing that steers it.

Wall-clock discipline mirrors test_spec_engine.py: every non-slow
engine test shares ONE shape (slots=2, segment=4, spec_k=4) over the
session tiny_server, so the model-draft program family ("mspec", kb in
{2, 4}) compiles once for the module; the paged twin and pipeline depth
2 are ``test_model_draft_paged_parity`` and
``test_model_draft_pipeline_depth2``."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np
import pytest

from lambdipy_tpu.runtime.continuous import AuxModelDraft, ContinuousBatcher
from lambdipy_tpu.runtime.metrics import SpecDecodeStats


def _mk(tiny_server, **kw):
    args = dict(slots=2, segment=4, spec_k=4)
    args.update(kw)
    return ContinuousBatcher(tiny_server, **args)


def _fresh_metrics(cb):
    cb.spec_metrics = SpecDecodeStats()
    return cb.spec_metrics


# -- _spec_chain_verify unit edges -----------------------------------------


def _greedy_select():
    import jax.numpy as jnp

    def select(lg, subs):
        lp = jnp.log(jnp.maximum(
            jnp.exp(lg - lg.max(-1, keepdims=True))
            / jnp.exp(lg - lg.max(-1, keepdims=True)).sum(-1,
                                                          keepdims=True),
            1e-38))
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return tok, jnp.take_along_axis(lp, tok[:, None], 1)[:, 0]

    return select


def test_chain_verify_accept_and_reject_rows():
    """Full-accept and all-rejected rows in one chunk: count is the
    accepted prefix + the always-correct chain token; a masked draft
    (-1 padding, the provider-failure filler) can never be accepted."""
    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.models.llama import _spec_chain_verify

    b, kb, v = 2, 4, 8
    lg = jnp.zeros((b, kb, v), jnp.float32)
    # the greedy chain at every position of every row is token 5
    lg = lg.at[:, :, 5].set(9.0)
    draft = jnp.asarray([[5, 5, 5],      # matches the chain: full accept
                         [-1, -1, -1]],  # masked filler: nothing accepted
                        jnp.int32)
    lp_in = jnp.asarray([-0.5, -0.25], jnp.float32)
    keys = jax.vmap(lambda s: jax.random.PRNGKey(s))(jnp.arange(2))
    lps, count, tok2, lp2, keys2 = _spec_chain_verify(
        _greedy_select(), lg, draft, lp_in, keys)
    assert count.tolist() == [kb, 1]
    assert tok2.tolist() == [5, 5]
    # column 0 is the pending token's carried logprob, untouched
    np.testing.assert_allclose(np.asarray(lps[:, 0]),
                               np.asarray(lp_in))
    assert lps.shape == (b, kb)


def test_chain_verify_k2_minimum_bucket():
    """kb=2 — the slow-start bucket every model/aux row begins at — is
    a real verify chunk: one draft position, count in {1, 2}."""
    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.models.llama import _spec_chain_verify

    b, kb, v = 2, 2, 8
    lg = jnp.zeros((b, kb, v), jnp.float32).at[:, :, 3].set(4.0)
    draft = jnp.asarray([[3], [4]], jnp.int32)
    keys = jax.vmap(lambda s: jax.random.PRNGKey(s))(jnp.arange(2))
    _, count, tok2, _, _ = _spec_chain_verify(
        _greedy_select(), lg, draft, jnp.zeros((b,), jnp.float32), keys)
    assert count.tolist() == [2, 1]
    assert tok2.tolist() == [3, 3]


def test_chain_verify_key_walk_rolls_back():
    """The rejected tail's PRNG splits roll back: the returned chain
    state is the walk after exactly `count` selections, so a sampled
    row continues bitwise where plain decode would."""
    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.models.llama import (_spec_chain_verify,
                                           _split_rows)

    def sampled(lg, subs):
        tok = jax.vmap(jax.random.categorical)(subs, lg).astype(jnp.int32)
        lp = jax.nn.log_softmax(lg, axis=-1)
        return tok, jnp.take_along_axis(lp, tok[:, None], 1)[:, 0]

    b, kb, v = 1, 4, 16
    key = jax.random.PRNGKey(0)
    lg = jax.random.normal(key, (b, kb, v), jnp.float32) * 3.0
    keys = jax.random.PRNGKey(42)[None, :]
    # walk the chain by hand to learn its tokens, then draft a prefix
    # of them so exactly 2 drafts are accepted (count = 3)
    cur, chain = keys, []
    for i in range(kb):
        cur, subs = _split_rows(cur)
        chain.append(int(sampled(lg[:, i, :], subs)[0][0]))
    wrong = (chain[2] + 1) % v
    draft = jnp.asarray([[chain[0], chain[1], wrong]], jnp.int32)
    _, count, tok2, _, keys2 = _spec_chain_verify(
        sampled, lg, draft, jnp.zeros((b,), jnp.float32), keys)
    assert int(count[0]) == 3
    assert int(tok2[0]) == chain[2]
    expect = keys
    for _ in range(3):
        expect, _ = _split_rows(expect)
    np.testing.assert_array_equal(np.asarray(keys2), np.asarray(expect))


def test_lookup_draft_hit_edges():
    """Empty context drafts zeros (miss); no n-gram match repeats the
    last token (miss); a match extrapolates the earlier continuation,
    padded with the last token when it runs short (still a hit)."""
    from lambdipy_tpu.models.llama import _lookup_draft_hit

    assert _lookup_draft_hit([], 3) == ([0, 0, 0], False)
    d, hit = _lookup_draft_hit([1, 2, 3, 4], 3)
    assert (d, hit) == ([4, 4, 4], False)
    d, hit = _lookup_draft_hit([7, 8, 9, 7, 8], 2)
    assert (d, hit) == ([9, 7], True)
    # the continuation after the match is shorter than k: pad-last
    d, hit = _lookup_draft_hit([5, 6, 5], 4)
    assert (d, hit) == ([6, 5, 5, 5], True)


# -- shallow exit ----------------------------------------------------------


def test_shallow_exit_full_depth_is_identity():
    """exit_layer == cfg.layers routes the exact full forward (same
    params looked up, same ops) — the shallow head is a strict prefix
    of the model, not a parallel approximation."""
    from lambdipy_tpu.models import registry

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    import jax.numpy as jnp

    toks = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
    full, _ = adapter.module.apply(params, toks)
    shallow, cache = adapter.module.apply(
        params, toks, exit_layer=adapter.config.layers)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(shallow))
    assert len(cache) == adapter.config.layers
    # a genuinely shallow exit carries one cache entry per RUN layer
    early, cache1 = adapter.module.apply(params, toks, exit_layer=1)
    assert early.shape == full.shape and len(cache1) == 1


# -- engine parity: the model-draft tier -----------------------------------


def test_model_draft_engine_parity(tiny_server):
    """The tier's bitwise contract: model-drafted rows (greedy and
    seeded-sampled, concurrent) emit exactly their solo outputs —
    drafts change tokens-per-weight-read, never the tokens — and the
    draft block appears on the metrics surface."""
    cb = _mk(tiny_server, draft_mode="model")
    metrics = _fresh_metrics(cb)
    prompts = [[5, 6, 7, 8], [9, 8, 7]]
    kws = [dict(), dict(temperature=0.8, seed=11)]
    solo = [tiny_server.generate(p, max_new_tokens=16, **kw)
            for p, kw in zip(prompts, kws)]

    def run(i):
        time.sleep(0.01 * i)
        return cb.generate(prompts[i], max_new_tokens=16, **kws[i])

    with ThreadPoolExecutor(max_workers=2) as ex:
        outs = list(ex.map(run, range(2)))
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o, solo[i], err_msg=f"row {i}")
    rep = metrics.report()
    assert rep["draft"]["providers"], rep["draft"]
    # slow-start: every dispatched k is a pow-2 within [2, spec_k]
    assert set(rep["draft"]["k_hist"]) <= {"2", "4"}, rep["draft"]


def test_model_draft_budget_shorter_than_k(tiny_server):
    """A row whose remaining budget is smaller than the draft width
    still lands bitwise: the verify chunk may overshoot, the collector
    truncates to the budget exactly like the plain engine."""
    cb = _mk(tiny_server, draft_mode="model")
    for n in (1, 3):
        ref = tiny_server.generate([5, 6, 7, 8], max_new_tokens=n)
        out = cb.generate([5, 6, 7, 8], max_new_tokens=n)
        np.testing.assert_array_equal(out, ref)


def test_model_draft_pipeline_depth2(tiny_server):
    """Depth >= 2 composes with the model tier: the shallow chain runs
    in-program off the device-true carry, so drafts are never stale and
    outputs stay bitwise solo's."""
    cb = _mk(tiny_server, draft_mode="model", pipeline_depth=2)
    ref = tiny_server.generate([5, 6, 7, 8], max_new_tokens=16)
    ref_s = tiny_server.generate([2, 4, 6], max_new_tokens=16,
                                 temperature=0.9, seed=5)
    np.testing.assert_array_equal(
        cb.generate([5, 6, 7, 8], max_new_tokens=16), ref)
    np.testing.assert_array_equal(
        cb.generate([2, 4, 6], max_new_tokens=16, temperature=0.9,
                    seed=5), ref_s)


def test_model_draft_paged_parity():
    """The paged twin of the model tier (_mspec_pseg_fn): shallow
    drafts over gathered pages, rejected tails absorbed by the null
    page — cold and sampled rows bitwise solo."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import init_page_arena, page_kv_bytes
    from lambdipy_tpu.runtime.pagepool import PagePool, page_width

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
                           spec_k=4, draft_mode="model")
    ref = server.generate([5, 6, 7, 8], max_new_tokens=12)
    np.testing.assert_array_equal(
        cb.generate([5, 6, 7, 8], max_new_tokens=12), ref)
    refs = server.generate([9, 8, 7], max_new_tokens=12,
                           temperature=0.9, seed=4)
    np.testing.assert_array_equal(
        cb.generate([9, 8, 7], max_new_tokens=12, temperature=0.9,
                    seed=4), refs)
    with cb._lock:
        while cb._engine_running:
            cb._lock.wait(0.05)
    pool.check_invariants()


# -- per-row adaptive k + the fallback chain -------------------------------


def test_spec_row_init_modes(tiny_server):
    """Admission state by engine mode: lookup keeps the legacy fixed k
    (no adaptivity); model/aux slow-start at the k=2 minimum bucket;
    off (or spec_k=0) admits plain rows."""
    assert _mk(tiny_server)._spec_row_init() == ("lookup", 4)
    assert _mk(tiny_server,
               draft_mode="model")._spec_row_init() == ("model", 2)
    assert _mk(tiny_server,
               draft_mode="off")._spec_row_init() == ("off", 1)
    assert _mk(tiny_server, spec_k=0,
               draft_mode="model")._spec_row_init() == ("off", 1)


def test_spec_adapt_grow_shrink_demote(tiny_server):
    """The per-row controller's whole state machine, driven directly:
    sustained acceptance grows k pow-2 up to spec_k, collapse shrinks
    it back to the minimum bucket, and collapse AT k=2 demotes the row
    down the sticky fallback chain model -> lookup -> off, counted
    under batching.spec.draft.fallbacks."""
    cb = _mk(tiny_server, draft_mode="model")
    metrics = _fresh_metrics(cb)
    entry = {"draft_mode": "model", "k_row": 2, "accept_ewma": None}
    cb._spec_adapt(entry, "model", 2, 2)          # frac 1.0: grow
    assert entry["k_row"] == 4 and entry["accept_ewma"] == 1.0
    cb._spec_adapt(entry, "model", 4, 4)          # capped at spec_k
    assert entry["k_row"] == 4
    for _ in range(3):                            # frac 0: ewma decays
        cb._spec_adapt(entry, "model", 4, 1)      # 0.7, 0.49, 0.343
    assert entry["k_row"] == 2, entry             # shrank, not demoted
    assert entry["draft_mode"] == "model"
    while entry["draft_mode"] == "model":         # collapse at k=2
        cb._spec_adapt(entry, "model", 2, 1)
    assert entry == {"draft_mode": "lookup", "k_row": 2,
                     "accept_ewma": None}
    cb._spec_adapt(entry, "lookup", 2, 1)         # fresh ewma 0.0
    assert entry["draft_mode"] == "off" and entry["k_row"] == 1
    assert metrics.report()["draft"]["fallbacks"] == {
        "model->lookup": 1, "lookup->off": 1}


def test_spec_adapt_stale_step_and_legacy_inert(tiny_server):
    """A step collected AFTER its row was demoted (depth >= 2) feeds
    the EWMA but never re-tunes k for the new provider; legacy lookup
    mode is entirely inert (fixed k, no demotion)."""
    cb = _mk(tiny_server, draft_mode="model")
    entry = {"draft_mode": "lookup", "k_row": 2, "accept_ewma": None}
    cb._spec_adapt(entry, "model", 4, 4)          # stale model step
    assert entry["k_row"] == 2 and entry["accept_ewma"] == 1.0
    legacy = _mk(tiny_server)                     # draft_mode="lookup"
    e2 = {"draft_mode": "lookup", "k_row": 4, "accept_ewma": None}
    legacy._spec_adapt(e2, "lookup", 4, 1)
    assert e2 == {"draft_mode": "lookup", "k_row": 4,
                  "accept_ewma": None}


def test_provider_switch_mid_row(tiny_server):
    """An adversarial row (sampled hot: greedy shallow drafts never
    match the chain) walks the whole fallback chain inside ONE request
    — model -> lookup -> off — while staying bitwise solo, and every
    dispatched k stays at the slow-start minimum bucket."""
    cb = _mk(tiny_server, draft_mode="model")
    metrics = _fresh_metrics(cb)
    kw = dict(temperature=1.5, seed=13)
    ref = tiny_server.generate([3, 1, 4, 1], max_new_tokens=24, **kw)
    out = cb.generate([3, 1, 4, 1], max_new_tokens=24, **kw)
    np.testing.assert_array_equal(out, ref)
    rep = metrics.report()["draft"]
    assert rep["fallbacks"].get("model->lookup", 0) >= 1, rep
    assert rep["fallbacks"].get("lookup->off", 0) >= 1, rep
    assert set(rep["k_hist"]) == {"2"}, rep


# -- the DraftProvider seam (aux twin models) ------------------------------


def test_draft_twin_and_aux_provider():
    """registry.draft_twin shrinks a llama-family adapter into a
    same-vocab TP-replicated draft server; AuxModelDraft adapts it to
    the DraftProvider seam with deterministic proposals."""
    from lambdipy_tpu.models import registry

    adapter = registry.get("llama-tiny").build()
    twin = registry.draft_twin(adapter, layers=1)
    prov = AuxModelDraft(twin)
    a = prov.propose([1, 2, 3], 4)
    assert len(a) == 4
    assert all(0 <= t < adapter.config.vocab_size for t in a)
    assert prov.propose([1, 2, 3], 4) == a


def test_draft_twin_rejects_non_llama():
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.registry import ModelError

    with pytest.raises(ModelError):
        registry.draft_twin(SimpleNamespace(config=None), layers=1)


def test_aux_engine_parity(tiny_server):
    """draft_mode="aux" through the engine: a separate 1-layer twin
    proposes, the chain verifies — greedy parity holds and the aux
    provider shows up in the per-provider counters."""
    from lambdipy_tpu.models import registry

    adapter = registry.get("llama-tiny").build()
    prov = AuxModelDraft(registry.draft_twin(adapter, layers=1))
    cb = _mk(tiny_server, draft_mode="aux", draft_provider=prov)
    metrics = _fresh_metrics(cb)
    ref = tiny_server.generate([5, 6, 7, 8], max_new_tokens=12)
    out = cb.generate([5, 6, 7, 8], max_new_tokens=12)
    np.testing.assert_array_equal(out, ref)
    provs = metrics.report()["draft"]["providers"]
    assert "aux" in provs or "lookup" in provs or "off" in provs, provs


def test_misbehaving_provider_degrades_safely(tiny_server):
    """A provider that raises or proposes garbage can only miss: the
    pad is RAW -1 (never accepted), so the row degrades toward plain
    decode while the output stays bitwise solo's."""

    class Hostile:
        def __init__(self):
            self.n = 0

        def propose(self, context, k):
            self.n += 1
            if self.n % 2:
                raise RuntimeError("injected provider failure")
            return [0] * (int(k) // 2)   # short AND wrong

    cb = _mk(tiny_server, draft_mode="aux", draft_provider=Hostile())
    ref = tiny_server.generate([5, 6, 7, 8], max_new_tokens=16)
    out = cb.generate([5, 6, 7, 8], max_new_tokens=16)
    np.testing.assert_array_equal(out, ref)


# -- metrics: the batching.spec.draft block --------------------------------


def test_spec_stats_draft_block():
    s = SpecDecodeStats()
    s.record_step(proposed=3, accepted=3, emitted=4, hit=True,
                  provider="model", k=4)
    s.record_step(proposed=3, accepted=3, emitted=4, hit=True,
                  provider="model", k=4)
    s.record_step(proposed=1, accepted=0, emitted=1, hit=False,
                  provider="lookup", k=2)
    s.record_draft_fallback("model->lookup")
    d = s.report()["draft"]
    assert d["providers"]["model"] == {
        "steps": 2, "proposed": 6, "accepted": 6, "acceptance_ewma": 1.0}
    assert d["providers"]["lookup"]["acceptance_ewma"] == 0.0
    assert d["k_hist"] == {"2": 1, "4": 2}
    assert d["fallbacks"] == {"model->lookup": 1}


# -- knob plumbing: /v1/debug/knobs draft_mode -----------------------------


@pytest.mark.slow  # two bundle loads; the validation itself is a pure
# dict-in/dict-out fn
def test_knobs_draft_mode_validation(tmp_path):
    """The admin knob's whole validation surface: auto aliases model,
    model/aux require a spec-on boot, aux additionally a wired
    provider, lookup/off always retune, junk is rejected."""
    from lambdipy_tpu.runtime.loader import load_bundle
    from tests.test_runtime import make_model_bundle

    bundle = make_model_bundle(
        tmp_path / "spec", model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "8", "batch_mode": "continuous",
               "batch_max": "2", "batch_segment": "4", "spec_k": "4"})
    report = load_bundle(bundle, warmup=False)
    knobs = report.state.knobs_admin_fn
    out = knobs({"draft_mode": "auto"})
    assert out["ok"] and out["draft_mode"] == "model"
    assert not knobs({"draft_mode": "banana"})["ok"]
    assert "draft_provider" in knobs({"draft_mode": "aux"})["error"]
    assert knobs({"draft_mode": "off"})["ok"]
    assert knobs({"draft_mode": "lookup"})["ok"]
    assert not knobs({"draft_mode": "model", "nonsense": 1})["ok"]

    plain_bundle = make_model_bundle(
        tmp_path / "plain", model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "8", "batch_mode": "continuous",
               "batch_max": "2", "batch_segment": "4"})
    plain = load_bundle(plain_bundle, warmup=False)
    pk = plain.state.knobs_admin_fn
    # spec off at boot: the tier can be steered down, never enabled
    assert "off at boot" in pk({"draft_mode": "model"})["error"]
    assert pk({"draft_mode": "lookup"})["ok"]


# -- policy + controller: the demote rule end to end -----------------------


def _view(name, **kw):
    from lambdipy_tpu.fleet.policy import ReplicaView

    args = dict(name=name, spec_k=4, draft_mode="model",
                draft_acceptance=0.05)
    args.update(kw)
    return ReplicaView(**args)


def test_policy_demotes_collapsed_draft_mode():
    """A routable replica whose model provider's acceptance EWMA sits
    below the floor gets draft_mode retuned to lookup; healthy, inert
    (lookup/off), unroutable, and signal-less replicas do not."""
    from lambdipy_tpu.fleet.policy import (SET_KNOB, PolicyConfig,
                                           PolicyState, Snapshot, decide)

    snap = Snapshot(t=100.0, replicas=(
        _view("r-collapsed"),
        _view("r-healthy", draft_acceptance=0.9),
        _view("r-lookup", draft_mode="lookup"),
        _view("r-unroutable", routable=False),
        _view("r-blind", draft_acceptance=None),
    ))
    actions = decide(snap, PolicyState(), PolicyConfig())
    assert [(a.kind, a.target, a.knob, a.value) for a in actions] == [
        (SET_KNOB, "r-collapsed", "draft_mode", "lookup")]


def test_policy_demote_respects_knob_cooldown():
    from lambdipy_tpu.fleet.policy import (PolicyConfig, PolicyState,
                                           Snapshot, decide)

    cfg = PolicyConfig()
    state = PolicyState()
    reps = (_view("r1"),)
    assert decide(Snapshot(t=10.0, replicas=reps), state, cfg)
    # inside the cooldown window the same retune is NOT re-emitted
    assert not decide(Snapshot(t=10.0 + cfg.knob_cooldown_s / 2,
                               replicas=reps), state, cfg)
    assert decide(Snapshot(t=10.0 + cfg.knob_cooldown_s + 1,
                           replicas=reps), state, cfg)


def test_controller_snapshot_extracts_draft_signals():
    """build_snapshot lifts batching.spec.draft off a /metrics scrape
    into the ReplicaView the demote rule reads — and a scrape without
    the draft block degrades to None, not a guess."""
    from lambdipy_tpu.fleet.controller import FleetController
    from lambdipy_tpu.fleet.policy import decide

    reps = {
        "r1": SimpleNamespace(name="r1", role="mixed", routable=True,
                              managed=False, outstanding=0,
                              state="ready"),
        "r2": SimpleNamespace(name="r2", role="mixed", routable=True,
                              managed=False, outstanding=0,
                              state="ready"),
    }
    router = SimpleNamespace(
        pool=SimpleNamespace(_lock=threading.Lock(), replicas=reps),
        ship_window=4)
    ctl = FleetController(router, interval_s=1.0, dry_run=True)
    snap = ctl.build_snapshot({
        "fleet": {},
        "replicas": {
            "r1": {"handler": {"batching": {"spec": {
                "k": 4, "acceptance_rate": 0.5, "draft_mode": "model",
                "draft": {"providers": {
                    "model": {"acceptance_ewma": 0.07}}},
            }}}},
            "r2": {"handler": {"batching": {}}},
        }}, t=50.0)
    v1, v2 = snap.replicas
    assert (v1.draft_mode, v1.draft_acceptance) == ("model", 0.07)
    assert (v2.draft_mode, v2.draft_acceptance) == (None, None)
    # the scraped signal drives the demote end to end
    actions = decide(snap, ctl.state, ctl.config)
    assert [(a.target, a.knob, a.value) for a in actions] == [
        ("r1", "draft_mode", "lookup")]
