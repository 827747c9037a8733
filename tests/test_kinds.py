"""The seam between the program and an architecture family (PR 44): an
attention kind is a module that answers ``llama.ATTN_KINDS``' interface, and
``LlamaBlock``, ``LlamaConfig``, ``_scan_decode``, the engine, the handler and
``runtime/metrics.py`` ask the kinds and name none.

- a kind defined HERE, registered for the test, is served by the continuous
  engine behind the generate handler, and its block is on ``/metrics`` with
  the right sums: nothing under ``lambdipy_tpu/runtime`` knows of it;
- each of the six kinds answers the whole interface;
- the one recorder reports, for the same recorded rows, what each of the five
  classes it replaced reported (the expected documents were taken from those
  classes on the parent commit, daf4355)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.models import llama
from lambdipy_tpu.models.llama import Counters, LlamaConfig, QDense, RMSNorm
from lambdipy_tpu.runtime.metrics import KindCounters

TINY = dict(vocab_size=512, hidden=64, heads=4, kv_heads=2, mlp=128,
            max_len=128, dtype=jnp.float32)
LATENT = dict(qk_nope=8, qk_rope=4, v_head=8, kv_lora_rank=16)


# -- a kind of the test's own: the running mean of a projection ------------

def _toy_attend(block, x, positions, mask, cache, lengths):
    """``out_t = mean(v_s : s <= t)``: the cache entry is one leaf, the
    running sum ``[rows, 1, 1, hidden]`` float32."""
    cfg = block.cfg
    b, s, _ = x.shape
    v = QDense(cfg.hidden, cfg.quant, jnp.float32, name="v_proj")(
        RMSNorm(cfg.norm_eps, name="attn_norm")(x))
    if cache is None:
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        live = jnp.arange(s)[None, :] < lengths[:, None]
        run = jnp.cumsum(jnp.where(live[..., None], v, 0.0), axis=1)
        out = run / (jnp.arange(s) + 1.0)[None, :, None]
        total = run[:, -1]
    else:
        at = jnp.broadcast_to(cache["index"], (b,))
        total = cache["sum"][:, 0, 0] + v[:, 0]
        out = (total / (at + 1.0)[:, None])[:, None]
        if block.layer == cfg.first_layer_of("toy"):
            block.sow("toy_stats", "steps", jnp.stack(
                [jnp.ones_like(at), at + 1], axis=-1).astype(jnp.int32))
    return out.astype(cfg.dtype), {"sum": total[:, None, None]}


def _toy_counters(cfg):
    def segment(sown, rows, steps):
        return {"row_steps": sown["toy_stats"][:, 0].sum(),
                "context": sown["toy_stats"][:, 1].sum(),
                "booked": rows * steps}

    def prefill(lengths, rows, s):
        return {"prefills": 1, "prompt_tokens": sum(lengths),
                "padded_positions": rows * s}

    return (Counters(
        "toy", "a toy model",
        dict.fromkeys(("row_steps", "context", "booked", "prefills",
                       "prompt_tokens", "padded_positions"), 0),
        {"toy_stats": lambda b: jnp.zeros((b, 2), jnp.int32)}, segment,
        prefill),)


TOY = types.SimpleNamespace(
    NAME="toy", PLACES=("layer_kinds",),
    validate=lambda cfg: None,
    cache_layout=lambda cfg: {"sum": (1, cfg.hidden)},
    cache_positions=lambda cfg, max_len: {"sum": 1},
    cache_dtypes=lambda cfg: {"sum": jnp.float32},
    cache_slot=lambda cfg, leaf, position: position * 0,
    refusal=lambda cfg, holder: f"{holder} cannot take a toy layer's sum",
    attend=_toy_attend, counters=_toy_counters)


def test_a_kind_of_the_tests_own_is_served_and_counted_on_metrics(
        monkeypatch):
    """No edit to ``runtime/``: the engine builds its recorders from
    ``cfg.counters()``, zips the segment's extra outputs with the declared
    names, books the prefill through the kind's own arithmetic, and the
    handler reports whatever blocks the engine holds."""
    from lambdipy_tpu.runtime.handlers import generate_handler

    monkeypatch.setitem(llama.ATTN_KINDS, "toy", TOY)
    assert llama.attn_kind_module("toy") is TOY
    ctx = types.SimpleNamespace(params_dir=None, bundle_dir=None,
                                manifest=None)
    spec = {"model": "llama-tiny", "dtype": "float32",
            "extra": {"layer_kinds": ("toy", "kv"), "batch_mode": "continuous",
                      "batch_max": "2", "batch_segment": "8",
                      "max_new_tokens": "8", "prefix_cache_mb": "0",
                      "warm_group_prefill": "0", "serve_aot": "0"}}
    handler = generate_handler(spec, ctx)
    assert handler.stats()["toy"] == dict.fromkeys(
        ("row_steps", "context", "booked", "prefills", "prompt_tokens",
         "padded_positions"), 0)
    prompt = [3, 1, 4, 1, 5]
    out = handler.invoke({"tokens": prompt})
    assert out["ok"] and len(out["tokens"][0]) == 8
    # one request alone: a prefill of 5 tokens in the smallest bucket, then
    # ONE segment of 8 steps from position 5, each step seeing itself and
    # what lies before it
    toy = handler.stats()["toy"]
    assert toy["prefills"] == 1 and toy["prompt_tokens"] == 5
    assert toy["padded_positions"] in (8, 16, 32)
    assert toy["row_steps"] == toy["booked"] == 8
    assert toy["context"] == sum(t + 1 for t in range(5, 13))
    # and the tokens are what the model generates with no engine
    from lambdipy_tpu.models import registry

    adapter = registry.get("llama-tiny").build(
        dtype="float32", extra={"layer_kinds": ("toy", "kv")})
    solo = adapter.make_server(adapter.init_params(seed=0)).generate(
        [prompt], max_new_tokens=8)[0].tolist()
    assert out["tokens"][0] == solo
    # a holder of per-head K/V rows refuses it in the kind's own words
    with pytest.raises(NotImplementedError, match="toy layer's sum"):
        llama.require_row_a_token(adapter.config, "X")
    assert not llama.segment_keeps_tail(adapter.config)


# -- the six kinds answer the whole interface ------------------------------

KIND_CFGS = {
    "kv": dict(TINY, layers=2),
    "eva": dict(TINY, layers=2, kv_heads=4, attn_kind="eva", window_size=8,
                chunk_size=4),
    "latent": dict(TINY, layers=2, attn_kind="latent", **LATENT),
    "sparse_kv": dict(TINY, layers=2, layer_kinds=("sparse_kv", "kv")),
    "linear": dict(TINY, layers=2, layer_kinds=("linear", "kv"), lin_heads=2,
                   lin_head_dim=8),
    "kda": dict(TINY, layers=2, layer_kinds=("kda", "kv"), kda_heads=2,
                kda_head_dim=8),
}


@pytest.mark.parametrize("kind", list(llama.ATTN_KINDS))
def test_every_kind_is_a_module_that_answers_the_whole_interface(kind):
    module = llama.attn_kind_module(kind)
    assert module is not None and module.NAME == kind
    assert set(module.PLACES) <= {"attn_kind", "layer_kinds"} and module.PLACES
    for name in ("validate", "cache_layout", "cache_positions",
                 "cache_dtypes", "cache_slot", "refusal", "attend"):
        assert callable(getattr(module, name)), name
    cfg = LlamaConfig(**KIND_CFGS[kind])
    assert cfg.kind_of(0) is module
    layout = module.cache_layout(cfg)
    assert list(module.cache_positions(cfg, 64)) == list(layout) \
        == list(module.cache_dtypes(cfg))
    assert all(len(row) == 2 for row in layout.values())
    # the config's own answers are the kind's, leaf for leaf
    assert cfg.cache_layout(0) == layout
    cache = llama.init_decode_cache(cfg, 2, 64)
    assert {name: val.shape for name, val in cache[0].items()
            if name != "index"} == {
        name: (2, module.cache_positions(cfg, 64)[name], *layout[name])
        for name in layout}
    for leaf in layout:
        assert int(module.cache_slot(cfg, leaf, 5)) \
            < module.cache_positions(cfg, 64)[leaf]
    # only per-head K/V rows are refused by nobody
    words = module.refusal(cfg, "HOLDER")
    assert (words is None) == (kind == "kv")
    assert words is None or words.startswith("HOLDER")
    # what is optional is a callable (or, FORMS, words) where it is there
    assert set(getattr(module, "FORMS", ())) <= {"sp_prefill", "band"}
    for name in ("absent", "row_a_token", "scales_softmax", "prompt_block",
                 "counters", "keeps_tail", "tail_fits", "tail_init",
                 "tail_step", "tail_merge"):
        assert callable(getattr(module, name, len)), name
    has_tail = [hasattr(module, name) for name in (
        "keeps_tail", "tail_init", "tail_step", "tail_merge")]
    assert all(has_tail) or not any(has_tail)
    assert llama.segment_keeps_tail(cfg) == (kind == "eva")
    assert llama.segment_keeps_tail(LlamaConfig(
        **{**KIND_CFGS[kind], "kv_heads": 4})) == (kind in ("kv", "eva"))
    # a prefill and one step trace through the block's one dispatch
    model = llama.LlamaModel(cfg)
    tokens = jnp.ones((2, 8), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)

    def prefill_and_step(params):
        logits, pre = model.apply(params, tokens)
        cache = llama.prefill_into_cache(cfg, pre, 2, 64, 8)
        for entry in cache:
            entry["index"] = jnp.full((2,), 8, jnp.int32)
        return logits, model.apply(params, tokens[:, :1],
                                   positions=jnp.full((2, 1), 8),
                                   cache=cache)[0]

    logits, step = jax.eval_shape(prefill_and_step, params)
    assert logits.shape == (2, 8, 512) and step.shape == (2, 1, 512)
    # a static form the kind does not take is refused, not ignored
    for form in {"sp_prefill", "band"} - set(getattr(module, "FORMS", ())):
        with pytest.raises(NotImplementedError, match="not written"):
            jax.eval_shape(lambda p: model.apply(p, tokens, **{form: 2}),
                           params)


def test_an_unknown_kind_and_a_kind_out_of_place_are_refused():
    with pytest.raises(KeyError):
        llama.attn_kind_module("nope")
    with pytest.raises(ValueError, match="attn_kind"):
        LlamaConfig(**dict(TINY, layers=2, attn_kind="linear"))
    with pytest.raises(ValueError, match="layer_kinds"):
        LlamaConfig(**dict(TINY, layers=2, layer_kinds=("eva", "kv")))
    # what only an absent kind reads is not ignored
    with pytest.raises(ValueError, match="index_topk"):
        LlamaConfig(**dict(TINY, layers=2, index_topk=4))


# -- the one recorder against the five classes it replaced -----------------

FAMILY_CFGS = {
    "moe": dict(TINY, layers=4, ffn_kind="routed", first_dense_layers=1,
                moe_experts=8, moe_top_k=2, moe_intermediate=16,
                moe_experts_held=4, moe_first_expert=2),
    "eva": KIND_CFGS["eva"],
    "dsa": dict(TINY, layers=2, attn_kind="latent", **LATENT, q_lora_rank=8,
                index_heads=2, index_head_dim=8, index_topk=4),
    "sala": dict(TINY, layers=4, lin_heads=2, lin_head_dim=8,
                 layer_kinds=("sparse_kv", "linear", "linear", "linear")),
    "kda": dict(TINY, layers=6, layer_kinds=("kda",) * 5 + ("latent",),
                kda_heads=2, kda_head_dim=8, **LATENT),
}
# three fetched segments of 16 steps over 4 slots, of which the first 3, 0
# and 2 rows are booked; three dispatched prefills (lengths, rows, padded)
BOOKED = (3, 0, 2)
PREFILLS = (([5, 9], 2, 16), ([300], 1, 512), ([40, 64, 7], 4, 64))
# MoeLoadStats(held=(2, 4)), EvaKeyStats, DsaKeyStats, SalaKeyStats(
# step_state_bytes=cfg.state_bytes_a_step, state_kernel=True) and KdaStats(
# layers=5, layer_bytes=cfg.kda_step_bytes, state_kernel=False) of the
# parent commit, fed the rows below and ``cfg.dsa_prefill_pairs`` /
# ``cfg.kda_scan_chunks`` of the prefills above
PARENT_REPORTS = {
    "moe": {"assignments": 159, "local_assignments": 93,
            "load": [19, 22, 30, 23, 22, 18, 9, 16], "experts_read": 14,
            "layer_steps": 144},
    "eva": {"row_steps": 80, "keys_attended": 101, "chunks_written": 116,
            "edge_row_steps": 183},
    "dsa": {"row_steps": 80, "keys_selected": 107, "keys_visible": 93,
            "prefill_pairs_run": 213504, "prefill_pairs_causal": 48138},
    "sala": {"row_steps": 80, "keys_attended": 114, "keys_visible": 174,
             "dense_steps": 88, "kc_writes": 129, "state_bytes": 245760,
             "kernel_row_steps": 80},
    "kda": {"row_steps": 400, "scan_chunks": 130, "state_bytes": 870400,
            "kernel_row_steps": 0},
}


def recorded_rows() -> dict:
    """What the segment programs returned, a family: the draws the parent's
    classes were fed, in their order."""
    rng = np.random.default_rng(44)
    rows = {"moe": [{"moe_stats": rng.integers(0, 9, (4, 8)),
                     "moe_reads": np.asarray(read)} for read in (5, 2, 7)]}
    for family, cols in (("eva", 3), ("dsa", 2), ("sala", 4)):
        rows[family] = [{f"{family}_stats": rng.integers(0, 50, (4, cols))}
                        for _ in BOOKED]
    rows["kda"] = [{} for _ in BOOKED]
    return rows


@pytest.mark.parametrize("family", list(PARENT_REPORTS))
def test_the_one_recorder_reports_what_the_family_s_class_reported(
        family, monkeypatch):
    from lambdipy_tpu.models import linear_attn

    # (the parent's SalaKeyStats was fed state_kernel=True: a TPU's answer)
    monkeypatch.setattr(linear_attn, "steps_in_place", lambda cfg: True)
    kinds = [kind for kind in LlamaConfig(**FAMILY_CFGS[family]).counters()
             if kind.block == family]
    recorder = KindCounters()
    for kind in kinds:
        recorder.add(kind)
    assert list(recorder.report()) == list(PARENT_REPORTS[family])
    assert all(val in (0, []) for val in recorder.report().values())
    for sown, n in zip(recorded_rows()[family], BOOKED):
        recorder.record_segment(sown, list(range(n)), 16)
    for lengths, rows, s in PREFILLS:
        recorder.record_prefill(lengths, rows, s)
    report = recorder.report()
    assert report == PARENT_REPORTS[family]
    assert list(report) == list(PARENT_REPORTS[family])   # and in its order
    assert all(type(val) in (int, list) for val in report.values())
