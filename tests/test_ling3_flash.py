"""The ``bailing-hybrid`` model (Ling-3.0-flash: Kimi-Delta-Attention layers
five to one with latent-attention layers, group-routed experts of which a
share) at toy widths on the CPU, in float32, against the plain reference of
its benchmark family (``benchmark/families/bailing_hybrid.py``: no cache, the
delta-rule recurrence a position, expanded latent attention, every held
expert on every token; it imports nothing of the program).

The toy twin (``benchmark/configs/rehearsal-kda.json``) holds published
layers 1 and 6-11 (KDA with the dense FFN, five routed KDA layers, one routed
MLA layer), 8 heads of 16, a convolution of 4, 16 experts in 4 groups of
which 4 experts are held; the scan's chunk is cut to 16 here so that a
104-token forward turns it seven times. Comparisons are of LOGITS: program
and reference are the same float32 function written two ways (a chunked scan
with a triangular solve against the recurrence, absorbed against expanded
attention, a scale after the dot against a dequantized kernel) and differ by
the order of float32 sums, about 7e-6 at logits of order 0.6; 2e-5 is what
every family's twin is held to, and four orders under what any of the
family's faults shows."""

import json
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, weights
from lambdipy_tpu.models import kda, llama, registry
from lambdipy_tpu.runtime.continuous import ContinuousBatcher

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-kda.json").read_text())
FAMILY = families.of(CONFIG)
DIMS = FAMILY.dims_of(CONFIG)
VOCAB = CONFIG["vocab_size"]
LOGIT_TOL = 2e-5
KINDS = ["kda"] * 6 + ["latent"]
# a row's step in one kda layer: the float32 state and the conv tail (float32
# here), each read once and written once
LAYER_BYTES = 2 * (4 * 8 * 16 * 16 + 3 * 3 * 128 * 4)


def build(quant="int8", **over):
    return registry.get("bailing-hybrid").build(dtype="float32", quant=quant,
                                                extra={**DIMS, **over})


def family_params(adapter, config=CONFIG):
    def fill(keypath, spec):
        name = "/".join(str(k.key) for k in keypath if k.key != "params")
        return jnp.asarray(weights.leaf(config, name, spec.shape, spec.dtype))

    return jax.tree_util.tree_map_with_path(
        fill, jax.eval_shape(lambda: adapter.init_params(seed=0)))


@pytest.fixture(autouse=True)
def small_turns(monkeypatch):
    monkeypatch.setattr(kda, "KDA_CHUNK", 16)


@pytest.fixture(scope="module")
def adapter():
    return build()


@pytest.fixture(scope="module")
def params(adapter):
    return family_params(adapter)


@pytest.fixture(scope="module")
def sample():
    return np.random.default_rng(1).integers(0, VOCAB, (3, 104)).astype(
        np.int32)


@pytest.fixture(scope="module")
def walked(sample):
    """The reference and every control over the sample: {flag: [3, 104, v]};
    ``stale_tail`` from position 64 on (as if 64 were the prompts' length)."""
    ids = np.asarray(sample, np.int32)
    rows = np.repeat(np.arange(ids.shape[0]), ids.shape[1])
    pos = np.tile(np.arange(ids.shape[1]), ids.shape[0])
    out = FAMILY.walk(CONFIG, ids, rows, pos,
                      (False, True) + tuple(f for f in FAMILY.FAULTS
                                            if f != "stale_tail"))
    late = pos >= 63
    out["stale_tail"] = np.zeros_like(np.asarray(out[False]))
    out["stale_tail"][late] = np.asarray(FAMILY.walk(
        CONFIG, ids, rows[late], pos[late], ("stale_tail",))["stale_tail"])
    return {flag: np.asarray(v).reshape(*ids.shape, -1)
            for flag, v in out.items()}


@pytest.fixture(scope="module")
def step(adapter):
    """One token a row through the cache, compiled once for the module."""
    def one(params, tok, pos, cache):
        for entry in cache:
            entry["index"] = pos
        return adapter.module.apply(params, tok, positions=pos[:, None],
                                    cache=cache)

    return jax.jit(one)


# -- the whole forward, and what each fault would show -------------------------

def test_the_whole_prompt_prefill_is_the_references(adapter, params, sample,
                                                    walked):
    got = np.asarray(adapter.module.apply(params, jnp.asarray(sample))[0])
    assert got.shape == (3, 104, VOCAB)
    assert np.std(walked[False]) > 0.1
    np.testing.assert_allclose(got, walked[False], atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("fault", [True, *FAMILY.FAULTS])
def test_each_fault_reads_over_the_tolerance(fault, walked):
    """The comparison can see every fault, a thousand tolerances wide;
    ``stale_tail`` moves nothing before the first served position."""
    at = slice(64, None) if fault == "stale_tail" else slice(None)
    moved = np.abs(walked[fault][:, at] - walked[False][:, at])
    assert moved.max() > 1000 * LOGIT_TOL


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_scan_is_the_step_form(chunk):
    """``kda.chunked_scan`` against ``kda.step`` a position, outputs and the
    state handed on, over 150 positions (no multiple of 16 or 64), from a
    state that is not zero, with decays drawn at both ends of (-5, 0): heads
    0-1 forget in a position (``log alpha`` near -5, where a quotient of two
    exponentials would overflow within a chunk), heads 2-3 hardly at all."""
    rng = np.random.default_rng(2)
    b, s, heads, d = 2, 150, 4, 8
    q, k, v = (rng.normal(size=(b, s, heads, d)).astype(np.float32)
               for _ in range(3))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(np.log(1e-4), np.log(0.05),
                            (b, s, heads, d))).astype(np.float32)
    g[:, :, :2] = -5.0 + 5.0 * np.exp(rng.uniform(
        np.log(1e-4), np.log(0.05), (b, s, 2, d))).astype(np.float32)
    beta = rng.uniform(0.05, 0.95, (b, s, heads)).astype(np.float32)
    state0 = rng.normal(size=(b, heads, d, d)).astype(np.float32)
    out, state = kda.chunked_scan(*(jnp.asarray(a) for a in
                                    (q, k, v, g, beta, state0)), chunk=chunk)
    want, S = [], jnp.asarray(state0)
    for t in range(s):
        o, S = kda.step(S, *(jnp.asarray(a[:, t]) for a in (q, k, v, g, beta)))
        want.append(np.asarray(o))
    np.testing.assert_allclose(np.asarray(out), np.stack(want, axis=1),
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(S), atol=2e-5,
                               rtol=1e-5)
    assert np.isfinite(np.asarray(out)).all()


# -- prefill, then decode through the two kinds' leaves ------------------------

@pytest.mark.parametrize("length", [3, 17])
def test_prefill_then_decode_is_the_references_whole_forward(
        length, adapter, params, sample, walked, step):
    """A ragged right-padded prefill (rows of ``length``, ``length`` - 1 and
    1: prompts shorter than the kernel among them, and 17 and 16 either side
    of a chunk's edge), then one token a step
    through ``state``, ``conv``, ``ckv`` and ``kpe`` (40 steps and more):
    every logit is the reference's, which never saw a cache, so each row
    handed on the state and the conv tail at ITS OWN length."""
    cfg, model = adapter.config, adapter.module
    want = walked[False]
    lengths = jnp.asarray([length, max(1, length - 1), 1], jnp.int32)
    logits, pre = model.apply(params, jnp.asarray(sample[:, :length]),
                              lengths=lengths)
    for r, n in enumerate(np.asarray(lengths)):   # past it: padding's
        np.testing.assert_allclose(np.asarray(logits[r, :n]), want[r, :n],
                                   atol=LOGIT_TOL, rtol=0)
    cache = llama.prefill_into_cache(cfg, pre, 3, 128, 0)
    pos = np.asarray(lengths)
    for _ in range(44):
        tok = sample[np.arange(3), pos][:, None]
        logits, cache = step(params, jnp.asarray(tok),
                             jnp.asarray(pos, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   want[np.arange(3), pos], atol=LOGIT_TOL,
                                   rtol=0)
        pos = pos + 1


def test_a_stale_conv_tail_is_what_the_fault_reads(adapter, params, sample,
                                                   walked, step):
    """The planted fault is the program's own: a decode that never writes
    the conv tail (the prefill's handed back every step) serves the
    reference's ``stale_tail`` stream."""
    cfg, model = adapter.config, adapter.module
    _, pre = model.apply(params, jnp.asarray(sample[:1, :64]))
    cache = llama.prefill_into_cache(cfg, pre, 1, 128, 64)
    tails = [entry.get("conv") for entry in cache]
    for t in range(64, 72):
        logits, cache = step(params, jnp.asarray(sample[:1, t:t + 1]),
                             jnp.full((1,), t, jnp.int32), cache)
        for entry, tail in zip(cache, tails):
            if tail is not None:
                entry["conv"] = tail
        np.testing.assert_allclose(np.asarray(logits[0, 0]),
                                   walked["stale_tail"][0, t],
                                   atol=LOGIT_TOL, rtol=0)


# -- the engine packs, buckets and segments a cache whose leaves differ --------

def test_the_engine_serves_kda_and_latent_layers_and_counts_both(adapter,
                                                                 params):
    cfg = adapter.config
    cache = llama.init_decode_cache(cfg, 3, 64)
    shapes = [{k: (v.shape, v.dtype) for k, v in e.items() if k != "index"}
              for e in cache]
    assert shapes[0] == {"state": ((3, 1, 128, 16), jnp.float32),
                         "conv": ((3, 3, 3, 128), jnp.float32)}
    assert shapes[6] == {"ckv": ((3, 64, 1, 32), jnp.float32),
                         "kpe": ((3, 64, 1, 8), jnp.float32)}
    server = adapter.make_server(params)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, VOCAB, n).tolist()
               for n in (5, 40, 2, 64, 12, 50)]
    solo = [server.generate(p, max_new_tokens=24)[0].tolist()
            for p in prompts]
    eng = ContinuousBatcher(server, slots=2, segment=8, cache_len=128)
    got = [None] * len(prompts)

    def run(i):
        got[i] = eng.generate(prompts[i], max_new_tokens=24)[0].tolist()

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # two slots each served three requests, shorter after longer; window
    # buckets under the cache's 128 cut ckv and kpe and hand the states and
    # tails on whole
    assert got == solo
    keys = {key[:1] + key[2:4] for key in server.buckets if key[0] == "seg_w"}
    assert ("seg_w", 128, 64) in keys
    stats, counted = eng.stats(), eng.counters["kda"].report()
    row_steps = stats["rows_in_segments"] * 8
    assert counted["row_steps"] == row_steps * 6
    assert counted["state_bytes"] == counted["row_steps"] * LAYER_BYTES
    # no Mosaic here: no layer-step's state went through the in-place kernel
    assert counted["kernel_row_steps"] == 0 and not kda.steps_in_place(cfg)
    assert kda.state_bytes_a_step(cfg) == LAYER_BYTES
    assert kda.counters(cfg)[0].segment({}, 1, 1)["state_bytes"] \
        == 6 * LAYER_BYTES
    # every prefill here was one row: a prompt bucket's chunks of 16, 6 layers
    assert counted["scan_chunks"] > 0 and counted["scan_chunks"] % 6 == 0
    # the routed FFN's load comes out of a segment whose entries differ by
    # layer: 6 routed layers x top-4 a booked row-step, a quarter held here
    load = eng.counters["moe"].report()
    assert load["assignments"] == row_steps * 6 * 4
    assert len(load["load"]) == 16 and sum(load["load"]) == load["assignments"]
    assert load["local_assignments"] == sum(load["load"][4:8])
    assert 0 < load["local_assignments"] < load["assignments"]
    assert load["layer_steps"] == stats["segments_run"] * 8 * 6
    assert 0 < load["experts_read"] / load["layer_steps"] <= 4
    before = eng.counters["kda"].report()
    eng.generate(prompts[1], max_new_tokens=24)
    after = eng.counters["kda"].report()
    assert after["row_steps"] - before["row_steps"] == 24 * 6
    # a 40-token prompt prefills at 64 positions: 4 chunks of 16 a layer
    assert after["scan_chunks"] - before["scan_chunks"] == 4 * 6
    assert not llama.segment_keeps_tail(cfg)


# -- the description, and what cannot take it says so --------------------------

def test_the_description_is_what_the_constructors_read(adapter):
    cfg = adapter.config
    assert [cfg.layer_spec(i) for i in range(7)] == [
        llama.LayerSpec(kind, "dense" if i == 0 else "routed")
        for i, kind in enumerate(KINDS)]
    assert cfg.attn_kinds == ("kda", "latent") and cfg.attn_kind == "kv"
    assert (cfg.first_layer_of("kda"), cfg.first_layer_of("latent"),
            cfg.first_layer_of("linear")) == (0, 6, -1)
    assert cfg.cache_layout(0) == {"state": (128, 16), "conv": (3, 128)}
    assert cfg.cache_layout(6) == {"ckv": (1, 32), "kpe": (1, 8)}
    assert cfg.cache_positions(100, 0) == {"state": 1, "conv": 3}
    assert cfg.cache_positions(100, 6) == {"ckv": 100, "kpe": 100}
    assert cfg.cache_dtypes(0) == {"state": jnp.float32, "conv": jnp.float32}
    assert (cfg.cache_slot("state", 70, 0), cfg.cache_slot("conv", 70, 0),
            cfg.cache_slot("ckv", 70, 6)) == (0, 0, 70)
    assert tuple(cfg.layer_kinds).count("kda") == 6
    assert [c.block for c in cfg.counters()] == ["moe", "kda"]
    assert cfg.attn_output_gate and cfg.attn_gate_headwise
    assert cfg.moe_held == (4, 4) and cfg.moe_n_group == 4
    # the kinds that are one a model answer the same questions as before
    v3 = registry.get("deepseek-v3").build(extra={
        "hidden": 64, "heads": 4, "layers": 2, "qk_nope": 16, "qk_rope": 8,
        "v_head": 16, "kv_lora_rank": 32, "moe_experts": 4, "moe_top_k": 2,
        "moe_intermediate": 32}).config
    assert v3.attn_kinds == ("latent",) and not v3.layer_kinds
    assert v3.cache_layout() == {"ckv": (1, 32), "kpe": (1, 8)}
    tree = jax.eval_shape(lambda: adapter.init_params(seed=0))["params"]
    assert set(tree["layer_0"]) == {
        "attn_norm", "q_proj", "k_proj", "v_proj", "conv_weight", "f_proj",
        "dt_bias", "A_log", "b_proj", "o_norm", "out_gate_proj", "o_proj",
        "mlp_norm", "gate_proj", "up_proj", "down_proj"}
    assert set(tree["layer_1"]) == (set(tree["layer_0"]) - {
        "gate_proj", "up_proj", "down_proj"}) | {"moe"}
    assert set(tree["layer_6"]) == {
        "attn_norm", "q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
        "out_gate_proj", "o_proj", "mlp_norm", "moe"}
    assert tree["layer_0"]["conv_weight"].shape == (4, 3, 128)
    assert tree["layer_0"]["out_gate_proj"]["kernel_int8"].shape == (128, 8)
    assert tree["layer_6"]["out_gate_proj"]["kernel_int8"].shape == (128, 8)
    assert tree["layer_1"]["moe"]["experts_up_int8"].shape == (4, 128, 32)
    assert tree["layer_1"]["moe"]["router"].shape == (128, 16)


@pytest.mark.parametrize("holder", [
    "require_kv_cache", "require_row_a_token", "refuse_kind_modules",
    "kvwire", "mesh", "init_page_arena", "prefix_store", "offload",
    "kv_quant", "attn_backend", "spec_k", "cache_prefix", "prefill_chunk",
    "kda_chunk", "pipeline"])
def test_a_holder_that_cannot_take_the_kind_names_it(holder, adapter, params):
    """Every holder that cuts a cache along one position axis refuses the
    model in ``kda.refusal``'s words (through ``ATTN_KINDS``), or in
    its own where the layout never reaches it."""
    from lambdipy_tpu.runtime import kvwire
    from lambdipy_tpu.runtime.offload import OffloadArena
    from lambdipy_tpu.runtime.prefixstore import PrefixStore

    cfg = adapter.config
    assert llama.attn_kind_module("kda") is kda
    block = [{name: np.zeros((1, 16, heads, width), np.float32)
              for name, (heads, width) in cfg.cache_layout(layer).items()}
             for layer in range(cfg.layers)]
    template = [[name, "float32", list(val.shape)]
                for name, val in block[0].items()]

    class Mesh:
        shape = {"tp": 2}

    def chunk_step():
        return adapter.module.apply(
            params, jnp.zeros((1, 4), jnp.int32),
            positions=jnp.arange(4)[None],
            cache=llama.init_decode_cache(cfg, 1, 32))

    def server():
        return adapter.make_server(params)

    kind = "a kda layer, whose cache is a gated delta-rule state"

    def kinds_words():    # what every refusal above is made of
        raise NotImplementedError(
            llama.attn_kind_module("kda").refusal(cfg, "X"))

    calls = {
        "require_kv_cache": (lambda: llama.require_kv_cache(cfg, "X"), kind),
        "require_row_a_token": (
            lambda: llama.require_row_a_token(cfg, "X"), kind),
        "refuse_kind_modules": (kinds_words, kind),
        "kvwire": (lambda: kvwire.encode_frame(list(range(16)), 16, [block]),
                   "kvwire"),
        "mesh": (lambda: llama.validate_serving_mesh(cfg, Mesh()),
                 "mesh .* " + kind),
        "init_page_arena": (lambda: llama.init_page_arena(cfg, 8, 16), kind),
        "prefix_store": (lambda: PrefixStore(server(), block=16), kind),
        "offload": (lambda: OffloadArena(page=16, layers=2).attach_template(
            template), "kvwire"),
        "kv_quant": (lambda: build(kv_quant="int8"), "kv_quant"),
        "attn_backend": (lambda: build(attn_backend="blocked"),
                         "attn_backend"),
        "spec_k": (lambda: ContinuousBatcher(server(), slots=2, segment=4,
                                             spec_k=4), "spec_k"),
        "cache_prefix": (lambda: server().cache_prefix([1, 2, 3]), kind),
        "prefill_chunk": (lambda: adapter.make_server(params,
                                                      prefill_chunk=16), kind),
        "kda_chunk": (chunk_step, "one token a row"),
        "pipeline": (lambda: llama.pipeline_forward(
            adapter.module, params, jnp.zeros((2, 8), jnp.int32), None,
            num_microbatches=1), "attention kind a layer"),
    }
    call, name = calls[holder]
    with pytest.raises((NotImplementedError, ValueError), match=name):
        call()


def test_what_is_left_out_is_refused_by_name():
    """The clamped SwiGLU and the multi-token-prediction layer, by family and
    by program; and a description that is not one is refused at build."""
    limits = [0, 0, 0, 0, 0, 4, 4]
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        FAMILY.dims_of({**CONFIG, "expert_swiglu_limit_list": limits})
    with pytest.raises(ValueError, match="share_expert_swiglu_limit_list"):
        FAMILY.dims_of({**CONFIG, "share_expert_swiglu_limit_list": limits})
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        FAMILY.dims_of({**CONFIG, "num_nextn_predict_layers": 1})
    with pytest.raises(NotImplementedError, match="swiglu_limit"):
        build(swiglu_limit=4)
    with pytest.raises(NotImplementedError, match="nextn_predict_layers"):
        build(nextn_predict_layers=1)
    for over in ({"layer_kinds": "kda,latent"},             # not one a layer
                 {"layer_kinds": ",".join(["eva"] * 7)},
                 {"layer_kinds": ""}, {"kda_heads": 0}, {"kda_conv": 1},
                 {"kda_lower_bound": 0.5}, {"kv_lora_rank": 0}):
        with pytest.raises(ValueError):
            build(**over)
    for key, value in (("use_kda_lora", True), ("kda_safe_gate", False),
                       ("group_norm_size", 4), ("q_lora_rank", 64),
                       ("gated_attention_proj_granularity_type", "full"),
                       ("layers_held", [1, 6, 7])):
        with pytest.raises(ValueError, match="bailing-hybrid family"):
            FAMILY.dims_of({**CONFIG, key: value})
    # the published numbering decides the kinds: a group's last layer is MLA
    assert FAMILY.dims_of({**CONFIG, "layers_held": [0, 1, 2, 3, 4, 5, 6]})[
        "layer_kinds"] == "kda,kda,kda,kda,kda,latent,kda"
