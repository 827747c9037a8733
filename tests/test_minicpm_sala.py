"""The ``minicpm-sala`` model (an attention kind a LAYER: block-sparse
InfLLM-V2 layers among Lightning linear-attention layers, muP scalars) at toy
widths on the CPU, in float32, against the plain reference of its benchmark
family (``benchmark/families/minicpm_sala.py``: no cache, the plain
recurrence a position, explicit gathers and ``top_k``; it imports nothing of
the program).

The toy twin (``benchmark/configs/rehearsal-sala.json``) has compressed keys
every 2 tokens over 4, blocks of 8, a top-4 of which block 0 and the 2 blocks
of the local window are forced, and ``dense_len`` 32, so that contexts of a
hundred positions select among a dozen blocks; its prefill's blocks and the
scan's chunk are cut to 16 here so that every loop turns several times.
Comparisons are of LOGITS: program and reference are the same float32
function written two ways (a chunked scan against the recurrence, a mask
from an exact threshold against ``top_k``, a scale after the dot against a
dequantized kernel) and differ by the order of float32 sums, about 1.5e-6 at
logits of order 0.15; 2e-5 leaves ten times that and is a thousand times
under what any of the family's faults shows."""

import json
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, weights
from lambdipy_tpu.models import linear_attn, llama, registry, sparse_kv
from lambdipy_tpu.runtime.continuous import ContinuousBatcher

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-sala.json").read_text())
FAMILY = families.of(CONFIG)
DIMS = FAMILY.dims_of(CONFIG)
SPARSE = CONFIG["sparse_config"]
VOCAB = CONFIG["vocab_size"]
LOGIT_TOL = 2e-5


def build(quant="int8", **over):
    return registry.get("minicpm-sala").build(dtype="float32", quant=quant,
                                              extra={**DIMS, **over})


def family_params(adapter, config=CONFIG):
    def fill(keypath, spec):
        name = "/".join(str(k.key) for k in keypath if k.key != "params")
        return jnp.asarray(weights.leaf(config, name, spec.shape, spec.dtype))

    return jax.tree_util.tree_map_with_path(
        fill, jax.eval_shape(lambda: adapter.init_params(seed=0)))


@pytest.fixture(autouse=True)
def small_turns(monkeypatch):
    """Blocks and chunks of 16: a 104-token forward is 7 query blocks in 4
    key blocks and 7 chunks of the scan."""
    monkeypatch.setattr(sparse_kv, "SPARSE_QUERY_BLOCK", 16)
    monkeypatch.setattr(sparse_kv, "SPARSE_KEY_BLOCK", 32)
    monkeypatch.setattr(sparse_kv, "DENSE_PREFILL_MAX", 16)
    monkeypatch.setattr(linear_attn, "LIN_CHUNK", 16)


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
    """The reference and every control over the sample: {flag: [3, 104, v]}."""
    ids = np.asarray(sample, np.int32)
    rows = np.repeat(np.arange(ids.shape[0]), ids.shape[1])
    pos = np.tile(np.arange(ids.shape[1]), ids.shape[0])
    out = FAMILY.walk(CONFIG, ids, rows, pos, (False, True) + FAMILY.FAULTS)
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


def forward(adapter, params, ids):
    return np.asarray(adapter.module.apply(params, jnp.asarray(ids))[0])


# -- the whole forward, and what each fault would show -------------------------

def test_the_whole_forward_is_the_references(adapter, params, sample, walked):
    got = forward(adapter, params, sample)
    assert got.shape == (3, 104, VOCAB)
    assert np.std(walked[False]) > 0.1
    np.testing.assert_allclose(got, walked[False], atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("fault", [True, *FAMILY.FAULTS])
def test_each_fault_moves_the_logits_a_thousand_tolerances(fault, walked):
    """The comparison can see every fault; the block-sparse layers' faults
    move nothing inside ``dense_len`` (rope apart, which moves everything
    from position 1), where the layer is plain causal attention."""
    moved = np.abs(walked[fault] - walked[False])
    assert moved.max() > 1000 * LOGIT_TOL
    if fault in ("dense_past", "no_forced", "stale_kc"):
        assert moved[:, :SPARSE["dense_len"]].max() == 0.0


def test_the_chunked_scan_is_the_recurrence():
    """``linear_attn.chunked_scan`` (chunks of 16, a ragged batch) against
    one position a step, outputs and the state each row hands on: the one at
    ITS length, whatever padding follows."""
    rng = np.random.default_rng(2)
    b, s, heads, d = 3, 70, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, heads, d)), jnp.float32)
               for _ in range(3))
    lengths = jnp.asarray([70, 33, 5], jnp.int32)
    out, state = linear_attn.chunked_scan(q, k, v, lengths)
    lam = np.exp(-np.exp2(-8.0 * np.arange(1, heads + 1) / heads))
    for r, n in enumerate(np.asarray(lengths)):
        S = np.zeros((heads, d, d))
        for t in range(n):
            S = lam[:, None, None] * S + np.einsum(
                "hd,he->hde", np.asarray(k[r, t]), np.asarray(v[r, t]))
            want = np.einsum("hd,hde->he", np.asarray(q[r, t]), S) * d ** -0.5
            np.testing.assert_allclose(np.asarray(out[r, t]), want,
                                       atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(state[r]), S, atol=2e-5,
                                   rtol=1e-5)


def test_the_selected_blocks_are_the_references():
    """``sparse_kv.select_blocks`` (scores, pool, forced blocks, the exact
    threshold as a mask) against the definition by hand with a sort, for
    every query of a row of 120 positions: top-4 of up to 15 blocks, block
    0 and the two most recent forced."""
    cfg = build().config
    rng = np.random.default_rng(3)
    s, heads, kvh, d = 120, cfg.heads, cfg.kv_heads, cfg.head_dim
    stride, kernel, block = 2, 4, 8
    q = jnp.asarray(rng.normal(size=(1, s, heads, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, kvh, d)), jnp.float32)
    kc = sparse_kv.compress(k, stride)
    pos = jnp.arange(s)[None]
    got = np.asarray(sparse_kv.select_blocks(cfg, q, kc, pos, s // block))
    qn, kn = np.asarray(q[0]), np.asarray(k[0])
    for t in range(s):
        cur = t // block
        if cur + 1 <= cfg.sparse_topk:
            assert got[0, :, t, :cur + 1].all() and not got[0, :, t, cur + 1:].any()
            continue
        nj = (t + 1 - kernel) // stride + 1
        comp = np.stack([kn[stride * j: stride * j + kernel].mean(0)
                         for j in range(nj)])                  # [nj, kvh, d]
        for g in range(kvh):
            heads_g = qn[t, g * (heads // kvh):(g + 1) * (heads // kvh)]
            lg = heads_g @ comp[:, g].T * d ** -0.5
            pr = np.exp(lg - lg.max(-1, keepdims=True))
            group = (pr / pr.sum(-1, keepdims=True)).sum(0)   # [nj]
            score = np.zeros(cur + 1)
            for blk in range(cur + 1):
                js = [j for j in range(4 * blk - 1, 4 * blk + 4)
                      if 0 <= j < nj]
                score[blk] = max((group[j] for j in js), default=0.0)
            score[0] = np.inf
            score[max(0, cur - 1):] = np.inf
            want = set(np.argsort(-score, kind="stable")[:cfg.sparse_topk])
            assert set(np.flatnonzero(got[0, g, t])) == want, (t, g)


# -- prefill, then decode through the two kinds' leaves ------------------------

@pytest.mark.parametrize("length", [3, 31, 32, 33, 47, 70])
def test_prefill_then_decode_is_the_references_whole_forward(
        length, adapter, params, sample, walked, step):
    """A ragged prefill to ``length`` (31, 32, 33: across ``dense_len``; 47:
    the next step completes a compressed key), then one token a step to 104
    through ``k``, ``v``, ``kc`` and ``state``: every logit is the
    reference's, which never saw a cache."""
    cfg, model = adapter.config, adapter.module
    want = walked[False]
    lengths = jnp.asarray([length, max(1, length - 2), length], jnp.int32)
    logits, pre = model.apply(params, jnp.asarray(sample[:, :length]),
                              lengths=lengths)
    for r, n in enumerate(np.asarray(lengths)):   # past it: padding's
        np.testing.assert_allclose(np.asarray(logits[r, :n]), want[r, :n],
                                   atol=LOGIT_TOL, rtol=0)
    cache = llama.prefill_into_cache(cfg, pre, 3, 128, 0)
    pos = np.asarray(lengths)
    for _ in range(104 - length):
        tok = sample[np.arange(3), pos][:, None]
        logits, cache = step(params, jnp.asarray(tok),
                             jnp.asarray(pos, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   want[np.arange(3), pos], atol=LOGIT_TOL,
                                   rtol=0)
        pos = pos + 1


def test_a_reused_slot_reads_nothing_of_the_last_tenant(adapter, params,
                                                        sample, walked, step):
    """Rows and compressed keys full of large garbage behind a shorter
    request's prefill: the masks give every such slot a probability of exact
    zero, and the step that completes a compressed key rewrites it before
    anything may see it."""
    cfg, model = adapter.config, adapter.module
    _, pre = model.apply(params, jnp.asarray(sample[:1, :37]))
    cache = llama.prefill_into_cache(cfg, pre, 1, 128, 37)
    for layer, entry in enumerate(cache):
        if cfg.layer_spec(layer).attn != "sparse_kv":
            continue
        later = np.arange(128) >= 37
        # compressed key j is whole once position 2 j + 3 is written
        open_kc = 2 * np.arange(64) + 4 > 37
        for name, hide in (("k", later), ("v", later), ("kc", open_kc)):
            entry[name] = jnp.where(hide[None, :, None, None], 1e4,
                                    entry[name])
    for t in range(37, 60):
        logits, cache = step(params, jnp.asarray(sample[:1, t:t + 1]),
                             jnp.full((1,), t, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(logits[0, 0]),
                                   walked[False][0, t], atol=LOGIT_TOL,
                                   rtol=0)


# -- the engine packs, buckets and segments a cache whose leaves differ --------

def test_the_engine_serves_a_cache_whose_leaves_differ_by_layer(adapter,
                                                                params):
    cfg = adapter.config
    cache = llama.init_decode_cache(cfg, 3, 64)
    shapes = [{k: (v.shape, v.dtype) for k, v in e.items() if k != "index"}
              for e in cache]
    assert shapes[0] == {"k": ((3, 64, 2, 16), jnp.float32),
                         "v": ((3, 64, 2, 16), jnp.float32),
                         "kc": ((3, 32, 2, 16), jnp.float32)}
    assert shapes[1] == {"state": ((3, 1, 128, 16), jnp.float32)}
    assert [list(s) for s in shapes] == [
        ["k", "v", "kc"], ["state"], ["state"], ["state"], ["k", "v", "kc"],
        ["state"]]
    server = adapter.make_server(params)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, VOCAB, n).tolist()
               for n in (5, 40, 70, 33, 20, 64)]
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
    # buckets under the cache's 128 cut k, v and kc and hand the states on
    assert got == solo
    keys = {key[:1] + key[2:4] for key in server.buckets if key[0] == "seg_w"}
    assert ("seg_w", 128, 64) in keys
    stats, sala = eng.stats(), eng.counters["sala"].report()
    assert sala["row_steps"] == stats["rows_in_segments"] * 8
    assert sala["state_bytes"] == sala["row_steps"] * 4 * 2 * 4 * 8 * 16 * 16
    # no Mosaic here: no row-step's states went through the in-place kernel
    assert sala["kernel_row_steps"] == 0 and not linear_attn.steps_in_place(cfg)
    assert 0 < sala["dense_steps"] < sala["row_steps"]
    # a row writes a compressed key every second step
    assert abs(2 * sala["kc_writes"] - sala["row_steps"]) <= 2 * len(prompts)
    assert sala["keys_attended"] < sala["keys_visible"]
    # by hand, one more request alone: 24 steps from position 40 (past
    # dense_len: 4 blocks of 8, the last one as far as the step has come)
    before = eng.counters["sala"].report()
    eng.generate(prompts[1], max_new_tokens=24)
    after = eng.counters["sala"].report()
    assert after["row_steps"] - before["row_steps"] == 24
    assert after["keys_attended"] - before["keys_attended"] == sum(
        3 * 8 + t % 8 + 1 for t in range(40, 64))
    assert after["keys_visible"] - before["keys_visible"] == sum(
        range(41, 65))
    assert after["dense_steps"] == before["dense_steps"]
    assert after["kc_writes"] - before["kc_writes"] == 12
    assert not llama.segment_keeps_tail(cfg)


# -- the description, and what cannot take it says so --------------------------

def test_the_description_is_what_the_constructors_read(adapter):
    cfg = adapter.config
    assert [cfg.layer_spec(i).attn for i in range(6)] == [
        "sparse_kv", "linear", "linear", "linear", "sparse_kv", "linear"]
    assert (cfg.first_layer_of("sparse_kv"), cfg.first_layer_of("linear"),
            cfg.first_layer_of("eva")) == (0, 1, -1)
    assert cfg.cache_layout(0) == {"k": (2, 16), "v": (2, 16), "kc": (2, 16)}
    assert cfg.cache_layout(1) == {"state": (128, 16)}
    assert cfg.cache_positions(100, 0) == {"k": 100, "v": 100, "kc": 50}
    assert cfg.cache_positions(100, 1) == {"state": 1}
    assert cfg.cache_dtypes(1) == {"state": jnp.float32}
    assert (cfg.cache_slot("k", 70, 0), cfg.cache_slot("kc", 70, 0),
            cfg.cache_slot("state", 70, 1)) == (70, 35, 0)
    assert [c.block for c in cfg.counters()] == ["sala", "sala"]
    assert linear_attn.counters(cfg)[0].segment({}, 1, 1)["state_bytes"] \
        == 4 * 2 * 4 * 8 * 16 * 16
    assert (cfg.embed_scale, cfg.logit_divisor) == (12.0, 4.0)
    assert cfg.residual_scale == pytest.approx(1.4 / 6 ** 0.5)
    # past two blocks of 4096 a prompt prefills at whole blocks
    for length, bucket in ((3, 16), (5000, 8192), (8192, 8192), (8193, 12288),
                           (16384, 16384), (16385, 20480), (20480, 20480)):
        assert cfg.prompt_bucket(length, 16) == bucket
    # the kinds that are one a model answer the same questions as before
    tiny = registry.get("llama-tiny").build().config
    assert tiny.cache_positions(64) == {"k": 64, "v": 64}
    assert not tiny.layer_kinds and not tiny.counters()
    assert tiny.prompt_bucket(9000, 16) == 16384
    tree = jax.eval_shape(lambda: adapter.init_params(seed=0))["params"]
    assert set(tree["layer_0"]) == {
        "attn_norm", "q_proj", "k_proj", "v_proj", "q_norm", "k_norm",
        "out_gate_proj", "o_proj", "mlp_norm", "gate_proj", "up_proj",
        "down_proj"}
    assert set(tree["layer_1"]) == set(tree["layer_0"]) | {"o_norm"}
    assert tree["layer_0"]["k_proj"]["kernel_int8"].shape == (128, 32)
    assert tree["layer_1"]["k_proj"]["kernel_int8"].shape == (128, 128)
    assert tree["layer_1"]["o_norm"]["scale"].shape == (16,)


@pytest.mark.parametrize("holder", [
    "init_page_arena", "page_kv_bytes", "prefix_store", "kvwire", "offload",
    "kv_quant", "attn_backend", "mesh", "spec_k", "cache_prefix",
    "prefill_chunk", "speculative", "concat_cache_blocks", "sparse_chunk",
    "linear_chunk", "pipeline"])
def test_a_holder_that_cannot_take_these_kinds_raises_by_name(
        holder, adapter, params):
    from lambdipy_tpu.runtime import kvwire
    from lambdipy_tpu.runtime.offload import OffloadArena
    from lambdipy_tpu.runtime.prefixstore import PrefixStore

    cfg = adapter.config
    server = adapter.make_server(params)
    cache = llama.init_decode_cache(cfg, 1, 32)
    block = [{name: np.zeros((1, 16, heads, width), np.float32)
              for name, (heads, width) in cfg.cache_layout(layer).items()}
             for layer in range(cfg.layers)]
    template = [[name, "float32", list(val.shape)]
                for name, val in block[0].items()]

    class Mesh:
        shape = {"tp": 2}

    def chunk_step(layer):
        one = build(layers=1, layer_kinds=cfg.layer_kinds[layer])
        return lambda: one.module.apply(
            one.init_params(seed=0), jnp.zeros((1, 4), jnp.int32),
            positions=jnp.arange(4)[None],
            cache=llama.init_decode_cache(one.config, 1, 32))

    calls = {
        "init_page_arena": (lambda: llama.init_page_arena(cfg, 8, 16),
                            "paged KV arena"),
        "page_kv_bytes": (lambda: llama.page_kv_bytes(cfg, 16), "page pool"),
        "prefix_store": (lambda: PrefixStore(server, block=16),
                         "PrefixStore"),
        "kvwire": (lambda: kvwire.encode_frame(list(range(16)), 16, [block]),
                   "kvwire"),
        "offload": (lambda: OffloadArena(page=16, layers=2).attach_template(
            template), "kvwire"),
        "kv_quant": (lambda: build(kv_quant="int8"), "kv_quant"),
        "attn_backend": (lambda: build(attn_backend="blocked"),
                         "attn_backend"),
        "mesh": (lambda: llama.validate_serving_mesh(cfg, Mesh()), "mesh"),
        "spec_k": (lambda: ContinuousBatcher(server, slots=2, segment=4,
                                             spec_k=4), "spec_k"),
        "cache_prefix": (lambda: server.cache_prefix([1, 2, 3]),
                         "cache_prefix"),
        "prefill_chunk": (lambda: adapter.make_server(params,
                                                      prefill_chunk=16),
                          "prefill_chunk"),
        "speculative": (lambda: server.generate_speculative(
            [1, 2, 3], max_new_tokens=4), "_spec_steps"),
        "concat_cache_blocks": (lambda: llama.concat_cache_blocks(
            cfg, [block], 32), "concat_cache_blocks"),
        "sparse_chunk": (chunk_step(0), "one-token step"),
        "linear_chunk": (chunk_step(1), "one token a row"),
        "pipeline": (lambda: llama.pipeline_forward(
            adapter.module, params, jnp.zeros((2, 8), jnp.int32), None,
            num_microbatches=1), "attention kind a layer"),
    }
    del cache
    call, name = calls[holder]
    with pytest.raises((NotImplementedError, ValueError), match=name):
        call()


def test_a_wrong_description_is_refused_at_build():
    for over in ({"layer_kinds": "sparse_kv,linear"},       # not one a layer
                 {"layer_kinds": ",".join(["eva"] * 6)},
                 {"layer_kinds": ""},
                 {"sparse_kernel": 6}, {"sparse_block": 7},
                 {"sparse_topk": 2},          # cannot hold the forced blocks
                 {"lin_heads": 0}, {"lin_head_dim": 15}):
        with pytest.raises(ValueError):
            build(**over)
    with pytest.raises(ValueError, match="lightning"):
        FAMILY.dims_of({**CONFIG, "lightning_nkv": 4})
    with pytest.raises(ValueError, match="mixer_types"):
        FAMILY.dims_of({**CONFIG, "mixer_types": ["mamba"] * 6})


def test_a_checkpoints_names_become_the_programs_tree():
    """``convert.import_minicpm_sala``: a state dict under the checkpoint's
    names (``convert.SALA_NAMES``, torch's ``[out, in]``), the layers' kinds
    from ``mixer_types``, becomes the tree the builder expects, leaf for
    leaf."""
    from lambdipy_tpu.models import convert

    float_adapter = build(quant=None)
    tree = jax.tree.map(np.asarray,
                        float_adapter.init_params(seed=3)["params"])
    hf_cfg = {k: CONFIG[k] for k in (
        "attention_bias", "attn_use_rope", "head_dim", "hidden_size",
        "intermediate_size", "lightning_head_dim", "lightning_nh",
        "lightning_nkv", "lightning_scale", "lightning_use_rope",
        "mixer_types", "num_attention_heads", "num_hidden_layers",
        "num_key_value_heads", "qk_norm", "rms_norm_eps", "vocab_size",
        "rope_theta", "scale_emb", "scale_depth", "dim_model_base",
        "tie_word_embeddings", "use_output_gate", "use_output_norm",
        "attn_use_output_gate", "sparse_config")}
    sd = {"model.embed_tokens.weight": tree["embed"]["embedding"],
          "model.norm.weight": tree["final_norm"]["scale"],
          "lm_head.weight": tree["lm_head"]["kernel"].T}
    for i in range(6):
        for ours, leaf in tree[f"layer_{i}"].items():
            (value,) = leaf.values()
            sd[f"model.layers.{i}.{convert.SALA_NAMES[ours]}.weight"] = \
                value.T if "kernel" in leaf else value
    assert "model.layers.1.self_attn.o_norm.weight" in sd
    assert "model.layers.0.self_attn.o_norm.weight" not in sd
    cfg, params = convert.import_minicpm_sala(sd, hf_cfg, max_len=256,
                                              dtype=jnp.float32)
    assert cfg == float_adapter.config
    got = params["params"]
    assert jax.tree.structure(got) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(a, b)
    # a checkpoint cut in depth keeps the published depth's residual scale
    cut = convert.minicpm_sala_config_from_hf(hf_cfg, published_layers=32)
    assert cut.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    with pytest.raises(ValueError, match="attn_use_rope"):
        convert.minicpm_sala_config_from_hf({**hf_cfg, "attn_use_rope": True})
