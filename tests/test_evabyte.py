"""The ``evabyte`` model (EVA chunked linearized attention: an exact window
beside one learned summary for every chunk of the earlier windows, under one
softmax; unit-offset norms; several prediction heads) at toy widths on the
CPU, in float32, against the plain reference of its benchmark family
(``benchmark/families/evabyte.py``: no cache, no ring, explicit exact and
summary sets a window at a time; it imports nothing of the program).

The toy twin (``benchmark/configs/rehearsal-eva.json``) has a window of 32
and chunks of 4, so that contexts of a hundred positions cross several
windows and dozens of chunks. Comparisons are of LOGITS: the program and
the reference are the same float32 function written two ways (a ring and a
summary leaf stepped a token at a time against whole windows; a scale
applied after the dot against a dequantized kernel), so they differ by the
order of float32 sums, about 2e-6 at logits of order 0.6; 2e-5 leaves ten
times that and is a thousand times under what any of the family's faults
shows (the smallest moves a logit by 0.3). Where served tokens are checked
the measure is the benchmark's own gap; 1e-4."""

import json
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, reference, weights
from lambdipy_tpu.models import eva, llama, registry
from lambdipy_tpu.runtime.continuous import ContinuousBatcher

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-eva.json").read_text())
FAMILY = families.of(CONFIG)
DIMS = FAMILY.dims_of(CONFIG)
WIN, CHUNK = CONFIG["window_size"], CONFIG["chunk_size"]
VOCAB = CONFIG["vocab_size"]
LOGIT_TOL = 2e-5
GAP_TOL = 1e-4


def build(model="evabyte", quant="int8", **over):
    return registry.get(model).build(dtype="float32", quant=quant,
                                     extra={**DIMS, **over})


def family_params(adapter, config=CONFIG):
    """The family's seeded leaves in the program's tree, as the bundle's
    parameter file holds them."""
    def fill(keypath, spec):
        name = "/".join(str(k.key) for k in keypath if k.key != "params")
        return jnp.asarray(weights.leaf(config, name, spec.shape, spec.dtype))

    return jax.tree_util.tree_map_with_path(
        fill, jax.eval_shape(lambda: adapter.init_params(seed=0)))


@pytest.fixture(scope="module")
def adapter():
    return build()


@pytest.fixture(scope="module")
def params(adapter):
    return family_params(adapter)


@pytest.fixture(scope="module")
def server(adapter, params):
    return adapter.make_server(params)


@pytest.fixture(scope="module")
def sample():
    return np.random.default_rng(1).integers(0, VOCAB, (3, 104)).astype(np.int32)


@pytest.fixture(scope="module")
def full(adapter, params, sample):
    """The program's whole forward over the sample: [3, 104, vocab]."""
    return np.asarray(adapter.forward(params, jnp.asarray(sample)))


def walk_logits(ids, flags=(False,), config=CONFIG):
    ids = np.asarray(ids, np.int32)
    rows = np.repeat(np.arange(ids.shape[0]), ids.shape[1])
    pos = np.tile(np.arange(ids.shape[1]), ids.shape[0])
    out = families.of(config).walk(config, ids, rows, pos, flags)
    return {flag: np.asarray(v).reshape(*ids.shape, -1)
            for flag, v in out.items()}


def served_gap(rows):
    """``rows``: (prompt, served tokens). The widest gap of the served
    tokens under the reference, the benchmark's measure of ``correct``."""
    pairs = [(list(p) + [int(t) for t in toks], len(p)) for p, toks in rows]
    length = -(-max(len(t) for t, _ in pairs) // 16) * 16
    new = max(len(t) - n for t, n in pairs)
    out = reference.served_gaps(CONFIG, pairs, shape=(len(pairs), length, new))
    assert out["served_tokens"] == sum(len(t) for _, t in rows)
    return max(out["gap"])


# -- (a) the whole forward, (e) what each fault would show ---------------------

def test_the_whole_forward_is_the_references(full, sample):
    ref = walk_logits(sample)[False]
    assert full.shape == (3, 104, VOCAB)       # the next byte's head alone
    assert np.std(ref) > 0.3                   # logits of the order served
    np.testing.assert_allclose(full, ref, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("block", [8, 16])
def test_a_window_attended_in_blocks_of_queries_is_the_same_window(
        block, adapter, params, full, sample, monkeypatch):
    """At the published window a prefill attends 128 queries a turn,
    sixteen turns a window; here 8 or 16 of 32."""
    monkeypatch.setattr(eva, "EVA_QUERY_BLOCK", block)
    got = np.asarray(adapter.module.apply(params, jnp.asarray(sample))[0])
    np.testing.assert_allclose(got, full, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("fault", [True, *FAMILY.FAULTS])
def test_each_fault_moves_the_logits_and_only_past_the_first_window(
        fault, sample):
    """The comparison can see every fault: each moves a logit by more than
    a thousand tolerances, and (the int4 control apart) none moves anything
    inside the first window, where EVA is plain causal attention."""
    out = walk_logits(sample, (False, fault))
    moved = np.abs(out[fault] - out[False])
    assert moved[:, WIN:].max() > 1000 * LOGIT_TOL
    if fault is not True:
        assert moved[:, :WIN].max() == 0.0


# -- (d) under one window it is the llama block --------------------------------

def test_a_context_under_one_window_is_plain_causal_attention(params, full,
                                                              sample):
    """The same weights through the ``kv`` kind (``llama-hf``: its norms
    told of the unit offset, its head of the further heads) give the same
    logits at every position of the first window, and other ones past it."""
    plain = build("llama-hf", norm_unit_offset="true")
    tree = {"params": {
        name: ({k: v for k, v in leaf.items() if not k.startswith("adaptive")}
               if name.startswith("layer_") else leaf)
        for name, leaf in params["params"].items()}}
    assert plain.config.attn_kind == "kv"
    got = np.asarray(plain.forward(tree, jnp.asarray(sample)))
    np.testing.assert_allclose(got[:, :WIN], full[:, :WIN], atol=LOGIT_TOL,
                               rtol=0)
    assert np.abs(got[:, WIN:] - full[:, WIN:]).max() > 1000 * LOGIT_TOL


# -- (b) prefill, then decode through ring and summaries -----------------------

@pytest.mark.parametrize("length", [3, 4, 31, 32, 33, 70])
def test_prefill_then_decode_is_the_whole_forward(length, adapter, params,
                                                  full, sample):
    """Ragged rows in one batch, prefilled to ``length``, ``length - 2``
    and ``length + 3`` positions (on both sides of a chunk's and of a
    window's edge), then 30 single-token steps through ring and summaries
    (every row crosses chunk edges, all but the shortest a window's): each
    step's logits are the whole forward's at that position."""
    cfg, model = adapter.config, adapter.module
    lens = np.asarray([length, max(1, length - 2), length + 3])
    s = int(lens.max())
    padded = np.where(np.arange(s)[None] < lens[:, None], sample[:, :s], 0)
    _, pre = model.apply(params, jnp.asarray(padded),
                         lengths=jnp.asarray(lens))
    assert set(pre[0]) == set(cfg.cache_layout())
    cache = llama.prefill_into_cache(cfg, pre, 3, 128, 0)
    assert {k: v.shape[1] for k, v in cache[0].items() if k != "index"} \
        == cfg.cache_positions(128) == {"k": WIN, "v": WIN,
                                        "sk": 128 // CHUNK, "sv": 128 // CHUNK}
    step = jax.jit(lambda tok, pos, cache: model.apply(
        params, tok, positions=pos[:, None], cache=cache))
    rows = np.arange(3)
    for j in range(30):
        pos = jnp.asarray(lens + j)
        for entry in cache:
            entry["index"] = pos
        logits, cache = step(jnp.asarray(sample[rows, lens + j][:, None]),
                             pos, cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   full[rows, lens + j], atol=LOGIT_TOL,
                                   rtol=0)


def test_generate_serves_the_references_choice_across_windows(server):
    rng = np.random.default_rng(4)
    rows = [rng.integers(0, VOCAB, n).tolist() for n in (5, 30, 33, 61)]
    toks = server.generate(rows, max_new_tokens=48)
    assert toks.shape == (4, 48)
    assert served_gap(list(zip(rows, toks))) <= GAP_TOL


# -- (c) the continuous engine, (f) a slot's next tenant -----------------------

def test_the_continuous_engine_is_solo_generation_token_for_token(server):
    """Ragged joiners pack into the B-slot cache (ring and summary leaves
    of their own lengths) and decode in 16-step segments through the
    window-bucketed programs, slots reused by shorter and longer requests
    in turn: every request's tokens are what it gets alone (solo
    generation writes ring and summaries every step; the engine's segments
    keep them read-only and merge their tails, PR 34), and the counters the
    segment programs return add up."""
    eng = ContinuousBatcher(server, slots=2, segment=16)
    rng = np.random.default_rng(5)
    lens = [70, 9, 33, 4, 62, 31]
    want_new = [40, 24, 50, 16, 33, 48]
    rows = [rng.integers(0, VOCAB, n).tolist() for n in lens]
    solo = [server.generate([r], max_new_tokens=n)[0].tolist()
            for r, n in zip(rows, want_new)]
    got = [None] * len(rows)

    def run(i):
        time.sleep(0.05 * i)
        got[i] = eng.generate(rows[i], max_new_tokens=want_new[i])[0].tolist()

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(rows))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # (f): the two slots each served three requests, shorter after longer
    assert got == solo
    # the 70-token prompt prefilled at three whole windows, not at 128
    prompts = {key[2] for key in server.buckets if key[0] == "stream"}
    assert 3 * WIN in prompts and 128 not in prompts
    stats, eva = eng.stats(), eng.counters["eva"].report()
    assert stats["rows_in_segments"] > stats["segments_run"]
    assert eva["row_steps"] == stats["rows_in_segments"] * 16
    # a row writes a summary every CHUNK steps
    assert abs(eva["chunks_written"] * CHUNK - eva["row_steps"]) \
        <= CHUNK * len(rows)
    keys = eva["keys_attended"] / eva["row_steps"]
    assert 1 <= keys <= WIN + 128 // CHUNK
    # by hand for one more request alone: 16 steps from position 40, each
    # seeing its window's ring rows so far and the first window's 8 chunks
    before = eng.counters["eva"].report()
    eng.generate(rows[0][:40], max_new_tokens=16)
    after = eng.counters["eva"].report()
    assert after["row_steps"] - before["row_steps"] == 16
    assert after["keys_attended"] - before["keys_attended"] == sum(
        (t % WIN + 1) + (t // WIN) * (WIN // CHUNK) for t in range(40, 56))
    assert after["chunks_written"] - before["chunks_written"] == 4
    assert after["edge_row_steps"] == before["edge_row_steps"]
    # and from position 20: the segment crosses into the second window at
    # 32, so its last four steps attend no frozen ring row
    eng.generate(rows[0][:20], max_new_tokens=16)
    edge = eng.counters["eva"].report()
    assert edge["edge_row_steps"] - after["edge_row_steps"] == 4
    assert edge["keys_attended"] - after["keys_attended"] == sum(
        (t % WIN + 1) + (t // WIN) * (WIN // CHUNK) for t in range(20, 36))
    # the engine's segments kept their tails; solo generation wrote every step
    assert llama.segment_keeps_tail(server.model.cfg)
    assert edge["chunks_written"] * CHUNK == edge["row_steps"]


def test_a_reused_slot_reads_nothing_of_the_last_tenant(adapter, params):
    """A ring and summaries full of large garbage (what a longer request
    could leave behind) under a shorter request's prefill: its rows land
    where the masks look, and every other slot gets a probability of exact
    zero, so nothing of it reaches a logit."""
    cfg, model = adapter.config, adapter.module
    ids = np.random.default_rng(6).integers(0, VOCAB, (1, 60)).astype(np.int32)
    want = np.asarray(adapter.forward(params, jnp.asarray(ids)))[0]
    _, pre = model.apply(params, jnp.asarray(ids[:, :37]))
    cache = llama.prefill_into_cache(cfg, pre, 1, 128, 37)
    ring = np.arange(WIN) >= 37 % WIN          # slots the masks hide
    later = np.arange(128 // CHUNK) > 37 // CHUNK
    for entry in cache:
        for name, hide in (("k", ring), ("v", ring), ("sk", later),
                           ("sv", later)):
            entry[name] = jnp.where(hide[None, :, None, None], 1e4,
                                    entry[name])
    for t in range(37, 60):
        for entry in cache:
            entry["index"] = jnp.full((1,), t, jnp.int32)
        logits, cache = model.apply(params, jnp.asarray(ids[:, t:t + 1]),
                                    positions=jnp.full((1, 1), t), cache=cache)
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[t],
                                   atol=LOGIT_TOL, rtol=0)


# -- the description, and (g) what cannot take it says so ----------------------

@pytest.mark.parametrize("length, bucket", [
    (3, 16), (31, 32), (32, 32), (33, 64), (64, 64), (65, 96), (70, 96),
    (96, 96), (97, 128), (129, 160)])
def test_a_prompt_past_one_window_prefills_at_whole_windows(length, bucket,
                                                            adapter):
    """Up to one window the power of two, as every kind; past it the next
    whole window: the prefill is one body a window, and a mix whose prompts
    fall between two powers of two pays for the windows it has."""
    assert adapter.config.prompt_bucket(length, 16) == bucket
    tiny = registry.get("llama-tiny").build().config
    assert tiny.prompt_bucket(length, 16) == llama._next_bucket(length, 16)


def test_the_description_is_what_the_constructors_read(adapter):
    cfg = adapter.config
    assert cfg.layer_spec(1) == ("eva", "dense")
    assert list(cfg.cache_layout()) == ["k", "v", "sk", "sv"]
    assert set(cfg.cache_layout().values()) == {(4, 16)}
    assert cfg.cache_positions(8192) == {"k": 32, "v": 32, "sk": 2048,
                                         "sv": 2048}
    assert cfg.cache_positions(20) == {"k": 20, "v": 20, "sk": 5, "sv": 5}
    assert (cfg.cache_slot("k", 70), cfg.cache_slot("sv", 70)) == (6, 17)
    assert [c.block for c in cfg.counters()] == ["eva"]
    assert llama.segment_keeps_tail(cfg)       # a ring and a summary tail
    cache = llama.init_decode_cache(cfg, 3, 64)
    assert {k: v.shape for k, v in cache[0].items() if k != "index"} == {
        "k": (3, 32, 4, 16), "v": (3, 32, 4, 16),
        "sk": (3, 16, 4, 16), "sv": (3, 16, 4, 16)}
    # the kinds that hold one row a token say so through the same questions
    tiny = registry.get("llama-tiny").build().config
    assert tiny.cache_positions(64) == {"k": 64, "v": 64}
    assert tiny.cache_slot("k", 70) == 70 and not tiny.counters()
    # int8 leaves the two learned vectors a head float32
    tree = jax.eval_shape(lambda: adapter.init_params(seed=0))["params"]
    assert tree["layer_0"]["adaptive_mu_k"].dtype == jnp.float32
    assert tree["layer_0"]["adaptive_phi"].shape == (4, 16)
    assert tree["lm_head"]["kernel_int8"].shape == (64, 2 * VOCAB)
    floats = llama.quantize_params(build(quant=None).init_params(seed=1))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), floats["params"]) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), tree)


@pytest.mark.parametrize("holder", [
    "init_page_arena", "page_kv_bytes", "prefix_store", "kvwire", "offload",
    "kv_quant", "attn_backend", "mesh", "spec_k", "cache_prefix",
    "prefill_chunk", "speculative", "concat_cache_blocks", "chunk_step"])
def test_a_holder_that_cannot_take_an_eva_cache_raises_by_name(
        holder, adapter, params, server):
    from lambdipy_tpu.runtime import kvwire
    from lambdipy_tpu.runtime.offload import OffloadArena
    from lambdipy_tpu.runtime.prefixstore import PrefixStore

    cfg = adapter.config
    cache = llama.init_decode_cache(cfg, 1, 32)
    # what a block of 16 positions would be if a leaf held a row a token
    block = [{name: np.zeros((1, 16, heads, width), np.float32)
              for name, (heads, width) in cfg.cache_layout().items()}
             for _ in range(cfg.layers)]
    template = [[name, "float32", list(val.shape)]
                for name, val in block[0].items()]

    class Mesh:
        shape = {"tp": 2}

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
        "chunk_step": (lambda: adapter.module.apply(
            params, jnp.zeros((1, 4), jnp.int32),
            positions=jnp.arange(4)[None], cache=cache), "one token a row"),
    }
    call, name = calls[holder]
    with pytest.raises((NotImplementedError, ValueError), match=name):
        call()


def test_a_wrong_description_is_refused_at_build():
    for over in ({"chunk_size": 0}, {"window_size": 30}, {"kv_heads": 2},
                 {"pred_heads": 0}):
        with pytest.raises(ValueError):
            build(**over)
