"""The ``deepseek-v3`` model (latent attention, a leading dense layer,
dropless sigmoid-routed experts beside shared ones) at toy widths on the CPU,
in float32, against the plain reference of its benchmark family
(``benchmark/families/deepseek_v3.py``: expanded attention, every expert on
every token; it imports nothing of the program).

Comparisons are of LOGITS. Tolerances, and why: the program and the
reference are the same float32 function written two ways (absorbed against
expanded attention, grouped against looped experts, a scale applied after
the dot against a dequantized kernel), so they differ by the order of
float32 sums: about 2e-6 at logits of order 0.6 here; 2e-5 leaves ten times
that and is a thousand times under what a wrong term would show (leaving
the rotary key out moves logits by 1e-2). Where served tokens are checked,
the measure is the benchmark's own: the gap by which a served token's
reference logit lies below the reference's best, which is 0 wherever the
program's choice is the reference's and at most a few float32 roundings of
the two largest logits elsewhere; 1e-4."""

import json
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, reference, weights
from lambdipy_tpu.models import llama, moe, registry
from lambdipy_tpu.runtime.continuous import ContinuousBatcher

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-mla-moe.json").read_text())
FAMILY = families.of(CONFIG)
DIMS = FAMILY.dims_of(CONFIG)
ROUTED_LAYERS = CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"]
TOP_K = CONFIG["num_experts_per_tok"]
LOGIT_TOL = 2e-5
GAP_TOL = 1e-4


def build(quant="int8", **over):
    return registry.get("deepseek-v3").build(
        dtype="float32", quant=quant, extra={**DIMS, **over})


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


def prompts(n, lo=5, hi=30, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CONFIG["vocab_size"],
                         int(rng.integers(lo, hi))).tolist() for _ in range(n)]


def walk_logits(ids, config=CONFIG):
    ids = np.asarray(ids, np.int32)
    rows = np.repeat(np.arange(ids.shape[0]), ids.shape[1])
    pos = np.tile(np.arange(ids.shape[1]), ids.shape[0])
    out = families.of(config).walk(config, ids, rows, pos, (False,))[False]
    return np.asarray(out).reshape(*ids.shape, -1)


def served_gap(rows):
    """``rows``: (prompt, served tokens). The widest gap of the served
    tokens under the reference, the benchmark's measure of ``correct``."""
    pairs = [(list(p) + [int(t) for t in toks], len(p)) for p, toks in rows]
    length = -(-max(len(t) for t, _ in pairs) // 16) * 16
    new = max(len(t) - n for t, n in pairs)
    out = reference.served_gaps(CONFIG, pairs, shape=(len(pairs), length, new))
    assert out["served_tokens"] == sum(len(t) for _, t in rows)
    return max(out["gap"])


# -- the whole forward ---------------------------------------------------------

@pytest.mark.parametrize("quant", ["int8", None])
def test_the_whole_forward_is_the_references(quant, adapter, params):
    ids = np.random.default_rng(1).integers(1, 512, (3, 40)).astype(np.int32)
    if quant is None:
        # a float tree quantized by the program's own converter lands in the
        # int8 layout the family fills: same paths, shapes and dtypes
        floats = build(None)
        quantized = llama.quantize_params(floats.init_params(seed=1))
        want = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        assert jax.tree.map(lambda x: (x.shape, x.dtype), quantized) == want
        return
    got = np.asarray(adapter.forward(params, jnp.asarray(ids)))
    ref = walk_logits(ids)
    assert np.std(ref) > 0.3           # logits of the order the cells serve
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)


def test_a_missing_term_would_show(adapter, params):
    """The tolerance is tight enough: a program whose rotary dims were NOT
    read as (even, odd) pairs is off by a thousand tolerances."""
    ids = np.random.default_rng(2).integers(1, 512, (2, 24)).astype(np.int32)
    ref = walk_logits(ids)
    plain = build(rope_interleave="false")
    got = np.asarray(plain.forward(params, jnp.asarray(ids)))
    assert np.abs(got - ref).max() > 1000 * LOGIT_TOL
    twin = dict(CONFIG, rope_interleave=False)
    np.testing.assert_allclose(got, walk_logits(ids, twin), atol=LOGIT_TOL,
                               rtol=0)


def test_deinterleave_pairs_each_even_dim_with_the_odd_one_after_it():
    x = jnp.arange(2 * 8, dtype=jnp.float32).reshape(2, 1, 1, 8)
    positions = jnp.asarray([[3], [5]])
    out, _ = llama.rope(llama._deinterleave(x), llama._deinterleave(x),
                        positions, 100.0)
    freqs = 1.0 / 100.0 ** (np.arange(0, 8, 2) / 8)
    for row, p in enumerate((3, 5)):
        even, odd = np.asarray(x[row, 0, 0, 0::2]), np.asarray(x[row, 0, 0, 1::2])
        c, s = np.cos(p * freqs), np.sin(p * freqs)
        want = np.concatenate([even * c - odd * s, even * s + odd * c])
        np.testing.assert_allclose(np.asarray(out[row, 0, 0]), want, rtol=1e-5)


# -- prefill, then decode through the latent cache -----------------------------

def test_absorbed_decode_is_expanded_attention(adapter, params):
    """Prefill 12 tokens expanded, then 20 single-token steps absorbed over
    the latent cache: each step's logits are the full forward's (expanded
    over the whole sequence) at that position."""
    cfg = adapter.config
    ids = np.random.default_rng(3).integers(1, 512, (2, 32)).astype(np.int32)
    full = np.asarray(adapter.forward(params, jnp.asarray(ids)))
    model = adapter.module
    _, pre = model.apply(params, jnp.asarray(ids[:, :12]))
    assert set(pre[0]) == {"ckv", "kpe"}
    assert pre[0]["ckv"].shape == (2, 12, 1, CONFIG["kv_lora_rank"])
    assert pre[0]["kpe"].shape == (2, 12, 1, CONFIG["qk_rope_head_dim"])
    cache = llama.prefill_into_cache(cfg, pre, 2, 64, 12)
    assert llama.cache_width(cache) == 64
    step = jax.jit(lambda tok, pos, cache: model.apply(
        params, tok, positions=pos, cache=cache))
    for t in range(12, 32):
        for entry in cache:
            entry["index"] = jnp.full((2,), t, jnp.int32)
        logits, cache = step(jnp.asarray(ids[:, t:t + 1]),
                             jnp.full((2, 1), t, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), full[:, t],
                                   atol=LOGIT_TOL, rtol=0)
    # a multi-token chunk at a scalar index (the prefix continuation's form)
    short = llama.prefill_into_cache(cfg, pre, 2, 64, 12)
    logits, _ = model.apply(params, jnp.asarray(ids[:, 12:20]),
                            positions=jnp.arange(12, 20)[None], cache=short)
    np.testing.assert_allclose(np.asarray(logits), full[:, 12:20],
                               atol=LOGIT_TOL, rtol=0)


def test_group_prefill_then_48_steps_through_the_latent_cache(server):
    rows = prompts(4, seed=4)                   # ragged: one padded group
    toks = server.generate(rows, max_new_tokens=48)
    assert toks.shape == (4, 48)
    assert served_gap(list(zip(rows, toks))) <= GAP_TOL


def test_the_continuous_engine_with_ragged_joiners(server):
    """Requests join a running decode at segment boundaries (group prefill
    of the joiners, pack into the B-slot latent cache, window-bucketed
    segments): every served token is the reference's choice, the engine
    booked one assignment per row-step, routed layer and pick: dropless, and
    the distinct experts each layer-step picked beside them."""
    eng = ContinuousBatcher(server, slots=4, segment=8)
    rows = prompts(7, seed=5)
    want = [24, 48, 16, 40, 48, 8, 32]
    got = [None] * len(rows)

    def run(i):
        time.sleep(0.03 * i)
        got[i] = eng.generate(rows[i], max_new_tokens=want[i])[0]

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(rows))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert [len(g) for g in got] == want
    assert served_gap(list(zip(rows, got))) <= GAP_TOL
    stats, load = eng.stats(), eng.counters["moe"].report()
    assert stats["rows_in_segments"] > stats["segments_run"]    # rows shared
    assert load["assignments"] == stats["rows_in_segments"] * stats["segment"] \
        * ROUTED_LAYERS * TOP_K
    assert len(load["load"]) == CONFIG["n_routed_experts"]
    assert sum(load["load"]) == load["assignments"]
    # the distinct experts a routed layer's call picked in one step, as sum
    # and count: every fetched segment counts its steps and routed layers,
    # and a call of 4 slots x 3 picks names between 3 and 12 of 16 experts
    assert load["layer_steps"] == stats["segments_run"] * stats["segment"] \
        * ROUTED_LAYERS
    assert TOP_K <= load["experts_read"] / load["layer_steps"] \
        <= min(CONFIG["n_routed_experts"], 4 * TOP_K)
    again = eng.counters["moe"].report()
    eng.generate(rows[0], max_new_tokens=8)
    after = eng.counters["moe"].report()
    assert after["assignments"] > again["assignments"]
    grown = after["layer_steps"] - again["layer_steps"]
    assert grown > 0 and grown % (8 * ROUTED_LAYERS) == 0
    assert TOP_K * grown <= after["experts_read"] - again["experts_read"] \
        <= 4 * TOP_K * grown


# -- dropless routing ----------------------------------------------------------

def test_a_tokens_logits_do_not_change_with_its_batch_companions(adapter,
                                                                 params):
    rng = np.random.default_rng(6)
    row = rng.integers(1, 512, (1, 20)).astype(np.int32)
    alone = np.asarray(adapter.forward(params, jnp.asarray(row)))
    for others in (3, 7):
        batch = np.concatenate(
            [rng.integers(1, 512, (others, 20)).astype(np.int32), row])
        among = np.asarray(adapter.forward(params, jnp.asarray(batch)))[-1:]
        # not one token dropped or reweighted by what it shares a batch with:
        # float32 roundings of a differently blocked matmul at most
        np.testing.assert_allclose(among, alone, atol=2e-6, rtol=0)


def test_the_bias_moves_the_choice_and_not_the_weight():
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0]])
    scores = np.asarray(jax.nn.sigmoid(logits))[0]
    kw = dict(scoring="sigmoid", norm=True, scaling=2.448)
    chosen, w = moe.route_dropless(logits, jnp.zeros(6), 2, **kw)
    assert chosen.tolist() == [[0, 1]]
    np.testing.assert_allclose(
        np.asarray(w)[0], scores[:2] / scores[:2].sum() * 2.448, rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.0, 1.0])
    chosen, w = moe.route_dropless(logits, bias, 2, **kw)
    assert chosen.tolist() == [[5, 0]]          # the bias put expert 5 first
    picked = scores[[5, 0]]
    np.testing.assert_allclose(                 # and is nowhere in its weight
        np.asarray(w)[0], picked / picked.sum() * 2.448, rtol=1e-6)
    # ties go to the lowest index; unnormalised weights are the raw scores
    chosen, w = moe.route_dropless(jnp.zeros((1, 6)), jnp.zeros(6), 3,
                                   scoring="sigmoid", norm=False, scaling=1.0)
    assert chosen.tolist() == [[0, 1, 2]] and np.allclose(w, 0.5)


@pytest.mark.parametrize("tokens", [5, 48, 300])
def test_grouped_experts_is_the_plain_sum_and_padding_routes_nowhere(tokens):
    """The grouped loop, the streamed form and a plain loop over experts
    give one sum (blocks of 8, 8 and 128 rows at these sizes); a token
    marked invalid gets zeros, whatever it would have picked."""
    rng = np.random.default_rng(tokens)
    e, k, h, m = 16, 3, 32, 24
    x = jnp.asarray(rng.normal(size=(tokens, h)), jnp.float32)
    stacks = [(jnp.asarray(rng.normal(size=s) / np.sqrt(s[1]), jnp.float32),
               None) for s in ((e, h, m), (e, h, m), (e, m, h))]
    chosen = jnp.asarray(np.stack([rng.permutation(e)[:k]
                                   for _ in range(tokens)]), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, k)), jnp.float32)
    valid = jnp.asarray(rng.uniform(size=tokens) > 0.3)

    def expert(i, rows):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(s[0], i, 0, False)
                      for s in stacks)
        return (jax.nn.silu(rows @ wg) * (rows @ wu)) @ wd

    want = np.zeros((tokens, h), np.float32)
    for t in range(tokens):
        if valid[t]:
            for j in range(k):
                want[t] += float(w[t, j]) * np.asarray(
                    expert(int(chosen[t, j]), x[t:t + 1]))[0]
    got = jax.jit(lambda: moe.grouped_experts(x, chosen, w, valid, expert, e))()
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)
    assert not np.asarray(got)[~np.asarray(valid)].any()
    streamed = moe.streamed_experts(x, chosen, w, valid, stacks, jnp.float32)
    np.testing.assert_allclose(np.asarray(streamed), want, atol=2e-5, rtol=0)


def test_padding_rows_of_a_group_prefill_touch_no_counter(adapter, params):
    ids = jnp.asarray(np.random.default_rng(7).integers(1, 512, (2, 16)),
                      jnp.int32)
    mask = jnp.asarray([[True] * 16, [True] * 9 + [False] * 7])
    (_, _), sown = adapter.module.apply(params, ids, mask=mask,
                                        mutable=["moe_stats"])
    load = sum(jax.tree.leaves(sown))
    assert load.shape == (2, CONFIG["n_routed_experts"])
    assert load.sum(axis=1).tolist() == [16 * ROUTED_LAYERS * TOP_K,
                                         9 * ROUTED_LAYERS * TOP_K]
    # and without the collection nothing is returned or kept
    logits, _ = adapter.module.apply(params, ids, mask=mask)
    assert logits.shape == (2, 16, CONFIG["vocab_size"])
    assert set(adapter.init_params(seed=0)) == {"params"}


# -- what cannot take a latent cache says so -----------------------------------

def test_the_description_is_what_the_constructors_read(adapter):
    cfg = adapter.config
    assert [cfg.layer_spec(i) for i in range(3)] == [
        ("latent", "dense"), ("latent", "routed"), ("latent", "routed")]
    assert cfg.cache_layout() == {"ckv": (1, 32), "kpe": (1, 8)}
    assert cfg.head_dim == 16       # what it is for the llama block, unused
    cache = llama.init_decode_cache(cfg, 3, 64)
    assert {k: v.shape for k, v in cache[0].items() if k != "index"} == {
        "ckv": (3, 64, 1, 32), "kpe": (3, 64, 1, 8)}
    blocks = llama.slice_cache_blocks(cache, 16, 16)
    assert blocks[0]["ckv"].shape == (3, 16, 1, 32)
    tiny = registry.get("llama-tiny").build().config
    assert tiny.layer_spec(1) == ("kv", "dense")
    assert tiny.cache_layout() == {"k": (2, 16), "v": (2, 16)}
    assert registry.get("llama-moe-tiny").build().config.layer_spec(0) == (
        "kv", "capacity")


@pytest.mark.parametrize("holder", [
    "init_page_arena", "page_kv_bytes", "prefix_store", "kvwire", "offload",
    "kv_quant", "attn_backend", "mesh", "spec_k"])
def test_a_holder_that_cannot_take_a_latent_cache_raises(holder, adapter,
                                                         server):
    from lambdipy_tpu.runtime import kvwire
    from lambdipy_tpu.runtime.offload import OffloadArena
    from lambdipy_tpu.runtime.prefixstore import PrefixStore

    cfg = adapter.config
    block = llama.slice_cache_blocks(llama.init_decode_cache(cfg, 1, 32), 0, 16)
    template = [[name, "float32", list(val.shape)]
                for name, val in block[0].items()]

    class Mesh:
        shape = {"tp": 2}

    calls = {
        "init_page_arena": lambda: llama.init_page_arena(cfg, 8, 16),
        "page_kv_bytes": lambda: llama.page_kv_bytes(cfg, 16),
        "prefix_store": lambda: PrefixStore(server, block=16),
        "kvwire": lambda: kvwire.encode_frame(list(range(16)), 16, [block]),
        "offload": lambda: OffloadArena(page=16, layers=3).attach_template(
            template),
        "kv_quant": lambda: build(kv_quant="int8"),
        "attn_backend": lambda: build(attn_backend="blocked"),
        "mesh": lambda: llama.validate_serving_mesh(cfg, Mesh()),
        "spec_k": lambda: ContinuousBatcher(server, slots=2, segment=4,
                                            spec_k=4),
    }
    with pytest.raises((NotImplementedError, ValueError),
                       match="latent|k/v|routed"):
        calls[holder]()


def test_a_wrong_description_is_refused_at_build():
    for over in ({"qk_rope": 7}, {"moe_top_k": 99}, {"scoring_func": "tanh"},
                 {"moe_intermediate": 0}):
        with pytest.raises(ValueError):
            build(**over)
    with pytest.raises(ValueError, match="attn_kind"):
        llama.LlamaConfig(attn_kind="window")
