"""The ``deepseek-v32`` model (latent attention with a compressed query under
DeepSeek Sparse Attention, group-limited routing, a chip's share of the
routed experts, YaRN) at toy widths on the CPU, in float32, against the plain
reference of its benchmark family (``benchmark/families/deepseek_v32.py``:
expanded attention under a ``jax.lax.top_k`` selection, every held expert on
every token; it imports nothing of the program).

The toy twin (``benchmark/configs/rehearsal-dsa.json``): hidden 128, 8 heads,
an indexer of 4 heads that picks 16 cached tokens, contexts to 80 here, 1
dense + 2 routed layers, 16 experts in 4 groups of which 2 stay, experts 4-7
held.

Comparisons are of LOGITS. Tolerances, and why: program and reference are
the same float32 function written two ways (a bit-by-bit threshold and a
mask against a sort and a scatter, absorbed against expanded attention,
grouped against looped experts), so they differ by the order of float32
sums: about 4e-6 at logits of order 0.6; 2e-5 leaves five times that and is
a thousand times under what a wrong term shows (the indexer's rope read as
pairs moves logits by 1e-2). A float32 rounding could in principle flip the
16th pick of a near-tied selection; with these seeds none does, and every
selected SET is held exactly where the scores are given (the select tests).
Served tokens are checked by the benchmark's own measure, the gap by which a
served token's reference logit lies below the reference's best: 1e-4."""

import json
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, reference, weights
from lambdipy_tpu.models import latent, llama, moe, registry
from lambdipy_tpu.runtime.continuous import ContinuousBatcher

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-dsa.json").read_text())
FAMILY = families.of(CONFIG)
DIMS = FAMILY.dims_of(CONFIG)
ROUTED_LAYERS = CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"]
TOP_K = CONFIG["num_experts_per_tok"]
EXPERTS = CONFIG["routed_experts_published"]
HELD = (CONFIG["first_routed_expert"], CONFIG["n_routed_experts"])
INDEX_TOPK = CONFIG["index_topk"]
LOGIT_TOL = 2e-5
GAP_TOL = 1e-4


def build(quant="int8", **over):
    return registry.get("deepseek-v32").build(
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


def prompts(n, lo=17, hi=40, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CONFIG["vocab_size"],
                         int(rng.integers(lo, hi))).tolist() for _ in range(n)]


def walk_logits(ids, config=CONFIG, flag=False):
    ids = np.asarray(ids, np.int32)
    rows = np.repeat(np.arange(ids.shape[0]), ids.shape[1])
    pos = np.tile(np.arange(ids.shape[1]), ids.shape[0])
    out = families.of(config).walk(config, ids, rows, pos, (flag,))[flag]
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


# -- the whole forward: a blocked prefill under the selection --------------------

@pytest.mark.parametrize("blocks", ["one key block", "key blocks of 32",
                                    "two heads a turn"])
def test_the_whole_forward_is_the_references(blocks, adapter, params,
                                             monkeypatch):
    """72 positions against a top-16: the selection is active from position
    16 on. With the block constants cut to toy size the same prompt runs
    three key blocks of 32 (the last a partial one) in query blocks of 8,
    and the head groups of the indexer's scores and of the attention."""
    if blocks == "key blocks of 32":
        monkeypatch.setattr(latent, "DSA_KEY_BLOCK", 32)
        monkeypatch.setattr(latent, "DSA_QUERY_BLOCK", 8)
    if blocks == "two heads a turn":
        monkeypatch.setattr(latent, "DSA_QUERY_BLOCK", 16)
        monkeypatch.setattr(llama, "DSA_SCORE_BYTES", 4 * 2 * 3 * 16 * 72)
        assert llama._head_group(8, 3 * 16 * 72) == 2
    ids = np.random.default_rng(1).integers(1, 512, (3, 72)).astype(np.int32)
    got = np.asarray(jax.jit(adapter.module.apply)(params, jnp.asarray(ids))[0])
    ref = walk_logits(ids)
    assert np.std(ref) > 0.3           # logits of the order the cell serves
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("lengths,turns", [
    pytest.param((66,), [4, 4, 1],
                 id="the last token in a key block's first turn"),
    pytest.param((64,), [4, 4, 0],
                 id="the last token on a key block's last position"),
    pytest.param((27,), [4, 0, 0], id="two trailing key blocks of padding"),
    pytest.param((72, 45), [4, 4, 1], id="two rows of different lengths"),
])
def test_a_prompt_shorter_than_its_bucket_runs_only_its_own_turns(
        lengths, turns, adapter, params, monkeypatch):
    """Right-padded rows in a bucket of 96 (three key blocks of 32, turns of
    8 queries): a key block runs the turns that begin before the longest
    row's last token and no other. Every real position's logits are the
    reference's, and the three cache leaves at the real positions are those
    of the same row run alone at its own length (another program: to the
    order of its float32 sums)."""
    monkeypatch.setattr(latent, "DSA_KEY_BLOCK", 32)
    monkeypatch.setattr(latent, "DSA_QUERY_BLOCK", 8)
    bucket = 96
    assert [live for _, _, live in latent.dsa_prefill_turns(
        max(lengths), bucket)] == turns
    rng = np.random.default_rng(7)
    rows = [rng.integers(1, 512, n).astype(np.int32) for n in lengths]
    ids = np.zeros((len(rows), bucket), np.int32)
    for r, row in enumerate(rows):
        ids[r, :len(row)] = row

    @jax.jit
    def run(ids, lengths):
        return adapter.module.apply(params, ids, lengths=lengths)

    logits, cache = run(jnp.asarray(ids), jnp.asarray(lengths, jnp.int32))
    for r, row in enumerate(rows):
        n = len(row)
        np.testing.assert_allclose(np.asarray(logits[r, :n]),
                                   walk_logits(row[None])[0],
                                   atol=LOGIT_TOL, rtol=0)
        _, alone = run(jnp.asarray(row[None]), jnp.asarray([n], jnp.int32))
        for entry, entry_alone in zip(cache, alone):
            assert set(entry) == {"ckv", "kpe", "kidx"}
            for leaf in entry:
                np.testing.assert_allclose(
                    np.asarray(entry[leaf][r, :n]),
                    np.asarray(entry_alone[leaf][0]), atol=LOGIT_TOL, rtol=0)


def test_the_iteration_space_is_what_the_engine_books(adapter):
    """The cell's bucket of 12288 (six key blocks of 2048, 16 turns of 128
    queries each) at three prompt lengths: the pairs a layer the turns are
    given, from the one function the loops take their trip counts from, and
    ``handler.dsa`` after the engine's booking of those three prefills."""
    from lambdipy_tpu.runtime.metrics import KindCounters

    assert (latent.DSA_KEY_BLOCK, latent.DSA_QUERY_BLOCK) == (2048, 128)
    want = {8193: 2048 * (2048 + 4096 + 6144 + 8192) + 128 * 10240,
            10240: 2048 * (2048 + 4096 + 6144 + 8192 + 10240),
            12288: 2048 * sum(range(2048, 12289, 2048))}
    assert [round(n / 1e6, 1) for n in want.values()] == [43.3, 62.9, 88.1]
    assert [live for _, _, live in latent.dsa_prefill_turns(8193, 12288)] \
        == [16, 16, 16, 16, 1, 0]
    stats, cfg = KindCounters(), adapter.module.cfg
    dsa, = latent.counters(cfg)
    stats.add(dsa)
    for length, pairs in want.items():
        assert dsa.prefill([length], 1, 12288) == {
            "prefill_pairs_run": pairs,
            "prefill_pairs_causal": length * (length + 1) // 2}
        stats.record_prefill([length], 1, 12288)
    report = stats.report()
    assert report["prefill_pairs_run"] == sum(want.values())
    assert report["prefill_pairs_causal"] == sum(
        n * (n + 1) // 2 for n in want)
    # (what the parent ran, whatever the length: 32 turns in each of three
    # key blocks of 4096, each against all keys to its block's end)
    assert round(4096 * (4096 + 8192 + 12288) / (10240 * 10241 / 2), 2) == 1.92
    assert round(want[10240] / (10240 * 10241 / 2), 2) == 1.2
    # two rows in a bucket of two: both run what the longer needs
    assert dsa.prefill([9000, 12288], 2, 12288) == {
        "prefill_pairs_run": 2 * want[12288],
        "prefill_pairs_causal": 9000 * 9001 // 2 + 12288 * 12289 // 2}


def test_the_int8_layout_is_what_the_programs_converter_writes(params):
    floats = build(None)
    quantized = llama.quantize_params(floats.init_params(seed=1))
    want = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), quantized) == want
    # the indexer's head weights and its key norm stay float32, like the router
    layer = params["params"]["layer_1"]
    assert layer["index_weights_proj"].dtype == jnp.float32
    assert layer["index_k_norm_bias"].shape == (CONFIG["index_head_dim"],)
    assert layer["moe"]["experts_up_int8"].shape[0] == HELD[1]
    assert layer["moe"]["router"].shape == (CONFIG["hidden_size"], EXPERTS)


@pytest.mark.parametrize("fault", ["index_interleaved", "no_indexer",
                                   "topk_half", "no_mscale", "no_yarn",
                                   "no_groups", "no_q_norm"])
def test_a_missing_term_would_show(fault, adapter, params):
    """The tolerance is tight enough: each fault of the family's list moves
    the reference's logits by hundreds of tolerances, and the program's are
    the sound reference's."""
    ids = np.random.default_rng(2).integers(1, 512, (2, 64)).astype(np.int32)
    ref = walk_logits(ids)
    wrong = walk_logits(ids, flag=fault)
    assert np.abs(wrong - ref).max() > 200 * LOGIT_TOL, fault
    got = np.asarray(adapter.forward(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)


# -- the selection ----------------------------------------------------------------

def test_every_selected_set_is_the_references_and_ties_go_to_the_lowest():
    rng = np.random.default_rng(3)
    scores = rng.normal(size=(2, 6, 64)).astype(np.float32)
    scores[0, 0, 5:25] = 0.75            # twenty tied at the threshold
    scores[0, 1] = 0.0                   # all tied: the lowest positions win
    scores[1, 2, ::2] = -0.0             # sorts under 0.0, for top_k and the bits
    scores[1, 2, 1::2] = 0.0
    visible = jnp.asarray(rng.random((2, 6, 64)) < 0.85)
    for k in (1, 16, 63):
        got = np.asarray(llama._dsa_select_mask(jnp.asarray(scores), visible,
                                                k))
        for r in range(2):
            want = np.asarray(FAMILY.select(jnp.asarray(scores[r]),
                                            visible[r], k))
            assert np.array_equal(got[r], want), (r, k)
        assert (got.sum(-1) == np.minimum(k, np.asarray(visible).sum(-1))
                ).all()
    tied = np.asarray(llama._dsa_select_mask(
        jnp.zeros((1, 64)), jnp.ones((1, 64), bool), 16))
    assert tied[0, :16].all() and not tied[0, 16:].any()
    # the reference's own pick is jax.lax.top_k: the same rule
    _, at = jax.lax.top_k(jnp.zeros((1, 64)), 16)
    assert at.tolist() == [list(range(16))]


def test_a_context_under_index_topk_selects_all_of_it(adapter, params):
    """Three rows step at positions 3, 20 and 40 of a 64-position cache:
    the counter of layer 0 says what each attended and chose from."""
    cfg = adapter.config
    ids = np.random.default_rng(4).integers(1, 512, (3, 48)).astype(np.int32)
    _, pre = adapter.module.apply(params, jnp.asarray(ids))
    cache = llama.prefill_into_cache(cfg, pre, 3, 64, 0)
    at = jnp.asarray([3, 20, 40], jnp.int32)
    for entry in cache:
        entry["index"] = at
    (_, _), sown = adapter.module.apply(
        params, jnp.asarray(ids[np.arange(3), np.asarray(at)])[:, None],
        positions=at[:, None], cache=cache, mutable=["dsa_stats"])
    assert sum(jax.tree.leaves(sown)).tolist() == [[4, 4], [16, 21], [16, 41]]


# -- prefill, then decode through the three-leaf cache ----------------------------

def test_decode_through_the_cache_is_the_references_forward(adapter, params):
    """Prefill 24 tokens (blocked, under the selection), then 48 one-token
    steps (scores over the cached indexer keys, the threshold's mask,
    absorbed attention under it): each step's logits are the reference's at
    that position."""
    cfg = adapter.config
    ids = np.random.default_rng(1).integers(1, 512, (3, 72)).astype(np.int32)
    ref = walk_logits(ids)
    model = adapter.module
    _, pre = model.apply(params, jnp.asarray(ids[:, :24]))
    assert {k: v.shape for k, v in pre[0].items()} == {
        "ckv": (3, 24, 1, 32), "kpe": (3, 24, 1, 8), "kidx": (3, 24, 1, 16)}
    cache = llama.prefill_into_cache(cfg, pre, 3, 128, 24)
    assert llama.cache_width(cache) == 128
    step = jax.jit(lambda tok, pos, cache: model.apply(
        params, tok, positions=pos, cache=cache))
    for t in range(24, 72):
        for entry in cache:
            entry["index"] = jnp.full((3,), t, jnp.int32)
        logits, cache = step(jnp.asarray(ids[:, t:t + 1]),
                             jnp.full((3, 1), t, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), ref[:, t],
                                   atol=LOGIT_TOL, rtol=0)
    # a chunk of several positions against the cache is not written
    short = llama.prefill_into_cache(cfg, pre, 3, 64, 24)
    with pytest.raises(NotImplementedError, match="one-token step"):
        model.apply(params, jnp.asarray(ids[:, 24:32]),
                    positions=jnp.arange(24, 32)[None], cache=short)


def test_a_step_writes_its_indexer_key_and_a_stale_one_would_show(adapter,
                                                                  params):
    """The family's ``stale_index`` fault (a decode step that never writes
    ``kidx``: zeros from the first served position on) is the one selection
    fault the benchmark's ``correct`` limit cannot see at the cell's widths
    (PERF.md section 2), so it is held here: the fault moves the
    reference's logits at the served positions by hundreds of tolerances
    (the steps of the test above are the SOUND reference's), and the rows a
    step wrote are the rows a whole prefill computes."""
    cfg, model = adapter.config, adapter.module
    ids = np.random.default_rng(1).integers(1, 512, (3, 72)).astype(np.int32)
    rows, pos = np.repeat(np.arange(3), 48), np.tile(np.arange(24, 72), 3)
    out = FAMILY.walk(CONFIG, ids, rows, pos, (False, "stale_index"))
    assert np.abs(np.asarray(out["stale_index"])
                  - np.asarray(out[False])).max() > 200 * LOGIT_TOL
    _, whole = model.apply(params, jnp.asarray(ids))
    _, pre = model.apply(params, jnp.asarray(ids[:, :24]))
    cache = llama.prefill_into_cache(cfg, pre, 3, 128, 24)
    step = jax.jit(lambda tok, pos, cache: model.apply(
        params, tok, positions=pos, cache=cache))
    for t in range(24, 40):
        for entry in cache:
            entry["index"] = jnp.full((3,), t, jnp.int32)
        _, cache = step(jnp.asarray(ids[:, t:t + 1]),
                        jnp.full((3, 1), t, jnp.int32), cache)
    for layer, entry in enumerate(cache):
        wrote = np.asarray(entry["kidx"][:, 24:40])
        assert np.abs(wrote).min(axis=-1).max() > 0          # no row of zeros
        np.testing.assert_allclose(wrote, np.asarray(whole[layer]["kidx"]
                                                     [:, 24:40]),
                                   atol=LOGIT_TOL, rtol=0)
        assert not np.asarray(entry["kidx"][:, 40:]).any()   # not yet written


def test_the_faults_on_served_tokens_are_read_as_the_harness_reads_int4(
        server):
    """``sample_gaps`` (what ``python3 -m benchmark.families.deepseek_v32
    --cell`` prints a window) against ``reference.served_gaps`` with its
    control on: the program's gap and the int4 control's are the same
    numbers, and ``stale_index`` starts at each row's first served place."""
    rows = prompts(3, seed=9)
    toks = server.generate(rows, max_new_tokens=24)
    pairs = [(list(p) + [int(t) for t in ts], len(p))
             for p, ts in zip(rows, toks)]
    shape = (4, 64, 24)                         # one row of padding
    got = FAMILY.sample_gaps(CONFIG, pairs, shape, (True, "stale_index"))
    want = reference.served_gaps(CONFIG, pairs, shape=shape, control=True)
    assert got["served_tokens"] == want["served_tokens"] == 72
    assert got["program"]["widest_gap"] == pytest.approx(max(want["gap"]),
                                                         abs=1e-6)
    assert got["program"]["widest_gap"] <= GAP_TOL
    assert got["int4"]["widest_gap"] == pytest.approx(
        max(want["control_gap"]), abs=1e-6)
    assert got["int4"]["widest_gap"] > 0.3
    assert got["stale_index"]["widest_gap"] > 0.3
    assert 0 < got["stale_index"]["other_first_share"] <= 1


def test_group_prefill_then_48_steps_through_the_cache(server):
    rows = prompts(4, seed=4)                   # ragged: one padded group
    toks = server.generate(rows, max_new_tokens=48)
    assert toks.shape == (4, 48)
    assert served_gap(list(zip(rows, toks))) <= GAP_TOL


def test_the_continuous_engine_with_ragged_joiners_counts_exactly(server):
    """Requests join a running decode at segment boundaries (group prefill,
    pack into the B-slot three-leaf cache, plain and window-bucketed
    segments): every served token is the reference's choice; every booked
    row-step attended exactly ``index_topk`` keys (every prompt is longer);
    the engine booked one assignment per row-step, routed layer and pick,
    and the share of them that went to the experts held here."""
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
    stats = eng.stats()
    dsa, load = eng.counters["dsa"].report(), eng.counters["moe"].report()
    assert {b for b in eng.window_stats.report()["buckets"]} \
        >= {"64", "128"}                        # bucketed and full-window
    row_steps = stats["rows_in_segments"] * stats["segment"]
    assert dsa["row_steps"] == row_steps
    assert dsa["keys_selected"] == INDEX_TOPK * row_steps
    assert dsa["keys_visible"] > 2 * dsa["keys_selected"]
    # the prefills' pairs: what causality needs of the seven prompts, and
    # what their group prefills ran (whole buckets of 32 or 64 a row)
    assert dsa["prefill_pairs_causal"] == sum(
        len(r) * (len(r) + 1) // 2 for r in rows)
    assert dsa["prefill_pairs_run"] % (32 * 32) == 0
    assert dsa["prefill_pairs_run"] > dsa["prefill_pairs_causal"]
    assert load["assignments"] == row_steps * ROUTED_LAYERS * TOP_K
    assert len(load["load"]) == EXPERTS
    assert sum(load["load"]) == load["assignments"]
    assert load["local_assignments"] == sum(
        load["load"][HELD[0]:HELD[0] + HELD[1]])
    assert 0 < load["local_assignments"] < load["assignments"]
    # the distinct HELD experts a routed layer's call picked in one step
    assert load["layer_steps"] == stats["segments_run"] * stats["segment"] \
        * ROUTED_LAYERS
    assert 0 < load["experts_read"] / load["layer_steps"] <= HELD[1]


# -- a chip's share of the experts ------------------------------------------------

def _routed_ffn(dims, held, first, shared, tree, x):
    over = registry._llama_overrides(dims)
    if "layer_kinds" in over:       # a recipe's comma-separated string
        over["layer_kinds"] = tuple(over["layer_kinds"].split(","))
    else:
        over["attn_kind"] = "latent"
    cfg = llama.LlamaConfig(**{
        **over, "ffn_kind": "routed", "dtype": jnp.float32, "quant": None,
        "moe_experts_held": held, "moe_first_expert": first,
        "n_shared_experts": shared})
    return np.asarray(moe.RoutedMLP(cfg).apply({"params": tree}, x))


@pytest.mark.parametrize("twin", ["rehearsal-dsa", "rehearsal-kda"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(twin):
    """The guide's share test, for each model that holds a share (the
    ``deepseek-v32`` and ``bailing-hybrid`` families' toy twins, groups and
    top-k at full width). Four chips hold experts 0-3, 4-7, 8-11 and
    12-15 of one routed layer and route over all 16 alike: the routed parts
    of the four shares, plus the shared expert ONCE, are the uncut layer's
    output, which is in turn the plain reference's (the family's ``route``,
    then every picked expert in a numpy loop)."""
    config = json.loads((REPO / "benchmark" / "configs"
                         / f"{twin}.json").read_text())
    family = families.of(config)
    dims = family.dims_of(config)
    rng = np.random.default_rng(6)
    h, m, e = dims["hidden"], dims["moe_intermediate"], dims["moe_experts"]
    assert e == EXPERTS and dims["moe_experts_held"] == 4

    def _ffn(*args):
        return _routed_ffn(dims, *args)
    full = {
        "router": rng.normal(size=(h, e)).astype(np.float32) / np.sqrt(h),
        "e_score_correction_bias":
            (0.05 * rng.normal(size=e)).astype(np.float32),
        "experts_gate": rng.normal(size=(e, h, m)).astype(np.float32)
        / np.sqrt(h),
        "experts_up": rng.normal(size=(e, h, m)).astype(np.float32)
        / np.sqrt(h),
        "experts_down": rng.normal(size=(e, m, h)).astype(np.float32)
        / np.sqrt(m)}
    shared = {f"shared_{n}_proj": {"kernel": rng.normal(size=s).astype(
        np.float32) / np.sqrt(s[0])}
        for n, s in (("gate", (h, m)), ("up", (h, m)), ("down", (m, h)))}
    x = jnp.asarray(rng.normal(size=(2, 9, h)), jnp.float32)

    def share(first, held):
        tree = dict(full)
        for name in ("experts_gate", "experts_up", "experts_down"):
            tree[name] = full[name][first:first + held]
        return tree

    whole = _ffn(0, 0, 1, {**full, **shared}, x)
    routed = sum(_ffn(4, first, 0, share(first, 4), x)
                 for first in (0, 4, 8, 12))
    shared_once = _ffn(4, 4, 1, {**share(4, 4), **shared}, x) \
        - _ffn(4, 4, 0, share(4, 4), x)
    np.testing.assert_allclose(routed + shared_once, whole, atol=2e-5, rtol=0)
    assert np.abs(routed).max() > 0.1 and np.abs(shared_once).max() > 0.1

    # and the uncut layer is the plain reference's
    silu = jax.nn.silu
    tokens = np.asarray(x).reshape(-1, h)
    scores = np.asarray(jax.nn.sigmoid(tokens @ full["router"]))
    chosen, gates = family.route(jnp.asarray(scores),
                                 full["e_score_correction_bias"], dims)
    want = np.zeros_like(tokens)
    for t in range(len(tokens)):
        for i, g in zip(np.asarray(chosen[t]), np.asarray(gates[t])):
            want[t] += g * np.asarray(
                (silu(tokens[t] @ full["experts_gate"][i])
                 * (tokens[t] @ full["experts_up"][i]))
                @ full["experts_down"][i])
        want[t] += np.asarray(
            (silu(tokens[t] @ shared["shared_gate_proj"]["kernel"])
             * (tokens[t] @ shared["shared_up_proj"]["kernel"]))
            @ shared["shared_down_proj"]["kernel"])
    np.testing.assert_allclose(whole.reshape(-1, h), want, atol=5e-5, rtol=0)


@pytest.mark.parametrize("tokens", [5, 300])
def test_every_form_of_the_sum_skips_an_absent_experts_assignment(tokens):
    """Local ids, the id past the last held expert for an absent one: the
    grouped loop, the streamed form and the kernel (in the interpreter) all
    leave it out and agree with a plain loop over the held experts."""
    from lambdipy_tpu.ops import grouped_experts as ops

    rng = np.random.default_rng(tokens)
    held, k, h, m = 4, 3, 128, 128
    x = jnp.asarray(rng.normal(size=(tokens, h)), jnp.float32)
    stacks = [(jnp.asarray(rng.normal(size=s) / np.sqrt(s[1]), jnp.float32),
               None) for s in ((held, h, m), (held, h, m), (held, m, h))]
    chosen = jnp.asarray(rng.integers(0, held + 1, (tokens, k)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, k)), jnp.float32)
    assert (np.asarray(chosen) == held).any()

    def expert(i, rows):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(s[0], i, 0, False)
                      for s in stacks)
        return (jax.nn.silu(rows @ wg) * (rows @ wu)) @ wd

    want = np.zeros((tokens, h), np.float32)
    for t in range(tokens):
        for j in range(k):
            if int(chosen[t, j]) < held:
                want[t] += float(w[t, j]) * np.asarray(
                    expert(int(chosen[t, j]), x[t:t + 1]))[0]
    got = jax.jit(lambda: moe.grouped_experts(x, chosen, w, None, expert,
                                              held))()
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5, rtol=0)
    streamed = ops.streamed_experts(x, chosen, w, None, stacks, jnp.float32)
    np.testing.assert_allclose(np.asarray(streamed), want, atol=5e-5, rtol=0)
    if tokens <= 16:
        picked, count = ops.picked_experts(x, chosen, w, None, stacks,
                                           jnp.float32, interpret=True)
        np.testing.assert_allclose(np.asarray(picked), want, atol=5e-5,
                                   rtol=0)
        assert int(count) == len(set(np.asarray(chosen).ravel()) - {held})


def test_an_expert_too_wide_for_the_kernel_is_seen_before_it_is_chosen():
    from lambdipy_tpu.ops import grouped_experts as ops

    # kanana2-30b's experts fit whole; DeepSeek-V3.2's need 172 MiB
    assert ops.kernel_vmem_bytes(8, 2048, 768, jnp.int8, jnp.bfloat16) \
        < ops.VMEM_CEILING
    assert ops.kernel_vmem_bytes(4, 7168, 2048, jnp.int8, jnp.bfloat16) \
        > ops.VMEM_CEILING


# -- group-limited routing ---------------------------------------------------------

def test_group_limited_routing_is_the_references():
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(40, EXPERTS)), jnp.float32)
    bias = jnp.asarray(0.1 * rng.normal(size=EXPERTS), jnp.float32)
    kw = dict(scoring="sigmoid", norm=True, scaling=2.5)
    chosen, w = moe.route_dropless(logits, bias, TOP_K, n_group=4,
                                   topk_group=2, **kw)
    want, gates = FAMILY.route(jax.nn.sigmoid(logits), bias, DIMS)
    assert np.array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_allclose(np.asarray(w), np.asarray(gates), rtol=1e-6)
    # every pick lies in two of the four groups of four
    assert all(len({int(i) // 4 for i in row}) <= 2
               for row in np.asarray(chosen))
    # and the groups matter: a plain top-4 of 16 picks otherwise somewhere
    plain, _ = moe.route_dropless(logits, bias, TOP_K, **kw)
    assert not np.array_equal(np.asarray(plain), np.asarray(chosen))
    ungrouped, _ = FAMILY.route(jax.nn.sigmoid(logits), bias, DIMS,
                                groups=False)
    assert np.array_equal(np.asarray(plain), np.asarray(ungrouped))


def test_one_group_is_todays_routing():
    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.normal(size=(12, 16)), jnp.float32)
    bias = jnp.asarray(0.1 * rng.normal(size=16), jnp.float32)
    kw = dict(scoring="sigmoid", norm=True, scaling=2.448)
    a = moe.route_dropless(logits, bias, 3, **kw)
    b = moe.route_dropless(logits, bias, 3, n_group=1, topk_group=1, **kw)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))
    # a group's score is the sum of its TWO largest, ties to the lowest group
    chosen, _ = moe.route_dropless(
        jnp.asarray([[3.0, -9.0, 2.0, 2.0, 2.0, 2.0, 0.0, 0.0]]),
        jnp.zeros(8), 2, n_group=4, topk_group=1, **kw)
    assert chosen.tolist() == [[2, 3]]          # 2 x sigmoid(2) beats group 0


# -- YaRN ---------------------------------------------------------------------------

def test_yarn_is_the_references_and_scales_the_softmax(adapter):
    cfg = adapter.config
    assert cfg.rope_scaling == ("yarn", 40.0, 32.0, 32.0, 1.0, 1.0)
    plain = 1.0 / (cfg.rope_theta ** (np.arange(0, 8, 2, dtype=np.float32)
                                      / 8))
    got = np.asarray(llama._scaled_rope_freqs(jnp.asarray(plain),
                                              cfg.rope_scaling,
                                              cfg.rope_theta))
    np.testing.assert_allclose(got, FAMILY.rope_freqs(DIMS), rtol=1e-6)
    np.testing.assert_allclose(FAMILY.rope_freqs(DIMS, yarn=False), plain)
    # the fastest pair keeps its frequency, the slowest is slowed 40 times
    assert got[0] == plain[0] and np.isclose(got[-1], plain[-1] / 40)
    m = 0.1 * np.log(40.0) + 1.0
    assert np.isclose(cfg.attn_scale_mult, m * m)
    assert np.isclose(FAMILY.softmax_scale(DIMS), m * m / np.sqrt(24))
    assert build(rope_factor=1).config.attn_scale_mult == 1.0
    # at the published sizes: correction dims 10 and 23 of 32 pairs
    big = dict(DIMS, qk_rope=64, rope_original_len=4096)
    freqs = FAMILY.rope_freqs(big)
    base = 1.0 / (1e4 ** (np.arange(0, 64, 2, dtype=np.float32) / 64))
    assert np.allclose(freqs[:11], base[:11])
    assert np.allclose(freqs[23:], base[23:] / 40)


def test_convert_takes_yarn_for_this_kind_alone():
    from lambdipy_tpu.models import convert

    rs = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
          "mscale": 1, "mscale_all_dim": 1,
          "original_max_position_embeddings": 4096}
    assert convert._rope_scaling_from_hf(
        {"model_type": "deepseek_v32", "rope_scaling": rs}) == (
        "yarn", 40.0, 4096.0, 32.0, 1.0, 1.0)
    with pytest.raises(ValueError, match="yarn"):
        convert._rope_scaling_from_hf({"model_type": "llama",
                                       "rope_scaling": rs})


@pytest.mark.parametrize("where", ["convert", "family"])
def test_an_mscale_that_would_scale_cos_and_sin_is_refused(where):
    """cos and sin are never scaled, which is the published rule only where
    ``mscale`` equals ``mscale_all_dim``: a checkpoint where they differ
    would be served wrongly with no error, so it is not taken."""
    from lambdipy_tpu.models import convert

    rs = dict(CONFIG["rope_scaling"], mscale=1.0, mscale_all_dim=0.707)
    with pytest.raises(ValueError, match="mscale_all_dim"):
        if where == "convert":
            convert._rope_scaling_from_hf({"model_type": "deepseek_v32",
                                           "rope_scaling": rs})
        else:
            FAMILY.dims_of(dict(CONFIG, rope_scaling=rs))


@pytest.mark.parametrize("model", ["the kv kind", "latent without index_topk",
                                   "the builder without index_topk"])
def test_yarn_outside_the_sparse_form_is_refused(model):
    """Only the sparse prefill and the absorbed sparse step multiply the
    softmax scale by ``attn_scale_mult``: any other model under YaRN would
    prefill and decode at different scales, so none is built."""
    yarn = ("yarn", 40.0, 64.0, 32.0, 1.0, 1.0)
    with pytest.raises(NotImplementedError, match="yarn"):
        if model == "the kv kind":
            llama.LlamaConfig(rope_scaling=yarn)
        elif model == "latent without index_topk":
            llama.LlamaConfig(attn_kind="latent", qk_nope=16, qk_rope=8,
                              v_head=16, kv_lora_rank=32, rope_scaling=yarn)
        else:
            build(index_topk=0)
    assert build().config.rope_scaling == (
        "yarn", DIMS["rope_factor"], float(DIMS["rope_original_len"]),
        DIMS["rope_beta_fast"], DIMS["rope_beta_slow"], DIMS["rope_mscale"])


# -- what cannot take the new leaf or a content-chosen key set says so ------------

def test_the_description_is_what_the_constructors_read(adapter):
    cfg = adapter.config
    assert [cfg.layer_spec(i) for i in range(3)] == [
        ("latent", "dense"), ("latent", "routed"), ("latent", "routed")]
    assert cfg.cache_layout() == {"ckv": (1, 32), "kpe": (1, 8),
                                  "kidx": (1, 16)}
    assert cfg.cache_positions(64) == {"ckv": 64, "kpe": 64, "kidx": 64}
    assert cfg.moe_held == HELD \
        and [c.block for c in cfg.counters()] == ["moe", "dsa"]
    cache = llama.init_decode_cache(cfg, 3, 64)
    assert {k: v.shape for k, v in cache[0].items() if k != "index"} == {
        "ckv": (3, 64, 1, 32), "kpe": (3, 64, 1, 8), "kidx": (3, 64, 1, 16)}
    # whole key blocks past the first (at the cell's sizes 8192 and 12288)
    assert [cfg.prompt_bucket(s, 16) for s in (3, 100, 4096, 4097, 8192,
                                               8193, 12288)] \
        == [16, 128, 4096, 8192, 8192, 12288, 12288]
    plain = registry.get("deepseek-v3").build(extra={
        k: v for k, v in DIMS.items() if not k.startswith(("rope_f", "rope_o",
                                                           "rope_b", "rope_m"))
        and k not in ("q_lora_rank", "index_heads", "index_head_dim",
                      "index_topk")}).config
    assert plain.cache_layout() == {"ckv": (1, 32), "kpe": (1, 8)}
    assert plain.prompt_bucket(5000, 16) == 8192 and [c.block for c in plain.counters()] == ["moe"]


@pytest.mark.parametrize("holder", [
    "init_page_arena", "page_kv_bytes", "prefix_store", "kvwire", "offload",
    "kv_quant", "attn_backend", "mesh", "spec_k", "prefill_chunk",
    "cache_prefix", "register_prefix", "concat_cache_blocks", "speculative",
    "band"])
def test_a_holder_that_cannot_take_a_sparse_latent_cache_raises(
        holder, adapter, params, server):
    from lambdipy_tpu.runtime import kvwire
    from lambdipy_tpu.runtime.offload import OffloadArena
    from lambdipy_tpu.runtime.prefixstore import PrefixStore

    cfg = adapter.config
    cache = llama.init_decode_cache(cfg, 1, 32)
    block = llama.slice_cache_blocks(cache, 0, 16)
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
        "prefill_chunk": lambda: adapter.make_server(params,
                                                     prefill_chunk=16),
        "cache_prefix": lambda: server.cache_prefix(list(range(1, 20))),
        "register_prefix": lambda: server.register_prefix("k", cache, 8),
        "concat_cache_blocks": lambda: llama.concat_cache_blocks(
            cfg, [block], 32),
        "speculative": lambda: next(iter(server._spec_steps(
            [list(range(1, 9))], 8, 4, -1, 3, {}))),
        "band": lambda: adapter.module.apply(
            params, jnp.ones((1, 1), jnp.int32),
            positions=jnp.zeros((1, 1), jnp.int32), cache=cache, band=16),
    }
    with pytest.raises((NotImplementedError, ValueError),
                       match="latent|k/v|routed|sparse"):
        calls[holder]()


def test_a_wrong_description_is_refused_at_build():
    for over in ({"index_topk": 16, "q_lora_rank": 0},
                 {"index_heads": 0}, {"index_head_dim": 4},
                 {"moe_n_group": 3}, {"moe_topk_group": 5},
                 {"moe_experts_held": 9, "moe_first_expert": 8}):
        with pytest.raises(ValueError):
            build(**over)
    with pytest.raises(ValueError, match="index_topk"):
        llama.LlamaConfig(index_topk=16, q_lora_rank=8, index_heads=2,
                          index_head_dim=16)         # not the latent kind
