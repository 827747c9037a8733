"""The benchmark's family seam under tier-1: the accepted families' golden
values and the manifest's rules (``benchmark/tests/test_families.py`` and
``test_manifest.py``, imported whole so that the driver's run counts them),
and the ``deepseek-v3`` family that PR 27 brought: its leaf rules against
the program's real tree at published widths, what it says a step needs, by
hand, and its toy twin through the whole command on the CPU; the same for the
``evabyte`` family that PR 33 brought, the ``deepseek-v32`` family that
PR 35 brought, the ``minicpm-sala`` family that PR 39 brought and the
``bailing-hybrid`` family that PR 41 brought. At the end,
the yardstick against the program: what every accepted configuration's
family says a step reads and computes, against the program's own parameter
tree, and the bounds the ledger's roofline shares stand on."""

import json
import os
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark import families, roofline, run, weights
from benchmark.tests.test_families import *  # noqa: F401,F403
from benchmark.tests.test_manifest import *  # noqa: F401,F403

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
KANANA = json.loads((BENCH / "configs" / "kanana2-30b.json").read_text())
# the twin has a manifest of its own: the accepted rehearsal manifest is a
# file the benchmark has, and only a benchmark PR edits it
TWIN_MANIFEST = BENCH / "rehearsal-mla-moe.json"
TWIN_CELL = "rehearsal-mla-moe.rehearsal-closed"

# the catalog's row for kanana-2-30b-a3b-instruct-2601 (the model-configs
# guide's architectures.jsonl, ``config``): every key, as published
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    changed = {k for k, v in PUBLISHED.items() if KANANA.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert KANANA["num_hidden_layers"] == 13 and KANANA["published"] == {
        "num_hidden_layers": 48, "max_position_embeddings": 32768}
    assert KANANA["reduced"] == ["num_hidden_layers", "engine_window",
                                 "max_position_embeddings"]
    assert set(KANANA["reduced_why"]) == set(KANANA["reduced"])
    assert (KANANA["engine_window"], KANANA["context_served"]) == (4096, 8192)
    assert set(KANANA["recipe_extra"]) == {"batch_cache_len", "max_new_tokens"}
    # the floors of a cut: a whole period, 4 following layers, 8 experts
    assert KANANA["num_hidden_layers"] - KANANA["first_k_dense_replace"] >= 4


@pytest.fixture(scope="module")
def real_tree():
    """The program's parameter tree at the published widths: shapes only."""
    from lambdipy_tpu.models import registry

    adapter = registry.get(KANANA["model"]).build(
        dtype="bfloat16", quant="int8",
        extra=families.of(KANANA).dims_of(KANANA))
    tree = jax.eval_shape(lambda: adapter.init_params(seed=0))
    return {"/".join(str(k.key) for k in path if k.key != "params"): spec
            for path, spec in jax.tree_util.tree_leaves_with_path(tree)}


def test_the_leaf_rules_name_every_path_of_the_real_tree(real_tree):
    family = families.of(KANANA)
    params = 0
    for path, spec in real_tree.items():
        params += int(np.prod(spec.shape))
        # a rule goes by path and dtype: ask it for a sliver of the leaf
        sliver = tuple(min(n, 2) for n in spec.shape)
        leaf = family.leaf(1, path, sliver, spec.dtype, KANANA)
        assert leaf is not None and leaf.shape == sliver, path
        assert leaf.dtype == np.dtype(spec.dtype), path
    assert not any("moe_stats" in path for path in real_tree)
    # the issue's arithmetic at 1 + 12 layers: 64 M + 12 x 640 M of kernels,
    # 2 x 263 M of embedding and head (scales, norms, routers, biases besides)
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    layer0 = attn + 3 * 2048 * 6144
    routed = attn + 3 * 2048 * 1536 + 128 * 3 * 2048 * 768 + 2048 * 128
    kernels = layer0 + 12 * routed + 2 * 128256 * 2048
    assert 0 < params - kernels < 0.002 * kernels
    assert 8.2e9 < kernels < 8.6e9
    shapes = {p: s.shape for p, s in real_tree.items()}
    assert shapes["layer_3/moe/experts_down_scale"] == (128, 1, 2048)
    assert shapes["layer_3/moe/e_score_correction_bias"] == (128,)
    assert shapes["layer_0/kv_b_proj/kernel_int8"] == (512, 32 * 256)
    assert "layer_0/moe/router" not in shapes and \
        "layer_1/gate_proj/kernel_int8" not in shapes


def test_the_seeded_values_are_what_the_configuration_says_it_assumed():
    leaf = weights.leaf
    assert np.all(leaf(KANANA, "layer_2/kv_a_norm/scale", (512,), "float32") == 1)
    for path, shape, fan_in in (
            ("layer_2/kv_b_proj/scale", (1, 8192), 512),
            ("layer_2/o_proj/scale", (1, 2048), 4096),
            ("layer_0/down_proj/scale", (1, 2048), 6144),
            ("layer_2/moe/shared_down_proj/scale", (1, 2048), 1536),
            ("layer_2/moe/experts_down_scale", (4, 1, 2048), 768),
            ("layer_2/moe/experts_up_scale", (4, 1, 768), 2048),
            ("lm_head/scale", (1, 64), 2048)):
        np.testing.assert_allclose(leaf(KANANA, path, shape, "float32"),
                                   1 / (127 * fan_in ** 0.5), rtol=1e-6)
    router = leaf(KANANA, "layer_2/moe/router", (2048, 128), "float32")
    logits = np.random.default_rng(0).normal(size=(64, 2048)) @ router
    assert 0.8 < logits.std() < 1.6                     # of unit order
    bias = leaf(KANANA, "layer_2/moe/e_score_correction_bias", (128,), "float32")
    assert 0 < np.abs(bias).max() <= 0.05 and len(np.unique(bias)) > 64
    # a layer's experts are kin: 7 parts of 8 the layer's common draw, whole
    # numbers all the way, at the scale every kernel has (asserted above)
    stack = leaf(KANANA, "layer_2/moe/experts_gate_int8", (3, 64, 32), "int8")
    assert stack.dtype == np.int8 and len(np.unique(stack)) > 200
    own = weights.int8_draw(11, "layer_2/moe/experts_gate_int8", (3, 64, 32))
    common = weights.int8_draw(11, "layer_2/moe/experts_gate_int8/common",
                               (64, 32))
    want = np.floor((7.0 * common[None] + own + 4) / 8)
    assert np.array_equal(stack, want) and np.abs(want).max() <= 128
    flat = stack.reshape(3, -1).astype(np.float64)
    assert np.corrcoef(flat)[0, 1] > 0.95 and not np.array_equal(flat[0], flat[1])
    with pytest.raises(ValueError, match="deepseek-v3.*q_a_proj"):
        leaf(KANANA, "layer_2/q_a_proj/kernel", (2, 2), "bfloat16")
    with pytest.raises(ValueError, match="group-limited"):
        families.of(KANANA).dims_of(dict(KANANA, n_group=8, topk_group=4))


def test_each_fault_of_the_routed_ffn_is_seen_and_the_reference_is_not_moved(
        capsys, tmp_path):
    family = families.of(KANANA)
    twin = json.loads((BENCH / "configs" / "rehearsal-mla-moe.json").read_text())
    ids = np.random.default_rng(5).integers(0, twin["vocab_size"], (2, 24))
    rows, at = np.repeat(np.arange(2), 16), np.tile(np.arange(8, 24), 2)
    alone = np.asarray(family.walk(twin, ids, rows, at, (False,))[False])
    flags = (False, True) + family.FAULTS
    streams = family.walk(twin, ids, rows, at, flags)
    assert np.array_equal(np.asarray(streams[False]), alone)
    moved = {flag: float(np.abs(np.asarray(streams[flag]) - alone).max())
             for flag in flags[1:]}
    assert all(v > 1e-3 for v in moved.values()), moved
    assert moved["no_routed"] > moved["half_scale"] > moved["int4_experts"]
    # the command a limit's readings come from (PERF.md section 2)
    path = tmp_path / "twin.json"
    path.write_text(json.dumps(twin))
    assert family.main(["--config", str(path), "--seeds", "3"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line) == {"seed", "int4", *family.FAULTS}
    assert all(line[k]["widest_gap"] >= 0 for k in line if k != "seed")


def test_what_a_step_needs_by_hand_at_8_rows():
    family = families.of(KANANA)
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048   # 26.3 M
    touched = 128 * (1 - (1 - 6 / 128) ** 8)                     # 40.9 of 128
    assert 40.5 < touched < 41.5
    moe = 12 * (4 * 2048 * 128 + 3 * 2048 * 1536
                + touched * 3 * 2048 * 768)
    assert family.moe_step_bytes(KANANA, rows=8) == pytest.approx(moe)
    assert 2.4e9 < moe < 2.5e9          # against 7.2 GB of resident experts
    cache = 8 * 300 * 13 * (512 + 64) * 2
    want = 13 * attn + 3 * 2048 * 6144 + 2048 * 128256 + moe + cache
    assert family.decode_step_bytes(KANANA, rows=8, context=300) == \
        pytest.approx(want)
    assert 3.0e9 < want < 3.2e9
    # one row touches its six experts, not 41; flops follow the six
    one = family.moe_step_bytes(KANANA, rows=1)
    assert one == pytest.approx(12 * (4 * 2048 * 128 + 3 * 2048 * 1536
                                      + 6 * 3 * 2048 * 768))
    flops = family.decode_step_flops(KANANA, rows=2, context=100)
    assert flops == 2 * family.decode_step_flops(KANANA, rows=1, context=100)
    active = 13 * attn + 3 * 2048 * 6144 + 12 * (
        2048 * 128 + 3 * 2048 * 1536 + 6 * 3 * 2048 * 768) + 2048 * 128256
    assert family.decode_step_flops(KANANA, rows=1, context=0) == \
        pytest.approx(2 * active)
    head = 2048 * 128256                  # once, at the last position
    assert family.prefill_flops(KANANA, rows=1, seq_len=128) > \
        2 * (128 * (active - head) + head)


def test_the_toy_twin_runs_the_whole_command_and_counts_its_experts(
        capsys, tmp_path, monkeypatch):
    # a rehearsal pins its children to one CPU device through this process's
    # environment (harness.prepare): put it back for the tests that follow
    for key in ("JAX_PLATFORMS", "XLA_FLAGS"):
        monkeypatch.setenv(key, os.environ.get(key, ""))
    rc = run.main(["--manifest", str(TWIN_MANIFEST), "--workload", TWIN_CELL,
                   "--seed", str(2**31 + 5), "--seconds", "3", "--trace", "1",
                   "--work-dir", str(tmp_path)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, lines[-3:]
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0
    window = next(ln for ln in lines if ln.get("stage") == "window")
    assert window["compiles_in_window"] == 0, window
    share = last["metrics"]["moe_load_max_share"]["value"]
    assert 100 / 16 <= share < 60       # 16 experts: even routing reads 6.25
    # a llama cell's line has no such metric: its reader finds no counter
    from benchmark import harness

    reader = harness.layer_metric("moe_load_max_share")
    assert reader.read({"m_open": {"handler": {}}, "m_close": {"handler": {}}}) \
        is None
    for name in ("decode_moe_ms", "mla_absorb_ms", "moe_hbm_pct"):
        assert harness.layer_metric(name).read(
            {"family": families.load("llama-hf"), "trace": {"busy_s": 1},
             "slice": {"live": [(8, 100.0)]}, "device": {"kind": "TPU v5 lite"},
             "config": {}}) is None


def test_the_experts_read_reader_takes_the_windows_delta_or_nothing():
    """``moe_experts_read`` (PR 28): distinct experts a routed layer's call
    picked in a decode step, from the window's two scrapes; None where the
    program counts no such thing (a llama cell; the parent of PR 28, whose
    ``handler.moe`` has ``assignments`` and ``load`` only)."""
    from benchmark import harness

    reader = harness.layer_metric("moe_experts_read")

    def moe(**block):
        return {"handler": {"moe": block}}

    assert reader.read({
        "m_open": moe(experts_read=4100, layer_steps=100, assignments=1),
        "m_close": moe(experts_read=4100 + 192 * 41, layer_steps=292,
                       assignments=2)}) == pytest.approx(41.0)
    idle = moe(experts_read=5, layer_steps=1)
    assert reader.read({"m_open": idle, "m_close": idle}) is None
    parent = moe(assignments=7, load=[3, 4])
    assert reader.read({"m_open": parent, "m_close": parent}) is None
    assert reader.read({"m_open": {"handler": {}},
                        "m_close": {"handler": {}}}) is None
    entry = next(m for m in json.loads((REPO / "BENCHMARK.json").read_text())[
        "per_layer"] if m["name"] == "moe_experts_read")
    assert entry["workloads"][:2] == ["kanana2-30b.decode-saturated",
                                      "deepseek-v32-exp.long-context-decode"]


# -- the evabyte family (PR 33) -------------------------------------------------

EVABYTE = json.loads((BENCH / "configs" / "evabyte6b.json").read_text())
EVA_TWIN_MANIFEST = BENCH / "rehearsal-eva.json"
EVA_TWIN_CELL = "rehearsal-eva.rehearsal-closed"

# the catalog's row for EvaByte (the model-configs guide's
# architectures.jsonl, ``config``): every key, as published
EVABYTE_PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


def test_evabyte_is_the_published_configuration_cut_in_depth_only():
    changed = {k for k, v in EVABYTE_PUBLISHED.items()
               if EVABYTE.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert EVABYTE["num_hidden_layers"] in (16, 12)     # the depth rule
    assert EVABYTE["published"] == {"num_hidden_layers": 32,
                                    "max_position_embeddings": 32768}
    assert EVABYTE["reduced"] == ["num_hidden_layers", "engine_window",
                                  "max_position_embeddings"]
    assert set(EVABYTE["reduced_why"]) == set(EVABYTE["reduced"])
    # the program's max_len stays apart from the engine window: equal, the
    # handler would switch on the prefix store, which refuses this layout
    assert (EVABYTE["engine_window"], EVABYTE["context_served"]) == (8192,
                                                                      16384)
    assert EVABYTE["recipe_extra"] == {"batch_cache_len": 8192,
                                       "batch_max": 4, "max_new_tokens": 16}
    assert {"weights", "quantization", "tokenizer", "chunk_pooling",
            "pooling_scale"} <= set(EVABYTE["assumed"])
    traffic = json.loads((BENCH / "traffic" / "long-decode.json").read_text())
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        <= EVABYTE["engine_window"]
    # no request stays under one window, where EVA is plain attention
    assert traffic["prompt_len"]["min"] >= EVABYTE["window_size"]
    assert traffic["clients"] == 2 * EVABYTE["recipe_extra"]["batch_max"]


def test_evabytes_leaf_rules_name_every_path_of_the_real_tree():
    from lambdipy_tpu.models import registry

    family = families.of(EVABYTE)
    adapter = registry.get(EVABYTE["model"]).build(
        dtype="bfloat16", quant="int8", extra=family.dims_of(EVABYTE))
    tree = jax.eval_shape(lambda: adapter.init_params(seed=0))
    shapes, params = {}, 0
    for path, spec in jax.tree_util.tree_leaves_with_path(tree):
        path = "/".join(str(k.key) for k in path if k.key != "params")
        shapes[path] = spec.shape
        params += int(np.prod(spec.shape))
        sliver = tuple(min(n, 2) for n in spec.shape)
        leaf = family.leaf(1, path, sliver, spec.dtype, EVABYTE)
        assert leaf is not None and leaf.shape == sliver, path
        assert leaf.dtype == np.dtype(spec.dtype), path
    # ISSUE 33's arithmetic: 202.4 M a layer, 1.3 M of embedding, a head of
    # 4096 x (8 x 320)
    layers = EVABYTE["num_hidden_layers"]
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 32 * 128
    assert 202.3e6 < layer < 202.5e6
    kernels = layers * layer + 320 * 4096 + 4096 * 8 * 320
    assert 0 < params - kernels < 0.001 * kernels
    assert shapes["layer_3/adaptive_mu_k"] == (32, 128)
    assert shapes["lm_head/kernel_int8"] == (4096, 2560)
    assert shapes["embed/embedding"] == (320, 4096)
    with pytest.raises(ValueError, match="evabyte.*kv_a_proj"):
        weights.leaf(EVABYTE, "layer_2/kv_a_proj/kernel", (2, 2), "bfloat16")
    with pytest.raises(ValueError, match="grouped K/V"):
        family.dims_of(dict(EVABYTE, num_key_value_heads=8))


def test_evabytes_seeded_values_are_what_the_configuration_says_it_assumed():
    family = families.of(EVABYTE)
    leaf = weights.leaf
    assert np.all(leaf(EVABYTE, "layer_2/attn_norm/scale", (64,), "float32") == 0)
    np.testing.assert_allclose(
        leaf(EVABYTE, "layer_2/down_proj/scale", (1, 8), "float32"),
        1 / (127 * 4096 ** 0.5), rtol=1e-6)
    # pooling logits over a chunk of unit order: keys of the projection's
    # own spread against the two learned vectors, the value side after 1/sqrt(d)
    keys = np.random.default_rng(0).normal(size=(4096, 32, 128)) * family.KEY_STD
    mu = leaf(EVABYTE, "layer_2/adaptive_mu_k", (32, 128), "float32")
    phi = leaf(EVABYTE, "layer_2/adaptive_phi", (32, 128), "float32")
    assert 0.8 < (keys * mu).sum(-1).std() < 1.25
    assert 0.8 < ((keys * phi).sum(-1) / 128 ** 0.5).std() < 1.25
    # far from the card's init_std, where a chunk's softmax is uniform
    assert mu.std() > 5 * EVABYTE["init_std"]
    assert not np.array_equal(mu, phi[:, ::-1]) and len(np.unique(mu)) > 200


def test_what_an_eva_step_needs_by_hand():
    family = families.of(EVABYTE)
    layers = EVABYTE["num_hidden_layers"]
    row = 2 * layers * 4096 * 2                  # K and V, bf16, all layers
    assert row == layers * 16384                 # ISSUE 33's 16 KB a layer
    kernels = layers * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 2560
    # under one window: the context itself, no summary
    assert family.keys_visible(EVABYTE, 300) == 300
    assert family.decode_step_bytes(EVABYTE, rows=8, context=300) == \
        kernels + 8 * 300 * row
    # past it: half a window of ring rows and a summary for every 16
    # positions before that; ISSUE 33's "1024 + 220" at a mean context of 4400
    assert family.keys_visible(EVABYTE, 4400) == 1024 + (4400 - 1024) / 16
    assert 1230 < family.keys_visible(EVABYTE, 4400) < 1240
    assert family.eva_step_bytes(EVABYTE, rows=3.8, context=4400) == \
        pytest.approx(3.8 * 1235 * row)
    # the counter's own keys take the place of the estimate
    assert family.eva_step_bytes(EVABYTE, rows=4, context=4400, keys=1250.0) \
        == 4 * 1250 * row
    assert family.decode_step_flops(EVABYTE, rows=2, context=0) == 4 * kernels
    # a prefill's scores are window-local: 8192 positions cost 4 windows of
    # 2048 squared and the summaries before each, far under 8192 squared
    pre = family.prefill_flops(EVABYTE, rows=1, seq_len=8192)
    matmuls = 2 * 8192 * (kernels - 4096 * 2560) + 2 * 4096 * 2560
    own = layers * 4096 * 2 * 4 * 2048 ** 2
    summaries = layers * 4096 * 4 * 2048 * 128 * (0 + 1 + 2 + 3)
    assert pre == pytest.approx(matmuls + own + summaries)
    assert pre - matmuls < 0.3 * layers * 2 * 4096 * 8192 ** 2


def test_each_fault_of_eva_attention_is_seen_and_the_reference_is_not_moved(
        capsys, tmp_path):
    family = families.of(EVABYTE)
    twin = json.loads((BENCH / "configs" / "rehearsal-eva.json").read_text())
    ids = np.random.default_rng(5).integers(0, twin["vocab_size"], (2, 80))
    rows, at = np.repeat(np.arange(2), 40), np.tile(np.arange(40, 80), 2)
    alone = np.asarray(family.walk(twin, ids, rows, at, (False,))[False])
    flags = (False, True) + family.FAULTS
    assert family.FAULTS == ("no_summaries", "mean_pool", "swapped_pool",
                             "stale_window")
    streams = family.walk(twin, ids, rows, at, flags)
    assert np.array_equal(np.asarray(streams[False]), alone)
    moved = {flag: float(np.abs(np.asarray(streams[flag]) - alone).max())
             for flag in flags[1:]}
    assert all(v > 1e-2 for v in moved.values()), moved
    assert moved["no_summaries"] > moved["mean_pool"]
    # the command a limit's readings come from (PERF.md section 2)
    path = tmp_path / "twin.json"
    path.write_text(json.dumps(twin))
    assert family.main(["--config", str(path), "--seeds", "3"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line) == {"seed", "int4", *family.FAULTS}
    assert all(line[k]["widest_gap"] >= 0 for k in line if k != "seed")


def test_the_eva_twin_runs_the_whole_command_and_counts_its_keys(
        capsys, tmp_path, monkeypatch):
    """The whole command over the toy twin, whose segments keep ring and
    summaries read-only (PR 34). Under the suite's other workers the
    warm-up's burst of four may not arrive as ONE group in its four rounds;
    it then says so (``still_missing``) and that program compiles inside
    the window (the driver's run of PR 33's tree: ``compiles_in_window``
    1, ``["stream", 4, 32, 128, 16]``). Such a run rehearsed a busy machine,
    not the program: it is made again over the bundle it built (25 s), and
    everything is asserted of a run whose warm-up covered its envelope."""
    from benchmark import harness

    for key in ("JAX_PLATFORMS", "XLA_FLAGS"):
        monkeypatch.setenv(key, os.environ.get(key, ""))
    windows = []
    run_window = harness.run_window
    monkeypatch.setattr(harness, "run_window", lambda *a, **kw: windows.append(
        run_window(*a, **kw)) or windows[-1])
    for attempt in range(5):
        rc = run.main(["--manifest", str(EVA_TWIN_MANIFEST), "--workload",
                       EVA_TWIN_CELL, "--seed", str(2**31 + 7 + attempt),
                       "--seconds", "3", "--trace", "1",
                       "--work-dir", str(tmp_path)])
        lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("{")]
        warm = next(ln for ln in lines if ln.get("stage") == "warmup")
        if not warm["still_missing"]:
            break
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, lines[-3:]
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0
    window = next(ln for ln in lines if ln.get("stage") == "window")
    assert window["compiles_in_window"] == 0, (warm, window)
    # requests of 32-80 positions over windows of 32 and chunks of 4: a
    # query sees between 1 and 32 + 16 keys
    keys = last["metrics"]["eva_keys_per_query"]["value"]
    assert 8 < keys < 48
    # over the server's life, warm-up included: a booked row's 16 steps
    # complete exactly four chunks of 4 wherever they begin, and a request
    # of at most 80 positions crosses a window's edge inside a segment at
    # most twice
    eva = windows[-1]["m_close"]["handler"]["eva"]
    assert eva["row_steps"] > 0
    assert eva["chunks_written"] * 4 == eva["row_steps"]
    assert 0 < eva["edge_row_steps"] < eva["row_steps"] / 2


def test_the_eva_readers_take_the_windows_delta_or_nothing():
    """The three readers PR 33 added: what they read, and None where the
    program has no such counter or scope (a llama cell; the parent)."""
    from benchmark import harness

    def eva(**block):
        return {"handler": {"eva": block}}

    reader = harness.layer_metric("eva_keys_per_query")
    a = eva(row_steps=1600, keys_attended=2_000_000, chunks_written=100)
    b = eva(row_steps=1600 + 640, keys_attended=2_000_000 + 640 * 1250,
            chunks_written=140)
    assert reader.read({"m_open": a, "m_close": b}) == pytest.approx(1250.0)
    assert reader.read({"m_open": a, "m_close": a}) is None
    assert reader.read({"m_open": {"handler": {}},
                        "m_close": {"handler": {}}}) is None
    llama = {"family": families.load("llama-hf"), "trace": {"busy_s": 1},
             "slice": {"live": [(4, 4400.0)]},
             "device": {"kind": "TPU v5 lite"}, "config": {},
             "m_open": {"handler": {}}, "m_close": {"handler": {}}}
    for name in ("eva_summarize_ms", "eva_cache_hbm_pct"):
        assert harness.layer_metric(name).read(llama) is None
    # no trace: no share, whatever the counters say
    assert harness.layer_metric("eva_cache_hbm_pct").read(
        {"family": families.of(EVABYTE), "config": EVABYTE, "trace": None,
         "slice": {"live": [(4, 4400.0)]}, "device": {"kind": "TPU v5 lite"},
         "m_open": a, "m_close": b}) is None
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in ("eva_summarize_ms", "eva_cache_hbm_pct",
                 "eva_keys_per_query"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == ["evabyte6b.long-decode"]


# -- the deepseek-v32 family (PR 35) ----------------------------------------------

DSV32 = json.loads((BENCH / "configs" / "deepseek-v32-exp.json").read_text())
DSA_TWIN_MANIFEST = BENCH / "rehearsal-dsa.json"
DSA_TWIN_CELL = "rehearsal-dsa.rehearsal-closed"
DSA_CELL = "deepseek-v32-exp.long-context-decode"

# the catalog's row for DeepSeek-V3.2-Exp (the model-configs guide's
# architectures.jsonl, ``config``): every key, as published
DSV32_PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}


def test_deepseek_v32_is_the_published_configuration_cut_to_a_chips_share():
    changed = {k for k, v in DSV32_PUBLISHED.items()
               if DSV32.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"}
    assert DSV32["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers", "engine_window",
        "max_position_embeddings"]
    assert set(DSV32["reduced_why"]) == set(DSV32["reduced"])
    assert DSV32["published"] == {
        k: DSV32_PUBLISHED[k] for k in DSV32["reduced"]
        if k != "engine_window"}
    # the depth rule, and the guide's floors: a period and 4 following
    # layers, 8 experts, an eighth of the vocabulary
    assert (DSV32["num_hidden_layers"], DSV32["first_k_dense_replace"]) in (
        (7, 1), (6, 1), (5, 1))
    assert DSV32["n_routed_experts"] == 16 >= 8
    assert (DSV32["routed_experts_published"], DSV32["first_routed_expert"],
            DSV32["layer_shared_by_chips"]) == (256, 0, 16)
    assert DSV32["vocab_size"] * 8 == DSV32_PUBLISHED["vocab_size"]
    assert DSV32["num_nextn_predict_layers"] == 0
    # the program's max_len stays apart from the engine window: equal, the
    # handler would switch on the prefix store, which refuses this layout
    assert (DSV32["engine_window"], DSV32["context_served"]) == (16384, 32768)
    assert DSV32["recipe_extra"] == {"batch_cache_len": 16384,
                                     "batch_max": 4, "max_new_tokens": 16}
    assert {"weights", "quantization", "indexer_precision",
            "indexer_rotation", "indexer_layout", "rope_interleave", "mscale",
            "multi_token_prediction", "tokenizer"} <= set(DSV32["assumed"])
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "deepseek-v32-exp")
    assert entry["reduced"] == DSV32["reduced"]
    assert entry["source"] == DSV32["source"]
    cell = next(w for w in manifest["workloads"] if w["name"] == DSA_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v32-exp", "long-context-decode", 1)
    traffic = json.loads((BENCH / "traffic" / "long-context-decode.json"
                          ).read_text())
    assert (traffic["kind"], traffic["clients"], traffic["pool"],
            traffic["lead_in_s"]) == ("closed_loop", 8, 192, 20)
    assert (traffic["prompt_len"], traffic["max_tokens"]) == (
        {"dist": "uniform", "min": 8192, "max": 12288},
        {"dist": "uniform", "min": 1280, "max": 1792})    # ISSUE 35's fallback
    # every context is 4-7 x index_topk and fits the engine window
    assert traffic["prompt_len"]["min"] >= 4 * DSV32["index_topk"]
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        <= DSV32["engine_window"]
    assert traffic["clients"] == 2 * DSV32["recipe_extra"]["batch_max"]
    # the warm-up's two singles ARE the program's two solo-prefill programs
    from benchmark import warmup
    from lambdipy_tpu.models import registry

    cov = warmup.coverage(traffic, DSV32)
    assert cov["singles"] == [(8192, 16), (12288, 16)]
    assert cov["decode_windows"] == [16384] and cov["group_buckets"] == []
    cfg = registry.get("deepseek-v32").build(
        extra=families.of(DSV32).dims_of(DSV32)).config
    assert {cfg.prompt_bucket(s, 16) for s in (8192,)} == {8192}
    assert {cfg.prompt_bucket(s, 16) for s in (8193, 10000, 12288)} == {12288}


def test_deepseek_v32s_leaf_rules_name_every_path_of_the_real_tree():
    from lambdipy_tpu.models import registry

    family = families.of(DSV32)
    adapter = registry.get(DSV32["model"]).build(
        dtype="bfloat16", quant="int8", extra=family.dims_of(DSV32))
    tree = jax.eval_shape(lambda: adapter.init_params(seed=0))
    shapes, sizes = {}, {}
    for path, spec in jax.tree_util.tree_leaves_with_path(tree):
        path = "/".join(str(k.key) for k in path if k.key != "params")
        shapes[path] = spec.shape
        sizes[path] = int(np.prod(spec.shape))
        sliver = tuple(min(n, 2) for n in spec.shape)
        leaf = family.leaf(1, path, sliver, spec.dtype, DSV32)
        assert leaf is not None and leaf.shape == sliver, path
        assert leaf.dtype == np.dtype(spec.dtype), path

    def int8_of(prefix, skip=()):
        return sum(n for p, n in sizes.items() if p.startswith(prefix)
                   and p.endswith("int8") and not any(s in p for s in skip))

    # ISSUE 35's arithmetic, a layer: attention 187.1 M, indexer 14.0 M
    # (0.46 M of it float32), a dense layer 597.4 M, an expert layer HERE
    # 951.5 M of which 704.6 M are the 16 held experts
    attention = int8_of("layer_1/", ("index_", "moe"))
    assert 187.0e6 < attention < 187.2e6
    indexer = int8_of("layer_1/index_") + sizes["layer_1/index_weights_proj"]
    assert 13.9e6 < indexer < 14.0e6
    assert 597.3e6 < int8_of("layer_0/") + sizes[
        "layer_0/index_weights_proj"] < 597.5e6
    held = int8_of("layer_1/moe/experts_")
    assert 704.5e6 < held < 704.7e6
    here = int8_of("layer_1/") + sizes["layer_1/index_weights_proj"] \
        + sizes["layer_1/moe/router"]
    assert 951.4e6 < here < 951.6e6
    total = int8_of("") + sizes["embed/embedding"]
    assert 6.5e9 < total < 6.6e9            # 6.31 + 0.116 + 0.116 G values
    assert shapes["layer_3/moe/experts_down_int8"] == (16, 2048, 7168)
    assert shapes["layer_3/moe/router"] == (7168, 256)
    assert shapes["layer_3/moe/e_score_correction_bias"] == (256,)
    assert shapes["layer_3/index_wq_b/kernel_int8"] == (1536, 64 * 128)
    assert shapes["layer_3/q_b_proj/kernel_int8"] == (1536, 128 * 192)
    assert shapes["lm_head/kernel_int8"] == (7168, 16160)
    assert "layer_0/moe/router" not in shapes and "layer_0/q_proj" not in str(
        list(shapes))
    with pytest.raises(ValueError, match="deepseek-v32.*k_proj"):
        weights.leaf(DSV32, "layer_2/k_proj/kernel", (2, 2), "bfloat16")
    with pytest.raises(ValueError, match="noaux_tc"):
        family.dims_of(dict(DSV32, topk_method="greedy"))


def test_deepseek_v32s_seeded_values_are_what_the_configuration_says():
    family = families.of(DSV32)
    leaf = weights.leaf
    assert np.all(leaf(DSV32, "layer_2/q_a_norm/scale", (64,), "float32") == 1)
    assert np.all(leaf(DSV32, "layer_2/index_k_norm_bias", (8,), "float32")
                  == 0)
    np.testing.assert_allclose(
        leaf(DSV32, "layer_2/index_wq_b/scale", (1, 8), "float32"),
        1 / (127 * 1536 ** 0.5), rtol=1e-6)
    # what makes the selection matter: q_b_proj's scale, and it alone
    np.testing.assert_allclose(
        leaf(DSV32, "layer_2/q_b_proj/scale", (1, 8), "float32"),
        family.Q_GAIN / (127 * 1536 ** 0.5), rtol=1e-6)
    assert family.Q_GAIN == 1.5
    w = leaf(DSV32, "layer_2/index_weights_proj", (7168, 64), "float32")
    assert w.dtype == np.float32 and (w < 0).mean() > 0.4
    # an expert is drawn under its PUBLISHED id: another share of the same
    # layer (experts 16-31) holds other experts, kin to the same common draw
    stack = "layer_2/moe/experts_up_int8"
    here = leaf(DSV32, stack, (2, 64, 32), "int8")
    there = weights.leaf(dict(DSV32, first_routed_expert=16), stack,
                         (2, 64, 32), "int8")
    again = weights.leaf(dict(DSV32, first_routed_expert=1), stack,
                         (1, 64, 32), "int8")
    assert np.array_equal(again[0], here[1])
    assert not np.array_equal(here, there)
    assert np.corrcoef(here.ravel().astype(float),
                       there.ravel().astype(float))[0, 1] > 0.9


def test_what_a_sparse_step_needs_by_hand():
    family = families.of(DSV32)
    d = family.dims_of(DSV32)
    layers, routed = 7, 6
    attention = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 \
        + 512 * 128 * 256 + 128 * 128 * 7168
    indexer = 1536 * 64 * 128 + 7168 * 128 + 4 * 7168 * 64
    expert = 3 * 7168 * 2048
    # ISSUE 35: a cached token of a layer is 1152 B of latent row and 256 B
    # of indexer key; the sparse side NEEDS, at 4 rows of 11k, 0.15 GB a step
    need = family.dsa_step_bytes(DSV32, rows=4, visible=11000, selected=2048)
    assert need == 4 * layers * (11000 * 256 + 2048 * 1152)
    assert 0.14e9 < need < 0.16e9
    # with so many rows that every HELD expert is touched and no context: a
    # step reads every int8 kernel once, the float32 routers and the
    # indexer's float32 head weights
    once = layers * (attention + indexer) + 3 * 7168 * 18432 \
        + 7168 * 16160 + routed * (4 * 7168 * 256 + expert + 16 * expert)
    assert family.decode_step_bytes(DSV32, rows=1e9, context=0) == once
    # at the cell's 4 rows: 1.9 of the 16 held experts a layer (4 x 8 picks x
    # 16 / 256 = 2 assignments), a weight stream of about 2.7 GB
    assert 1.8 < family.experts_touched(d, 4) < 2.0
    step = family.decode_step_bytes(DSV32, rows=4, context=11000)
    assert step == pytest.approx(
        once - routed * (16 - family.experts_touched(d, 4)) * expert + need)
    assert 2.6e9 < step - need < 2.8e9
    # the selection caps what attention reads, not what the indexer scores
    short = family.decode_step_bytes(DSV32, rows=4, context=1000)
    assert step - short == 4 * layers * (10000 * 256 + 1048 * 1152)
    flops = family.decode_step_flops(DSV32, rows=1, context=11000)
    token = layers * (attention + indexer - 3 * 7168 * 64) \
        + 3 * 7168 * 18432 + routed * (7168 * 256 + 1.5 * expert)
    assert flops == pytest.approx(
        2 * token + 2 * 7168 * 16160 + layers * (
            2 * 64 * 128 * 11000 + 2 * 128 * 2048 * (2 * 512 + 64)))
    # ISSUE 35: a 12288 prompt is about 14 TFLOP a layer
    pre = family.prefill_flops(DSV32, rows=1, seq_len=12288)
    assert 13e12 * layers < pre < 16e12 * layers


def test_each_fault_of_sparse_attention_is_seen_and_the_reference_is_not_moved(
        capsys, tmp_path):
    family = families.of(DSV32)
    twin = json.loads((BENCH / "configs" / "rehearsal-dsa.json").read_text())
    ids = np.random.default_rng(5).integers(1, twin["vocab_size"], (2, 80))
    rows, at = np.repeat(np.arange(2), 40), np.tile(np.arange(40, 80), 2)
    alone = np.asarray(family.walk(twin, ids, rows, at, (False,))[False])
    flags = (False, True) + family.FAULTS
    assert family.FAULTS == (
        "no_indexer", "topk_half", "stale_index", "index_interleaved",
        "no_mscale", "no_yarn", "no_groups", "no_q_norm", "int4_experts")
    streams = family.walk(twin, ids, rows, at, flags)
    assert np.array_equal(np.asarray(streams[False]), alone)
    moved = {flag: float(np.abs(np.asarray(streams[flag]) - alone).max())
             for flag in flags[1:]}
    assert all(v > 3e-3 for v in moved.values()), moved
    assert min(moved["no_indexer"], moved["topk_half"]) > 1.0
    # the command a limit's readings come from (PERF.md section 2)
    path = tmp_path / "twin.json"
    path.write_text(json.dumps(twin))
    assert family.main(["--config", str(path), "--seeds", "3", "--length",
                        "80", "--served", "40", "--rows", "2"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line) == {"seed", "int4", *family.FAULTS}
    assert all(line[k]["widest_gap"] >= 0 for k in line if k != "seed")
    assert family.main(["--config", str(path), "--seeds", "3", "--length",
                        "48", "--served", "8", "--faults",
                        "int4,topk_half"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line) == {"seed", "int4", "topk_half"}


def test_the_dsa_twin_runs_the_whole_command_and_counts_its_keys(
        capsys, tmp_path, monkeypatch):
    """The whole command over the toy twin (contexts of 32-80 positions
    against a top-16, experts 4-7 of 16 held). A run whose warm-up's burst
    did not arrive as one group says so (``still_missing``) and is made
    again over the bundle it built, as the eva twin's is."""
    from benchmark import harness

    for key in ("JAX_PLATFORMS", "XLA_FLAGS"):
        monkeypatch.setenv(key, os.environ.get(key, ""))
    windows = []
    run_window = harness.run_window
    monkeypatch.setattr(harness, "run_window", lambda *a, **kw: windows.append(
        run_window(*a, **kw)) or windows[-1])
    for attempt in range(5):
        rc = run.main(["--manifest", str(DSA_TWIN_MANIFEST), "--workload",
                       DSA_TWIN_CELL, "--seed", str(2**31 + 11 + attempt),
                       "--seconds", "3", "--trace", "1",
                       "--work-dir", str(tmp_path)])
        lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("{")]
        warm = next(ln for ln in lines if ln.get("stage") == "warmup")
        if not warm["still_missing"]:
            break
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, lines[-3:]
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0
    window = next(ln for ln in lines if ln.get("stage") == "window")
    assert window["compiles_in_window"] == 0, (warm, window)
    # prompts of 8-32 tokens: most steps attend 16 keys, the first of the
    # shortest prompts fewer
    keys = last["metrics"]["dsa_keys_per_query"]["value"]
    assert 14 < keys <= 16
    assert 5 < last["metrics"]["moe_local_share"]["value"] < 60
    # over the server's life, warm-up included: a booked row-step attends
    # min(context, 16) keys, so never more than 16 and never more than it saw
    dsa = windows[-1]["m_close"]["handler"]["dsa"]
    moe = windows[-1]["m_close"]["handler"]["moe"]
    assert 0 < dsa["keys_selected"] <= 16 * dsa["row_steps"]
    assert dsa["keys_selected"] < dsa["keys_visible"]
    assert moe["assignments"] == dsa["row_steps"] * 2 * 4   # layers x picks
    assert moe["local_assignments"] == sum(moe["load"][4:8])


def test_the_prefill_share_is_the_window_less_its_decode_steps(monkeypatch):
    """``dsa_prefill_share``: the window's seconds less its decode steps at
    the traced step's device time; nothing without a trace, without
    segments, or on a program of another family."""
    from benchmark import harness, scopes

    reader = harness.layer_metric("dsa_prefill_share")

    def scrape(segments):
        return {"handler": {"batching": {"segments_run": segments,
                                         "segment": 16}}}

    ctx = {"family": families.of(DSV32), "trace": {"busy_s": 2.0},
           "m_open": scrape(100), "m_close": scrape(100 + 290)}
    monkeypatch.setattr(scopes, "step_ms", lambda ctx, names=None: 5.0)
    monkeypatch.setattr("sys.argv", ["run.py", "--seconds", "50"])
    # 290 segments x 16 steps x 5 ms = 23.2 s of 50: 53.6 % is not decode
    assert reader.read(ctx) == pytest.approx(53.6)
    monkeypatch.setattr("sys.argv", ["run.py", "--seconds=25"])
    assert reader.read(ctx) == pytest.approx(7.2)
    assert reader.read(dict(ctx, m_close=scrape(100))) is None
    assert reader.read(dict(ctx, m_open={})) is None
    assert reader.read(dict(ctx, family=families.load("llama-hf"))) is None
    monkeypatch.setattr("sys.argv", ["run.py"])
    assert reader.read(ctx) is None
    monkeypatch.setattr(scopes, "step_ms", lambda ctx, names=None: None)
    monkeypatch.setattr("sys.argv", ["run.py", "--seconds", "50"])
    assert reader.read(ctx) is None                      # an untraced run


def test_the_dsa_readers_take_the_windows_delta_or_nothing():
    """The readers PR 35 added: what they read, and None where the
    program has no such counter or scope (a llama cell; the parent)."""
    from benchmark import harness

    def metrics(dsa, moe):
        return {"handler": {"dsa": dsa, "moe": moe}}

    a = metrics(dict(row_steps=1600, keys_selected=3_000_000,
                     keys_visible=16_000_000),
                dict(assignments=76800, local_assignments=4800))
    b = metrics(dict(row_steps=1600 + 640, keys_selected=3_000_000 + 640 * 2048,
                     keys_visible=16_000_000 + 640 * 11000),
                dict(assignments=76800 + 30720, local_assignments=4800 + 1920))
    ctx = {"m_open": a, "m_close": b}
    assert harness.layer_metric("dsa_keys_per_query").read(ctx) == 2048.0
    assert harness.layer_metric("dsa_keys_per_query").means(ctx) == (
        2048.0, 11000.0)
    assert harness.layer_metric("moe_local_share").read(ctx) == 6.25
    empty = {"m_open": {"handler": {}}, "m_close": {"handler": {}}}
    for name in ("dsa_keys_per_query", "moe_local_share"):
        assert harness.layer_metric(name).read(empty) is None
        assert harness.layer_metric(name).read({"m_open": a, "m_close": a}) \
            is None
    # the parent's handler.moe has no local count: nothing, not a KeyError
    old = {"handler": {"moe": {"assignments": 5}}}
    assert harness.layer_metric("moe_local_share").read(
        {"m_open": old, "m_close": {"handler": {"moe": {"assignments": 9}}}}
    ) is None
    llama = {"family": families.load("llama-hf"), "trace": {"busy_s": 1},
             "slice": {"live": [(4, 11000.0)]},
             "device": {"kind": "TPU v5 lite"}, "config": {}, **empty}
    for name in ("dsa_index_ms", "dsa_select_ms", "dsa_cache_hbm_pct",
                 "dsa_prefill_share"):
        assert harness.layer_metric(name).read(llama) is None
    # no trace: no share, whatever the counters say
    assert harness.layer_metric("dsa_cache_hbm_pct").read(
        {"family": families.of(DSV32), "config": DSV32, "trace": None,
         "slice": {"live": [(4, 11000.0)]}, "device": {"kind": "TPU v5 lite"},
         **ctx}) is None
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in ("dsa_index_ms", "dsa_select_ms", "dsa_cache_hbm_pct",
                 "dsa_keys_per_query", "moe_local_share",
                 "dsa_prefill_share"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        # (moe_local_share: the later cell that holds a share, PR 41, too)
        assert entry["workloads"][0] == DSA_CELL and len(
            entry["workloads"]) == 1 + (name == "moe_local_share")
    for name in ("out_tok_s", "decode_hbm_pct", "hbm_peak_gb",
                 "engine_host_ms", "decode_step_ms", "decode_matmul_ms",
                 "decode_attend_ms", "decode_sample_ms", "decode_moe_ms",
                 "mla_absorb_ms", "moe_load_max_share", "moe_experts_read"):
        entry = next(m for m in manifest["end_to_end"] + manifest["per_layer"]
                     if m["name"] == name)
        assert DSA_CELL in entry["workloads"], name
    # moe_hbm_pct takes its experts from the live rows alone: not this cell's
    assert DSA_CELL not in next(m for m in manifest["per_layer"]
                                if m["name"] == "moe_hbm_pct")["workloads"]
    assert not hasattr(families.of(DSV32), "moe_step_bytes")


def test_a_sparse_decode_step_at_4_rows_is_bound_by_its_weight_bytes():
    """The cell's premise: at 4 rows of 11k the bytes take ten times longer
    than the operations, nine tenths of the bytes are weights, and what the
    sparse side NEEDS is a twentieth: what it takes is the finding."""
    family = families.of(DSV32)
    need = family.decode_step_bytes(DSV32, rows=4, context=11000)
    flops = family.decode_step_flops(DSV32, rows=4, context=11000)
    assert need / V5E.hbm_bytes_s > 10 * flops / V5E.bf16_flops
    assert family.decode_step_bytes(DSV32, rows=4, context=0) > 0.9 * need
    assert 3.2e-3 < need / V5E.hbm_bytes_s < 3.7e-3


# -- the minicpm-sala family (PR 39): an attention kind a layer ---------------

SALA = json.loads((BENCH / "configs" / "minicpm-sala.json").read_text())
SALA_TWIN_MANIFEST = BENCH / "rehearsal-sala.json"
SALA_TWIN_CELL = "rehearsal-sala.rehearsal-closed"
SALA_CELL = "minicpm-sala.long-document"
_LIN, _SP = "lightning-attn", "minicpm4"

# the catalog's row for MiniCPM-SALA (the model-configs guide's
# architectures.jsonl, ``config``): every key, as published
SALA_PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": [_SP] + [_LIN] * 8 + [_SP] + [_LIN] * 6 + [_SP, _SP]
    + [_LIN] * 4 + [_SP] + [_LIN] * 6 + [_SP] * 3,
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}


def test_minicpm_sala_is_the_published_configuration_cut_in_depth_only():
    changed = {k for k, v in SALA_PUBLISHED.items()
               if SALA.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "mixer_types",
                       "max_position_embeddings"}
    assert SALA["reduced"] == ["num_hidden_layers", "mixer_types",
                               "engine_window", "max_position_embeddings"]
    assert set(SALA["reduced_why"]) == set(SALA["reduced"])
    assert SALA["published"] == {k: SALA_PUBLISHED[k] for k in SALA["reduced"]
                                 if k != "engine_window"}
    # entries 9-24 of the published order: 4 + 12, the published 1 : 3, the
    # two adjacent sparse layers among them
    assert len(SALA_PUBLISHED["mixer_types"]) == 32
    assert SALA["mixer_types"] == SALA_PUBLISHED["mixer_types"][9:25]
    assert [i for i, m in enumerate(SALA["mixer_types"]) if m == _SP] == [
        0, 7, 8, 13]
    assert SALA["num_hidden_layers"] == 16
    assert (SALA["engine_window"], SALA["context_served"]) == (32768, 65536)
    assert SALA["recipe_extra"] == {"batch_cache_len": 32768, "batch_max": 8,
                                    "max_new_tokens": 16}
    assert SALA["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    assert {"weights", "quantization", "sparse_config", "sparse_scores",
            "qk_norm", "lightning_rope", "lightning_decay",
            "lightning_output", "mup", "tokenizer"} <= set(SALA["assumed"])
    assert SALA["cache_leaves"]["state"] == "float32"
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == "minicpm-sala")
    assert entry["reduced"] == SALA["reduced"]
    assert entry["source"] == SALA["source"]
    cell = next(w for w in manifest["workloads"] if w["name"] == SALA_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala", "long-document", 1)
    traffic = json.loads((BENCH / "traffic" / "long-document.json"
                          ).read_text())
    assert (traffic["kind"], traffic["clients"], traffic["pool"],
            traffic["lead_in_s"], traffic["order_seed"]) == (
        "closed_loop", 16, 192, 30, 20261002)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 16384,
                                     "max": 20480}
    assert traffic["max_tokens"]["dist"] == "uniform"
    assert (traffic["max_tokens"]["min"], traffic["max_tokens"]["max"]) in (
        (1536, 2048), (1664, 1920))        # ISSUE 39's one stated fallback
    # every context is 2-2.75 x dense_len and fits the engine window
    assert traffic["prompt_len"]["min"] >= 2 * SALA["sparse_config"][
        "dense_len"]
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        <= SALA["engine_window"]
    assert traffic["clients"] == 2 * SALA["recipe_extra"]["batch_max"]
    # the warm-up's two singles ARE the mix's two solo-prefill programs, and
    # its one decode window the full one
    from benchmark import warmup
    from lambdipy_tpu.models import registry

    cov = warmup.coverage(traffic, SALA)
    assert cov["singles"] == [(16384, 16), (20480, 16)]
    assert cov["decode_windows"] == [32768] and cov["group_buckets"] == []
    cfg = registry.get("minicpm-sala").build(
        extra=families.of(SALA).dims_of(SALA)).config
    assert cfg.prompt_bucket(16384, 16) == 16384
    assert {cfg.prompt_bucket(s, 16) for s in (16385, 18000, 20480)} == {20480}
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert (cfg.embed_scale, cfg.logit_divisor) == (12.0, 16.0)


def test_minicpm_salas_leaf_rules_name_every_path_of_the_real_tree():
    from lambdipy_tpu.models import registry

    family = families.of(SALA)
    adapter = registry.get(SALA["model"]).build(
        dtype="bfloat16", quant="int8", extra=family.dims_of(SALA))
    tree = jax.eval_shape(lambda: adapter.init_params(seed=0))
    shapes, sizes = {}, {}
    for path, spec in jax.tree_util.tree_leaves_with_path(tree):
        path = "/".join(str(k.key) for k in path if k.key != "params")
        shapes[path] = spec.shape
        sizes[path] = int(np.prod(spec.shape))
        sliver = tuple(min(n, 2) for n in spec.shape)
        leaf = family.leaf(1, path, sliver, spec.dtype, SALA)
        assert leaf is not None and leaf.shape == sliver, path
        assert leaf.dtype == np.dtype(spec.dtype), path

    def int8_of(prefix):
        return sum(n for p, n in sizes.items() if p.startswith(prefix)
                   and p.endswith("int8"))

    # ISSUE 39's arithmetic: a minicpm4 layer 253.8 M, a lightning-attn
    # layer 285.2 M, 4 + 12 of them 4.44 G, embedding and head 300.8 M each
    assert 253.7e6 < int8_of("layer_0/") < 253.9e6
    assert 285.1e6 < int8_of("layer_1/") < 285.3e6
    assert 4.43e9 < int8_of("layer_") < 4.45e9
    assert sizes["embed/embedding"] == sizes["lm_head/kernel_int8"] \
        == 73448 * 4096
    assert shapes["layer_7/k_proj/kernel_int8"] == (4096, 256)
    assert shapes["layer_1/k_proj/kernel_int8"] == (4096, 4096)
    assert shapes["layer_8/out_gate_proj/kernel_int8"] == (4096, 4096)
    assert shapes["layer_1/o_norm/scale"] == (128,)
    assert "layer_0/o_norm/scale" not in shapes
    with pytest.raises(ValueError, match="minicpm-sala.*kv_a_proj"):
        weights.leaf(SALA, "layer_2/kv_a_proj/scale", (1, 2), "float32")
    assert np.all(weights.leaf(SALA, "layer_2/q_norm/scale", (8,), "float32")
                  == 1)
    # what makes the selection matter: q_norm's gain in the minicpm4 layers,
    # and it alone (layers 0, 7, 8, 13 here)
    assert SALA["sparse_q_gain"] == 3.0
    assert np.all(weights.leaf(SALA, "layer_7/q_norm/scale", (8,), "float32")
                  == 3)
    assert np.all(weights.leaf(SALA, "layer_7/k_norm/scale", (8,), "float32")
                  == 1)
    np.testing.assert_allclose(
        weights.leaf(SALA, "layer_2/down_proj/scale", (1, 8), "float32"),
        1 / (127 * 16384 ** 0.5), rtol=1e-6)
    # a step reads every int8 kernel once (no rows, so no cache)
    assert family.decode_step_bytes(SALA, rows=0, context=0) == \
        int8_of("")
    assert family.decode_step_flops(SALA, rows=1, context=0) == \
        pytest.approx(2 * int8_of("") + 12 * 6 * 32 * 128 * 128, rel=1e-12)


def test_what_a_sala_step_needs_by_hand():
    family = families.of(SALA)
    d = family.dims_of(SALA)
    row, state = 2 * 2 * 128, 4 * 32 * 128 * 128
    # ISSUE 39: a cached token is 1056 B a minicpm4 layer (K, V and a
    # sixteenth of a compressed key), a state 2.10 MB a slot
    assert 2 * row + row // 16 == 1056 and state == 2097152
    assert family.visible_kc(d, 20000) == (20000 - 32) // 16 + 1
    assert (family.attended_keys(d, 5000), family.attended_keys(d, 20000)) \
        == (5000, 4096)
    need = family.sala_step_bytes(SALA, rows=8, visible=20000, attended=4096)
    assert need == 8 * (4 * row * (1249 + 2 * 4096) + 12 * 2 * state)
    # what the two kinds NEED at 8 rows of 20k: 0.55 GB a step, three
    # quarters of it the states; what the masked read FETCHES is 8 x 32768 x
    # 4 x 1024 B = 1.07 GB of rows beside them (ISSUE 39)
    assert 0.5e9 < need < 0.6e9
    step = family.decode_step_bytes(SALA, rows=8, context=20000)
    kernels = family.decode_step_bytes(SALA, rows=0, context=0)
    assert step == kernels + need and 4.7e9 < kernels < 4.8e9
    # ISSUE 39: a 20480 prefill is about 182 TFLOP, 8.9 GFLOP a token
    pre = family.prefill_flops(SALA, rows=1, seq_len=20480)
    assert 175e12 < pre < 200e12


def test_each_fault_of_the_two_kinds_is_seen_and_the_reference_is_not_moved(
        capsys, tmp_path):
    family = families.of(SALA)
    twin = json.loads((BENCH / "configs" / "rehearsal-sala.json").read_text())
    ids = np.random.default_rng(5).integers(1, twin["vocab_size"], (2, 80))
    rows, at = np.repeat(np.arange(2), 40), np.tile(np.arange(40, 80), 2)
    alone = np.asarray(family.walk(twin, ids, rows, at, (False,))[False])
    flags = (False, True) + family.FAULTS
    assert family.FAULTS == ("dense_past", "no_forced", "stale_kc",
                             "sparse_rope", "no_decay", "bf16_states")
    streams = family.walk(twin, ids, rows, at, flags)
    assert np.array_equal(np.asarray(streams[False]), alone)
    moved = {flag: float(np.abs(np.asarray(streams[flag]) - alone).max())
             for flag in flags[1:]}
    assert all(v > 3e-3 for v in moved.values()), moved
    # the command a limit's readings come from (PERF.md section 2)
    path = tmp_path / "twin.json"
    path.write_text(json.dumps(twin))
    assert family.main(["--config", str(path), "--seeds", "3", "--length",
                        "80", "--served", "40", "--rows", "2"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line) == {"seed", "int4", *family.FAULTS}
    assert all(line[k]["widest_gap"] >= 0 for k in line if k != "seed")


def test_the_sala_twin_runs_the_whole_command_and_counts_its_keys(
        capsys, tmp_path, monkeypatch):
    """The whole command over the toy twin (contexts of 8-80 positions
    against ``dense_len`` 32 and a top-4 of blocks of 8). A run whose
    warm-up's burst did not arrive as one group says so (``still_missing``)
    and is made again over the bundle it built, as the other twins' are."""
    from benchmark import harness

    for key in ("JAX_PLATFORMS", "XLA_FLAGS"):
        monkeypatch.setenv(key, os.environ.get(key, ""))
    windows = []
    run_window = harness.run_window
    monkeypatch.setattr(harness, "run_window", lambda *a, **kw: windows.append(
        run_window(*a, **kw)) or windows[-1])
    for attempt in range(5):
        rc = run.main(["--manifest", str(SALA_TWIN_MANIFEST), "--workload",
                       SALA_TWIN_CELL, "--seed", str(2**31 + 17 + attempt),
                       "--seconds", "3", "--trace", "1",
                       "--work-dir", str(tmp_path)])
        lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("{")]
        warm = next(ln for ln in lines if ln.get("stage") == "warmup")
        if not warm["still_missing"]:
            break
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, lines[-3:]
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0
    window = next(ln for ln in lines if ln.get("stage") == "window")
    assert window["compiles_in_window"] == 0, (warm, window)
    # prompts of 8-32 tokens and answers of 24-48: inside dense_len a step
    # attends its context, past it 3 blocks of 8 and the open one
    keys = last["metrics"]["sala_keys_per_query"]["value"]
    assert 16 < keys <= 32
    sala = windows[-1]["m_close"]["handler"]["sala"]
    assert 0 < sala["keys_attended"] <= sala["keys_visible"]
    assert 0 < sala["dense_steps"] < sala["row_steps"]
    assert sala["state_bytes"] == sala["row_steps"] * 4 * 2 * 4 * 8 * 16 * 16
    assert 0 < sala["kc_writes"] <= sala["row_steps"] // 2 + 64


def test_the_sala_readers_take_the_windows_delta_or_nothing():
    """The readers PR 39 added: what they read, and None where the program
    has no such counter or scope (a llama cell; the parent)."""
    from benchmark import harness

    def metrics(**sala):
        return {"handler": {"sala": sala}}

    a = metrics(row_steps=1600, keys_attended=6_000_000,
                keys_visible=30_000_000)
    b = metrics(row_steps=1600 + 640, keys_attended=6_000_000 + 640 * 4070,
                keys_visible=30_000_000 + 640 * 20000)
    ctx = {"m_open": a, "m_close": b}
    assert harness.layer_metric("sala_keys_per_query").read(ctx) == 4070.0
    assert harness.layer_metric("sala_keys_per_query").means(ctx) == (
        4070.0, 20000.0)
    empty = {"m_open": {"handler": {}}, "m_close": {"handler": {}}}
    reader = harness.layer_metric("sala_keys_per_query")
    assert reader.read(empty) is None
    assert reader.read({"m_open": a, "m_close": a}) is None
    llama = {"family": families.load("llama-hf"), "trace": {"busy_s": 1},
             "slice": {"live": [(8, 20000.0)]},
             "device": {"kind": "TPU v5 lite"}, "config": {}, **empty}
    for name in ("sala_select_ms", "lin_state_ms", "sala_cache_hbm_pct"):
        assert harness.layer_metric(name).read(llama) is None
    # no trace: no share, whatever the counters say
    assert harness.layer_metric("sala_cache_hbm_pct").read(
        {"family": families.of(SALA), "config": SALA, "trace": None,
         "slice": {"live": [(8, 20000.0)]}, "device": {"kind": "TPU v5 lite"},
         **ctx}) is None
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in ("sala_select_ms", "lin_state_ms", "sala_keys_per_query",
                 "sala_cache_hbm_pct"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [SALA_CELL]
    for name in ("out_tok_s", "decode_hbm_pct", "hbm_peak_gb",
                 "engine_host_ms", "decode_step_ms", "decode_matmul_ms",
                 "decode_attend_ms", "decode_sample_ms"):
        entry = next(m for m in manifest["end_to_end"] + manifest["per_layer"]
                     if m["name"] == name)
        assert SALA_CELL in entry["workloads"][-2:], name


def test_a_sala_decode_step_at_8_rows_is_bound_by_its_bytes():
    """The cell's premise: at 8 rows of 20k the bytes take some ten times
    longer than the operations, nine tenths of the bytes are weights, and
    what the two kinds NEED is a tenth."""
    family = families.of(SALA)
    need = family.decode_step_bytes(SALA, rows=8, context=20000)
    flops = family.decode_step_flops(SALA, rows=8, context=20000)
    assert need / V5E.hbm_bytes_s > 8 * flops / V5E.bf16_flops
    assert family.decode_step_bytes(SALA, rows=0, context=0) > 0.88 * need
    assert 6.0e-3 < need / V5E.hbm_bytes_s < 6.8e-3


# -- the bailing-hybrid family (PR 41): kda and latent layers in one model ------

LING = json.loads((BENCH / "configs" / "ling3-flash.json").read_text())
LING_TWIN_MANIFEST = BENCH / "rehearsal-kda.json"
LING_TWIN_CELL = "rehearsal-kda.rehearsal-closed"
LING_CELL = "ling3-flash.reasoning-decode"
LING_READERS = ("kda_state_ms", "kda_state_hbm_pct", "kda_scan_share",
                "kda_bytes_per_row_step")

# the catalog's row for Ling-3.0-flash (the model-configs guide's
# architectures.jsonl, ``config``): every key, as published
LING_PUBLISHED = {
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": 'head_wise',
    "group_norm_size": 1, "head_dim": 128, "hidden_act": 'silu',
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": 'sigmoid',
    "scoring_func": 'sigmoid', "seq_aux": True, "short_conv_kernel_size": 4,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": 'noaux_tc',
    "up_proj_norm": False, "use_bias": False, "use_kda_lora": False,
    "use_mla_nope": False, "use_nGPT": False, "use_qk_norm": True,
    "use_qkv_bias": False, "v_head_dim": 128, "value_norm": False,
    "vocab_size": 157184, "model_type": 'bailing_hybrid',
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}


def test_ling3_flash_is_the_published_configuration_cut_to_a_chips_share():
    changed = {k for k, v in LING_PUBLISHED.items()
               if LING.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace",
                       "num_experts", "vocab_size",
                       "num_nextn_predict_layers", "expert_swiglu_limit_list",
                       "share_expert_swiglu_limit_list",
                       "max_position_embeddings"}
    assert LING["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "num_nextn_predict_layers", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list", "engine_window",
        "max_position_embeddings"]
    assert set(LING["reduced_why"]) == set(LING["reduced"])
    assert LING["published"] == {k: LING_PUBLISHED[k] for k in LING["reduced"]
                                 if k != "engine_window"}
    # 1 + 6 of 42: published layer 1 and one whole period in published order
    held = LING["layers_held"]
    assert held == [1, 6, 7, 8, 9, 10, 11] and LING["num_hidden_layers"] == 7
    assert LING["expert_swiglu_limit_list"] == [
        LING_PUBLISHED["expert_swiglu_limit_list"][i] for i in held] == [0] * 7
    assert LING["share_expert_swiglu_limit_list"] == [0] * 7
    d = families.of(LING).dims_of(LING)
    assert d["layer_kinds"] == "kda,kda,kda,kda,kda,kda,latent"
    assert (d["first_dense_layers"], d["moe_experts"], d["moe_experts_held"],
            d["moe_first_expert"]) == (1, 512, 128, 0)
    assert (LING["routed_experts_published"], LING["layer_shared_by_chips"],
            LING["vocab_size"] * 4) == (512, 4, 157184)
    assert (LING["engine_window"], LING["context_served"]) == (8192, 16384)
    assert LING["recipe_extra"] == {"batch_cache_len": 8192, "batch_max": 16,
                                    "max_new_tokens": 16}
    assert {"mla_position", "kda_qk_norm", "rotary_dim", "kda_gate",
            "kda_gate_rank", "kda_output_norm", "output_gate", "mla_qk_norm",
            "group_score", "weights", "quantization", "tokenizer"} \
        <= set(LING["assumed"])
    assert "stands_for" in LING and "4 chips share each layer" in \
        LING["stands_for"]
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == "ling3-flash")
    assert entry["reduced"] == LING["reduced"]
    assert entry["source"] == LING["source"]
    assert len(manifest["workloads"]) == 8
    cell = next(w for w in manifest["workloads"] if w["name"] == LING_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling3-flash", "reasoning-decode", 1)
    traffic = json.loads((BENCH / "traffic" / "reasoning-decode.json"
                          ).read_text())
    assert (traffic["kind"], traffic["clients"], traffic["pool"]) == (
        "closed_loop", 32, 192)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 512,
                                     "max": 1536}
    assert traffic["max_tokens"] == {"dist": "uniform", "min": 3072,
                                     "max": 4096}
    assert traffic["lead_in_s"] >= 20
    assert traffic["clients"] == 2 * LING["recipe_extra"]["batch_max"]
    assert traffic["prompt_len"]["max"] < LING["vocab_size"]
    # every prompt is a solo prefill of a power-of-two bucket, and the
    # decode windows are the buckets of 528 .. 5632 positions
    from benchmark import warmup

    cov = warmup.coverage(traffic, LING)
    assert cov["prompt_buckets"] == [512, 1024, 2048]
    assert cov["group_buckets"] == [] and cov["slots"] == 16
    assert cov["decode_windows"] == [1024, 2048, 4096, 8192]


def test_ling3_flashs_leaf_rules_name_every_path_of_the_real_tree():
    from lambdipy_tpu.models import registry

    family = families.of(LING)
    adapter = registry.get(LING["model"]).build(
        dtype="bfloat16", quant="int8", extra=family.dims_of(LING))
    tree = jax.eval_shape(lambda: adapter.init_params(seed=0))
    shapes, sizes, dtypes = {}, {}, {}
    for path, spec in jax.tree_util.tree_leaves_with_path(tree):
        path = "/".join(str(k.key) for k in path if k.key != "params")
        shapes[path], dtypes[path] = spec.shape, np.dtype(spec.dtype)
        sizes[path] = int(np.prod(spec.shape))
        sliver = tuple(min(n, 2) for n in spec.shape)
        leaf = family.leaf(1, path, sliver, spec.dtype, LING)
        assert leaf is not None and leaf.shape == sliver, path
        assert leaf.dtype == np.dtype(spec.dtype), path

    def params_of(prefix, skip=()):
        return sum(n for p, n in sizes.items() if p.startswith(prefix)
                   and p.endswith(("int8", "/router", "conv_weight"))
                   and not any(s in p for s in skip))

    # ISSUE 41's arithmetic: a KDA layer's attention 52.6 M, an MLA layer's
    # 31.9 M, a routed FFN here 762.2 M, the dense FFN 47.2 M; the stage
    # 99.8 + 5 x 814.8 + 794.1 M = 4.97 G of kernels
    ffn = ("moe/", "gate_proj", "up_proj", "down_proj")
    assert 52.5e6 < params_of("layer_0/", ffn + ("out_gate",)) + sizes[
        "layer_0/out_gate_proj/kernel_int8"] < 52.7e6
    assert 31.8e6 < params_of("layer_6/", ffn) < 32.0e6
    assert 762.1e6 < params_of("layer_1/moe/") < 762.3e6
    assert 47.1e6 < params_of("layer_0/", ("_proj/kernel_int8",)) \
        - sizes["layer_0/conv_weight"] + sum(
            sizes[f"layer_0/{n}_proj/kernel_int8"]
            for n in ("gate", "up", "down")) < 47.3e6
    assert 99.7e6 < params_of("layer_0/") < 99.9e6
    assert 814.7e6 < params_of("layer_1/") < 814.9e6
    assert 794.0e6 < params_of("layer_6/") < 794.2e6
    assert 4.96e9 < params_of("layer_") < 4.98e9
    assert sizes["embed/embedding"] == sizes["lm_head/kernel_int8"] \
        == 39296 * 2560
    assert shapes["layer_2/moe/experts_gate_int8"] == (128, 2560, 768)
    assert shapes["layer_2/moe/router"] == (2560, 512)
    assert shapes["layer_2/f_proj/kernel_int8"] == (2560, 4096)
    assert shapes["layer_2/conv_weight"] == (4, 3, 4096)
    assert shapes["layer_2/o_norm/scale"] == (4096,)
    assert shapes["layer_6/out_gate_proj/kernel_int8"] == (2560, 32)
    assert shapes["layer_6/kv_a_proj/kernel_int8"] == (2560, 576)
    assert dtypes["layer_2/A_log"] == dtypes["layer_2/dt_bias"] == np.float32
    with pytest.raises(ValueError, match="bailing-hybrid.*q_a_proj"):
        weights.leaf(LING, "layer_2/q_a_proj/scale", (1, 2), "float32")
    # what was drawn, and why (the configuration's ``assumed.weights``)
    taps = weights.leaf(LING, "layer_2/conv_weight", (4, 3, 64), "float32")
    assert np.abs(taps).max() <= 1.5 * 128 / 127 and np.std(taps) > 0.7
    # taps of either sign in most channels: no moving average, no last tap
    assert ((taps > 0).any(axis=0) & (taps < 0).any(axis=0)).mean() > 0.8
    bias = weights.leaf(LING, "layer_2/dt_bias", (4096,), "float32")
    assert -8.0 <= bias.min() < -7.9 and -0.1 < bias.max() <= 0.0
    rate = weights.leaf(LING, "layer_2/A_log", (32,), "float32")
    assert np.abs(rate).max() <= np.log(2) + 1e-6 and np.std(rate) > 0.2
    np.testing.assert_allclose(
        weights.leaf(LING, "layer_2/f_proj/scale", (1, 8), "float32"),
        1 / (127 * 2560 ** 0.5), rtol=1e-6)
    np.testing.assert_allclose(
        weights.leaf(LING, "layer_2/moe/experts_down_scale", (2, 1, 8),
                     "float32"), 1 / (127 * 768 ** 0.5), rtol=1e-6)
    # a step with so many rows that every held expert is touched reads every
    # int8 kernel once, the float32 routers, conv weights and gate biases
    read = sum(n * (4 if dtypes[p] == np.float32 else 1)
               for p, n in sizes.items()
               if p.endswith(("int8", "/router", "conv_weight", "dt_bias")))
    assert family.decode_step_bytes(LING, rows=1e9, context=0) - \
        family.kda_step_bytes(LING, rows=1e9) == pytest.approx(read, rel=1e-9)


def test_what_a_ling_step_needs_by_hand():
    family = families.of(LING)
    d = family.dims_of(LING)
    state, tail = 4 * 32 * 128 * 128, 2 * 3 * 3 * 4096
    # ISSUE 41: a slot is 6 x (2.10 MB of state + 74 KB of tail) = 13.0 MB
    assert (state, tail) == (2097152, 73728)
    assert 12.9e6 < 6 * (state + tail) < 13.1e6
    assert family.kda_step_bytes(LING, rows=16) == 16 * 6 * 2 * (state + tail)
    # 16 rows x top-8: 32 local assignments a layer touch about 28 of the 128
    assert 28 < family.experts_touched(d, 16) < 29
    step = family.decode_step_bytes(LING, rows=16, context=3000)
    # about 2.0 GB: 1.0 GB of experts, 0.42 GB of state and tail both ways,
    # 0.06 GB of latent rows, the rest other kernels and the head slice
    assert 1.95e9 < step < 2.15e9
    experts = 6 * family.experts_touched(d, 16) * 3 * 2560 * 768
    assert 0.95e9 < experts < 1.05e9
    rows = 16 * 3000 * 1152
    assert step - family.decode_step_bytes(LING, rows=16, context=0) == rows
    # the step is bound by its bytes, eight times over
    flops = family.decode_step_flops(LING, rows=16, context=3000)
    assert step / V5E.hbm_bytes_s > 8 * flops / V5E.bf16_flops
    assert 2.3e-3 < step / V5E.hbm_bytes_s < 2.7e-3
    # one 1536-token prompt is under 0.1 s of operations at the peak
    assert family.prefill_flops(LING, rows=1, seq_len=1536) / V5E.bf16_flops \
        < 0.1


def test_the_kda_twin_runs_the_whole_command_and_counts_its_layer_steps(
        capsys, tmp_path, monkeypatch):
    """The whole command over the toy twin (prompts of 8-32 tokens, answers
    of 24-48, through build, deploy, HTTP and the continuous engine). A run
    whose warm-up's burst did not arrive as one group says so
    (``still_missing``) and is made again over the bundle it built, as the
    other twins' are."""
    from benchmark import harness

    for key in ("JAX_PLATFORMS", "XLA_FLAGS"):
        monkeypatch.setenv(key, os.environ.get(key, ""))
    windows = []
    run_window = harness.run_window
    monkeypatch.setattr(harness, "run_window", lambda *a, **kw: windows.append(
        run_window(*a, **kw)) or windows[-1])
    for attempt in range(5):
        rc = run.main(["--manifest", str(LING_TWIN_MANIFEST), "--workload",
                       LING_TWIN_CELL, "--seed", str(2**31 + 41 + attempt),
                       "--seconds", "3", "--trace", "1",
                       "--work-dir", str(tmp_path)])
        lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("{")]
        warm = next(ln for ln in lines if ln.get("stage") == "warmup")
        if not warm["still_missing"]:
            break
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, lines[-3:]
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0
    window = next(ln for ln in lines if ln.get("stage") == "window")
    assert window["compiles_in_window"] == 0, (warm, window)
    # the shape's value exactly: a float32 state of 8 x 16 x 16 and a
    # float32 tail of 3 x 3 x 128, each once each way
    assert last["metrics"]["kda_bytes_per_row_step"]["value"] == \
        2 * (4 * 8 * 16 * 16 + 4 * 3 * 3 * 128)
    counted = windows[-1]["m_close"]["handler"]["kda"]
    assert counted["row_steps"] % 6 == 0 and counted["scan_chunks"] > 0
    assert counted["state_bytes"] == counted["row_steps"] * 25600
    # the routed FFN's load under layer_kinds: a quarter of the picks local
    assert 10 < last["metrics"]["moe_local_share"]["value"] < 45
    assert 0 < last["metrics"]["moe_experts_read"]["value"] <= 4


def test_the_kda_readers_read_a_number_or_say_why_not(monkeypatch):
    """The readers PR 41 added: a number where the program counts or names
    what they read, None where it does not (a llama cell; the parent)."""
    from benchmark import harness, scopes

    def metrics(**kda):
        return {"handler": {"kda": kda}}

    a = metrics(row_steps=9600, state_bytes=9600 * 4341760)
    b = metrics(row_steps=9600 + 960, state_bytes=(9600 + 960) * 4341760)
    ctx = {"m_open": a, "m_close": b}
    reader = harness.layer_metric("kda_bytes_per_row_step")
    assert reader.read(ctx) == 4341760 == 2 * (2097152 + 73728)
    empty = {"m_open": {"handler": {}}, "m_close": {"handler": {}}}
    assert reader.read(empty) is None
    assert reader.read({"m_open": a, "m_close": a}) is None
    llama = {"family": families.load("llama-hf"), "trace": {"busy_s": 1},
             "slice": {"live": [(16, 3000.0)]},
             "device": {"kind": "TPU v5 lite"}, "config": {}, **empty}
    for name in ("kda_state_ms", "kda_state_hbm_pct", "kda_scan_share"):
        assert harness.layer_metric(name).read(llama) is None
    family = families.of(LING)
    ling = {**llama, "family": family, "config": LING,
            "m_close": {"handler": {"batching": {"segment": 16}}}}
    # no trace: no device number, whatever the counters say
    for name in ("kda_state_ms", "kda_state_hbm_pct", "kda_scan_share"):
        assert harness.layer_metric(name).read({**ling, "trace": None}) \
            is None
    # a split of 10 segment runs whose kda scopes took 1.2 ms a step: 16
    # rows' 0.417 GB over 1.2 ms is 42.4 % of 819 GB/s
    monkeypatch.setattr(scopes, "for_run", lambda family: {
        "runs": 10, "run_s": 10 * 16 * 6e-3, "scoped": True,
        "by_scope": {"kda_conv": 0.016, "kda_gate": 0.016,
                     "kda_state": 0.16, "mlp": 0.4}})
    assert harness.layer_metric("kda_state_ms").read(ling) == \
        pytest.approx(1.2)
    assert harness.layer_metric("kda_state_hbm_pct").read(ling) == \
        pytest.approx(100 * 16 * 6 * 4341760 / 1.2e-3 / 819e9)
    # the share of a scope over a recorded v5e trace (a llama segment: the
    # scope it does have; kda_scan reads 0 there, not None)
    share = harness.layer_metric("kda_scan_share").scope_share
    fixture = BENCH / "tests" / "data" / "v5e_seg_3ms.xplane.pb"
    assert 55 < share(fixture, families.load("llama-hf"), "mlp") < 65
    assert share(fixture, family, "kda_scan") == 0.0
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in LING_READERS:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [LING_CELL]
    for name in ("out_tok_s", "decode_hbm_pct", "hbm_peak_gb",
                 "engine_host_ms", "decode_step_ms", "decode_matmul_ms",
                 "decode_attend_ms", "decode_sample_ms", "decode_moe_ms",
                 "mla_absorb_ms", "moe_load_max_share", "moe_experts_read",
                 "moe_local_share"):
        entry = next(m for m in manifest["end_to_end"] + manifest["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"][-1] == LING_CELL, name
    moe_hbm = next(m for m in manifest["per_layer"]
                   if m["name"] == "moe_hbm_pct")
    assert LING_CELL not in moe_hbm["workloads"]


# -- the yardstick against the program ---------------------------------------

CELL_CONFIGS = ("mistral7b", "deepseek7b", "kanana2-30b", "evabyte6b")
V5E = roofline.peaks_for("TPU v5 lite")


def cell_config(name: str, **cut) -> dict:
    return {**json.loads((BENCH / "configs" / f"{name}.json").read_text()),
            **cut}


@pytest.mark.parametrize("name", CELL_CONFIGS)
def test_what_the_family_says_a_step_reads_is_the_programs_own_tree(name):
    """``decode_hbm_pct`` and ``moe_hbm_pct`` divide the family's bytes by a
    measured time: a kernel the program gained or lost and the family did
    not makes a share nobody can read (``impossible_reading``). Cut to two
    layers (``kanana2-30b``: its dense layer and one routed), shapes only:
    with so many rows that every expert is touched and no cached context, a
    step reads every int8 kernel once and the float32 routers, and nothing
    else (the embedding is a gather; scales and norms are noise); one row's
    matmuls use every kernel but its own ``top_k`` of the experts."""
    from lambdipy_tpu.models import registry

    config = cell_config(name, num_hidden_layers=2)
    family = families.of(config)
    adapter = registry.get(config["model"]).build(
        dtype=config["precision"]["activations"],
        quant=config["precision"]["weights"], extra=family.dims_of(config))
    tree = jax.eval_shape(lambda: adapter.init_params(seed=0))
    read = used = 0.0
    share = config.get("num_experts_per_tok", 0) / config.get(
        "n_routed_experts", 1)
    for path, spec in jax.tree_util.tree_leaves_with_path(tree):
        path = "/".join(str(k.key) for k in path)
        size = int(np.prod(spec.shape))
        if spec.dtype == np.int8:
            read += size
            used += size * (share if "/experts_" in path else 1)
        elif path.endswith("moe/router"):
            read += 4 * size
            used += size
    assert read > 1e8
    assert family.decode_step_bytes(config, rows=1e9, context=0) == read
    assert family.decode_step_flops(config, rows=1, context=0) == \
        pytest.approx(2 * used, rel=1e-12)


def step_bound_tok_s(config: dict, rows: int, context: int = 300) -> float:
    """Tokens a second a v5e cannot exceed at ``rows`` live rows."""
    family = families.of(config)
    step_s = max(family.decode_step_bytes(config, rows=rows, context=context)
                 / V5E.hbm_bytes_s,
                 family.decode_step_flops(config, rows=rows, context=context)
                 / V5E.bf16_flops)
    return rows / step_s


@pytest.mark.parametrize("name", CELL_CONFIGS)
def test_a_decode_step_at_8_rows_is_bound_by_its_weight_bytes(name):
    """The saturated cells' premise, and why their share is ``*_hbm_pct``:
    at 8 rows the bytes take longer than the operations, and most of the
    bytes are weights (the ledger's ``decode_step_ms`` 11.075 / 15.367 /
    4.64 sit 20-45 % over these floors)."""
    config = cell_config(name)
    family = families.of(config)
    need = family.decode_step_bytes(config, rows=8, context=300)
    flops = family.decode_step_flops(config, rows=8, context=300)
    assert need / V5E.hbm_bytes_s > 5 * flops / V5E.bf16_flops
    assert family.decode_step_bytes(config, rows=8, context=0) > 0.8 * need


@pytest.mark.parametrize("name,gain", [("mistral7b", 7.0), ("deepseek7b", 6.0),
                                       ("kanana2-30b", 2.5)])
def test_rows_amortize_the_weight_read(name, gain):
    """Eight rows share one read of the weights: near-linear on the dense
    models (less where the cache is wide), and under 3 x on the routed one,
    whose 8 rows touch 41 experts a layer where one touches 6."""
    config = cell_config(name)
    ratio = step_bound_tok_s(config, 8) / step_bound_tok_s(config, 1)
    assert gain < ratio <= 8.0


@pytest.mark.parametrize("name,tokens", [("mistral7b", 1024),
                                         ("deepseek7b", 1024),
                                         ("kanana2-30b", 2048)])
def test_a_long_prefill_is_bound_by_its_operations(name, tokens):
    """The other side of ``WEIGHT_BOUND_ROWS``: 1024 tokens of one row keep
    the MXU longer than one read of every weight keeps the HBM, and a
    sixteenth of them do not. The routed model needs twice the tokens: a
    token computes with 6 of the 128 experts the call reads, so its 1k
    prefill still sits just under the ridge."""
    config = cell_config(name)
    family = families.of(config)

    def mxu_over_hbm(seq_len):
        flops = family.prefill_flops(config, rows=1, seq_len=seq_len)
        once = family.decode_step_bytes(config, rows=seq_len, context=0)
        return (flops / V5E.bf16_flops) / (once / V5E.hbm_bytes_s)

    assert mxu_over_hbm(tokens) > 1 > mxu_over_hbm(tokens // 16)
    if name == "kanana2-30b":
        assert 0.9 < mxu_over_hbm(1024) < 1


def test_no_share_is_computed_against_an_assumed_peak():
    """Peaks come from a table keyed by ``device_kind``, each with its
    source; a kind the table does not hold raises (this suite's own CPU
    included)."""
    assert V5E.source and (V5E.bf16_flops, V5E.hbm_bytes_s) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks_for(jax.devices()[0].device_kind)
