"""The serving programs of the llama block keep their text (ROADMAP D11).

jax hashes a program's computation into the compile-cache key, so a change
of text under an unchanged shape key is a cold first run for every bundle
of the accepted cells (13-16 minutes each on the chip), and an AOT
executable of the old text would be loaded for the new
(``LlamaServer._AOT_GEN`` salts that by hand). The hashes below are of the
lowered StableHLO (no source locations in it) of four programs of four
builds, taken on the parent of PR 27 (commit 635a34e): a PR that adds a
layer kind beside the llama block leaves them as they are. A PR that means
to change a program's text bumps ``_AOT_GEN`` and takes the hashes anew in
the same commit; a PR that moved one without meaning to has found out here
and not on the chip.

Generation g5 (PR 30) moved TWO of the twenty-four: ``deepseek7b``'s
full-window and window-bucketed segment programs, which write a
segment-long tail now (``llama.segment_keeps_tail``: one query a KV head).
Every group prefill and fused generate, ``mistral7b``'s four programs and
the four toy builds (all grouped-query, 2 KV heads of 4) are the hashes of
commit 635a34e still.

The toy builds above say that the block's text is kept; the second test
says it at the accepted cells' OWN shape keys (the configuration files'
widths, 8 slots, their engine window, int8 kernels, 16-step segments), on
abstract parameters (``jax.eval_shape``: nothing of 7 B is allocated), so
that a change which only shows at those widths (a row-count threshold, a
window bucket) is caught here too. Same parent, same rule.

The ``evabyte`` model's programs (PR 33: ``attn_kind`` "eva") are held the
same way at ``evabyte6b.long-decode``'s own shape key (4 slots of 8192): the
solo prefills of the smallest and largest prompt bucket its mix reaches
(2048; 6144, three whole windows), the full-window
segment and the 4096 bucket's. They came with g5 and move with the block's
eva path alone.

Generation g6 (PR 34) moved TWO of those four and nothing else: the eva
model's full-window and 4096-bucket segment programs keep ring and
summaries read-only inside their scan (a ring tail and a summary tail,
``eva._eva_tail_attend`` since PR 44). The two solo prefills and every
llama, ``mistral7b`` and ``deepseek7b`` hash are what they were.

Generation g7 (PR 38) moved NONE of them: it changed the set of NAMES (a
window-bucketed segment has one now, and a server snapshots a program where
it first compiles), not a program's text.

A routed-FFN model's programs have no golden text: PR 28 gave its segment
programs a second counter, and on a TPU backend their small calls take a
Pallas kernel (``ops/grouped_experts.py``), which changes their cache keys
on its own. What is held here is the other side of that dispatch: on this
backend the toy twin's programs contain no kernel call at any call size.
Since PR 35, which put query compression, sparse attention, grouped routing
and a chip's share of the experts into the same latent and routed paths,
``kanana2-30b``'s four programs are held as THIS backend lowers them, at the
cell's own shape key, like the two llama cells'.

PR 39 (an attention kind a LAYER: ``LlamaConfig.layer_kinds``, the
constructors and the window buckets read a layer's own leaves, three muP
scalars in block and model) moved NONE of them either: a model without
``layer_kinds`` takes every path it took, and the scalars are python
comparisons against 1.0 that add nothing to a program.

PR 41 (``latent`` a kind a LAYER may have beside the new ``kda``; the cache
constructors, ``_kv_store`` and the holders ask the layer's kind, the latent
path gains an output gate behind ``attn_output_gate``) moved NONE of them
either, and holds two more models at their cells' own shape keys, as its
parent lowers them: ``deepseek-v32-exp`` and ``minicpm-sala``
(``KINDS_GOLDEN``), beside its own ``ling3-flash``.

Generation g8 (PR 42: where Mosaic compiles, the decode step of a ``kda``
and of a ``linear`` layer is the kernel of ``ops/state_step.py``, which
steps the state leaf in place; the ``linear`` state leaf turned from
``[slots, 1, heads, d x d]`` to ``[slots, 1, heads x d, d]`` so that the
kernel's view of it is free) moved ``minicpm-sala``'s three programs, taken
anew here, and NO other: the five configurations with neither kind keep
their parents' hashes, which is the test that nobody else runs the changed
code, and ``ling3-flash``'s three are PR 41's too, because what is hashed is
what THIS backend lowers (``kda.step`` as it was, now a call of the kernel's
reference): its segment on a TPU holds one Mosaic call a kda layer and
another text, which ``tests/test_chip_compile.py`` compiles.

Generation g9 (PR 43: a sparse prefill runs, a key block of 2048, only the
turns of queries that begin before the longest row's last token:
``latent._sparse_prefill_attend`` since PR 44) moved ONE hash of all those held
here: ``deepseek-v32-exp``'s solo prefill. Its two segments, and every
program of the seven other configurations, keep their parents' hashes:
nobody else runs the changed function, and the ``latent`` kind's dense
branch (``kanana2-30b``, ``ling3-flash``) lowers as it did."""

import hashlib
import json
from pathlib import Path

import jax
import pytest

from benchmark import families
from lambdipy_tpu.models import registry
from lambdipy_tpu.models.llama import LlamaServer

HF_TOY = dict(vocab_size=512, hidden=128, layers=2, heads=4, kv_heads=2,
              mlp=256, max_len=256)
# build -> (group prefill, full-window segment, window-bucketed segment,
# fused generate)
GOLDEN = {
    ("llama-tiny", "int8", None): (
        "ac8e71e0cf41", "4cb3e40c0117", "7b678b5bb05f", "5cbabb75c32d"),
    ("llama-tiny", None, "int8"): (
        "f8498cb116a5", "ccd1be5cec2c", "9df739b88baf", "6297c1b7e0a4"),
    ("llama-moe-tiny", "int8", None): (
        "58279adf20e1", "0910c464005e", "275a2fc53809", "7ae7322e8c17"),
    ("llama-hf", "int8", None): (
        "f09c98151802", "db88543dfb76", "ffdda39ac408", "dc8dc8bf0699"),
}


def text_hash(fn, *args) -> str:
    return hashlib.sha256(fn.lower(*args).as_text().encode()).hexdigest()[:12]


@pytest.mark.parametrize("model,quant,kv_quant", list(GOLDEN))
def test_a_llama_program_lowers_to_the_text_its_generation_was_taken_at(
        model, quant, kv_quant):
    assert LlamaServer._AOT_GEN == "g9", "new generation: take the hashes anew"
    extra = dict(HF_TOY) if model == "llama-hf" else {}
    if kv_quant:
        extra["kv_quant"] = kv_quant
    adapter = registry.get(model).build(dtype="bfloat16", quant=quant,
                                        extra=extra)
    params = adapter.init_params(seed=0)
    server = adapter.make_server(params)
    key = ("stream", 4, 32, 128, 16)
    prefill, seg = server._stream_fns(*key[1:])
    pre_ops, seg_ops = server._aot_examples(key)
    got = (text_hash(prefill, params, *pre_ops),
           text_hash(seg, params, *seg_ops),
           text_hash(server._windowed_seg_fn(4, 128, 64, 16), params, *seg_ops),
           text_hash(server._compiled(2, 16, 16), params,
                     *server._aot_examples((2, 16, 16))[0]))
    assert got == GOLDEN[model, quant, kv_quant]


# configuration -> (a window bucket its cells decode in; hashes of: the
# first-burst prefill of 8 x 64, a group prefill of 2 joiners x 128, the
# full-window segment, the window-bucketed segment)
CELL_GOLDEN = {
    "mistral7b": (512, (
        "68b128eeb1bf", "dbf45417a475", "ca9524f17c20", "d592697fcc05")),
    "deepseek7b": (256, (
        "c9cc88b7ee32", "9eb36fcacfd8", "27bc15b2c4bf", "5e84d28f90b7")),
    # the latent kind beside its sparse form (PR 35), taken on PR 35's
    # parent (commit 4a60241), as THIS backend lowers them: the pure-jax
    # forms of the routed sum (on a TPU the small calls hold a Pallas kernel
    # and another text; tests/test_chip_compile.py compiles that side)
    "kanana2-30b": (512, (
        "6ec1c0eee436", "e79f79d82119", "722cd8459188", "7720ae423518")),
}


@pytest.mark.parametrize("name", list(CELL_GOLDEN))
def test_an_accepted_cells_programs_keep_their_text_at_the_cells_shapes(name):
    assert LlamaServer._AOT_GEN == "g9", "new generation: take the hashes anew"
    window, golden = CELL_GOLDEN[name]
    config = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                         / f"{name}.json").read_text())
    adapter = registry.get(config["model"]).build(
        dtype=config["precision"]["activations"],
        quant=config["precision"]["weights"],
        extra=families.of(config).dims_of(config))
    params = jax.eval_shape(lambda: adapter.init_params(seed=0))
    server = adapter.make_server(params)
    slots, cache_len = 8, config["engine_window"]
    key = ("stream", slots, 64, cache_len, 16)
    prefill, seg = server._stream_fns(*key[1:])
    pre_ops, seg_ops = jax.eval_shape(lambda: server._aot_examples(key))
    join_key = ("stream", 2, 128, cache_len, 16)
    join_ops = jax.eval_shape(lambda: server._aot_examples(join_key))[0]
    got = (text_hash(prefill, params, *pre_ops),
           text_hash(server._stream_fns(*join_key[1:])[0], params, *join_ops),
           text_hash(seg, params, *seg_ops),
           text_hash(server._windowed_seg_fn(slots, cache_len, window, 16),
                     params, *seg_ops))
    assert got == golden


# configuration -> (a window bucket its cell decodes in, a solo prompt bucket;
# hashes of: that solo prefill, the full-window segment, the window-bucketed
# segment) at the cell's own slots and engine window. ``deepseek-v32-exp``
# (the sparse latent kind, a chip's share of the experts) and ``minicpm-sala``
# (kinds a layer) as PR 41's PARENT lowers them (commit 3756d35): PR 41 made
# ``latent`` a kind a layer may have, gave ``_kv_store`` the layer and the
# latent path an output gate that these models do not switch on, and moved
# none of their text. ``ling3-flash`` (kda and latent layers, PR 41) came
# with that PR and moves with ``models/kda.py`` and the block's latent path.
# ``minicpm-sala`` as PR 42 lowers it: the linear layers' state leaf turned
# (g8; on PR 41: "83660f79b20c", "28531c5eb28d", "9e6b4880e9e1").
# ``deepseek-v32-exp``'s solo prefill as PR 43 lowers it (g9; on PR 42:
# "7f9f60f12f08"): its two segments are PR 41's parent's still
KINDS_GOLDEN = {
    "deepseek-v32-exp": (8192, 4096, (
        "3cf05d8576e9", "fe1dc5bf5b0e", "c8e68c3abb9a")),
    "minicpm-sala": (16384, 4096, (
        "21c48de865f3", "e3b7d197f9ee", "fd1b8ea4e01f")),
    "ling3-flash": (2048, 1024, (
        "deb0bf1dc4e8", "65c090cd74bc", "117d8445beac")),
}


@pytest.mark.parametrize("name", list(KINDS_GOLDEN))
def test_a_model_of_newer_kinds_keeps_its_text_at_its_cells_shapes(name):
    assert LlamaServer._AOT_GEN == "g9", "new generation: take the hashes anew"
    window, bucket, golden = KINDS_GOLDEN[name]
    config = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                         / f"{name}.json").read_text())
    adapter = registry.get(config["model"]).build(
        dtype=config["precision"]["activations"],
        quant=config["precision"]["weights"],
        extra=families.of(config).dims_of(config))
    params = jax.eval_shape(lambda: adapter.init_params(seed=0))
    server = adapter.make_server(params)
    slots = config["recipe_extra"]["batch_max"]
    cache_len = config["engine_window"]
    key = ("stream", slots, 16, cache_len, 16)
    _, seg = server._stream_fns(*key[1:])
    _, seg_ops = jax.eval_shape(lambda: server._aot_examples(key))
    solo = ("stream", 1, bucket, cache_len, 16)
    pre_ops = jax.eval_shape(lambda: server._aot_examples(solo))[0]
    got = (text_hash(server._stream_fns(*solo[1:])[0], params, *pre_ops),
           text_hash(seg, params, *seg_ops),
           text_hash(server._windowed_seg_fn(slots, cache_len, window, 16),
                     params, *seg_ops))
    assert got == golden


# (solo prefill of the 2048 bucket, of the 6144 bucket (three whole windows:
# ``LlamaConfig.prompt_bucket``), the full-window segment, the 4096 bucket's
# segment) of ``evabyte6b`` at 4 slots of 8192
EVA_GOLDEN = ("14d510489925", "601575a18f6e", "b64dddbb4f8b", "ee9e9fd9fc61")


def eva_hashes() -> tuple:
    config = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                         / "evabyte6b.json").read_text())
    adapter = registry.get(config["model"]).build(
        dtype=config["precision"]["activations"],
        quant=config["precision"]["weights"],
        extra=families.of(config).dims_of(config))
    params = jax.eval_shape(lambda: adapter.init_params(seed=0))
    server = adapter.make_server(params)
    slots = config["recipe_extra"]["batch_max"]
    cache_len = config["engine_window"]
    key = ("stream", slots, 16, cache_len, 16)
    _, seg = server._stream_fns(*key[1:])
    _, seg_ops = jax.eval_shape(lambda: server._aot_examples(key))
    got = []
    for bucket in (2048, 6144):
        solo = ("stream", 1, bucket, cache_len, 16)
        pre_ops = jax.eval_shape(lambda: server._aot_examples(solo))[0]
        got.append(text_hash(server._stream_fns(*solo[1:])[0], params,
                             *pre_ops))
    got += [text_hash(seg, params, *seg_ops),
            text_hash(server._windowed_seg_fn(slots, cache_len, 4096, 16),
                      params, *seg_ops)]
    return tuple(got)


def test_the_eva_programs_keep_their_text_at_the_cells_shapes():
    assert LlamaServer._AOT_GEN == "g9", "new generation: take the hashes anew"
    assert eva_hashes() == EVA_GOLDEN


def test_a_routed_models_programs_hold_no_kernel_call_on_this_backend():
    """``RoutedMLP`` takes ``picked_experts`` for calls of at most
    ``STREAM_ROWS`` tokens where Mosaic compiles; here (the CPU) the decode
    segment of 4 slots x 1 token and the group prefill of 4 x 32 tokens of
    the toy twin lower without a Pallas call: the pure-jax forms, as on the
    parent. (The kernel's side of the dispatch, under the ``experts`` scope:
    ``tests/test_chip_compile.py``; its bound: ``tests/test_grouped_experts
    .py``.)"""
    from lambdipy_tpu import ops

    assert not ops.kernels_compile_here()
    config = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                         / "rehearsal-mla-moe.json").read_text())
    adapter = registry.get("deepseek-v3").build(
        dtype="float32", quant="int8",
        extra=families.of(config).dims_of(config))
    params = jax.eval_shape(lambda: adapter.init_params(seed=0))
    server = adapter.make_server(params)
    key = ("stream", 4, 32, 128, 16)
    prefill, seg = server._stream_fns(*key[1:])
    pre_ops, seg_ops = jax.eval_shape(lambda: server._aot_examples(key))
    for fn, operands in ((prefill, pre_ops), (seg, seg_ops),
                         (server._windowed_seg_fn(4, 128, 64, 16), seg_ops)):
        text = fn.lower(params, *operands).as_text()
        assert "tensor<16x64x24xi8>" in text    # an expert stack: the sum is here
        assert "custom_call" not in text and "pallas" not in text
