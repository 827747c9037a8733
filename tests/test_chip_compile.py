"""Every Pallas kernel in ``ops/`` compiled by the TPU's own compiler, for
a v5e that is described and not attached, at Llama-3-8B head shapes.

Interpret-mode tests cannot see what Mosaic refuses (block shapes off the
(8, 128) tiling, too much VMEM): the paged decode kernel passed them from
PR 8 to PR 20 and had never compiled. Nothing runs here — a compile that
passes says nothing about results or times — so each case asserts only
that the program lowers to a ``tpu_custom_call`` and the compiler accepts
it. ~1 s each; skipped where the topology cannot be described.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Llama-3-8B attention heads; a decode batch of 8 over a 2048 window
B, H, KVH, D, T = 8, 32, 8, 128, 2048
HIDDEN, MLP = 4096, 14336


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, no described chip
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compilation_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: the next run would warn and
    compile again. Keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile_for(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Mosaic kernel in the compiled program"


def _kv(dtype, lead):
    """K/V (+ int8 scales) operand shapes behind a leading shape."""
    kv = [((*lead, KVH, D), dtype)] * 2
    if dtype == jnp.int8:
        kv += [((*lead, KVH, 1), jnp.float32)] * 2
    return kv


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8kv"])
def test_blocked_decode_attention_compiles(v5e, kv_dtype):
    from lambdipy_tpu.ops.decode_attention import blocked_decode_attention

    def fn(q, lens, k, v, *scales):
        ks, vs = scales or (None, None)
        return blocked_decode_attention(q, k, v, lens, k_scale=ks, v_scale=vs)

    _compile_for(v5e, fn, ((B, 1, H, D), jnp.bfloat16), ((B,), jnp.int32),
                 *_kv(kv_dtype, (B, T)))


@pytest.mark.parametrize("page", [16, 128])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8kv"])
def test_paged_decode_attention_compiles(v5e, kv_dtype, page):
    """At the arena's real layout ``[P, page, kvh, d]`` (models/llama.py
    init_page_arena), for the smallest and largest page widths."""
    from lambdipy_tpu.ops.decode_attention import (
        paged_blocked_decode_attention)

    n_pages, nb = B * T // page + 1, T // page

    def fn(q, tables, lens, k, v, *scales):
        ks, vs = scales or (None, None)
        return paged_blocked_decode_attention(
            q, k, v, tables, lens, k_scale_pages=ks, v_scale_pages=vs)

    _compile_for(v5e, fn, ((B, 1, H, D), jnp.bfloat16),
                 ((B, nb), jnp.int32), ((B,), jnp.int32),
                 *_kv(kv_dtype, (n_pages, page)))


def test_flash_attention_causal_compiles(v5e):
    from lambdipy_tpu.ops.attention import flash_attention

    _compile_for(v5e, lambda q, k, v: flash_attention(q, k, v, causal=True),
                 ((1, T, H, D), jnp.bfloat16), ((1, T, KVH, D), jnp.bfloat16),
                 ((1, T, KVH, D), jnp.bfloat16))


# kanana2-30b's routed FFN (benchmark/configs/kanana2-30b.json)
EXPERTS, TOP_K, EXPERT_HIDDEN, EXPERT_MLP = 128, 6, 2048, 768


@pytest.mark.parametrize("tokens", [8, 32, 256])
def test_picked_experts_compiles(v5e, tokens):
    """A decode step's 8 rows, and calls up to ``STREAM_ROWS``, at the
    cell's widths: an expert's three int8 blocks are 4.7 MB, twice for the
    pipeline, and the kernel asks for the fast memory it needs."""
    from lambdipy_tpu.ops.grouped_experts import picked_experts

    def fn(x, chosen, w, *stacks):
        return picked_experts(x, chosen, w, None,
                              list(zip(stacks[::2], stacks[1::2])),
                              jnp.bfloat16)

    def stack(fan_in, fan_out):
        return [((EXPERTS, fan_in, fan_out), jnp.int8),
                ((EXPERTS, 1, fan_out), jnp.float32)]

    _compile_for(v5e, fn, ((tokens, EXPERT_HIDDEN), jnp.float32),
                 ((tokens, TOP_K), jnp.int32), ((tokens, TOP_K), jnp.float32),
                 *stack(EXPERT_HIDDEN, EXPERT_MLP),
                 *stack(EXPERT_HIDDEN, EXPERT_MLP),
                 *stack(EXPERT_MLP, EXPERT_HIDDEN))


@pytest.mark.parametrize("rows,kind", [(16, "kda"), (8, "lightning")])
def test_stepped_in_place_compiles(v5e, rows, kind):
    """The recurrent-state step at the two cells' shapes, 32 heads of 128 x
    128: ``ling3-flash``'s 16 rows (the delta rule, a channel's decay) and
    ``minicpm-sala``'s 8 (neither). A row's states are 2 MB each way, twice
    for the pipeline; the state operand is the result's buffer."""
    from lambdipy_tpu.ops.state_step import stepped_in_place

    heads, d = 32, 128
    vec = ((rows, heads, d), jnp.float32)
    shapes = [((rows, 1, heads * d, d), jnp.float32), vec, vec, vec] + (
        [vec, ((rows, heads), jnp.float32)] if kind == "kda"
        else [((heads,), jnp.float32)])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in shapes]
    text = jax.jit(stepped_in_place, donate_argnums=0).lower(
        *args).compile().as_text()
    call = next(ln for ln in text.splitlines() if " custom-call(" in ln
                and "tpu_custom_call" in ln)
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(\d, \{\}\)\}",
                     call), call[:400]


def _decode_segment_text(chip, *, steps, window=512, cache_len=T, layers=1,
                         rows=B, **widths):
    """Optimized HLO of the decode segment (`jit_seg`, the window-bucketed
    variant the continuous engine dispatches) compiled for ``chip``: int8
    weights, bf16 activations, ``rows`` rows with a per-row ``index``; shapes
    only, nothing is placed or run."""
    from lambdipy_tpu.models.llama import (LlamaConfig, LlamaModel,
                                           LlamaServer, init_decode_cache)

    cfg = LlamaConfig(layers=layers, dtype=jnp.bfloat16, quant="int8",
                      **widths)
    model = LlamaModel(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32)))
    cache = jax.eval_shape(lambda: init_decode_cache(cfg, rows, cache_len))
    for entry in cache:
        entry["index"] = jax.ShapeDtypeStruct((rows,), jnp.int32)
    row = {name: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
           for name, (shape, dtype) in {
               "f32": ((rows,), jnp.float32), "i32": ((rows,), jnp.int32),
               "bool": ((rows,), jnp.bool_),
               "keys": ((rows, 2), jnp.uint32)}.items()}
    seg = LlamaServer(model, None)._windowed_seg_fn(rows, cache_len, window,
                                                    steps)
    assert seg.__name__ == "seg"
    return seg.lower(
        params, row["f32"], row["i32"], row["f32"],      # knobs
        row["i32"], row["f32"], on_chip(cache), row["i32"], row["bool"],
        row["keys"], row["i32"]).compile().as_text()


MISTRAL_7B = dict(vocab_size=32768, hidden=HIDDEN, heads=H, kv_heads=KVH,
                  mlp=MLP, rope_theta=1e6, norm_eps=1e-5, max_len=8192)
DEEPSEEK_7B = dict(vocab_size=102400, hidden=HIDDEN, heads=H, kv_heads=H,
                   mlp=11008, rope_theta=1e4, norm_eps=1e-6, max_len=4096)


def test_scope_names_survive_the_tpu_compiler(v5e):
    """The decode segment at Mistral-7B widths and one layer: after XLA's
    fusion every scope of ``models/llama.py`` is still the op_name of some
    device operation — what ``benchmark/scopes.py`` splits a trace by
    (PERF.md section 3). ~20 s."""
    text = _decode_segment_text(v5e, steps=16, **MISTRAL_7B)
    found = set()
    for op_name in re.findall(r' (?:fusion|copy|dynamic-update-slice)\('
                              r'[^\n]*op_name="([^"]*)"', text):
        found.update(op_name.split("/"))
    assert {"embed", "qkv_proj", "kv_write", "attend", "o_proj", "mlp",
            "lm_head", "sample", "kv_window"} <= found


KANANA2 = dict(vocab_size=128256, hidden=EXPERT_HIDDEN, heads=32, kv_heads=32,
               mlp=6144, rope_theta=1e6, norm_eps=1e-6, max_len=8192,
               attn_kind="latent", qk_nope=128, qk_rope=64, v_head=128,
               kv_lora_rank=512, rope_interleave=True, ffn_kind="routed",
               first_dense_layers=1, moe_experts=EXPERTS, moe_top_k=TOP_K,
               moe_intermediate=EXPERT_MLP, n_shared_experts=2,
               routed_scaling_factor=2.448, scoring_func="sigmoid")


def test_a_routed_decode_step_takes_the_kernel_under_its_scope(v5e,
                                                               monkeypatch):
    """The decode segment at kanana2-30b's widths, one dense and one routed
    layer, lowered as a TPU backend lowers it (this process's backend is
    the CPU, so the test says what ``kernels_compile_here`` would): the
    routed sum of 8 rows is ONE Mosaic call whose op_name lies under
    ``experts`` (what ``decode_moe_ms`` and ``moe_hbm_pct`` gather a
    trace's operations by), beside the operations of ``router`` and
    ``shared_expert``. ~30 s."""
    from lambdipy_tpu.models import moe

    assert B <= moe.STREAM_ROWS
    monkeypatch.setattr(moe, "kernels_compile_here", lambda: True)
    text = _decode_segment_text(v5e, steps=2, layers=2, **KANANA2)
    calls = re.findall(r' custom-call\([^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    assert len(calls) == 1, calls
    assert "experts" in calls[0].split("/")
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(op_name.split("/"))
    assert {"router", "experts", "shared_expert", "mla_absorb"} <= found


# HLO opcodes that name or view an array and write none
NO_WRITE = {"parameter", "get-tuple-element", "tuple", "bitcast"}


def dequantized_weights_in_loops(text: str, widths: dict) -> list:
    """``(name, dtype, shape, op_name)`` of every operation inside a
    ``while`` body of the optimized HLO ``text`` that PRODUCES an array
    with the shape of one of the model's kernels (either way round) in
    another type than the kernel's own int8: a dequantized copy of a
    weight, written once per decode step. The int8 arrays of that shape
    in a loop body are the compiler's prefetches of the kernels
    themselves."""
    hidden, mlp = widths["hidden"], widths["mlp"]
    kv = hidden // widths["heads"] * widths["kv_heads"]
    kernels = {(hidden, hidden), (hidden, kv), (hidden, mlp),
               (hidden, widths["vocab_size"])}
    kernels |= {(b, a) for a, b in kernels}
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    assert bodies, "no while loop in the program"
    found, inside = [], False
    for line in text.splitlines():
        if not line.startswith(" "):  # a computation's header, or its "}"
            head = re.match(r"%?([\w.\-]+) \(", line)
            inside = bool(head) and head.group(1) in bodies
            continue
        op = inside and re.match(
            r"\s+(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(",
            line)
        if not op or op.group(2) == "s8" or op.group(4) in NO_WRITE:
            continue
        shape = tuple(int(d) for d in op.group(3).split(","))
        if shape in kernels:
            name = re.search(r'op_name="([^"]*)"', line)
            found.append((op.group(1), op.group(2), shape,
                          name.group(1) if name else ""))
    return found


@pytest.mark.parametrize("widths", [MISTRAL_7B, DEEPSEEK_7B],
                         ids=["mistral7b", "deepseek7b"])
def test_decode_loop_writes_no_dequantized_weight(v5e, widths):
    """A 2-layer, 4-step int8 decode scan at the benchmark's two widths:
    no operation of the loop body writes a dequantized copy of a kernel.
    With the scale applied to the weight before the dot (PR 24 and
    earlier) XLA fused the MLP's convert-multiply into its dots but split
    the attention projections' off: six ``qkv_proj/*/mul`` fusions, each
    writing a whole bf16 kernel per step (PERF.md section 6, PR 25).
    ~25 s each."""
    text = _decode_segment_text(v5e, steps=4, layers=2, **widths)
    assert dequantized_weights_in_loops(text, widths) == []


# what may have a cache leaf's shape inside the decode loop: the carried
# tuple's members, and the compiler's read prefetch of a leaf into the
# fast memory (slices, joined by a ConcatBitcast custom call)
CACHE_READS = NO_WRITE | {"slice-start", "slice-done"}


def cache_writes_in_loops(text: str, leaf_shapes: set) -> list:
    """``(name, opcode, op_name)`` of every operation inside a ``while``
    body of the optimized HLO ``text`` whose result has a cache leaf's
    WHOLE shape and is not a read of it: a ``fusion`` that scatters into a
    copy, a ``scatter`` or ``dynamic-update-slice`` in place, a
    ``copy-done`` that takes the copy home."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    assert bodies, "no while loop in the program"
    found, inside = [], False
    for line in text.splitlines():
        if not line.startswith(" "):
            head = re.match(r"%?([\w.\-]+) \(", line)
            inside = bool(head) and head.group(1) in bodies
            continue
        op = inside and re.match(
            r"\s+(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(", line)
        if not op or op.group(3) in CACHE_READS:
            continue
        if op.group(3) == "custom-call" and "ConcatBitcast" in line:
            continue
        if tuple(int(d) for d in op.group(2).split(",")) in leaf_shapes:
            name = re.search(r'op_name="([^"]*)"', line)
            found.append((op.group(1), op.group(3),
                          name.group(1) if name else ""))
    return found


def state_kernel_calls(text: str, leaf: tuple, scope: str) -> list:
    """The lines of ``text`` that are a Mosaic call of ``ops/state_step.py``
    under ``scope``, each asserted to step a state leaf of shape ``leaf`` in
    place: the leaf is an operand in HBM and the result's buffer."""
    calls = [ln for ln in text.splitlines() if " custom-call(" in ln
             and "tpu_custom_call" in ln and "stepped_in_place" in ln]
    dims = ",".join(str(n) for n in leaf)
    for line in calls:
        assert f"/{scope}/stepped_in_place" in line, line[:600]
        result = line.split(" custom-call(")[0]
        assert re.search(rf"f32\[{dims}\]\{{3,2,1,0:T\(8,128\)\}}\)$",
                         result), result       # home in HBM, not S(1)
        aliased = re.search(
            r"output_to_operand_aliasing=\{\{1\}: \((\d+), \{\}\)\}", line)
        constraints = re.search(
            r"operand_layout_constraints=\{(.*?)\}, output_to_operand",
            line).group(1).split("}, ")
        assert aliased and constraints[int(aliased.group(1))].startswith(
            f"f32[{dims}]"), line[:900]
    return calls


@pytest.mark.parametrize("widths,layers,window,cache_len", [
    (DEEPSEEK_7B, 30, 512, 512), (MISTRAL_7B, 32, 512, 2048)],
    ids=["deepseek7b", "mistral7b"])
def test_a_decode_segment_writes_back_no_whole_cache_leaf(
        v5e, widths, layers, window, cache_len):
    """The engine's plain segment at the two cells' own widths, depths and
    windows, a 4-step scan, and the two halves of the rule
    ``llama.segment_keeps_tail`` (PERF.md section 6, PR 30). ~30 s each.

    DeepSeek (one query a KV head, 60 leaves of ``[8, 512, 32, 128]``)
    keeps a tail: inside the loop NOTHING produces an array of a cache
    leaf's whole shape but the loop's own tuple and the read prefetch; the
    new positions go to the segment's tail and one scatter a leaf merges
    it AFTER the loop. On the parent (PR 29, a scatter into the cache
    every step) this trips on 52 ``fusion`` of ``kv_write/scatter`` whose
    result lies in ``S(1)``, each followed by a ``copy-done`` of the
    whole 33.5 MB leaf back to HBM (1745 MB a step), and 8 scatter
    fusions in place in HBM. At 6 layers the parent reads 8 + 4 fusions
    and 4 ``copy-done``, and the change still evicts ONE leaf it had
    parked in the fast memory, which at the cell's depth it has no room
    to: hence full depth. (The compiler also hands the loop its tails
    UNINITIALISED, ``AllocateBuffer``, because it sees every position
    written: ``_attend`` reads none before its step.)

    Mistral (four queries a KV head, 64 leaves of ``[8, 512, 8, 128]``)
    keeps the per-step write, because there the compiler does it where
    the cache lies: 64 scatter fusions a step, at most 3 of them through
    ``S(1)`` with a ``copy-done`` home (27 MB a step; none at the 1024
    and 2048 windows). With a tail this program has no such operation
    either, and on the chip it is 1 % faster at this window and 6 %
    slower at the full 2048 one, where the compiler prefetches read-only
    leaves in place of weights."""
    from lambdipy_tpu.models.llama import LlamaConfig, segment_keeps_tail

    text = _decode_segment_text(v5e, steps=4, layers=layers, window=window,
                                cache_len=cache_len, **widths)
    kv_heads, d = widths["kv_heads"], widths["hidden"] // widths["heads"]
    writes = cache_writes_in_loops(
        text, {(B, window, kv_heads, d), (B, cache_len, kv_heads, d)})
    if segment_keeps_tail(LlamaConfig(layers=layers, **widths)):
        assert writes == []
        # the merge is there, once a leaf, outside the loop
        assert len(re.findall(
            r' fusion\([^\n]*op_name="jit\(seg\)/kv_write/scatter"',
            text)) == 2 * layers
    else:
        by_opcode = {}
        for _, opcode, op_name in writes:
            by_opcode.setdefault(opcode, []).append(op_name)
        assert set(by_opcode) <= {"fusion", "copy-done"}
        assert len(by_opcode["fusion"]) == 2 * layers
        assert all(name.endswith("kv_write/scatter")
                   for name in by_opcode["fusion"])
        assert len(by_opcode.get("copy-done", [])) <= 4


# evabyte6b's widths (benchmark/configs/evabyte6b.json): 4 slots of 8192
EVABYTE = dict(vocab_size=320, hidden=HIDDEN, heads=H, kv_heads=H, mlp=11008,
               rope_theta=1e5, norm_eps=1e-5, max_len=16384, attn_kind="eva",
               window_size=2048, chunk_size=16, pred_heads=8,
               norm_unit_offset=True)


def test_an_eva_segment_compiles_and_copies_no_ring(v5e):
    """The engine's window-bucketed segment at ``evabyte6b.long-decode``'s
    own shape key (4 slots, cache 8192, the 4096 bucket, 16 layers, 16
    steps: the cell's program itself; at 4 steps the compiler parks one
    summary leaf in the fast memory and evicts it inside the loop, 8 MB).
    ~20 s.

    It compiles for the chip, and every scope ``benchmark/families/
    evabyte.py`` gathers a trace's operations by is still the op_name of
    some operation: the pooling and the summary writes carry
    ``eva_summarize``, beside the llama block's scopes.

    The segment keeps ring and summaries READ-ONLY inside its scan (PR 34,
    ``models/eva.py _eva_tail_attend``): nothing in the loop produces an
    array of a cache leaf's whole shape but the loop's own tuple and the
    read prefetch, and after the loop ONE scatter a leaf merges the tails,
    four a layer: two ring leaves under ``kv_write``, two summary leaves
    under ``eva_summarize``. On the parent (PR 33, a scatter into ring and
    summaries every step) this trips on 64 scatter fusions a step and the
    ``copy-done`` of the 14 ring leaves of 32 the compiler updated in the
    fast memory and took home whole, 67 MB each, 0.94 GB a step.

    Neither does the program copy a ring into another layout, in the loop
    or at its head, which it did twice on the way here: with a chunk's rows
    fetched by ONE gather (PR 33: 32 copies of 67 MB a step; a slice a row
    does not), and with those slices taken from the frozen ring INSIDE the
    scan, where they are pooled beside the tail's rows and hand the ring
    the tail's layout (batch next to the lanes): 32 transposing copies at
    the head of every segment. ``eva.tail_init`` fetches the open chunk
    once, before the scan, a slice a row.

    The no-write assertion also holds two forms of the merge and of that
    fetch apart that read shorter and compile worse: scattering the WHOLE
    ring tail (rows of other positions dropped by an out-of-range slot),
    or fetching the open chunk as one gather over the ring seen as whole
    chunks, makes the compiler park a ring in the fast memory and evict it
    inside the loop, 67 MB written home a step (PR 34). The merge gathers
    the segment's own rows from the (small) tail first."""
    from benchmark.families import evabyte

    layers, window, slots = 16, 4096, 4
    text = _decode_segment_text(v5e, steps=16, layers=layers, window=window,
                                cache_len=8192, rows=slots, **EVABYTE)
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(op_name.split("/"))
    assert {"embed", "qkv_proj", "kv_write", "attend", "eva_summarize",
            "o_proj", "mlp", "lm_head", "sample", "kv_window"} <= found
    assert set(evabyte.SCOPES) <= found
    leaves = {(slots, 2048, H, D), (slots, window // 16, H, D),
              (slots, 8192 // 16, H, D)}
    assert cache_writes_in_loops(text, leaves) == []
    for scope, shape in (("kv_write", "2048"), ("eva_summarize", "512")):
        assert len(re.findall(
            r' = bf16\[%d,%s,%d,%d\]\S* fusion\([^\n]*'
            r'op_name="jit\(seg\)/%s/scatter"' % (slots, shape, H, D, scope),
            text)) == 2 * layers, scope
    # every ring keeps the layout it arrives in, positions major
    assert not re.findall(r"bf16\[%d,2048,%d,%d\]\{3,0,2,1" % (slots, H, D),
                          text)


@pytest.mark.parametrize("block, in_hbm", [(128, False), (512, True)])
def test_an_eva_prefill_keeps_a_turns_scores_in_the_fast_memory(
        v5e, block, in_hbm, monkeypatch):
    """The solo prefill of ``evabyte6b.long-decode``'s 6144 bucket (three
    whole windows), one layer. ~12 s a case.

    What ``EVA_QUERY_BLOCK`` rests on: at 128 queries a turn every float32
    score tensor the loop's fusions hand on (32 heads x 128 x 2048 keys, x
    384 summaries) lies in the chip's fast memory (``S(1)`` in the layout),
    so the softmax's passes cost no HBM traffic; at 512 the 2048-key scores
    are HBM buffers, written twice and read three times a turn."""
    from lambdipy_tpu.models import eva, llama

    monkeypatch.setattr(eva, "EVA_QUERY_BLOCK", block)
    cfg = llama.LlamaConfig(layers=1, dtype=jnp.bfloat16, quant="int8",
                            **EVABYTE)
    model = llama.LlamaModel(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
            tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32)))
    server = llama.LlamaServer(model, None)
    key = ("stream", 1, cfg.prompt_bucket(5000, 16), 8192, 16)
    assert key[2] == 6144
    operands = on_chip(jax.eval_shape(lambda: server._aot_examples(key))[0])
    text = server._stream_fns(*key[1:])[0].lower(
        params, *operands).compile().as_text()
    handed_on = re.findall(
        r"= \(?((?:f32\[(?:1,)?32,%d,\d+\]\{[^}]*\}(?:, )?)+)\)? fusion\("
        % block, text)
    scores = [layout for shapes in handed_on
              for layout in re.findall(r"f32\[[\d,]+\]\{[^}]*\}", shapes)]
    assert scores, "no fusion hands on a score tensor: the program changed"
    assert any("S(1)" not in layout for layout in scores) == in_hbm, scores


# deepseek-v32-exp's widths (benchmark/configs/deepseek-v32-exp.json): 4
# slots of 16384, this chip's 16 of 256 experts and its slice of the head
DEEPSEEK_V32 = dict(
    vocab_size=16160, hidden=7168, heads=128, kv_heads=128, mlp=18432,
    rope_theta=1e4, norm_eps=1e-6, max_len=32768, attn_kind="latent",
    qk_nope=128, qk_rope=64, v_head=128, kv_lora_rank=512,
    rope_interleave=True, q_lora_rank=1536, index_heads=64,
    index_head_dim=128, index_topk=2048, ffn_kind="routed",
    first_dense_layers=1, moe_experts=256, moe_top_k=8, moe_intermediate=2048,
    n_shared_experts=1, routed_scaling_factor=2.5, scoring_func="sigmoid",
    moe_n_group=8, moe_topk_group=4, moe_experts_held=16, moe_first_expert=0,
    rope_scaling=("yarn", 40.0, 4096.0, 32.0, 1.0, 1.0))


def test_a_sparse_segment_compiles_and_what_it_does_with_its_three_leaves(
        v5e, monkeypatch):
    """The engine's segment at ``deepseek-v32-exp.long-context-decode``'s own
    shape key (4 slots of 16384, the full window, 1 + 6 layers, 16 steps),
    lowered as a TPU backend lowers it. ~40 s.

    It compiles for the chip, and every scope ``benchmark/families/
    deepseek_v32.py`` gathers a trace's operations by is the op_name of some
    operation: ``dsa_index`` and ``dsa_select`` beside the latent and routed
    kinds' (no ``kv_window``: the cell decodes in the full window).

    What is recorded of the compiler, not asked of it. The selection is a
    mask found by an exact bit-by-bit threshold: NO sort lies under
    ``dsa_select`` (``jax.lax.top_k`` of the ``[4, 16384]`` scores lowers to
    one full stable sort a layer on this backend, and the gather of the
    picked rows behind it was the slower form on the chip: PERF.md section
    6, PR 35). The three-leaf per-step write stays in place: nothing in the
    loop produces an array of a cache leaf's whole shape but the loop's own
    tuple and READS, some of them whole-leaf prefetches into the fast
    memory ahead of the masked read (``copy-done`` into ``S(1)``: at most
    one a leaf). So the latent kind keeps its per-step write under sparse
    attention too (``llama.segment_keeps_tail``).

    The routed sum holds no Mosaic call: an expert of 3 x 7168 x 2048 int8
    is too wide for ``picked_experts`` to hold whole, ``RoutedMLP`` sees
    that from the shapes and runs the grouped loop, whose trip count is the
    picks'."""
    from benchmark.families import deepseek_v32
    from lambdipy_tpu.models import llama, moe

    monkeypatch.setattr(moe, "kernels_compile_here", lambda: True)
    layers, slots, window = 7, 4, 16384
    text = _decode_segment_text(v5e, steps=16, layers=layers, window=window,
                                cache_len=window, rows=slots, **DEEPSEEK_V32)
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(op_name.split("/"))
    assert set(deepseek_v32.SCOPES) - {"kv_window"} <= found
    sorts = re.findall(r' sort\([^\n]*op_name="([^"]*)"', text)
    assert sorts and not any("dsa_select" in name for name in sorts)
    assert "tpu_custom_call" not in text
    leaves = {(slots, window, 1, width) for width in (512, 64, 128)}
    writes = cache_writes_in_loops(text, leaves)
    assert {opcode for _, opcode, _ in writes} <= {"copy-done"}
    assert len(writes) <= 3 * layers
    assert not llama.segment_keeps_tail(llama.LlamaConfig(
        layers=layers, **DEEPSEEK_V32))


def test_a_sparse_prefill_keeps_a_turns_scores_in_the_fast_memory(v5e,
                                                                  monkeypatch):
    """The solo prefill of the cell's 12288 bucket (six key blocks of 2048),
    one layer. ~45 s.

    No operation of the program produces a ``[heads, s, s]`` score: the
    largest float32 tensor a fusion hands on is a GROUP of heads' scores of
    one block of 128 queries (``llama.DSA_SCORE_BYTES``: 4 heads at 12288
    keys, 24 MiB; 16 heads at the first key block's 2048), and every one of
    them lies in the chip's fast memory (``S(1)`` in its layout), as do the
    index scores a turn selects by; whole, a turn's 128 heads x 128 x 12288
    float32 scores are 0.8 GB.

    The turns of a key block are a loop whose trip count is no constant of
    the program: its condition compares the counter with an operand of the
    loop (what the rows' length operand makes it), one such loop a key
    block, where the loops inside a turn (the head groups, the threshold's
    32 bits) compare with constants."""
    from lambdipy_tpu.models import latent, llama, moe

    monkeypatch.setattr(moe, "kernels_compile_here", lambda: True)
    cfg = llama.LlamaConfig(layers=1, dtype=jnp.bfloat16, quant="int8",
                            **DEEPSEEK_V32)
    model = llama.LlamaModel(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
            tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32)))
    server = llama.LlamaServer(model, None)
    key = ("stream", 1, cfg.prompt_bucket(9000, 16), 16384, 16)
    assert key[2] == 12288
    assert latent.DSA_QUERY_BLOCK == 128
    assert llama._head_group(128, 128 * 12288) == 4
    operands = on_chip(jax.eval_shape(lambda: server._aot_examples(key))[0])
    text = server._stream_fns(*key[1:])[0].lower(
        params, *operands).compile().as_text()
    assert not re.search(r"f32\[(?:1,)?128,\d{4,},\d{4,}\]", text)
    # (a fusion that only bitcasts hands on a view, not a tensor)
    handed_on = re.findall(
        r"= \(?((?:\w+\[[\d,]+\]\{[^}]*\}(?:, )?)+)\)? fusion\("
        r"(?![^\n]*calls=%bitcast_fusion)", text)
    extents = "|".join(str(t) for t in range(2048, 12288 + 1, 2048))
    scores = [layout for shapes in handed_on for layout in re.findall(
        rf"f32\[(?:\d+,)+(?:{extents})\]\{{[^}}]*\}}", shapes)
        if layout.count(",") >= 3]
    assert any(s.startswith("f32[4,128,12288]") for s in scores), scores[:5]
    assert any(s.startswith("f32[16,128,2048]") for s in scores), scores[:5]
    assert max(math.prod(map(int, re.findall(r"\d+", s.split("]")[0])[1:]))
               for s in scores) == 4 * 128 * 12288
    assert all("S(1)" in layout for layout in scores), scores
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(op_name.split("/"))
    assert {"dsa_index", "dsa_select", "attend", "qkv_proj"} <= found
    conditions = dict(re.findall(r"^%([\w.]+) \([^\n]*\{\n(.*?)^\}", text,
                                 re.S | re.M))
    loops = re.findall(r' while\([^\n]*condition=%([\w.]+), body=[^\n]*'
                       r'op_name="([^"]*)"', text)
    turns = [conditions[c] for c, name in loops
             if name.endswith("_sparse_prefill_attend/while")]
    inner = [conditions[c] for c, name in loops
             if "_sparse_prefill_attend/while/body/" in name]
    assert len(turns) == 12288 // latent.DSA_KEY_BLOCK == 6
    assert not any("constant(" in cond for cond in turns)
    assert inner and all("constant(" in cond for cond in inner)


# minicpm-sala's widths (benchmark/configs/minicpm-sala.json): 8 slots of
# 32768, published layers 9-24: 4 block-sparse among 12 linear
MINICPM_SALA = dict(
    vocab_size=73448, hidden=4096, heads=32, kv_heads=2, mlp=16384,
    rope_theta=1e4, norm_eps=1e-6, max_len=65536, qk_norm=True,
    attn_output_gate=True, lin_heads=32, lin_head_dim=128, embed_scale=12.0,
    residual_scale=1.4 / 32 ** 0.5, logit_divisor=16.0)
SALA_KINDS = ("sparse_kv",) + ("linear",) * 6 + ("sparse_kv",) * 2 \
    + ("linear",) * 4 + ("sparse_kv",) + ("linear",) * 2


def test_a_sala_segment_compiles_and_what_it_does_with_its_leaves(
        v5e, monkeypatch):
    """The engine's segment at ``minicpm-sala.long-document``'s own shape key
    (8 slots of 32768, the full window, 16 layers of kinds a layer, 16
    steps), lowered as a TPU backend lowers it. ~40 s.

    It compiles for the chip, and every scope ``benchmark/families/
    minicpm_sala.py`` gathers a trace's operations by is the op_name of some
    operation (no ``kv_window``: the cell decodes in the full window; no
    ``lin_scan``: the prefill's). No sort lies under ``sala_select``: the
    blocks are picked by the exact threshold, as a mask.

    What is recorded of the compiler, not asked of it (ISSUE 39: "whether a
    linear state write inside the scan costs a copy is read from the
    compiled text"). A block-sparse layer's rows are written IN PLACE: the
    only operations of the loop with a ``k`` / ``v`` leaf's whole shape are
    the two scatter fusions a layer under ``kv_write``, and one under
    ``sala_compress`` with the compressed keys' shape, each aliasing its
    operand; nothing copies a K/V leaf home. A linear layer's state (8 x
    2.1 MB a layer) is a carry that is rewritten whole every step by
    nature. With the step XLA's (PR 39 to PR 41) the compiler moved it
    between HBM and the fast memory (``copy-done``), up to three such moves
    a layer a step beside a ``reshape`` of the leaf ``[slots, 1, heads, d x
    d]``, and with the leaf as it is now it writes ``v`` broadcast to the
    state's shape besides (PERF.md section 6, PR 42). With the kernel of
    ``ops/state_step.py`` (PR 42: what a TPU backend lowers) ONE Mosaic call
    a linear layer under ``lin_state`` steps the leaf in place, its state
    operand aliased to its result, and nothing else in the loop has the
    leaf's shape in either view: no ``copy-done``, no ``reshape``."""
    from benchmark.families import minicpm_sala
    from lambdipy_tpu.models import linear_attn, llama

    # (this process's backend is the CPU: say what a TPU's would)
    monkeypatch.setattr(linear_attn, "kernels_compile_here", lambda: True)

    layers, slots, window = 16, 8, 32768
    text = _decode_segment_text(v5e, steps=16, layers=layers, window=window,
                                cache_len=window, rows=slots,
                                layer_kinds=SALA_KINDS, **MINICPM_SALA)
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(op_name.split("/"))
    assert set(minicpm_sala.SCOPES) - {"kv_window", "lin_scan"} <= found
    sorts = re.findall(r' sort\([^\n]*op_name="([^"]*)"', text)
    assert not any("sala_select" in name for name in sorts)
    rows = cache_writes_in_loops(text, {(slots, window, 2, 128)})
    assert sorted(op for _, op, _ in rows) == ["fusion"] * 8
    assert all(name.endswith("kv_write/scatter") for _, _, name in rows)
    # (the compressed keys, 1 MB a leaf, also travel to the fast memory and
    # back: a copy-done a layer)
    compressed = [w for w in cache_writes_in_loops(
        text, {(slots, window // 16, 2, 128)}) if w[1] != "copy-done"]
    assert [(op, name.split("/")[-2]) for _, op, name in compressed] == [
        ("fusion", "sala_compress")] * 4
    for name, _, _ in rows + compressed:
        line = next(ln for ln in text.splitlines()
                    if f"%{name} = " in ln)
        assert '"aliasing_operands"' in line, name
    leaf = (slots, 1, 32 * 128, 128)
    states = cache_writes_in_loops(text, {leaf, (slots, 32, 128, 128)})
    assert len(state_kernel_calls(text, leaf, "lin_state")) == 12
    assert not states, states
    assert not llama.segment_keeps_tail(llama.LlamaConfig(
        layers=layers, layer_kinds=SALA_KINDS, **MINICPM_SALA))


# ling3-flash's widths (benchmark/configs/ling3-flash.json): 16 slots of 8192,
# a routed kda layer and the routed latent layer of one period
LING3_FLASH = dict(
    vocab_size=39296, hidden=2560, heads=32, kv_heads=32, mlp=6144,
    rope_theta=6e6, norm_eps=1e-6, max_len=16384, kda_heads=32,
    kda_head_dim=128, qk_nope=128, qk_rope=64, v_head=128, kv_lora_rank=512,
    rope_interleave=True, attn_output_gate=True, attn_gate_headwise=True,
    ffn_kind="routed", moe_experts=512, moe_experts_held=128, moe_n_group=8,
    moe_topk_group=4, moe_top_k=8, moe_intermediate=768, n_shared_experts=1,
    routed_scaling_factor=2.5, scoring_func="sigmoid")


def test_a_kda_segment_compiles_with_its_two_leaves_and_the_latent_rows(
        v5e, monkeypatch):
    """The engine's segment at ``ling3-flash.reasoning-decode``'s own shape
    key (16 slots of 8192, the 2048 window bucket, 16 steps), one routed kda
    layer and the routed latent layer, lowered as a TPU backend lowers it.
    ~25 s.

    It compiles for the chip (the expert kernel at 128 held experts of 2560
    x 768 and 16 rows among it), and every scope ``benchmark/families/
    bailing_hybrid.py`` gathers a trace's operations by is the op_name of
    some operation (no ``kda_scan``: the prefill's). The window bucket cuts
    the latent layer's ``ckv`` and ``kpe`` alone: no operation of the loop
    has a kda leaf's shape at another length than its own."""
    from benchmark.families import bailing_hybrid
    from lambdipy_tpu.models import kda, llama, moe

    # (this process's backend is the CPU: say what a TPU's would)
    monkeypatch.setattr(moe, "kernels_compile_here", lambda: True)
    monkeypatch.setattr(kda, "kernels_compile_here", lambda: True)
    slots, window = 16, 8192
    text = _decode_segment_text(v5e, steps=16, layers=2, window=2048,
                                cache_len=window, rows=slots,
                                layer_kinds=("kda", "latent"), **LING3_FLASH)
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(op_name.split("/"))
    assert set(bailing_hybrid.SCOPES) - {"kda_scan"} <= found
    assert "picked_experts" in text             # the experts' kernel
    shapes = {tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"\w+\[([\d,]+)\]", text)}
    assert (slots, 2048, 1, 512) in shapes and (slots, window, 1, 512) in shapes
    assert (slots, 1, 4096, 128) in shapes and (slots, 3, 3, 4096) in shapes
    assert not any(s[0] == slots and s[2:] in ((4096, 128), (3, 4096))
                   and s[1] not in (1, 3) for s in shapes if len(s) == 4)
    # the step views the state leaf [slots, 1, heads x d_k, d_v] as [slots,
    # heads, d_k, d_v] for free: with d_k x d_v flattened into the last axis
    # the compiler re-tiled every state on its way in and out of every step
    # (a reshape of 33.5 MB a layer each way: kda_state_hbm_pct 21, my chip
    # run, PR 41)
    assert not re.findall(r' reshape\([^\n]*op_name="[^"]*kda_state', text)
    # ONE Mosaic call a kda layer under kda_state steps the leaf in place
    # (ops/state_step.py, PR 42): its state operand is its result's buffer,
    # and no other operation of the loop has the leaf's shape in either
    # view: no second read, no reshape, no copy-done of it
    leaf = (slots, 1, 4096, 128)
    assert len(state_kernel_calls(text, leaf, "kda_state")) == 1
    assert not cache_writes_in_loops(text, {leaf, (slots, 32, 128, 128)})
    assert not llama.segment_keeps_tail(llama.LlamaConfig(
        layers=2, layer_kinds=("kda", "latent"), **LING3_FLASH))


def test_neither_new_prefill_builds_a_heads_s_s_score(v5e):
    """The solo prefill of the cell's 20480 bucket (five key blocks of
    4096), one block-sparse and one linear layer. ~40 s.

    No operation of the program produces a ``[heads, s, s]`` tensor of any
    type: the largest float32 score the block-sparse layer's loop hands on
    is 2 heads' of one block of 128 queries against the 20480 keys (21 MB:
    ``llama.DSA_SCORE_BYTES``), beside the 16 heads of a KV group against
    the 1280 compressed keys; the linear layer's is a chunk's ``[32, 256,
    256]``. (The one float32 array with two long axes is the SwiGLU's
    ``[20480, 16384]``.)"""
    from lambdipy_tpu.models import llama, sparse_kv

    cfg = llama.LlamaConfig(layers=2, dtype=jnp.bfloat16, quant="int8",
                            layer_kinds=("sparse_kv", "linear"),
                            **MINICPM_SALA)
    model = llama.LlamaModel(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
            tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32)))
    server = llama.LlamaServer(model, None)
    key = ("stream", 1, cfg.prompt_bucket(17000, 16), 32768, 16)
    assert key[2] == 20480 and sparse_kv.SPARSE_QUERY_BLOCK == 128
    assert llama._head_group(16, 128 * 20480) == 2
    operands = on_chip(jax.eval_shape(lambda: server._aot_examples(key))[0])
    text = server._stream_fns(*key[1:])[0].lower(
        params, *operands).compile().as_text()
    shapes = {tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"\w+\[([\d,]+)\]", text)}
    long = [s for s in shapes if sum(n >= 4096 for n in s) >= 2]
    assert all(set(s) - {1} <= {20480, 16384, 4096, 73448} and len(
        [n for n in s if n > 1]) == 2 for s in long), long
    assert (1, 1, 2, 128, 20480) in shapes and (1, 2, 16, 128, 1280) in shapes
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(op_name.split("/"))
    assert {"sala_compress", "sala_select", "attend", "lin_scan",
            "qkv_proj"} <= found
