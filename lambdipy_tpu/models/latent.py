"""Attention kind ``latent``: multi-head latent attention as DeepSeek-V2/V3
publish it, and under it DeepSeek Sparse Attention as V3.2 does; a layer's
module.

The cache row of a token is ONE compressed latent of ``kv_lora_rank`` values,
after its norm (``ckv``), plus ONE rotary key of ``qk_rope`` values shared by
all heads, after rope (``kpe``); query/key heads are ``qk_nope + qk_rope``
wide, value heads ``v_head``. Prefill attends expanded keys and values; every
program that attends a cache absorbs the up-projection into the query and the
output instead of re-expanding the window. ``q_lora_rank`` > 0: the query is
compressed (``q_a_proj``, ``q_a_norm``, ``q_b_proj`` in place of ``q_proj``).

``index_topk`` > 0 (the model's one kind only, never a kind a layer): a
lightning indexer (``index_heads`` heads of ``index_head_dim``, fed by the
compressed query) scores every cached position against one indexer key a
token, the third cache leaf ``kidx``; a query attends only the ``index_topk``
positions of largest score (all of them while its context is shorter), chosen
exactly, ties to the lowest position: the key set is chosen by CONTENT, so no
holder that cuts a cache by position alone can take it (:func:`row_a_token`).

Scopes: ``qkv_proj``, ``kv_write``, ``mla_absorb``, ``attend``, ``dsa_index``,
``dsa_select``. The interface is ``llama.ATTN_KINDS``'. Keys: ``qk_nope``,
``qk_rope``, ``v_head``, ``kv_lora_rank``, ``rope_interleave``,
``q_lora_rank``, ``index_heads``, ``index_head_dim``, ``index_topk``,
``attn_output_gate`` (``LlamaConfig``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from lambdipy_tpu.models.llama import (Counters, QDense, QKernel, RMSNorm,
                                       _attend, _cache_write, _deinterleave,
                                       _dsa_select_mask, _head_group,
                                       _kv_store, block_method, output_gate,
                                       rope)

NAME = "latent"
PLACES = ("attn_kind", "layer_kinds")
# the static form of the block's call this kind takes (the sliding band;
# never under sparse attention)
FORMS = ("band",)


def validate(cfg) -> None:
    if min(cfg.qk_nope, cfg.qk_rope, cfg.v_head, cfg.kv_lora_rank) <= 0 \
            or cfg.qk_rope % 2:
        raise ValueError(
            "latent attention needs qk_nope, qk_rope (even), v_head "
            "and kv_lora_rank")
    if cfg.kv_quant is not None:
        raise NotImplementedError(
            f"kv_quant={cfg.kv_quant!r} cannot hold a latent cache: "
            "the int8 cache layout quantizes per-head K/V rows "
            "(_kv_store)")
    if cfg.attn_backend != "dense":
        raise NotImplementedError(
            f"attn_backend={cfg.attn_backend!r} attends per-head "
            "K/V; latent attention runs the dense backend")
    if cfg.index_topk and (
            cfg.layer_kinds or not cfg.q_lora_rank
            or min(cfg.index_heads, cfg.index_head_dim) <= 0
            or not cfg.qk_rope <= cfg.index_head_dim):
        raise ValueError(_INDEXER_NEEDS)


def absent(cfg) -> None:
    """A model without this kind that asks for the indexer all the same."""
    if cfg.index_topk:
        raise ValueError(_INDEXER_NEEDS)


_INDEXER_NEEDS = ("sparse attention (index_topk) needs the latent kind with "
                  "q_lora_rank, index_heads and index_head_dim >= qk_rope")


def cache_layout(cfg) -> dict:
    row = {"ckv": (1, cfg.kv_lora_rank), "kpe": (1, cfg.qk_rope)}
    if cfg.index_topk:
        # the indexer's key of the token, on the same position axis
        row["kidx"] = (1, cfg.index_head_dim)
    return row


def cache_positions(cfg, max_len: int) -> dict:
    return dict.fromkeys(cache_layout(cfg), max_len)


def cache_dtypes(cfg) -> dict:
    return dict.fromkeys(cache_layout(cfg), cfg.dtype)


def cache_slot(cfg, leaf: str, position):
    return position


def refusal(cfg, holder: str) -> str:
    if cfg.index_topk:
        return (f"{holder} attends every cached position a query may see "
                "and cannot take sparse attention, whose indexer chooses "
                f"the {cfg.index_topk} positions a query attends by content "
                "from a third cache leaf (kidx; PERF.md section 7)")
    return (f"{holder} holds per-head k/v cache leaves and cannot take the "
            f"latent cache layout {sorted(cache_layout(cfg))} (PERF.md "
            "section 7)")


def row_a_token(cfg) -> bool:
    """One row a token on one position axis, every one attended: but under
    sparse attention, whose selection only the whole-prompt prefill and the
    one-token step compute."""
    return not cfg.index_topk


def scales_softmax(cfg) -> bool:
    """Whether every path multiplies the softmax scale by YaRN's
    ``attn_scale_mult``: the sparse prefill and the absorbed sparse step
    do; elsewhere prefill and decode would disagree."""
    return bool(cfg.index_topk)


def prompt_block(cfg) -> tuple | None:
    """A sparse prefill's cost follows the prompt, not the bucket (it runs no
    turn of queries that holds only padding): whole blocks, a program each."""
    if cfg.index_topk:
        return DSA_PROMPT_BLOCK, DSA_PROMPT_BLOCK
    return None


def counters(cfg) -> tuple:
    """The ``handler.dsa`` block on ``/metrics`` of a sparse-attention model,
    only growing. Of its decode segments, from the segment programs' own masks,
    for the rows the collector books (layer 0 sows ``dsa_stats``, int32 ``[b,
    2]`` a step: every layer's counts are the same; a segment program returns
    their sum over its steps): ``row_steps``: booked rows x segment steps.
    ``keys_selected``: the cached positions those steps attended, summed:
    ``min(context, index_topk)`` a step exactly, so more or fewer shows as a
    difference. ``keys_visible``: the positions they were chosen from (the
    step's context). Of the prefills the engine dispatched, booked on the host
    from the iteration space the program's loops take their trip counts from
    (:func:`dsa_prefill_turns`; every row runs the turns the longest needs):
    ``prefill_pairs_run``: the query-key pairs their turns were given, a layer;
    ``prefill_pairs_causal``: the pairs causality needs, ``L (L + 1) / 2`` a
    prompt of ``L`` tokens. Their ratio is the prefill's overwork: padding and
    the overhang of a key block over the causal frontier."""
    if not cfg.index_topk:
        return ()

    def segment(sown, rows: int, steps: int) -> dict:
        keys = sown["dsa_stats"]
        return {"row_steps": rows * steps, "keys_selected": keys[:, 0].sum(),
                "keys_visible": keys[:, 1].sum()}

    def prefill(lengths, rows: int, s: int) -> dict:
        run = sum(live * min(s, DSA_QUERY_BLOCK) * t
                  for _, t, live in dsa_prefill_turns(max(lengths), s))
        return {"prefill_pairs_run": rows * run,
                "prefill_pairs_causal": sum(n * (n + 1) // 2
                                            for n in lengths)}

    return (Counters(
        "dsa", "a sparse-attention model",
        dict.fromkeys(("row_steps", "keys_selected", "keys_visible",
                       "prefill_pairs_run", "prefill_pairs_causal"), 0),
        {"dsa_stats": lambda b: jnp.zeros((b, 2), jnp.int32)}, segment,
        prefill),)


# A sparse (DeepSeek Sparse Attention) prefill runs one body a turn of
# DSA_QUERY_BLOCK queries inside each block of DSA_KEY_BLOCK keys: a turn
# scores, selects among and attends the keys up to its own key block's end,
# so a prompt of n key blocks is given n (n + 1) / 2 of them, not n x n; and
# a key block runs only the turns that BEGIN before the last real token of
# the longest row (a loop whose trip count is the rows' length operand's):
# the turns that hold nothing but a bucket's padding are not run, and their
# outputs, which no real position reads, are zeros (:func:`dsa_prefill_turns`,
# the iteration space, which the engine's counter reads too). Two constants
# on purpose. DSA_KEY_BLOCK, the loop's, is ``index_topk`` of the model
# served: a key block is the overhang of a turn's keys over its causal
# frontier, so the smaller the less is run that causality hides, and the
# first block (no more keys than the selection takes) runs neither indexer
# score nor selection. DSA_PROMPT_BLOCK, the bucket's, is what prompts past
# it are padded to whole multiples of (``LlamaConfig.prompt_bucket``): a
# program a bucket, so the coarser the fewer programs; what it pads, the
# loop does not run. Inside a turn the heads (the indexer's, then the
# attention's) go a group at a time, so that no float32 score tensor
# ``[heads of a group, queries, keys]`` is larger than DSA_SCORE_BYTES: what
# the v5e compiler keeps in its fast memory through every pass of the
# softmax (``tests/test_chip_compile.py``: 24 MiB it keeps, 32 it spills);
# whole, 128 heads x 128 x 12288 float32 scores are 0.8 GB a turn. 128
# queries a turn is what this tree's served runs were made at; alone on the
# chip (zero weights, no server) the 12288 prefill of a routed layer took
# 0.337 s at 128 queries a turn, 0.318 at 256 and 0.312 at 512 (PERF.md
# section 6, PR 35, which also says why 128 stayed; PR 43 for the blocks).
DSA_QUERY_BLOCK = 128
DSA_KEY_BLOCK = 2048
DSA_PROMPT_BLOCK = 4096


def dsa_prefill_turns(longest, s: int, clip=None):
    """The iteration space of a sparse prefill padded to ``s`` positions
    whose longest row has ``longest`` real tokens: for each key block
    ``(at, t, turns run)``: the queries ``at .. t - 1`` go, DSA_QUERY_BLOCK
    a turn, against the keys ``0 .. t - 1``, and only the turns that begin
    before position ``longest`` are run. ``longest`` is an int (the engine's
    counter) or a traced scalar with ``clip=jnp.clip`` (the program's trip
    counts): one arithmetic for both."""
    block = min(s, DSA_QUERY_BLOCK)
    clip = clip or (lambda x, lo, hi: max(lo, min(x, hi)))
    for at in range(0, s, DSA_KEY_BLOCK):
        t = min(at + DSA_KEY_BLOCK, s)
        yield at, t, clip(-(-(longest - at) // block), 0,
                          -(-(t - at) // block))


def _dsa_scores(q_idx, w_idx, k_idx):
    """The lightning indexer's score of every key for every query: ``q_idx``
    ``[b, s, index heads, d]``, ``w_idx`` ``[b, s, index heads]`` float32
    (already scaled), ``k_idx`` ``[b, t, d]`` -> ``[b, s, t]`` float32, ``sum_j
    w_j ReLU(q_j . k)``: the per-head products accumulate in float32 and the
    weighted sum over heads is a float32 multiply-reduce, a group of heads at a
    time where all at once would be a large tensor (:func:`_head_group`)."""
    b, s, heads, d = q_idx.shape

    def part(q_g, w_g):
        dots = jnp.einsum("bsjd,btd->bsjt", q_g, k_idx,
                          preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots) * w_g[..., None], axis=2)

    group = _head_group(heads, b * s * k_idx.shape[1])
    if group == heads:
        return part(q_idx, w_idx)
    q_g = jnp.moveaxis(q_idx.reshape(b, s, heads // group, group, d), 2, 0)
    w_g = jnp.moveaxis(w_idx.reshape(b, s, heads // group, group), 2, 0)
    total, _ = jax.lax.scan(
        lambda acc, qw: (acc + part(*qw), None),
        jnp.zeros((b, s, k_idx.shape[1]), jnp.float32), (q_g, w_g))
    return total


def _dsa_block_attend(q, k_groups, v_groups, seen, scale, dtype):
    """A block of queries under its selection: ``q`` ``[b, block, heads,
    d]``; ``k_groups`` / ``v_groups`` ``[groups, b, t or more, heads a
    group, d]`` (the expanded keys and values, regrouped once a size of
    group: a turn reads the first ``t`` where they lie); ``seen`` ``[b,
    block, t]`` bool. One float32 softmax a head over the seen keys, a
    group of heads a turn. ``[b, block, heads, v width]``."""
    groups, b, _, group, _ = k_groups.shape
    block, t = q.shape[1], seen.shape[-1]

    def turn(args):
        g, q_g = args
        # (one dynamic slice of the extent read: a slice of a whole group
        # would be a copy of it a turn)
        k_g, v_g = (jax.lax.dynamic_slice(
            x, (g, 0, 0, 0, 0), (1, b, t, group, x.shape[-1]))[0]
            for x in (k_groups, v_groups))
        logits = jnp.einsum("bqhd,bthd->bhqt", q_g, k_g,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(seen[:, None], logits, jnp.float32(-1e9))
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqt,bthd->bqhd", probs.astype(dtype), v_g)

    q_groups = jnp.moveaxis(q.reshape(b, block, groups, group, q.shape[-1]),
                            2, 0)
    if groups == 1:
        return turn((0, q_groups[0]))
    out = jax.lax.map(turn, (jnp.arange(groups), q_groups))
    return jnp.moveaxis(out, 0, 2).reshape(b, block, groups * group, -1)


@block_method
def _latent_attend(block, x, positions, mask, cache, lengths=None,
                   band: int = 0):
    """Multi-head latent attention: returns the heads' outputs ``[b, s, heads,
    v_head]`` and the new cache entry, the latent ``ckv`` (after its norm) and
    the one rotary key ``kpe`` (after rope) of each token. Without a cache
    (prefill) keys and values are expanded through ``kv_b_proj`` and attended
    like any multi-head layer. With a cache the same function is computed
    absorbed: the key half of ``kv_b_proj`` goes into the query (per head,
    qk_nope -> kv_lora_rank), scores and the weighted sum run over the cached
    latents themselves, and the value half maps the sum to v_head; the window
    is never re-expanded (8 rows x 400 tokens x 32 heads x 256 through a
    512-wide matmul would be 0.4 TFLOP a step).

    Under ``index_topk`` the indexer (:func:`_indexer`) scores every visible
    position for every query; prefill then attends in blocks of queries and
    never builds a ``[heads, s, s]`` score (:func:`_sparse_prefill_attend`); a
    one-token step scores the window's cached ``kidx`` rows, finds the
    selection as a mask (:func:`_sparse_select`) and attends the window's rows
    under it, absorbed as ever."""
    cfg = block.cfg
    heads, dn, dr = cfg.heads, cfg.qk_nope, cfg.qk_rope
    dv, rank = cfg.v_head, cfg.kv_lora_rank
    b, s, _ = x.shape
    if cfg.index_topk and (band or (cache is not None and s != 1)):
        raise NotImplementedError(
            "sparse attention is computed by the whole-prompt prefill "
            f"and the one-token step: a chunk of {s} positions against "
            "a cache (a prefix continued, a draft verified) or a "
            "sliding band is not written (PERF.md section 7)")
    with jax.named_scope("qkv_proj"):
        h = RMSNorm(cfg.norm_eps, name="attn_norm")(x)
        if cfg.q_lora_rank:
            # the compressed query, which the indexer shares
            c_q = RMSNorm(cfg.norm_eps, name="q_a_norm")(
                QDense(cfg.q_lora_rank, cfg.quant, cfg.dtype,
                       name="q_a_proj")(h))
            q = QDense(heads * (dn + dr), cfg.quant, cfg.dtype,
                       name="q_b_proj")(c_q)
        else:
            q = QDense(heads * (dn + dr), cfg.quant, cfg.dtype,
                       name="q_proj")(h)
        kva = QDense(rank + dr, cfg.quant, cfg.dtype, name="kv_a_proj")(h)
        ckv = RMSNorm(cfg.norm_eps, name="kv_a_norm")(kva[..., :rank])
        q = q.reshape(b, s, heads, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        k_pe = kva[..., rank:].reshape(b, s, 1, dr)
        if cfg.rope_interleave:
            q_pe, k_pe = _deinterleave(q_pe), _deinterleave(k_pe)
        q_pe, k_pe = rope(q_pe, k_pe, positions, cfg.rope_theta,
                          cfg.rope_scaling)
        ckv = ckv.reshape(b, s, 1, rank)
    w, w_scale = QKernel(rank, heads * (dn + dv), cfg.quant, cfg.dtype,
                         name="kv_b_proj")()
    if cfg.index_topk:
        with jax.named_scope("dsa_index"):
            q_idx, k_idx, w_idx = _indexer(block, h, c_q, positions)

    if cache is None:
        with jax.named_scope("qkv_proj"):
            kv = jnp.matmul(ckv[:, :, 0], w.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
            if w_scale is not None:
                kv = kv * w_scale
            kv = kv.astype(cfg.dtype).reshape(b, s, heads, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_pe, (b, s, heads, dr))], axis=-1)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
        if cfg.index_topk:
            out = _sparse_prefill_attend(block, q, k, kv[..., dn:], q_idx,
                                              k_idx[:, :, 0], w_idx, mask,
                                              lengths)
            return out, {"ckv": ckv, "kpe": k_pe, "kidx": k_idx}
        with jax.named_scope("attend"):
            causal = jnp.tril(jnp.ones((s, s), dtype=jnp.bool_))
            out = _attend(q, k, kv[..., dn:],
                          mask[:, None, :] & causal[None, :, :])
        return output_gate(cfg, out, h), {"ckv": ckv, "kpe": k_pe}

    with jax.named_scope("kv_write"):
        new_cache, valid, t = _cache_write(
            cache, _kv_store(cfg, ckv, k_pe, layer=block.layer),
            cache["index"], b, s, band)
    if cfg.index_topk:
        new_cache["kidx"], valid = _sparse_select(block, 
            cache, q_idx, k_idx, w_idx, jnp.broadcast_to(valid, (b, s, t)))
    w = w.reshape(rank, heads, dn + dv)
    with jax.named_scope("mla_absorb"):
        # q' = q_nope W_k^T per head; a per-output-channel scale sits
        # on the contracted axis here, so it multiplies the query
        if w_scale is not None:
            q_nope = (q_nope.astype(jnp.float32)
                      * w_scale.reshape(heads, dn + dv)[:, :dn]
                      ).astype(cfg.dtype)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope,
                           w[..., :dn].astype(cfg.dtype))
    with jax.named_scope("attend"):
        lat, kpe = new_cache["ckv"][:, :, 0], new_cache["kpe"][:, :, 0]
        logits = (jnp.einsum("bshr,btr->bhst", q_lat, lat,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshd,btd->bhst", q_pe, kpe,
                               preferred_element_type=jnp.float32))
        logits = logits / jnp.sqrt(dn + dr).astype(jnp.float32)
        if cfg.attn_scale_mult != 1.0:
            logits = logits * jnp.float32(cfg.attn_scale_mult)
        logits = jnp.where(
            jnp.broadcast_to(valid, (b, s, t))[:, None, :, :], logits,
            jnp.float32(-1e9))
        probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
        ctx = jnp.einsum("bhst,btr->bshr", probs, lat)
    with jax.named_scope("mla_absorb"):
        out = jnp.einsum("bshr,rhd->bshd", ctx,
                         w[..., dn:].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        if w_scale is not None:
            out = out * w_scale.reshape(heads, dn + dv)[:, dn:]
    return output_gate(cfg, out.astype(cfg.dtype), h), new_cache


attend = _latent_attend


@block_method
def _indexer(block, h, c_q, positions):
    """The lightning indexer's projections (under ``dsa_index``), from the
    normed input ``h`` and the compressed query ``c_q``: ``index_heads``
    queries ``q_j = W_qb,j c_q`` ``[b, s, heads, d]``, ONE key a token ``k =
    LayerNorm(W_k h)`` ``[b, s, 1, d]`` (gain and bias), the first ``qk_rope``
    dims of both roped with the attention's frequencies as HALVES (never
    interleaved), and a float32 weight a head ``w = W_w h x index_heads^-1/2 x
    index_head_dim^-1/2`` ``[b, s, heads]``."""
    cfg = block.cfg
    n_idx, d_idx, dr = cfg.index_heads, cfg.index_head_dim, cfg.qk_rope
    b, s, _ = h.shape
    q_idx = QDense(n_idx * d_idx, cfg.quant, cfg.dtype,
                   name="index_wq_b")(c_q).reshape(b, s, n_idx, d_idx)
    k32 = QDense(d_idx, cfg.quant, cfg.dtype, name="index_wk")(h).astype(
        jnp.float32)
    k32 = k32 - jnp.mean(k32, axis=-1, keepdims=True)
    k32 = k32 * jax.lax.rsqrt(
        jnp.mean(k32 * k32, axis=-1, keepdims=True) + 1e-6)
    k_idx = (k32 * block.param("index_k_norm_scale", nn.initializers.ones,
                              (d_idx,), jnp.float32)
             + block.param("index_k_norm_bias", nn.initializers.zeros,
                          (d_idx,), jnp.float32)
             ).astype(cfg.dtype).reshape(b, s, 1, d_idx)
    q_rot, k_rot = rope(q_idx[..., :dr], k_idx[..., :dr], positions,
                        cfg.rope_theta, cfg.rope_scaling)
    q_idx = jnp.concatenate([q_rot, q_idx[..., dr:]], axis=-1)
    k_idx = jnp.concatenate([k_rot, k_idx[..., dr:]], axis=-1)
    # float32 all the way, like the router: the weights decide a top-k
    # with near-ties
    w_idx = jnp.matmul(
        h.astype(jnp.float32),
        block.param("index_weights_proj", nn.initializers.lecun_normal(),
                   (h.shape[-1], n_idx), jnp.float32),
        precision=jax.lax.Precision.HIGHEST) \
        * jnp.float32((n_idx * d_idx) ** -0.5)
    return q_idx, k_idx, w_idx


@block_method
def _sparse_prefill_attend(block, q, k, v, q_idx, k_idx, w_idx, mask,
                           lengths=None):
    """A sparse prefill's attention over the expanded keys ``k`` and values
    ``v`` ``[b, s, heads, ..]``: ONE body runs a turn of ``DSA_QUERY_BLOCK``
    queries inside each block of ``DSA_KEY_BLOCK`` keys: it scores the keys up
    to the key block's end (``q_idx``, ``w_idx``; ``k_idx`` ``[b, s, d]``),
    selects (:func:`_dsa_select_mask`) and attends under the selection's mask,
    a group of heads at a time (:func:`_dsa_block_attend`). ``[b, s, heads, v
    width]``.

    What is run (:func:`dsa_prefill_turns`): in a key block, the turns that
    begin before the longest row's last real token (``lengths`` ``[b]``, each
    right-padded row's; without it, a row ends where its ``mask`` does), each
    against the keys up to its own key block's end. What is not: a turn that
    holds only padding, hence a key block past the prompt, and the keys past a
    turn's key block (causally hidden from every query of it). A turn not run
    leaves zeros, at positions no real position reads (a real query sees ``mask
    & causal``, the logits are read at ``length - 1``, the cache's index is the
    length)."""
    cfg = block.cfg
    heads, topk = cfg.heads, cfg.index_topk
    b, s = q.shape[:2]
    scale = jnp.float32(cfg.attn_scale_mult / math.sqrt(q.shape[-1]))
    block = min(s, DSA_QUERY_BLOCK)
    if lengths is None:
        lengths = jnp.max(jnp.where(mask, jnp.arange(1, s + 1), 0), axis=-1)
    plan = [(at, t, live, _head_group(heads, b * block * t))
            for at, t, live in dsa_prefill_turns(jnp.max(lengths), s,
                                                 jnp.clip)]
    # the keys and values regrouped ONCE a size of head group, to the
    # farthest key any block of that size reads: a key block takes a
    # prefix of it
    reach = {group: t for _, t, _, group in plan}
    grouped = {group: tuple(jnp.moveaxis(x[:, :t].reshape(
        b, t, heads // group, group, x.shape[-1]), 2, 0) for x in (k, v))
        for group, t in reach.items()}
    outs = []
    for at, t, live, group in plan:
        # the queries at .. t - 1 against the keys 0 .. t - 1
        width = -(-(t - at) // block) * block
        k_t, v_t = grouped[group]
        q_t, qi_t, wi_t = (
            jnp.pad(x[:, at:t], ((0, 0), (0, width - (t - at)))
                    + ((0, 0),) * (x.ndim - 2))
            for x in (q, q_idx, w_idx))

        def turn(i, out, at=at, t=t, k_t=k_t, v_t=v_t, q_t=q_t,
                 qi_t=qi_t, wi_t=wi_t):
            q_i, qi_i, wi_i = (jax.lax.dynamic_slice_in_dim(
                x, i * block, block, 1) for x in (q_t, qi_t, wi_t))
            pos = at + i * block + jnp.arange(block)
            seen = mask[:, None, :t] & (jnp.arange(t)[None, :]
                                        <= pos[:, None])[None]
            if t > topk:
                with jax.named_scope("dsa_index"):
                    score = _dsa_scores(qi_i, wi_i, k_idx[:, :t])
                with jax.named_scope("dsa_select"):
                    seen = _dsa_select_mask(score, seen, topk)
            with jax.named_scope("attend"):
                return jax.lax.dynamic_update_slice_in_dim(
                    out, _dsa_block_attend(q_i, k_t, v_t, seen, scale,
                                           cfg.dtype), i * block, 1)

        out = jax.lax.fori_loop(
            0, live, turn,
            jnp.zeros((b, width, heads, v.shape[-1]), cfg.dtype))
        outs.append(out[:, :t - at])
    return jnp.concatenate(outs, axis=1)


@block_method
def _sparse_select(block, cache, q_idx, k_idx, w_idx, valid):
    """A sparse one-token step's selection: writes the step's indexer key into
    the ``kidx`` leaf and scores every cached position of the window (under
    ``dsa_index``), then finds, of the ``valid`` ``[b, 1, t]`` positions, the
    ``index_topk`` of largest score as a MASK (under ``dsa_select``; a window
    no longer than that keeps them all). Returns ``(the new kidx leaf, the mask
    [b, 1, t])``. The window's rows are then attended where they lie: at 4 rows
    of 16384 a gather of the picked rows behind ``jax.lax.top_k`` took 4.2 ms a
    step of 7 layers (3.5 of it the gather, 0.6 the sort) where the mask and
    the masked read take 1.6 (PERF.md section 6, PR 35); a window many times
    ``index_topk`` long would want the gather back."""
    cfg = block.cfg
    b, _, t = valid.shape
    with jax.named_scope("dsa_index"):
        kidx = _cache_write(cache, {"kidx": k_idx.astype(cfg.dtype)},
                            cache["index"], b, 1)[0]["kidx"]
        score = _dsa_scores(q_idx, w_idx, kidx[:, :, 0])
    picked = valid
    if t > cfg.index_topk:
        with jax.named_scope("dsa_select"):
            picked = _dsa_select_mask(score, valid, cfg.index_topk)
    if block.layer == 0:
        # the keys a row's step attended and those it chose from: every
        # layer's counts are the same (counters; /metrics handler.dsa)
        block.sow("dsa_stats", "keys", jnp.stack(
            [picked.sum(-1)[:, 0], valid.sum(-1)[:, 0]],
            axis=-1).astype(jnp.int32))
    return kidx, picked
