"""Attention kind ``sparse_kv``: grouped-query K/V attention whose key set is
chosen by BLOCKS (InfLLM-V2, arXiv:2509.24663, as MiniCPM4 / MiniCPM-SALA
configure it), a layer's module.

The cache entry of a layer has leaves of two lengths: ``k``, ``v`` one row
a token (``kv_heads`` x ``head_dim``, no rope) and ``kc`` one COMPRESSED key
every ``sparse_stride`` tokens: ``kc_j = mean(k_s : stride j <= s < stride j
+ sparse_kernel)``, written by the decode step that completes its window (one
write every ``sparse_stride``-th step) and all at once by a prefill.

A query at position t with ``t + 1 <= sparse_dense_len`` attends every key
``s <= t`` (plain causal attention). Past that: each head softmaxes the
compressed keys whose window lies inside ``0 .. t`` (scale ``d^-1/2``), the
probabilities of a KV group's heads are summed, a block of ``sparse_block``
tokens scores the max over the compressed keys whose window touches it, block
0 (``sparse_init_blocks``) and the ``sparse_window / sparse_block`` most
recent blocks are forced, and the ``sparse_topk`` blocks of largest score
(the forced ones among them; exact, ties to the lowest block) are attended,
one selection for all heads of a KV group: at most ``sparse_topk x
sparse_block`` keys a query. The selection is a MASK over the whole leaves
(:func:`lambdipy_tpu.models.llama._dsa_select_mask`, the exact threshold: no
sort, no gather), in the step and in the prefill, whose one body runs a block
of ``SPARSE_QUERY_BLOCK`` queries a turn inside each block of
``SPARSE_KEY_BLOCK`` keys and a few heads at a time, so that no float32
score is ``[heads, s, s]``.

Scopes: ``sala_compress`` (the compressed keys), ``sala_select`` (scores,
pool, threshold), ``attend``, ``kv_write``, ``qkv_proj``. The interface is
``llama.ATTN_KINDS``'."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from lambdipy_tpu.models.llama import (Counters, QDense, RMSNorm,
                                       SALA_PROMPT_BLOCK, _attend,
                                       _cache_write, _dsa_select_mask,
                                       _head_group, require_own_leaves,
                                       whole_prompt_blocks)

NAME = "sparse_kv"
PLACES = ("layer_kinds",)
# a prefill's turn: queries a turn, and the keys of one python-level block
# (a prompt past DENSE_PREFILL_MAX prefills at whole key blocks,
# ``LlamaConfig.prompt_bucket``)
SPARSE_QUERY_BLOCK = 128
SPARSE_KEY_BLOCK = SALA_PROMPT_BLOCK
# up to here a prompt inside ``sparse_dense_len`` takes the llama block's
# one-shot causal attention ([heads, s, s] scores: 0.5 GB at 32 heads)
DENSE_PREFILL_MAX = 2048


def validate(cfg) -> None:
    require_own_leaves(cfg, NAME)
    stride, kernel, block = (cfg.sparse_stride, cfg.sparse_kernel,
                             cfg.sparse_block)
    if stride < 1 or kernel != 2 * stride or block % stride \
            or block < kernel or cfg.sparse_topk < 1 \
            or cfg.sparse_window % block or cfg.sparse_init_blocks < 0:
        raise ValueError(
            "block-sparse attention needs sparse_kernel = 2 x sparse_stride "
            "(a compressed key is the mean of two strides), sparse_block a "
            "multiple of sparse_stride and at least sparse_kernel, "
            "sparse_window a multiple of sparse_block and sparse_topk >= 1")
    if cfg.sparse_topk < cfg.sparse_init_blocks \
            + cfg.sparse_window // block:
        raise ValueError("sparse_topk must hold the forced blocks "
                         "(sparse_init_blocks and the local window's)")
    if cfg.heads % cfg.kv_heads:
        raise ValueError("kv_heads must divide heads")


def cache_layout(cfg) -> dict:
    row = (cfg.kv_heads, cfg.head_dim)
    return {"k": row, "v": row, "kc": row}


def cache_positions(cfg, max_len: int) -> dict:
    return {"k": max_len, "v": max_len,
            "kc": -(-max_len // cfg.sparse_stride)}


def cache_dtypes(cfg) -> dict:
    return dict.fromkeys(("k", "v", "kc"), cfg.dtype)


def cache_slot(cfg, leaf: str, position):
    """A compressed key lies at the slot of the stride its window BEGINS
    in."""
    return position // cfg.sparse_stride if leaf == "kc" else position


def refusal(cfg, holder: str) -> str:
    return (f"{holder} holds per-head k/v rows, one a token on one position "
            "axis, and attends every one a query may see; it cannot take a "
            "block-sparse layer, whose entry has a second leaf of another "
            f"length (kc, one compressed key every {cfg.sparse_stride} "
            f"tokens) and whose {cfg.sparse_topk} attended blocks are chosen "
            "by content by the whole-prompt prefill and the one-token step "
            "alone (PERF.md section 7)")


prompt_block = whole_prompt_blocks


def counters(cfg) -> tuple:
    """This kind's share of the ``handler.sala`` block on ``/metrics``, only
    growing, from the segment programs' own masks, for the rows the
    collector books (the first block-sparse layer sows ``sala_stats``, int32
    ``[b, 4]`` a step: every such layer's are the same; a segment program
    returns their sum over its steps). ``row_steps``: booked rows x segment
    steps. ``keys_attended``: the keys a block-sparse layer's steps
    attended, summed (a step's context while it lies inside
    ``sparse_dense_len``, at most ``sparse_topk x sparse_block`` past it);
    ``keys_visible``: the positions they could see; ``dense_steps``: the
    row-steps at or under ``sparse_dense_len``; ``kc_writes``: the
    compressed keys written (one every ``sparse_stride``-th step a row)."""
    def segment(sown, rows: int, steps: int) -> dict:
        keys = sown["sala_stats"]
        return {"row_steps": rows * steps, "keys_attended": keys[:, 0].sum(),
                "keys_visible": keys[:, 1].sum(),
                "dense_steps": keys[:, 2].sum(),
                "kc_writes": keys[:, 3].sum()}

    return (Counters(
        "sala", "a model with block-sparse layers",
        dict.fromkeys(("row_steps", "keys_attended", "keys_visible",
                       "dense_steps", "kc_writes"), 0),
        {"sala_stats": lambda b: jnp.zeros((b, 4), jnp.int32)}, segment),)


def compress(k, stride: int):
    """Every compressed key of a whole sequence: ``k`` ``[b, s, kvh, d]`` ->
    ``[b, ceil(s / stride), kvh, d]``, slot j the float32 mean of rows
    ``stride j .. stride j + 2 stride - 1`` (the last slot's window hangs
    over the end: nothing may see it before a step has rewritten it)."""
    b, s, kvh, d = k.shape
    n = -(-s // stride)
    halves = jnp.pad(k, ((0, 0), (0, (n + 1) * stride - s), (0, 0), (0, 0))
                     ).astype(jnp.float32).reshape(b, n + 1, stride, kvh, d
                                                   ).sum(axis=2)
    return ((halves[:, :-1] + halves[:, 1:]) / (2.0 * stride)).astype(k.dtype)


def select_blocks(cfg, q, kc, pos, n_blocks: int):
    """The blocks each query attends, as a mask: ``q`` ``[b, s, heads, d]``,
    ``kc`` ``[b, n, kvh, d]`` (the compressed keys of tokens ``0 .. n x
    stride``), ``pos`` ``[b, s]`` the queries' positions -> ``[b, kvh, s,
    n_blocks]`` bool, ``n_blocks`` blocks from token 0."""
    stride, kernel, block = (cfg.sparse_stride, cfg.sparse_kernel,
                             cfg.sparse_block)
    per = block // stride
    b, s, heads, d = q.shape
    kvh, n = kc.shape[2], kc.shape[1]
    # compressed key j is visible once its window lies inside 0 .. pos
    seen = (stride * jnp.arange(n) + kernel)[None, None, :] \
        <= (pos + 1)[:, :, None]                                # [b, s, n]
    logits = jnp.einsum(
        "bskgd,bjkd->bkgsj", q.reshape(b, s, kvh, heads // kvh, d), kc,
        preferred_element_type=jnp.float32) * jnp.float32(d ** -0.5)
    probs = jax.nn.softmax(jnp.where(seen[:, None, None], logits,
                                     jnp.float32(-1e9)), axis=-1)
    group = jnp.sum(jnp.where(seen[:, None, None], probs, 0.0), axis=2)
    # a block scores the max over the windows that touch it: its own ``per``
    # and the last of the block before (max-pool of per + 1, stride per)
    group = jnp.pad(group, ((0, 0),) * 3 + ((0, max(0, n_blocks * per - n)),)
                    )[..., :n_blocks * per].reshape(b, kvh, s, n_blocks, per)
    own = group.max(axis=-1)
    before = jnp.pad(group[..., -1], ((0, 0),) * 3 + ((1, 0),))[..., :-1]
    score = jnp.maximum(own, before)
    blk = jnp.arange(n_blocks)[None, None, :]
    cur = (pos // block)[:, :, None]                            # [b, s, 1]
    visible = jnp.broadcast_to((blk <= cur)[:, None], score.shape)
    forced = (blk < cfg.sparse_init_blocks) \
        | (blk > cur - cfg.sparse_window // block)
    score = jnp.where(forced[:, None], jnp.float32(jnp.inf), score)
    if n_blocks <= cfg.sparse_topk:
        return visible
    return _dsa_select_mask(score, visible, cfg.sparse_topk)


def _seen_tokens(cfg, picked, causal, pos):
    """``picked`` ``[b, kvh, s, blocks]`` -> ``[b, kvh, s, t]``: the tokens
    of the picked blocks a query may see, every visible one while the query
    lies inside ``sparse_dense_len``. ``causal`` ``[b, s, t]``."""
    t = causal.shape[-1]
    tokens = jnp.repeat(picked, cfg.sparse_block, axis=-1)[..., :t]
    dense = (pos + 1 <= cfg.sparse_dense_len)[:, None, :, None]
    return causal[:, None] & (dense | tokens)


def group_attend(q, k, v, seen):
    """Grouped-query attention under a mask a KV head: ``q`` ``[b, s, heads,
    d]``, ``k`` / ``v`` ``[b, t, kvh, d]``, ``seen`` ``[b, kvh, s, t]``;
    float32 scores and softmax. ``[b, s, heads, d]``."""
    b, s, heads, d = q.shape
    kvh = k.shape[2]
    logits = jnp.einsum("bskgd,btkd->bkgst",
                        q.reshape(b, s, kvh, heads // kvh, d), k,
                        preferred_element_type=jnp.float32) \
        * jnp.float32(d ** -0.5)
    probs = jax.nn.softmax(jnp.where(seen[:, :, None], logits,
                                     jnp.float32(-1e9)), axis=-1)
    return jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v
                      ).reshape(b, s, heads, d)


def _prefill_attend(cfg, q, k, v, kc, mask):
    """A prefill past the one-shot form: ONE body runs a block of
    ``SPARSE_QUERY_BLOCK`` queries a turn (``lax.map``) inside each block of
    ``SPARSE_KEY_BLOCK`` keys: it selects among the blocks up to the key
    block's end (where a query of it can lie past ``sparse_dense_len``) and
    attends under the mask, a few heads of one KV group a turn so that a
    turn's float32 scores stay what the chip keeps in its fast memory
    (``llama._head_group``)."""
    b, s, heads, d = q.shape
    kvh = k.shape[2]
    block = min(s, SPARSE_QUERY_BLOCK)
    outs = []
    for at in range(0, s, SPARSE_KEY_BLOCK):
        t = min(at + SPARSE_KEY_BLOCK, s)
        turns = -(-(t - at) // block)
        hg = _head_group(heads // kvh, b * block * t)

        def by_head(a, t=t):   # [kvh, b, t, 1, d]: a turn takes one whole
            return jnp.moveaxis(a[:, :t], 2, 0)[:, :, :, None]

        def body(args, at=at, t=t, hg=hg, k_t=by_head(k), v_t=by_head(v)):
            i, q_i = args
            pos = jnp.broadcast_to(at + i * block + jnp.arange(block),
                                   (b, block))
            causal = mask[:, None, :t] & (jnp.arange(t)[None, None, :]
                                          <= pos[:, :, None])
            if t > cfg.sparse_dense_len:
                with jax.named_scope("sala_select"):
                    picked = select_blocks(
                        cfg, q_i, kc[:, :-(-t // cfg.sparse_stride)], pos,
                        -(-t // cfg.sparse_block))
                    seen = _seen_tokens(cfg, picked, causal, pos)
            else:
                seen = jnp.broadcast_to(causal[:, None], (b, kvh, block, t))

            seen = jnp.moveaxis(seen, 1, 0)[:, :, None]

            def heads_turn(args):
                g, q_g = args      # q_g [b, block, hg, d], of KV head g
                return group_attend(q_g, *(
                    jax.lax.dynamic_index_in_dim(a, g, 0, False)
                    for a in (k_t, v_t, seen)))

            with jax.named_scope("attend"):
                n_g = heads // hg
                q_g = jnp.moveaxis(q_i.reshape(b, block, n_g, hg, d), 2, 0)
                out = jax.lax.map(heads_turn,
                                  (jnp.arange(n_g) * hg // (heads // kvh),
                                   q_g))
                return jnp.moveaxis(out, 0, 2).reshape(b, block, heads, d)

        q_t = jnp.pad(q[:, at:t], ((0, 0), (0, turns * block - (t - at)),
                                   (0, 0), (0, 0)))
        out = jax.lax.map(body, (jnp.arange(turns), jnp.moveaxis(
            q_t.reshape(b, turns, block, heads, d), 1, 0)))
        outs.append(jnp.moveaxis(out, 0, 1).reshape(
            b, turns * block, heads, d)[:, :t - at])
    return jnp.concatenate(outs, axis=1)


def attend(block, x, positions, mask, cache, lengths):
    """The layer's attention inside ``block`` (a ``LlamaBlock`` under its
    ``nn.compact`` call): ``(the heads' outputs [b, s, heads x d] after the
    gate, the new cache entry)``."""
    cfg = block.cfg
    heads, kvh, d = cfg.heads, cfg.kv_heads, cfg.head_dim
    stride, kernel = cfg.sparse_stride, cfg.sparse_kernel
    b, s, _ = x.shape
    with jax.named_scope("qkv_proj"):
        h = RMSNorm(cfg.norm_eps, name="attn_norm")(x)
        q = QDense(heads * d, cfg.quant, cfg.dtype, name="q_proj")(h)
        k = QDense(kvh * d, cfg.quant, cfg.dtype, name="k_proj")(h)
        v = QDense(kvh * d, cfg.quant, cfg.dtype, name="v_proj")(h)
        q = q.reshape(b, s, heads, d)
        k, v = k.reshape(b, s, kvh, d), v.reshape(b, s, kvh, d)
        if cfg.qk_norm:
            q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        q, k, v = (a.astype(cfg.dtype) for a in (q, k, v))

    if cache is None:
        with jax.named_scope("sala_compress"):
            kc = compress(k, stride)
        if s <= min(cfg.sparse_dense_len, DENSE_PREFILL_MAX):
            with jax.named_scope("attend"):
                causal = jnp.tril(jnp.ones((s, s), dtype=jnp.bool_))
                out = _attend(q, k, v, mask[:, None, :] & causal[None])
        else:
            out = _prefill_attend(cfg, q, k, v, kc, mask)
        new_cache = {"k": k, "v": v, "kc": kc}
    else:
        if s != 1:
            raise NotImplementedError(
                "a block-sparse layer's selection is computed by the "
                "whole-prompt prefill and the one-token step: a chunk of "
                f"{s} positions against a cache (a prefix continued, a "
                "draft verified) is not written (PERF.md section 7)")
        idx = jnp.broadcast_to(cache["index"], (b,))
        with jax.named_scope("kv_write"):
            new_cache, valid, t = _cache_write(
                cache, {"k": k, "v": v}, cache["index"], b, 1)
        n_kc = cache["kc"].shape[1]
        with jax.named_scope("sala_compress"):
            # the compressed key whose window this position completes: a
            # slice a row (one gather would copy the leaf into a layout of
            # its liking: eva._eva_chunk_rows)
            lands = ((idx + 1) % stride == 0) & (idx + 1 >= kernel)
            first = jnp.maximum(idx + 1 - kernel, 0)
            window = jnp.concatenate(
                [jax.lax.dynamic_slice(new_cache["k"], (r, first[r], 0, 0),
                                       (1, min(kernel, t), kvh, d))
                 for r in range(b)], axis=0)
            new_cache["kc"] = cache["kc"].at[
                jnp.arange(b), jnp.where(lands, first // stride, n_kc)].set(
                    jnp.mean(window.astype(jnp.float32), axis=1
                             ).astype(cfg.dtype))
        causal = jnp.broadcast_to(valid, (b, 1, t))
        if t > cfg.sparse_dense_len:
            with jax.named_scope("sala_select"):
                picked = select_blocks(cfg, q, new_cache["kc"], idx[:, None],
                                       -(-t // cfg.sparse_block))
                seen = _seen_tokens(cfg, picked, causal, idx[:, None])
        else:
            seen = jnp.broadcast_to(causal[:, None], (b, kvh, 1, t))
        with jax.named_scope("attend"):
            out = group_attend(q, new_cache["k"], new_cache["v"], seen)
        if block.layer == cfg.first_layer_of(NAME):
            # what a row's step attended and could see, whether it lay inside
            # dense_len, whether it wrote a compressed key: every sparse
            # layer's are the same (counters; /metrics handler.sala)
            block.sow("sala_stats", "keys", jnp.stack(
                [seen.sum((-1, -2, -3)) // kvh, idx + 1,
                 idx + 1 <= cfg.sparse_dense_len, lands],
                axis=-1).astype(jnp.int32))
    out = out.reshape(b, s, heads * d)
    if cfg.attn_output_gate:
        with jax.named_scope("qkv_proj"):
            out = out * jax.nn.sigmoid(QDense(
                heads * d, cfg.quant, cfg.dtype, name="out_gate_proj")(h))
    return out, new_cache
