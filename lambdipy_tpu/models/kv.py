"""Attention kind ``kv``: per-head K/V rows, one a token (the llama block),
a layer's module.

The cache entry of a layer is two leaves, ``k`` and ``v`` ``[rows, positions,
kv_heads, head_dim]`` after rope (int8 values beside float32 scales under
``kv_quant``: ``llama._kv_store``), and a query attends every row a causal
mask lets it see, grouped-query where ``kv_heads`` < ``heads``
(``llama._attend``). The one kind every cache holder can take
(:func:`lambdipy_tpu.models.llama.require_kv_cache`), and the one that takes
both static forms of the block's call: ``sp_prefill`` (the whole-prompt
sequence-parallel prefill) and ``band`` (the long-context sliding band).

A decode segment may keep this kind's cache read-only inside its scan
(:func:`keeps_tail`): the segment's own K/V rows go to a tail ``[b, segment,
..]`` a leaf, in lockstep, a step attends cache and tail under one softmax
(:func:`_tail_write`), and one ragged write after the scan puts the tail at
each row's position.

Scopes: ``qkv_proj``, ``kv_write``, ``attend``. The interface is
``llama.ATTN_KINDS``'."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from lambdipy_tpu.models.llama import (QDense, RMSNorm, _active_sp_mesh,
                                       _attend, _cache_write, _kv_dequantize,
                                       _kv_store, block_method, rope)

NAME = "kv"
PLACES = ("attn_kind", "layer_kinds")
# the static forms of the block's call this kind takes
FORMS = ("sp_prefill", "band")


def validate(cfg) -> None:
    """Nothing of its own: the widths are the model's."""


def cache_layout(cfg) -> dict:
    row = (cfg.kv_heads, cfg.head_dim)
    return {"k": row, "v": row}


def cache_positions(cfg, max_len: int) -> dict:
    return {"k": max_len, "v": max_len}


def cache_dtypes(cfg) -> dict:
    return {"k": cfg.dtype, "v": cfg.dtype}


def cache_slot(cfg, leaf: str, position):
    return position


def refusal(cfg, holder: str) -> None:
    """Nothing refuses per-head K/V rows."""
    return None


def row_a_token(cfg) -> bool:
    return True


def keeps_tail(cfg) -> bool:
    """Decided from the shapes, by what the v5e compiler does with the
    per-step write (PERF.md section 6, PR 30; ``tests/test_chip_compile.py``
    holds both halves):

    - ONE query a KV head (multi-head K/V): the scores are a multiply-
      reduce served from a prefetched copy of the cache, the scatter
      updates that copy, and the WHOLE copy goes home every layer of every
      step. The tail takes the write out of the loop: 15.6 -> 12.6 ms a
      step at DeepSeek-7B widths.
    - several queries a KV head (grouped-query K/V): the scores are a
      convolution that reads HBM and the scatter is in place there, 0.6 ms
      a step at Mistral-7B widths; with a read-only cache the compiler
      prefetches the leaves in place of weights, 13.0 -> 13.7 ms at the
      full 2048 window. They keep the per-step write.

    The blocked Pallas kernel and the sp-sharded decode step
    (``parallel/spdecode.py``) attend the ONE cache they are handed, so
    their segments write it every step too."""
    if cfg.heads != cfg.kv_heads or cfg.attn_backend == "blocked":
        return False
    return cfg.attn_backend != "ring" or _active_sp_mesh() is None


def tail_init(cfg, frozen: list, base, steps: int) -> list:
    """A segment's tails, a layer, before its scan: zeros here; on the chip
    the compiler sees that the loop writes every position and hands it the
    buffer uninitialised (AllocateBuffer), whatever the value: ``_attend``
    reads no position before its step wrote it."""
    return [{name: jnp.zeros((val.shape[0], steps) + val.shape[2:], val.dtype)
             for name, val in entry.items()} for entry in frozen]


def tail_step(cfg, frozen: list, tails: list, base, j) -> list:
    """The entries the layers of step ``j`` read: the frozen leaves, each
    row's position when the segment began, the tail and the step."""
    return [{**entry, "index": base, "tail": tail, "step": j}
            for entry, tail in zip(frozen, tails)]


def tail_merge(cfg, full: list, tails: list, base, steps: int) -> list:
    """The segment's ONE write of each layer's entry, after its scan: the
    ragged write of a chunk, whose out-of-range positions drop (so a
    finished slot's stale position lands nowhere live)."""
    with jax.named_scope("kv_write"):
        return [_cache_write(entry, tail, base, base.shape[0], steps)[0]
                for entry, tail in zip(full, tails)]


def _tail_write(cache, store):
    """A decode segment's write (:func:`_scan_decode`, ``tail_window``):
    the layer's cache leaves are READ here and never written; this step's
    ``store`` leaves go to position ``cache["step"]`` of the segment's
    tail, ``cache["tail"]`` (a leaf ``[b, segment, ...]`` for each cache
    leaf, in its dtype), the same position for every row because a
    segment's rows advance in lockstep. Returns ``(new tail, valid [b, 1,
    t], seen [segment])``: a row attends what its cache held when the
    segment began (``t < index``) and the tail positions written so far."""
    from lambdipy_tpu.parallel.sharding import shard_hint

    j = cache["step"]
    tail = {name: shard_hint(
                jax.lax.dynamic_update_slice(cache["tail"][name], val,
                                             (0, j, 0, 0)), "dp", None, "tp")
            for name, val in store.items()}
    first = next(iter(store))
    valid = (jnp.arange(cache[first].shape[1])[None, None, :]
             < cache["index"][:, None, None])
    return tail, valid, jnp.arange(tail[first].shape[1]) <= j


@block_method
def _prefill_attend(block, q, k, v, mask, sp_prefill: int = 0):
    """Causal prefill attention via the configured backend.

    ``sp_prefill >= 2`` requests the whole-prompt sequence-parallel
    tier regardless of the configured backend: the first chunk of an
    sp-prefill program ring-shards the full prompt's attention over
    the sp axis. Falls through to the configured backend when no
    usable sp mesh exists (the caller counts the stand-down)."""
    cfg = block.cfg
    s = q.shape[1]
    backend = cfg.attn_backend
    if backend == "ring" or sp_prefill >= 2:
        from lambdipy_tpu.parallel.ring import ring_attention

        mesh = _active_sp_mesh()
        if mesh is not None:
            # sequence-parallel long-context path; the padding mask is
            # threaded as the ring's key-validity mask, so padded
            # batches match the dense backend exactly
            return ring_attention(q, k, v, mesh, causal=True,
                                  kv_mask=mask)
        backend = cfg.attn_backend if backend != "ring" else "dense"
    if backend == "flash":
        from lambdipy_tpu.ops import kernels_compile_here
        from lambdipy_tpu.ops.attention import (flash_attention,
                                                mha_reference)

        if kernels_compile_here():
            return flash_attention(q, k, v, causal=True)
        return mha_reference(q, k, v, causal=True)
    causal = jnp.tril(jnp.ones((s, s), dtype=jnp.bool_))
    attn_mask = mask[:, None, :] & causal[None, :, :]
    return _attend(q, k, v, attn_mask)


@block_method
def _project_qkv(block, x, positions):
    """The per-head kinds' projections: ``q`` ``[b, s, heads, d]``,
    ``k`` / ``v`` ``[b, s, kv_heads, d]``, ``q`` and ``k`` after rope."""
    cfg = block.cfg
    d = cfg.head_dim
    b, s, _ = x.shape
    with jax.named_scope("qkv_proj"):
        h = RMSNorm(cfg.norm_eps, cfg.norm_unit_offset,
                    name="attn_norm")(x)
        q = QDense(cfg.heads * d, cfg.quant, cfg.dtype, name="q_proj")(h)
        k = QDense(cfg.kv_heads * d, cfg.quant, cfg.dtype, name="k_proj")(h)
        v = QDense(cfg.kv_heads * d, cfg.quant, cfg.dtype, name="v_proj")(h)
        q = q.reshape(b, s, cfg.heads, d)
        k = k.reshape(b, s, cfg.kv_heads, d)
        v = v.reshape(b, s, cfg.kv_heads, d)
        q, k = rope(q, k, positions, cfg.rope_theta, cfg.rope_scaling)
    return q, k, v


@block_method
def _kv_attend(block, x, positions, mask, cache, lengths=None,
               sp_prefill: int = 0, band: int = 0):
    """Per-head K/V attention inside ``block`` (a ``LlamaBlock`` under its
    ``nn.compact`` call): ``(the heads' outputs [b, s, heads, head_dim], the
    new cache entry)``. ``lengths`` is not read: a row a token needs none."""
    cfg = block.cfg
    b, s, _ = x.shape
    q, k, v = _project_qkv(block, x, positions)

    if cache is None:
        with jax.named_scope("attend"):
            out = _prefill_attend(block, q, k, v, mask, sp_prefill)
        new_cache = {"k": k, "v": v}
    else:
        from lambdipy_tpu.parallel.sharding import shard_hint

        # decode: append this step's k/v at cache index, attend over
        # prefix. The cache stays kv-head-sharded over tp across the
        # scan — the dominant serving HBM object must never be
        # gathered per step
        idx = cache["index"]  # int32 scalar, or [b] per-row positions
        # sequence-parallel decode (attn_backend="ring" + an sp
        # mesh): the cache seq dim stays SHARDED over sp for the
        # whole scan and each step combines per-shard online-softmax
        # partials with O(b*h*d) collectives — the long-context
        # decode path, pairing with ring-attention prefill
        # (parallel/spdecode.py). Composes with kv_quant: the int8
        # cache leaves shard the same way and the per-shard dequant
        # fuses into the local attention einsum.
        sp_done = False
        if jnp.ndim(idx) != 0 and cfg.attn_backend == "ring":
            sp_mesh = _active_sp_mesh()
            if sp_mesh is not None and s == 1:
                from lambdipy_tpu.parallel.spdecode import (
                    sp_decode_step)

                sp_new = _kv_store(cfg, k, v, layer=block.layer)
                sp_cache = {name: cache[name] for name in sp_new}
                with jax.named_scope("attend"):
                    out, new_cache = sp_decode_step(
                        q, sp_new, sp_cache, idx, sp_mesh)
                sp_done = True
            elif sp_mesh is not None:
                # a multi-token verify chunk under the ring backend:
                # sp decode is a one-token-step formulation, so the
                # chunk runs the replicated dense path — observable,
                # not silent (ROADMAP direction-2 note)
                from lambdipy_tpu.parallel.spdecode import (
                    note_standdown)

                note_standdown("multi_token_chunk")
        elif jnp.ndim(idx) != 0 and _active_sp_mesh() is not None:
            # the mesh HAS an sp axis but the configured backend
            # (blocked/dense/flash) routes decode around sp_decode:
            # the cache this step reads is replicated despite the
            # sharding the operator asked for. Count + log once per
            # reason so the condition is visible on /metrics.
            from lambdipy_tpu.parallel.spdecode import note_standdown

            note_standdown(f"attn_backend={cfg.attn_backend}")

        def kv_of(leaves):
            if cfg.kv_quant == "int8":
                return (_kv_dequantize(leaves["k_int8"],
                                       leaves["k_scale"], cfg.dtype),
                        _kv_dequantize(leaves["v_int8"],
                                       leaves["v_scale"], cfg.dtype))
            return leaves["k"], leaves["v"]

        if "tail" in cache:
            # a segment's step (_scan_decode, tail_window): the cache
            # is read as the segment found it, this step's k/v joins
            # the tail, one softmax over both
            with jax.named_scope("kv_write"):
                new_cache, valid, seen = _tail_write(
                    cache, _kv_store(cfg, k, v, layer=block.layer))
            with jax.named_scope("attend"):
                out = _attend(q, *kv_of(cache), valid,
                              tail=(*kv_of(new_cache), seen))
        elif not sp_done:
            with jax.named_scope("kv_write"):
                # quantize this chunk's k/v once under kv_quant; the
                # cache stays int8 in HBM and the dequant fuses into
                # the attention einsum
                new_cache, valid, t = _cache_write(
                    cache, _kv_store(cfg, k, v, layer=block.layer), idx,
                    b, s, band)
            with jax.named_scope("attend"):
                # length-aware blocked decode attention: one-token steps
                # read each row's ACTIVE window instead of the full
                # static cache (bytes scale with context actually held).
                # Manual (unpartitioned) op like QDense's pallas backend:
                # only taken with no ambient mesh; the valid mask built
                # above is exactly "position < index + 1", so active_len
                # = idx + 1 reproduces it row for row.
                blocked = False
                if cfg.attn_backend == "blocked" and s == 1:
                    from lambdipy_tpu.ops.decode_attention import (
                        decode_attention)
                    from lambdipy_tpu.parallel.mesh import current_mesh

                    if current_mesh() is None:
                        active = jnp.broadcast_to(
                            jnp.asarray(idx, jnp.int32) + 1, (b,))
                        if cfg.kv_quant == "int8":
                            out = decode_attention(
                                q, new_cache["k_int8"],
                                new_cache["v_int8"], active,
                                k_scale=new_cache["k_scale"],
                                v_scale=new_cache["v_scale"])
                        else:
                            out = decode_attention(
                                q, new_cache["k"], new_cache["v"], active)
                        blocked = True
                if not blocked:
                    ck, cv = kv_of(new_cache)
                    attn_mask = jnp.broadcast_to(valid, (b, s, t))
                    sp_mesh = (_active_sp_mesh()
                               if (sp_prefill >= 2 and s > 1
                                   and jnp.ndim(idx) == 0
                                   and s % sp_prefill == 0) else None)
                    if sp_mesh is not None:
                        # sp-prefill continuation chunk: queries shard
                        # over sp, the cache stays replicated (as decode
                        # keeps it) — score memory and the softmax walk
                        # split across the mesh, no per-layer collective
                        from lambdipy_tpu.parallel.ring import (
                            sp_chunk_attention)

                        out = sp_chunk_attention(q, ck, cv, attn_mask,
                                                 sp_mesh)
                    else:
                        out = _attend(q, ck, cv, attn_mask)
    return out, new_cache



attend = _kv_attend
