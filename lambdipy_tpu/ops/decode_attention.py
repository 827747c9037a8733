"""Length-aware blocked decode attention: Pallas TPU kernel + pure-jax
reference.

Decode is memory-bound and the static-cache decode path reads the FULL
``cache_len`` K/V window every step — a row 300 tokens into an 8k-window
server streams all 8k positions from HBM per token. This op makes decode
KV bytes scale with each row's *actual* context instead of its allocated
window (the mechanism of PagedAttention / Flash-Decoding, specialized to
the repo's contiguous static cache):

- grid is ``(batch x kv_heads, kv_blocks)`` with the kv dimension
  innermost — TPU grid execution is sequential, so the online-softmax
  f32 scratch accumulators (running max / sum / weighted-V) carry across
  kv steps exactly like ``ops/attention.py``'s ``_flash_kernel``;
- a per-row ``active_len`` operand rides in scalar-prefetch (SMEM):
  blocks fully past a row's length SKIP their compute under ``pl.when``,
  and their K/V BlockSpec index maps CLAMP to the row's last active
  block — Pallas elides the DMA when consecutive grid steps map to the
  same block, so the skipped blocks cost neither FLOPs nor HBM bytes.
  The partially-active boundary block masks per-position;
- GQA-aware: each program attends ONE kv head against its ``group`` =
  heads/kv_heads query rows, so grouped K/V is read once per kv head,
  never re-read per query head;
- composes with the int8 KV layout (``models/llama.py _kv_quantize``):
  int8 values + per-position f32 scales stream through the same blocked
  index maps and dequantize in VMEM right before the dot.

The pure-jax :func:`decode_attention_reference` is the numerics oracle
and what runs off the TPU. Its math mirrors ``models/llama.py _attend``
operation for operation (same einsums, same f32 ``/ sqrt(d)`` scaling,
same ``-1e9`` mask fill), so with a float KV cache its output is
BITWISE the dense decode path's — the parity the blocked backend's
on/off tests assert. ``decode_attention`` is the dispatcher the model
layer calls: the kernel on a TPU backend (a shape it cannot tile raises
there), the reference on any other (Mosaic compiles only for the TPU;
tests exercise the kernel explicitly via ``interpret=True``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9  # matches models/llama.py _attend's mask fill


def decode_attention_reference(q, k, v, active_len, *, scale=None):
    """Length-masked GQA decode attention, dense-path-bitwise.

    q: [b, s, h, d] (s = 1 for decode steps); k/v: [b, t, kvh, d] float
    (kv heads grouped, NOT pre-broadcast); active_len: [b] int32 — row r
    attends positions ``< active_len[r]``. Returns [b, s, h, d].

    The computation is ``models/llama.py _attend`` with the validity
    mask built from ``active_len``: same grouped einsums, f32 logits
    divided by ``sqrt(d)``, ``-1e9`` fill, f32 softmax — so on the same
    inputs the output is bitwise the dense decode path's.
    """
    b, s, h, d = q.shape
    t = k.shape[1]
    kvh = k.shape[2]
    group = h // kvh
    qg = q.reshape(b, s, kvh, group, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    if scale is None:
        logits = logits / jnp.sqrt(d).astype(jnp.float32)
    else:
        logits = logits * jnp.float32(scale)
    valid = (jnp.arange(t)[None, :]
             < jnp.asarray(active_len, jnp.int32)[:, None])  # [b, t]
    logits = jnp.where(valid[:, None, None, None, :], logits,
                       jnp.float32(NEG_INF))
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def windowed_decode_attention_reference(q, k, v, base, local_len, window,
                                        *, scale=None):
    """LOGICAL-window decode attention over a dense big-window cache —
    the long-context tier's parity oracle.

    k/v hold the FULL logical context ``[b, T, kvh, d]`` (T >= window);
    row r's attention runs over the sliding view ``[base[r], base[r] +
    window)`` with ``local_len[r]`` positions valid inside it — exactly
    the view the block table maps for the windowed paged path
    (``models/llama.py _lpaged_seg_fn``). Implemented as slice-then-
    :func:`decode_attention_reference`: the sliced computation has
    IDENTICAL shapes and operations to what the gathered-window path
    computes on the same values, so their outputs are bitwise equal by
    the same shape-identity argument the paged reference rests on. (A
    mask-over-full-T formulation is mathematically equal but reduces
    over a different tree — allclose, not bitwise — so the SLICE is the
    oracle.)"""
    b = q.shape[0]
    base = jnp.broadcast_to(jnp.asarray(base, jnp.int32), (b,))
    k_win = jax.vmap(
        lambda kk, b0: jax.lax.dynamic_slice_in_dim(kk, b0, window, 0)
    )(k, base)
    v_win = jax.vmap(
        lambda vv, b0: jax.lax.dynamic_slice_in_dim(vv, b0, window, 0)
    )(v, base)
    return decode_attention_reference(q, k_win, v_win, local_len,
                                      scale=scale)


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, block_k: int, scale: float, quant: bool,
                   ks_ref=None, vs_ref=None):
    """One (row, kv-block) grid step. Scratch m/l/acc carry the online
    softmax across the sequential kv dimension; blocks past the row's
    active length skip compute entirely (their data was never fetched —
    the clamped index map re-addressed the previous block)."""
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    alen = lens_ref[bh]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * block_k < alen)
    def _compute():
        q = q_ref[0]  # [group, d]
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]
        if quant:
            k = k.astype(jnp.float32) * ks_ref[0].astype(jnp.float32)
            v = v.astype(jnp.float32) * vs_ref[0].astype(jnp.float32)
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [group, block_k]
        pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < alen, s, NEG_INF)
        m_prev = m_ref[...]  # [group, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def blocked_decode_attention(q, k, v, active_len, *, k_scale=None,
                             v_scale=None, scale=None, block_k: int = 128,
                             interpret: bool = False):
    """The Pallas blocked decode kernel. q: [b, 1, h, d]; k/v:
    [b, t, kvh, d] (float, or int8 with ``k_scale``/``v_scale``
    [b, t, kvh, 1] f32); active_len: [b] int32, PER-ROW >= 1 — a decode
    step always attends at least its own freshly-written position (the
    model passes ``index + 1``), and the kernel relies on that: at
    ``active_len = 0`` no block ever computes, so the finalize would
    emit exact zeros where the reference emits the uniform-softmax mean
    of V. Raises ``ValueError`` for a multi-token q or a window that
    does not tile (``t % block_k``). ``interpret=True`` runs the Pallas
    interpreter instead of compiling for the chip (tests)."""
    b, s, h, d = q.shape
    t = k.shape[1]
    kvh = k.shape[2]
    group = h // kvh
    quant = k_scale is not None
    block_k = min(block_k, t)
    if s != 1 or t % block_k:
        raise ValueError(
            f"blocked_decode_attention: needs a single-token q and a window "
            f"that tiles by block_k={block_k}; got s={s}, t={t}")
    scale = float(d ** -0.5 if scale is None else scale)
    nk = t // block_k

    # fold to per-(row, kv-head) programs: q [b*kvh, group, d],
    # k/v [b*kvh, t, d] — each program reads ONE kv head once for all
    # its group query heads (the GQA byte win)
    qf = q.reshape(b, kvh, group, d).reshape(b * kvh, group, d)

    def fold_kv(x, w):
        return x.transpose(0, 2, 1, 3).reshape(b * kvh, t, w)

    kf, vf = fold_kv(k, d), fold_kv(v, d)
    lens = jnp.repeat(jnp.asarray(active_len, jnp.int32).reshape(b), kvh)

    def kv_index(bh, ki, lens_ref):
        # clamp past-the-length blocks to the row's LAST active block:
        # consecutive identical block indices elide the DMA, so inactive
        # blocks cost no HBM traffic (their compute is pl.when-skipped)
        last = jnp.maximum(
            (lens_ref[bh] + block_k - 1) // block_k - 1, 0)
        return (bh, jnp.minimum(ki, last), 0)

    in_specs = [
        pl.BlockSpec((1, group, d), lambda bh, ki, lens: (bh, 0, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    operands = [qf, kf, vf]
    if quant:
        in_specs += [
            pl.BlockSpec((1, block_k, 1), kv_index),
            pl.BlockSpec((1, block_k, 1), kv_index),
        ]
        operands += [fold_kv(k_scale, 1), fold_kv(v_scale, 1)]

    def kernel(lens_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
        else:
            ks_ref, vs_ref = None, None
            o_ref, m_ref, l_ref, acc_ref = rest
        _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                       acc_ref, block_k=block_k, scale=scale, quant=quant,
                       ks_ref=ks_ref, vs_ref=vs_ref)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * kvh, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, group, d), lambda bh, ki, lens: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * kvh, group, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(lens, *operands)
    return out.reshape(b, kvh, group, d).reshape(b, 1, h, d)


def paged_decode_attention_reference(q, k_pages, v_pages, block_tables,
                                     active_len, *, k_scale_pages=None,
                                     v_scale_pages=None, scale=None):
    """Pure-jax oracle for PAGED decode attention, mirroring
    :func:`decode_attention_reference` operation for operation after one
    extra step: materialize each row's KV from its block table.

    q: [b, s, h, d]; k_pages/v_pages: [P, page, kvh, d] — the paged KV
    arena (``models/llama.py init_page_arena``); block_tables: [b, nb]
    int32 — row r's absolute positions ``[j*page, (j+1)*page)`` live in
    arena page ``block_tables[r, j]``; active_len: [b]. Table entries at
    or past a row's length may point anywhere (the null page): their
    values are masked to exact zeros by the same ``active_len`` mask the
    dense reference applies, so on tables whose gathered values equal a
    dense cache's the output is BITWISE the dense reference's."""
    b, nb = block_tables.shape
    page = k_pages.shape[1]
    tbl = jnp.asarray(block_tables, jnp.int32).reshape(-1)

    def gather(pages):
        g = jnp.take(pages, tbl, axis=0)  # [b*nb, page, kvh, w]
        return g.reshape(b, nb * page, *pages.shape[2:])

    k, v = gather(k_pages), gather(v_pages)
    if k_scale_pages is not None:
        k = k.astype(q.dtype) * gather(k_scale_pages).astype(q.dtype)
        v = v.astype(q.dtype) * gather(v_scale_pages).astype(q.dtype)
    return decode_attention_reference(q, k, v, active_len, scale=scale)


def _paged_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                  l_ref, acc_ref, *, page: int, kvh: int, scale: float,
                  quant: bool, ks_ref=None, vs_ref=None):
    """One (row, kv-page) grid step of the paged kernel: the same
    online-softmax math as ``_decode_kernel``, with the K/V block fetched
    through the row's BLOCK TABLE instead of a contiguous offset. The
    table itself is consumed ONLY by the ``kv_index`` BlockSpec maps
    (scalar prefetch) — inside the kernel body the indirection is
    already done. A block is one whole arena page, ALL kv heads of it
    (the arena's own layout: the compiler takes the last two block dims
    only whole or in (8, 128) tiles, and one head of ``[page, kvh, d]``
    is neither), so the body walks the kv heads itself."""
    r = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    alen = lens_ref[r]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * page < alen)
    def _compute():
        for j in range(kvh):
            q = q_ref[0, j]        # [group, d]
            k = k_ref[0, :, j, :]  # [page, d]
            v = v_ref[0, :, j, :]
            if quant:
                ks = ks_ref[0, :, j, :].astype(jnp.float32)
                vs = vs_ref[0, :, j, :].astype(jnp.float32)
                k = (k.astype(jnp.float32) * ks).astype(q.dtype)
                v = (v.astype(jnp.float32) * vs).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [group, page]
            pos = ki * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < alen, s, NEG_INF)
            m_prev = m_ref[j]  # [group, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[j] = m_new
            acc_ref[j] = acc_ref[j] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_blocked_decode_attention(q, k_pages, v_pages, block_tables,
                                   active_len, *, k_scale_pages=None,
                                   v_scale_pages=None, scale=None,
                                   interpret: bool = False):
    """The Pallas PAGED decode kernel: the length-aware blocked kernel
    with the contiguous clamp in its K/V index maps replaced by a BLOCK
    TABLE lookup riding scalar-prefetch — each (row, page) program DMAs
    exactly the arena page its table names, so a row's KV never has to
    be contiguous (and prefix pages shared between rows are fetched from
    one physical location). Shapes as
    :func:`paged_decode_attention_reference`; q must be single-token
    ([b, 1, h, d]) — raises ``ValueError`` otherwise. Past-the-length
    pages clamp to the row's LAST active table entry — consecutive
    identical page ids elide the DMA, the same early-exit economics as
    the contiguous kernel. ``interpret=True`` runs the Pallas interpreter
    instead of compiling for the chip (tests)."""
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(
            f"paged_blocked_decode_attention: single-token q only, got s={s}")
    page = k_pages.shape[1]
    kvh = k_pages.shape[2]
    group = h // kvh
    nb = block_tables.shape[1]
    quant = k_scale_pages is not None
    scale = float(d ** -0.5 if scale is None else scale)

    qf = q.reshape(b, kvh, group, d)
    lens = jnp.asarray(active_len, jnp.int32).reshape(b)
    tables = jnp.asarray(block_tables, jnp.int32)

    def kv_index(r, ki, lens_ref, tables_ref):
        # the paged indirection: the page COORDINATE comes from the
        # row's table, clamped to its last active entry so inactive
        # grid steps re-address the previous page (DMA elided) exactly
        # like the contiguous kernel's clamp
        last = jnp.maximum((lens_ref[r] + page - 1) // page - 1, 0)
        return (tables_ref[r, jnp.minimum(ki, last)], 0, 0, 0)

    def row_index(r, ki, lens_ref, tables_ref):
        return (r, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, kvh, group, d), row_index),
        pl.BlockSpec((1, page, kvh, d), kv_index),
        pl.BlockSpec((1, page, kvh, d), kv_index),
    ]
    operands = [qf, k_pages, v_pages]
    if quant:
        in_specs += [
            pl.BlockSpec((1, page, kvh, 1), kv_index),
            pl.BlockSpec((1, page, kvh, 1), kv_index),
        ]
        operands += [k_scale_pages, v_scale_pages]

    def kernel(lens_ref, tables_ref, q_ref, k_ref, v_ref, *rest):
        # tables_ref rides scalar prefetch for the kv_index maps only
        del tables_ref
        if quant:
            ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
        else:
            ks_ref, vs_ref = None, None
            o_ref, m_ref, l_ref, acc_ref = rest
        _paged_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref,
                      m_ref, l_ref, acc_ref, page=page, kvh=kvh,
                      scale=scale, quant=quant, ks_ref=ks_ref,
                      vs_ref=vs_ref)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, group, d), row_index),
        scratch_shapes=[
            pltpu.VMEM((kvh, group, 1), jnp.float32),
            pltpu.VMEM((kvh, group, 1), jnp.float32),
            pltpu.VMEM((kvh, group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(lens, tables, *operands)
    return out.reshape(b, 1, h, d)


def paged_decode_attention(q, k_pages, v_pages, block_tables, active_len,
                           *, k_scale_pages=None, v_scale_pages=None,
                           scale=None):
    """Backend dispatcher for paged decode attention, mirroring
    :func:`decode_attention`: the block-table kernel on a TPU backend
    (single-token steps only — anything else raises there), the
    gather-then-dense reference on any other (bitwise the dense path on
    float KV — the runtime's paged engine gathers through the same
    tables, so the two agree by construction)."""
    if jax.default_backend() == "tpu":
        return paged_blocked_decode_attention(
            q, k_pages, v_pages, block_tables, active_len,
            k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
            scale=scale)
    return paged_decode_attention_reference(
        q, k_pages, v_pages, block_tables, active_len,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        scale=scale)


def decode_attention(q, k, v, active_len, *, k_scale=None, v_scale=None,
                     scale=None, block_k: int = 128):
    """Backend dispatcher for the ``attn_backend="blocked"`` decode path.

    On a TPU backend: the blocked kernel (real early-exit — bytes scale
    with ``active_len``); a multi-token q or a window that does not tile
    raises there, it is never served by the reference under the
    kernel's name. On any other backend: the pure-jax reference, whose
    output is bitwise the dense path's on float KV — the byte win on the
    XLA path comes from the runtime's window bucketing instead
    (``runtime/continuous.py``), which shrinks ``t`` itself.
    Inputs/shapes as :func:`blocked_decode_attention`."""
    if jax.default_backend() == "tpu":
        return blocked_decode_attention(
            q, k, v, active_len, k_scale=k_scale, v_scale=v_scale,
            scale=scale, block_k=block_k)
    if k_scale is not None:
        k = k.astype(q.dtype) * k_scale.astype(q.dtype)
        v = v.astype(q.dtype) * v_scale.astype(q.dtype)
    return decode_attention_reference(q, k, v, active_len, scale=scale)
