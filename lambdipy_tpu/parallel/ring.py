"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Long-context attention where the sequence is sharded across devices and
K/V blocks rotate around the ring via ``lax.ppermute`` (one ICI hop per
step) while each device accumulates online-softmax partial results for its
local Q block — compute overlaps the rotation, full attention is recovered
exactly, and no device ever materializes more than (s/sp)^2 scores. This is
the blockwise/ring formulation (Liu et al.) expressed the TPU way:
``shard_map`` + XLA collectives over the mesh, not a hand-rolled transport
(SURVEY.md §3.2, §6 long-context row).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


NEG_INF = -1e30


def _block_attend(q, k, v, mask, scale):
    """One blockwise attention contribution. q: [b,sq,h,d]; k/v: [b,sk,h,d];
    mask: bool broadcastable to [b,h,sq,sk], or None. Returns (m, l, acc)
    partials in f32."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [b,h,q]
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would give 1s
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)  # [b,h,q]
    acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(jnp.float32)
    return m_safe, l, acc


def _combine(m1, l1, acc1, m2, l2, acc2):
    """Merge two online-softmax partials."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    # broadcast [b,h,q] coefficients onto [b,q,h,d] accumulators
    def bcast(a):
        return jnp.transpose(a, (0, 2, 1))[..., None]
    acc = acc1 * bcast(a1) + acc2 * bcast(a2)
    return m, l, acc


def _ring_attention_local(q, k, v, km=None, *, axis_name: str, causal: bool,
                          scale: float, vary_axes: tuple[str, ...] = ()):
    """Per-shard body (runs inside shard_map). q/k/v: [b, s_local, h, d];
    km: [b, s_local] bool key-validity block (padding mask) or None — it
    rotates around the ring with its k/v block."""
    sp = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape

    causal_block = jnp.tril(jnp.ones((sq, sq), jnp.bool_)) if causal else None
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # mark the initial accumulators as varying over the ring axis so the
    # scan carry type matches its device-varying outputs (jax vma
    # tracking)
    def varying(x):
        from lambdipy_tpu.parallel.mesh import pcast_varying

        return pcast_varying(x, vary_axes or (axis_name,))

    m0 = varying(jnp.full((b, h, sq), NEG_INF, jnp.float32))
    l0 = varying(jnp.zeros((b, h, sq), jnp.float32))
    acc0 = varying(jnp.zeros((b, sq, h, d), jnp.float32))

    def step(carry, i):
        m, l, acc, kb, vb, kmb = carry
        src = (my - i) % sp  # which global block this kv currently is
        if causal:
            # src < my: fully visible; src == my: causal; src > my: skip
            pos = jnp.where(src < my, jnp.ones((sq, sq), jnp.bool_),
                            jnp.where(src == my, causal_block,
                                      jnp.zeros((sq, sq), jnp.bool_)))
            mask = pos[None, None]  # [1,1,sq,sk]
        else:
            mask = None
        if kmb is not None:
            kmask = kmb[:, None, None, :]  # [b,1,1,sk]
            mask = kmask if mask is None else mask & kmask
        bm, bl, bacc = _block_attend(q, kb, vb, mask, scale)
        m, l, acc = _combine(m, l, acc, bm, bl, bacc)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        if kmb is not None:
            kmb = jax.lax.ppermute(kmb, axis_name, perm)
        return (m, l, acc, kb, vb, kmb), None

    carry0 = (m0, l0, acc0, k, v, None if km is None else km)
    (m, l, acc, _, _, _), _ = jax.lax.scan(step, carry0, jnp.arange(sp))
    l = jnp.maximum(l, 1e-30)
    out = acc / jnp.transpose(l, (0, 2, 1))[..., None]
    return out.astype(q.dtype)


def _sp_chunk_local(q, k, v, mask, *, nblocks: int, scale: float,
                    vary_axes: tuple[str, ...]):
    """Per-shard body for :func:`sp_chunk_attention` (runs inside
    shard_map). q: [b, sq_local, h, d]; k/v: [b, t, h, d] (the FULL,
    replicated cache); mask: [b, sq_local, t] bool. The key axis is
    walked in ``nblocks`` blocks through the same ``_block_attend`` /
    ``_combine`` online-softmax pair the ring path uses, so the combine
    math is block-exact and per-shard score memory is
    (sq/sp) x ceil(t/nblocks), never the full (sq x t) sheet."""
    from lambdipy_tpu.parallel.mesh import pcast_varying

    b, sq, h, d = q.shape
    t = k.shape[1]
    m = pcast_varying(jnp.full((b, h, sq), NEG_INF, jnp.float32), vary_axes)
    l = pcast_varying(jnp.zeros((b, h, sq), jnp.float32), vary_axes)
    acc = pcast_varying(jnp.zeros((b, sq, h, d), jnp.float32), vary_axes)
    kb = -(-t // nblocks)  # ceil
    for i in range(nblocks):
        lo = i * kb
        hi = min(t, lo + kb)
        if lo >= hi:
            break
        bm, bl, bacc = _block_attend(q, k[:, lo:hi], v[:, lo:hi],
                                     mask[:, None, :, lo:hi], scale)
        m, l, acc = _combine(m, l, acc, bm, bl, bacc)
    l = jnp.maximum(l, 1e-30)
    out = acc / jnp.transpose(l, (0, 2, 1))[..., None]
    return out.astype(q.dtype)


def sp_chunk_attention(q, k, v, mask, mesh: Mesh, *, axis: str = "sp",
                       scale: float | None = None):
    """Sequence-parallel prefill-CHUNK attention: the chunk's queries are
    sharded over ``axis`` while the full K/V cache (prefix + this chunk,
    already written at the cache index) stays replicated — each shard
    owns s/sp query rows and attends the whole key range under the
    caller's validity mask. This is the continuation-chunk member of the
    whole-prompt sp-prefill family: the first chunk has no cache and
    ring-shards both operands (:func:`ring_attention`); every later
    chunk reads a cache that decode keeps replicated anyway, so only the
    query/score side shards and no collective is needed beyond the
    out-spec gather.

    q: [b, s, h, d] with ``s`` divisible by the ``axis`` size;
    k/v: [b, t, kvh, d]; mask: [b, s, t] bool (True = attend).
    """
    h, kvh = q.shape[2], k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    sp = mesh.shape[axis]
    if q.shape[1] % sp:
        raise ValueError(
            f"sp_chunk_attention: chunk width {q.shape[1]} not divisible "
            f"by {axis}={sp}")
    batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    bspec = batch_axes if batch_axes else None
    qspec = P(bspec, axis, None, None)
    kspec = P(bspec, None, None, None)
    mspec = P(bspec, axis, None)
    local = partial(_sp_chunk_local, nblocks=sp, scale=scale,
                    vary_axes=batch_axes + (axis,))
    fn = jax.shard_map(local, mesh=mesh,
                          in_specs=(qspec, kspec, kspec, mspec),
                          out_specs=qspec)
    return fn(q, k, v, mask)


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "sp",
                   causal: bool = True, scale: float | None = None,
                   kv_mask=None):
    """Full attention over sequence-sharded q/k/v: [b, s, h, d] with the
    ``s`` dim sharded over ``axis``. GQA kv heads are broadcast first.

    kv_mask: optional [b, s] bool key-validity (padding) mask, sharded like
    the sequence; masked key positions are excluded on every ring step, so
    padded batches attend identically to the dense backend."""
    h, kvh = q.shape[2], k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    spec = P(batch_axes if batch_axes else None, axis, None, None)
    local = partial(_ring_attention_local, axis_name=axis, causal=causal,
                    scale=scale, vary_axes=batch_axes + (axis,))
    if kv_mask is not None:
        mspec = P(batch_axes if batch_axes else None, axis)
        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(spec, spec, spec, mspec), out_specs=spec)
        return fn(q, k, v, kv_mask)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(q, k, v)
