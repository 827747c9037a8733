"""Flash attention: Pallas TPU kernel + pure-jax reference.

Online-softmax blocked attention (one pass over K/V with running max/sum),
the standard memory-bound formulation: K/V tiles stream through VMEM, the
(s x s) score matrix never materializes in HBM. Grid is
(batch*heads, q_blocks, k_blocks) with the k dimension innermost — TPU grid
execution is sequential, so the f32 scratch accumulators carry across k
steps and are finalized on the last one.

The pure-jax `mha_reference` is the numerics oracle (tests run the kernel
in interpret mode against it) and what the model takes off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def mha_reference(q, k, v, *, causal: bool = False, scale: float | None = None):
    """Plain attention. q/k/v: [b, s, h, d] (kv may have fewer heads for GQA
    — they are broadcast). Returns [b, s, h, d]."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sk = k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # [bq, d]
    k = k_ref[0]  # [bk, d]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]

    if causal:
        q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)

    m_prev = m_ref[:]  # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)  # [bq, bk] f32
    alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
    l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[:] = m_new
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = False, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Flash attention. q/k/v: [b, s, h, d]; kv heads broadcast for GQA.
    Sequence lengths must tile by the (clamped) block sizes — raises
    ``ValueError`` otherwise. ``interpret=True`` runs the Pallas
    interpreter instead of compiling for the chip (tests)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash_attention: sequence lengths ({sq}, {sk}) do not tile by "
            f"blocks ({block_q}, {block_k})")

    # [b, s, h, d] -> [b*h, s, d]
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf, kf, vf = fold(q), fold(k), fold(v)
    grid = (b * h, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
