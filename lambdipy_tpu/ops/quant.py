"""Int8 weight-only matmul: Pallas TPU kernel + pure-jax reference.

The HBM-bound op of quantized serving (models/llama.py QDense): weights
live in HBM as int8 + per-output-channel fp32 scales (1 byte/param of
traffic), tiles are upcast to bf16 in VMEM so the MXU still does bf16
math, and the fp32 accumulator is scaled once at finalize. Grid is
(m_blocks, n_blocks, k_blocks) with k innermost — TPU grid execution is
sequential, so the f32 scratch accumulator carries across k steps (same
pattern as ops/attention.py).

The pure-jax ``int8_matmul_reference`` is the numerics oracle and what the
model takes off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def int8_matmul_reference(x, w_i8, scale):
    """x: [m, k] (bf16/f32); w_i8: [k, n] int8; scale: [1, n] f32.
    Returns [m, n] in x.dtype: (x @ dequant(w)) with per-channel scales."""
    w = w_i8.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16)
    return (x.astype(jnp.bfloat16) @ w).astype(x.dtype)


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[...].astype(jnp.bfloat16)
    wb = w_ref[...].astype(jnp.bfloat16)  # int8 -> bf16 upcast in VMEM
    acc_ref[...] += jnp.dot(xb, wb, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def int8_matmul(x, w_i8, scale, *, block_m: int = 128, block_n: int = 128,
                block_k: int = 128, interpret: bool = False):
    """Blocked int8-weight matmul. ``m`` is clamped to one block when it
    is smaller (serving decode has m as small as 1); a shape that then
    does not tile raises ``ValueError``. ``interpret=True`` runs the
    Pallas interpreter instead of compiling for the chip (tests)."""
    m, k = x.shape
    k2, n = w_i8.shape
    if k != k2 or scale.shape != (1, n):
        raise ValueError(f"int8_matmul: operand shapes disagree: x {x.shape}, "
                         f"w {w_i8.shape}, scale {scale.shape}")
    block_m = min(block_m, m)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"int8_matmul: ({m}, {k}) x ({k}, {n}) does not tile by blocks "
            f"({block_m}, {block_k}, {block_n})")
    n_k = k // block_k
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, w_i8, scale)
