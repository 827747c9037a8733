"""One token's step of a batch of recurrent states: a Pallas TPU kernel that
steps every state IN PLACE, beside its pure-jax reference.

A row-head's state is a ``[d_k, d_v]`` float32 matrix ``S``. With ``a`` the
decay (a value a channel of ``d_k``, or one a head), ``q``, ``k`` ``[d_k]``,
``v`` ``[d_v]`` and ``beta`` a scalar, a step is

    u  = beta (v - S^T (a * k))        the delta rule; u = v without it
    o  = S^T (a * q) + (k . q) u
    S' = a * S + k u^T                 (a * S: row c of S times a_c)

which is ``models/kda.py``'s step with the delta rule on and a channel's
decay (Kimi Delta Attention) and ``models/linear_attn.py``'s with it off and
a head's (Lightning): ``o`` is ``S'^T q`` in both. States, decays and every
product that touches a state are float32, the decay folded into the two
vectors before they meet the state.

``stepped_reference`` is that arithmetic as XLA runs it: one multiply-reduce
over every state for ``S^T (a * k)`` and ``S^T (a * q)``, then the fusion
that writes ``S'``: two reads and a write of every state, since a reduce's
result cannot feed an elementwise update of its own operand in one fusion.
``stepped_in_place`` takes the state LEAF as the cache keeps it, ``[rows, 1,
heads x d_k, d_v]``; a grid step brings one row's states into the fast
memory (2 MB at 32 heads of 128 x 128), takes both products and the update
there on the vector unit, and sends them home into the buffer they came
from (``input_output_aliases``): every state is read once and written once.
The vectors that multiply along ``d_k`` must vary along SUBLANES and be
broadcast along lanes; an operand ``[.., d_k, 1]`` would be padded to 128
lanes in HBM and weigh what the state does, so XLA hands them over as one
``[rows, d_k, heads x n]`` array (64 KB a row at 32 heads) whose columns the
kernel broadcasts; the scalars a head (``beta``, ``k . q``, a head's decay)
come through the scalar memory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8                # float32 rows of one tile
LANES = 128
VMEM_CEILING = 100 << 20    # of a v5e core's 128 MiB


def stepped_reference(state, q, k, v, decay, beta=None):
    """The reference, and what a backend without Mosaic serves. ``state``
    ``[b, heads, d_k, d_v]``, ``q``, ``k`` ``[b, heads, d_k]``, ``v`` ``[b,
    heads, d_v]``, ``decay`` ``[b, heads, d_k]`` (a channel's) or ``[heads]``
    (a head's), ``beta`` ``[b, heads]`` or None (no delta rule), float32.
    Returns ``(o [b, heads, d_v], the new state)``. Multiply-reduces in
    float32: 16 rows x 32 heads x 128 x 128."""
    if decay.ndim == 1:
        decay = decay[None, :, None]
    # (a S)^T k and (a S)^T q in ONE pass over the state, the decay folded
    # into the two vectors: a decayed copy of the state would be written
    # and read back (compiled text for a v5e, PR 41)
    both = jnp.sum(state[:, :, None]
                   * (decay[:, :, None] * jnp.stack([k, q], axis=2))[..., None],
                   axis=-2)
    u = v if beta is None else beta[..., None] * (v - both[:, :, 0])
    out = both[:, :, 1] + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return out, decay[..., None] * state + k[..., :, None] * u[..., None, :]


def _kernel(*refs, heads: int, d_k: int, n_cols: int, delta: bool):
    scalars, (cols_ref, v_ref, state_ref, out_ref, new_ref) = \
        refs[:-5], refs[-5:]
    kq_ref, rest = scalars[0], list(scalars[1:])
    beta_ref = rest.pop(0) if delta else None
    decay_ref = rest.pop(0) if n_cols == 2 else None
    row = pl.program_id(0)
    d_v = state_ref.shape[-1]

    for h in range(heads):      # static: a head's columns are lane offsets
        s = state_ref[h * d_k:(h + 1) * d_k, :]

        def column(j):
            c = h * n_cols + j
            return jnp.broadcast_to(cols_ref[:, c:c + 1], (d_k, d_v))

        k_b, q_b = column(0), column(1)
        a_b = column(2) if decay_ref is None else decay_ref[0, h]
        u = v_ref[h:h + 1, :]
        if delta:
            u = beta_ref[row, h] * (u - jnp.sum(s * (a_b * k_b), axis=0,
                                                keepdims=True))
        out_ref[h:h + 1, :] = jnp.sum(s * (a_b * q_b), axis=0,
                                      keepdims=True) + kq_ref[row, h] * u
        new_ref[h * d_k:(h + 1) * d_k, :] = a_b * s + k_b * u


def kernel_vmem_bytes(heads: int, d_k: int, d_v: int) -> int:
    """Fast memory :func:`stepped_in_place` needs: one row's states twice
    each way (the pipeline's two buffers in, two out), its columns (padded
    to whole lane tiles) and vectors twice, and room for a head's
    intermediates."""
    lanes = -(-heads * 3 // LANES) * LANES
    return 4 * (4 * heads * d_k * d_v + 2 * d_k * lanes
                + 4 * heads * d_v) + (4 << 20)


def kernel_fits(heads: int, d_k: int, d_v: int) -> bool:
    """Whether :func:`stepped_in_place` takes states of this shape: what a
    model layer asks before it chooses the kernel."""
    return not (d_v % LANES or d_k % SUBLANES) \
        and kernel_vmem_bytes(heads, d_k, d_v) <= VMEM_CEILING


def stepped_in_place(leaf, q, k, v, decay, beta=None, *,
                     interpret: bool = False):
    """The kernel: ``leaf`` ``[rows, 1, heads x d_k, d_v]`` float32, the
    other operands as :func:`stepped_reference` (``decay.ndim`` says whose
    the decay is, ``beta is None`` that the delta rule is off). Returns
    ``(o [rows, heads, d_v] float32, the new leaf)``; the new leaf IS the
    old one's buffer where the caller donates it. ``interpret=True`` runs
    the Pallas interpreter instead of compiling for the chip (tests).
    Raises ``ValueError`` for widths off the (8, 128) tiling and for a row
    whose double-buffered states exceed the core's fast memory."""
    rows, heads, d_k = k.shape
    d_v = v.shape[-1]
    channel = decay.ndim == 3
    if (leaf.shape != (rows, 1, heads * d_k, d_v) or q.shape != k.shape
            or v.shape != (rows, heads, d_v)
            or decay.shape != (k.shape if channel else (heads,))
            or (beta is not None and beta.shape != (rows, heads))):
        raise ValueError(
            f"stepped_in_place: operand shapes disagree: leaf {leaf.shape}, "
            f"q {q.shape}, k {k.shape}, v {v.shape}, decay {decay.shape}, "
            f"beta {None if beta is None else beta.shape}")
    if d_v % LANES or d_k % SUBLANES:
        raise ValueError(
            f"stepped_in_place: a state of ({d_k}, {d_v}) does not tile by "
            f"({SUBLANES}, {LANES})")
    need = kernel_vmem_bytes(heads, d_k, d_v)
    if need > VMEM_CEILING:
        raise ValueError(
            f"stepped_in_place: one row's {heads} states of {d_k} x {d_v} "
            f"float32 need {need >> 20} MiB of fast memory; the kernel holds "
            "a row's states whole")

    f32 = jnp.float32
    cols = [k, q] + ([decay] if channel else [])
    n_cols = len(cols)
    # [rows, d_k, heads x n]: d_k on sublanes, a head's vectors side by side
    cols = jnp.stack(cols, axis=-1).astype(f32).transpose(0, 2, 1, 3) \
        .reshape(rows, d_k, heads * n_cols)
    scalars = [jnp.sum(k * q, axis=-1).astype(f32)]
    if beta is not None:
        scalars.append(beta.astype(f32))
    if not channel:
        scalars.append(decay.astype(f32)[None])

    def of_row(row):
        return (row, 0, 0)

    state_spec = pl.BlockSpec((None, None, heads * d_k, d_v),
                              lambda row: (row, 0, 0, 0))
    return tuple(pl.pallas_call(
        functools.partial(_kernel, heads=heads, d_k=d_k, n_cols=n_cols,
                          delta=beta is not None),
        grid=(rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(scalars)
        + [pl.BlockSpec((None, d_k, heads * n_cols), of_row),
           pl.BlockSpec((None, heads, d_v), of_row), state_spec],
        out_specs=[pl.BlockSpec((None, heads, d_v), of_row), state_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, heads, d_v), f32),
                   jax.ShapeDtypeStruct(leaf.shape, f32)],
        input_output_aliases={len(scalars) + 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=need),
        name="stepped_in_place",
        interpret=interpret,
    )(*scalars, cols, v.astype(f32), leaf))
