"""The routed experts of a call of few tokens: a Pallas TPU kernel that
fetches only the experts the call's rows picked, beside its pure-jax
reference, which runs every expert.

Both compute ``sum_k weights[t, k] * expert(experts[t, k], tokens[t])`` for
SwiGLU experts held as three stacks ``[E, in, out]`` (models/moe.py
``RoutedMLP``), rounded at the same places: tokens and kernels cast to
``dtype`` (an int8 kernel exactly), products accumulated in float32, the
float32 scale of an int8 stack applied AFTER its dot, ``silu(gate) * up``
in float32 and cast to ``dtype`` before the down product, an expert's
float32 result weighted by its float32 gate, the experts added in the order
of their indices.

A decode step of 8 rows x 6 picks needs about 41 of 128 experts a layer.
``streamed_experts`` reads all 128 (three batched products over the whole
stacks; XLA streams them near the HBM roofline, but two thirds of those
bytes are multiplied by zero). ``picked_experts`` makes the sorted list of
the distinct experts in jax, hands it to the kernel as a scalar-prefetch
operand, and the kernel's grid runs over that list: the ``index_map`` of
the stacks selects block ``ids[slot]``, so the pipeline's DMA brings one
needed expert while the last one computes. Every grid step runs ALL the
call's rows through its one expert (at a handful of rows the MXU's time is
moving the kernel through it, whatever the rows) and the dense gate matrix
``[slots, t]`` zeroes the rows that did not pick it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 16               # bfloat16 sublanes of one tile: rows are padded to it
LANES = 128
VMEM_CEILING = 100 << 20    # of a v5e core's 128 MiB


def streamed_experts(tokens, experts, weights, valid, stacks, dtype):
    """The reference, and what a backend without Mosaic serves: the same sum
    as ``models/moe.py grouped_experts`` with EVERY expert run on every
    token and the unchosen weighted zero: three batched products over the
    whole stacks, no sort, no loop. ``stacks``: (gate, up, down), each
    ``(kernel [E, in, out], scale [E, 1, out] or None)``; ``valid`` [t] bool
    or None: an invalid token has no assignment. [t, h] float32.
    (XLA's CPU backend has no bfloat16 x bfloat16 -> float32 product with the
    batch axis in the middle, which the last of the three is: on a CPU serve
    a routed model in float32, as the benchmark's toy twin does. An
    expert-major order runs there too but compiles to other convolutions on
    the chip, so it waits for a PR that measures it: PERF.md section 7.)"""
    t, _ = experts.shape
    num_experts = stacks[0][0].shape[0]
    if valid is not None:
        weights = weights * valid[:, None]
    gate_of = jnp.zeros((t, num_experts), jnp.float32).at[
        jnp.arange(t)[:, None], experts].add(weights)

    def product(spec, rows, stack):
        w, scale = stack
        out = jnp.einsum(spec, rows.astype(dtype), w.astype(dtype),
                         preferred_element_type=jnp.float32)
        return out if scale is None else out * scale[:, 0][None]

    act = jax.nn.silu(product("th,ehm->tem", tokens, stacks[0])) \
        * product("th,ehm->tem", tokens, stacks[1])
    out = product("tem,emh->teh", act, stacks[2])
    return jnp.sum(out * gate_of[:, :, None], axis=1)


def distinct_experts(experts, valid, num_experts: int, slots: int):
    """``experts`` [t, k] int32 -> (ids [slots] int32, count int32): the
    distinct experts the valid rows picked, in the order of their indices,
    the list padded to ``slots`` entries by repeating its last real one (0
    where no row is valid). Compares and sums only: no sort, no scatter."""
    t, k = experts.shape
    index = jnp.arange(num_experts, dtype=jnp.int32)
    hit = experts.reshape(t * k, 1) == index[None]
    if valid is not None:
        hit = hit & jnp.repeat(valid, k)[:, None]
    picked = jnp.any(hit, axis=0)
    rank = jnp.cumsum(picked.astype(jnp.int32)) - 1    # place among the picked
    count = rank[-1] + 1
    place = jnp.minimum(jnp.arange(slots, dtype=jnp.int32),
                        jnp.maximum(count - 1, 0))
    ids = jnp.sum(jnp.where(picked[None] & (rank[None] == place[:, None]),
                            index[None], 0), axis=1)
    return ids, count


def _kernel(ids_ref, count_ref, x_ref, gates_ref, wg_ref, sg_ref, wu_ref,
            su_ref, wd_ref, sd_ref, out_ref, *, dtype):
    del ids_ref   # the index maps' operand
    slot = pl.program_id(0)

    @pl.when(slot == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(slot < count_ref[0])   # a padded slot: nothing fetched, nothing run
    def _expert():
        def product(rows, w_ref, s_ref):
            return jnp.dot(rows, w_ref[...].astype(dtype),
                           preferred_element_type=jnp.float32) * s_ref[...]

        x = x_ref[...]
        act = jax.nn.silu(product(x, wg_ref, sg_ref)) \
            * product(x, wu_ref, su_ref)
        out_ref[...] += product(act.astype(dtype), wd_ref, sd_ref) \
            * gates_ref[...]


def kernel_vmem_bytes(t: int, hidden: int, mid: int, stack_dtype,
                      dtype) -> int:
    """Fast memory :func:`picked_experts` needs for ``t`` rows: an expert's
    three blocks twice (the pipeline's two buffers), once more converted to
    ``dtype``, and the rows' operands and intermediates. Over
    ``VMEM_CEILING`` the kernel refuses (it holds experts whole: one of 3 x
    7168 x 2048 int8 needs 172 MiB), and ``models/moe.py RoutedMLP`` asks
    before it chooses the kernel."""
    rows = -(-t // ROW_TILE) * ROW_TILE
    return 3 * hidden * mid * (2 * jnp.dtype(stack_dtype).itemsize
                               + jnp.dtype(dtype).itemsize) \
        + rows * (hidden + mid) * 16 + (4 << 20)


def picked_experts(tokens, experts, weights, valid, stacks, dtype, *,
                   interpret: bool = False):
    """The kernel: operands as :func:`streamed_experts`; returns (the sum
    [t, h] float32, the number of distinct experts it fetched, int32).
    ``interpret=True`` runs the Pallas interpreter instead of compiling for
    the chip (tests). Raises ``ValueError`` for widths off the 128-lane
    tiling and for experts whose double-buffered blocks exceed the core's
    fast memory."""
    t, k = experts.shape
    (wg, _), (wu, _), (wd, _) = stacks
    num_experts, hidden, mid = wg.shape
    if (tokens.shape != (t, hidden) or wu.shape != wg.shape
            or wd.shape != (num_experts, mid, hidden)):
        raise ValueError(
            f"picked_experts: operand shapes disagree: tokens {tokens.shape}, "
            f"stacks {wg.shape}, {wu.shape}, {wd.shape}")
    if hidden % LANES or mid % LANES:
        raise ValueError(
            f"picked_experts: expert widths ({hidden}, {mid}) do not tile by "
            f"{LANES} lanes")
    rows = -(-t // ROW_TILE) * ROW_TILE
    need = kernel_vmem_bytes(t, hidden, mid, wg.dtype, dtype)
    if need > VMEM_CEILING:
        raise ValueError(
            f"picked_experts: one expert of 3 x {hidden} x {mid} "
            f"{wg.dtype.name} needs {need >> 20} MiB of fast memory beside "
            f"{rows} rows; the kernel holds experts whole")

    slots = min(t * k, num_experts)
    ids, count = distinct_experts(experts, valid, num_experts, slots)
    if valid is not None:
        weights = weights * valid[:, None]
    gates = jnp.sum(jnp.where(experts[None] == ids[:, None, None],
                              weights[None], 0.0), axis=-1)     # [slots, t]
    gates = jnp.pad(gates, ((0, 0), (0, rows - t)))[:, :, None]
    x = jnp.pad(tokens.astype(dtype), ((0, rows - t), (0, 0)))
    operands = []
    for w, scale in stacks:
        if scale is None:   # a float stack: x 1.0 is exact
            scale = jnp.ones((num_experts, 1, w.shape[2]), jnp.float32)
        operands += [w, scale]

    def resident(slot, ids_ref, count_ref):
        return (0, 0)

    def of_slot(slot, ids_ref, count_ref):
        return (slot, 0, 0)

    def of_expert(slot, ids_ref, count_ref):
        # a padded slot names the block already resident: no DMA is issued
        return (ids_ref[slot], 0, 0)

    def expert_specs(fan_in, fan_out):
        return [pl.BlockSpec((None, fan_in, fan_out), of_expert),
                pl.BlockSpec((None, 1, fan_out), of_expert)]

    out = pl.pallas_call(
        functools.partial(_kernel, dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[pl.BlockSpec((rows, hidden), resident),
                      pl.BlockSpec((None, rows, 1), of_slot),
                      *expert_specs(hidden, mid), *expert_specs(hidden, mid),
                      *expert_specs(mid, hidden)],
            out_specs=pl.BlockSpec((rows, hidden), resident)),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=need),
        name="picked_experts",
        interpret=interpret,
    )(ids, count.reshape(1), x, gates, *operands)
    return out[:t], count
