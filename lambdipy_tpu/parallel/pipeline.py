"""Pipeline parallelism: GPipe-style microbatched schedule over ``pp``.

The reference has no distributed components (SURVEY.md §3.2); this is new
TPU-first surface. Stage s of the network lives on pp-rank s (stage params
are stacked on a leading dim sharded over ``pp``), a batch is split into
microbatches, and activations flow stage→stage via ``lax.ppermute`` — one
ICI hop per tick, compute overlapping communication, the whole schedule one
``lax.scan`` under jit (no Python control flow, static shapes, SURVEY.md
§6 distributed row).

Schedule: ``num_microbatches + num_stages - 1`` ticks. At tick t, stage 0
ingests microbatch t (while t < nmb), every stage applies its local
``stage_fn``, the last stage banks the finished microbatch ``t - (S-1)``,
and outputs rotate forward. Warmup/drain bubbles run on zero activations
and their outputs are discarded — the standard GPipe bubble cost of
``(S-1)/(nmb+S-1)``, minimized by choosing nmb >> S.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from lambdipy_tpu.parallel.sharding import no_shard_hints


def split_microbatches(batch, num_microbatches: int):
    """[B, ...] -> [nmb, B/nmb, ...] (leading-dim split, order preserved)."""

    def split(leaf):
        b = leaf.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"batch {b} not divisible by num_microbatches={num_microbatches}")
        return leaf.reshape((num_microbatches, b // num_microbatches) + leaf.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def merge_microbatches(out):
    """Inverse of :func:`split_microbatches`."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.reshape((-1,) + leaf.shape[2:]), out)


def stack_stage_params(stage_params: list):
    """Stack S per-stage pytrees (identical treedefs/shapes) into one pytree
    with a leading stage dim, ready to shard ``P("pp", ...)``."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stage_params)


def _pipeline_local(params, x, const, *, stage_fn, axis_name: str,
                    vary_axes: tuple[str, ...]):
    """Per-device body (inside shard_map). params: stage slice with leading
    dim 1; x: [nmb, mb, ...] microbatches (pp-replicated); const: broadcast
    extras passed to every stage_fn call."""
    params = jax.tree_util.tree_map(lambda p: jnp.squeeze(p, axis=0), params)
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    nmb = x.shape[0]
    ticks = nmb + n_stages - 1
    # non-cyclic shift: stage i -> i+1; stage 0 receives zeros (overwritten
    # by the next microbatch), the last stage's output leaves the ring
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def varying(v):
        from lambdipy_tpu.parallel.mesh import pcast_varying

        return pcast_varying(v, vary_axes)

    state0 = varying(jnp.zeros_like(x[0]))
    out0 = varying(jnp.zeros_like(x))

    def tick(carry, t):
        state, out = carry
        x_t = varying(jax.lax.dynamic_index_in_dim(
            x, jnp.minimum(t, nmb - 1), axis=0, keepdims=False))
        inp = jnp.where(stage == 0, x_t, state)
        y = stage_fn(params, inp, const)
        # bank microbatch t-(S-1) on the last stage; other stages keep zeros
        # so the closing psum recovers the result everywhere
        widx = jnp.maximum(t - (n_stages - 1), 0)
        slot = jax.lax.dynamic_index_in_dim(out, widx, axis=0, keepdims=False)
        banked = jnp.where((stage == n_stages - 1) & (t >= n_stages - 1), y, slot)
        out = jax.lax.dynamic_update_index_in_dim(out, banked, widx, axis=0)
        state = jax.lax.ppermute(y, axis_name, perm)
        return (state, out), None

    (_, out), _ = jax.lax.scan(tick, (state0, out0), jnp.arange(ticks))
    return jax.lax.psum(out, axis_name)


def pipeline_apply(stage_fn, stacked_params, microbatches, mesh: Mesh, *,
                   const=None, axis: str = "pp"):
    """Run microbatches through a pp-sharded stage pipeline.

    - ``stage_fn(stage_params, x, const) -> y`` with ``y.shape == x.shape``
      (the GPipe constraint: inter-stage activations are homogeneous);
    - ``stacked_params``: pytree with leading stage dim (see
      :func:`stack_stage_params`), sharded over ``axis``;
    - ``microbatches``: [nmb, mb, ...] array (see :func:`split_microbatches`);
      the mb dim is additionally sharded over dp/fsdp when those axes exist;
    - ``const``: pytree broadcast to every stage call (positions, masks).

    Returns [nmb, mb, ...] outputs, replicated over ``axis``.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
    batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    x_spec = P(None, batch_axes if batch_axes else None)
    params_specs = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    const_specs = jax.tree_util.tree_map(lambda _: P(), const)
    fn = jax.shard_map(
        partial(_pipeline_local, stage_fn=stage_fn, axis_name=axis,
                vary_axes=batch_axes + (axis,)),
        mesh=mesh,
        in_specs=(params_specs, x_spec, const_specs),
        out_specs=x_spec,
    )
    # stage_fn bodies trace inside the manual region — whole-mesh
    # constraints (models' shard_hint calls) must not fire there
    with no_shard_hints():
        return fn(stacked_params, microbatches, const)
