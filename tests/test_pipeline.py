"""Pipeline parallelism (GPipe over pp) vs sequential application on the
8-device virtual mesh (SURVEY.md §5.4 pattern)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
from lambdipy_tpu.parallel.pipeline import (
    merge_microbatches,
    pipeline_apply,
    split_microbatches,
    stack_stage_params,
)


def _stage_params(n_stages, layers_per_stage, dim, seed=0):
    """Per-stage params: [layers_per_stage] residual-MLP kernels each."""
    rng = np.random.default_rng(seed)
    stages = []
    for _ in range(n_stages):
        stages.append({
            "w": jnp.asarray(
                rng.normal(scale=0.2, size=(layers_per_stage, dim, dim)),
                jnp.float32),
            "b": jnp.asarray(
                rng.normal(scale=0.1, size=(layers_per_stage, dim)), jnp.float32),
        })
    return stages


def _stage_fn(params, x, const):
    for j in range(params["w"].shape[0]):
        x = x + jnp.tanh(x @ params["w"][j] + params["b"][j])
    return x


def _sequential(stages, x):
    for p in stages:
        x = _stage_fn(p, x, None)
    return x


@pytest.mark.parametrize("num_microbatches", [4, 8])
def test_pipeline_matches_sequential(cpu_devices, num_microbatches):
    n_stages, dim, batch = 4, 16, 8
    stages = _stage_params(n_stages, layers_per_stage=2, dim=dim)
    x = jnp.asarray(np.random.default_rng(9).normal(size=(batch, dim)), jnp.float32)
    ref = _sequential(stages, x)

    mesh = make_mesh({"pp": 4}, devices=cpu_devices[:4])
    stacked = stack_stage_params(stages)
    mb = split_microbatches(x, num_microbatches)
    with use_mesh(mesh):
        out = merge_microbatches(pipeline_apply(_stage_fn, stacked, mb, mesh))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_composes_with_dp(cpu_devices):
    n_stages, dim, batch = 4, 8, 8
    stages = _stage_params(n_stages, layers_per_stage=1, dim=dim, seed=3)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(batch, dim)), jnp.float32)
    ref = _sequential(stages, x)

    mesh = make_mesh({"dp": 2, "pp": 4})
    stacked = stack_stage_params(stages)
    mb = split_microbatches(x, 4)
    with use_mesh(mesh):
        out = merge_microbatches(pipeline_apply(_stage_fn, stacked, mb, mesh))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_const_and_jit(cpu_devices):
    """const pytree reaches every stage; the whole schedule jits."""
    n_stages, dim, batch = 2, 8, 4
    stages = _stage_params(n_stages, layers_per_stage=1, dim=dim, seed=5)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(batch, dim)), jnp.float32)
    shift = jnp.float32(0.25)

    def stage_fn(params, x, const):
        return _stage_fn(params, x, None) + const["shift"]

    ref = x
    for p in stages:
        ref = stage_fn(p, ref, {"shift": shift})

    mesh = make_mesh({"pp": 2}, devices=cpu_devices[:2])
    stacked = stack_stage_params(stages)
    mb = split_microbatches(x, 2)
    with use_mesh(mesh):
        fn = jax.jit(lambda s, m: pipeline_apply(
            stage_fn, s, m, mesh, const={"shift": shift}))
        out = merge_microbatches(fn(stacked, mb))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_split_merge_roundtrip():
    x = jnp.arange(24, dtype=jnp.float32).reshape(12, 2)
    mb = split_microbatches(x, 4)
    assert mb.shape == (4, 3, 2)
    np.testing.assert_array_equal(np.asarray(merge_microbatches(mb)), np.asarray(x))
    with pytest.raises(ValueError):
        split_microbatches(x, 5)


def test_pipeline_requires_pp_axis(cpu_devices):
    mesh = make_mesh({"dp": 8})
    stages = _stage_params(2, 1, 4)
    with pytest.raises(ValueError):
        pipeline_apply(_stage_fn, stack_stage_params(stages),
                       split_microbatches(jnp.zeros((4, 4)), 2), mesh)


@pytest.mark.slow  # heavyweight composition parity (tier-1 wall budget); fast siblings cover the mechanism
def test_llama_pipeline_forward_matches(cpu_devices):
    """llama-tiny blocks pipelined over pp=2 reproduce the plain forward."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import pipeline_forward

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, 500, (4, 12)), jnp.int32)
    ref = adapter.forward(params, tokens)

    mesh = make_mesh({"pp": 2}, devices=cpu_devices[:2])
    with use_mesh(mesh):
        out = pipeline_forward(adapter.module, params, tokens, mesh,
                               num_microbatches=2)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # heavyweight composition parity (tier-1 wall budget); fast siblings cover the mechanism
def test_llama_pipeline_forward_composes_with_dp(cpu_devices):
    """pp=2 × dp=2: replicated const broadcasts against dp-local batches."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import pipeline_forward

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    tokens = jnp.asarray(np.random.default_rng(11).integers(0, 500, (4, 8)),
                         jnp.int32)
    ref = adapter.forward(params, tokens)
    mesh = make_mesh({"dp": 2, "pp": 2}, devices=cpu_devices[:4])
    with use_mesh(mesh):
        out = pipeline_forward(adapter.module, params, tokens, mesh,
                               num_microbatches=2)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # heavyweight parity; subsystem keeps a fast test
def test_pipeline_forward_with_moe_blocks(cpu_devices):
    """MoE blocks trace inside the pipeline's manual region: expert
    sharding hints are suppressed there (no whole-mesh constraints inside
    shard_map) and the pp forward still matches the dense forward.

    Ample capacity, deliberately: GShard routing competes for capacity
    within whatever batch it sees, so under capacity pressure a
    microbatched forward legitimately drops different tokens than the
    full-batch one — parity is only defined when nothing overflows."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.models.llama import pipeline_forward

    adapter = registry.get("llama-moe-tiny").build(
        extra={"moe_capacity_factor": 8.0})
    params = adapter.init_params(seed=0)
    tokens = jnp.asarray(np.random.default_rng(9).integers(0, 500, (4, 8)),
                         jnp.int32)
    ref = adapter.forward(params, tokens)
    mesh = make_mesh({"pp": 2}, devices=cpu_devices[:2])
    with use_mesh(mesh):
        out = pipeline_forward(adapter.module, params, tokens, mesh,
                               num_microbatches=2)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-4, atol=1e-4)
