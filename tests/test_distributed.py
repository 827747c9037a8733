"""Multi-host bootstrap + hybrid mesh construction + train checkpoint
resume (single-process exercises of the multi-host code paths)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.parallel.distributed import (
    DistributedContext,
    initialize_from_env,
    make_hybrid_mesh,
    process_batch_slice,
)
from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh


def test_initialize_noop_single_process(monkeypatch):
    for var in ("LAMBDIPY_COORDINATOR", "JAX_COORDINATOR_ADDRESS",
                "LAMBDIPY_NUM_PROCESSES", "JAX_NUM_PROCESSES"):
        monkeypatch.delenv(var, raising=False)
    ctx = initialize_from_env()
    assert ctx == DistributedContext(False, 0, 1, None)
    assert ctx.is_primary


def test_initialize_ignores_single_process_env(monkeypatch):
    monkeypatch.setenv("LAMBDIPY_COORDINATOR", "localhost:1234")
    monkeypatch.setenv("LAMBDIPY_NUM_PROCESSES", "1")
    ctx = initialize_from_env()
    assert not ctx.initialized
    assert ctx.coordinator == "localhost:1234"


def test_hybrid_mesh_single_slice(cpu_devices):
    mesh = make_hybrid_mesh({"dp": 2, "tp": 4})
    assert mesh.axis_names == ("dp", "tp")
    assert dict(mesh.shape) == {"dp": 2, "tp": 4}
    # DCN-ready ordering: tp (innermost) varies fastest over raw devices
    arr = np.asarray(mesh.devices)
    assert [d.id for d in arr[0]] == [0, 1, 2, 3]


def test_hybrid_mesh_dcn_factor(cpu_devices):
    """dcn dp=2 over ici tp=4: each 'slice' (process-contiguous block)
    holds one tp group."""
    mesh = make_hybrid_mesh({"tp": 4}, {"dp": 2})
    assert mesh.axis_names == ("dp", "tp")
    assert dict(mesh.shape) == {"dp": 2, "tp": 4}


def test_hybrid_mesh_validation(cpu_devices):
    with pytest.raises(ValueError):
        make_hybrid_mesh({"xx": 8})
    with pytest.raises(ValueError):
        make_hybrid_mesh({"dp": 3})  # 3 != 8 devices


def test_hybrid_mesh_runs_collectives(cpu_devices):
    """A psum over the hybrid mesh produces correct numbers."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_hybrid_mesh({"dp": 2, "tp": 4})
    x = jnp.arange(8.0)
    with use_mesh(mesh):
        xs = jax.device_put(x.reshape(2, 4), NamedSharding(mesh, P("dp", "tp")))
        total = jax.jit(jnp.sum)(xs)
    assert float(total) == float(x.sum())


def test_process_batch_slice():
    local, offset = process_batch_slice(32)
    assert (local, offset) == (32, 0)
    # explicit multi-process overrides exercise the slicing + the guard
    assert process_batch_slice(32, process_index=3, process_count=4) == (8, 24)
    with pytest.raises(ValueError):
        process_batch_slice(33, process_index=0, process_count=2)


@pytest.mark.slow  # heavyweight parity; subsystem keeps a fast test
def test_train_checkpoint_resume(tmp_path, cpu_devices):
    """Save at steps 1..3, restore latest into a fresh run, training
    continues with identical state (SURVEY.md §6 checkpoint/resume row)."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.train.checkpoint import TrainCheckpointer
    from lambdipy_tpu.train.step import sharded_train_step

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 500, (4, 16)),
                         jnp.int32)

    with use_mesh(mesh):
        step, state, batch_sharding = sharded_train_step(
            adapter.forward, params, mesh, adapter.tp_rules)
        batch = jax.device_put(tokens, batch_sharding)
        with TrainCheckpointer(tmp_path / "ckpt", max_to_keep=2) as ckpt:
            for i in range(1, 4):
                state, _ = step(state, batch)
                assert ckpt.save(i, state)
        final_params = jax.device_get(state.params)

    ckpt2 = TrainCheckpointer(tmp_path / "ckpt")
    assert ckpt2.latest_step() == 3
    assert ckpt2.all_steps() == [2, 3]  # retention pruned step 1

    with use_mesh(mesh):
        step2, state2, batch_sharding2 = sharded_train_step(
            adapter.forward, params, mesh, adapter.tp_rules)
        restored, at = ckpt2.restore(state2)
        assert at == 3
        assert int(restored.step) == 3
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(jax.device_get(a)), np.asarray(b)),
            restored.params, final_params)
        # resumed training takes a step without recompiling state shapes
        state3, metrics = step2(restored, jax.device_put(tokens, batch_sharding2))
        assert int(state3.step) == 4
        assert np.isfinite(float(metrics["loss"]))
    ckpt2.close()


def test_checkpoint_empty_dir(tmp_path):
    from lambdipy_tpu.train.checkpoint import TrainCheckpointer

    ckpt = TrainCheckpointer(tmp_path / "empty")
    state, step = ckpt.restore({"a": jnp.zeros((2,))})
    assert state is None and step is None
    ckpt.close()
