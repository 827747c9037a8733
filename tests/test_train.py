"""Sharded train-step tests on the virtual CPU mesh (SURVEY.md §5.4)."""

import pytest
import jax.numpy as jnp
import numpy as np

from lambdipy_tpu.models import registry
from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
from lambdipy_tpu.train.step import sharded_train_step


def test_sharded_train_step_runs_and_loss_decreases(cpu_devices):
    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    with use_mesh(mesh):
        step, state, batch_sharding = sharded_train_step(
            adapter.forward, params, mesh, adapter.tp_rules, learning_rate=5e-3)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, 500, (4, 16)), jnp.int32)
        import jax

        tokens = jax.device_put(tokens, batch_sharding)
        state, m0 = step(state, tokens)
        first = float(m0["loss"])
        for _ in range(5):
            state, m = step(state, tokens)
        assert np.isfinite(first) and float(m["grad_norm"]) > 0
        assert float(m["loss"]) < first  # memorizing a fixed batch
        assert int(jax.device_get(state.step)) == 6


def test_fsdp_params_actually_sharded(cpu_devices):
    import jax
    from jax.sharding import NamedSharding

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    mesh = make_mesh({"dp": 4, "tp": 2})
    with use_mesh(mesh):
        _, state, _ = sharded_train_step(
            adapter.forward, params, mesh, adapter.tp_rules)
    specs = {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf.sharding.spec
        for path, leaf in jax.tree_util.tree_leaves_with_path(state.params)
        if isinstance(leaf.sharding, NamedSharding)
    }
    # at least one kernel carries both dp (fsdp) and tp axes
    assert any("dp" in str(s) and "tp" in str(s) for s in specs.values()), specs


def test_make_optimizer_clips_global_norm():
    from lambdipy_tpu.train.step import make_optimizer

    opt = make_optimizer(1.0, grad_clip=0.5)
    params = {"w": jnp.zeros(4)}
    grads = {"w": jnp.asarray([10.0, 0.0, 0.0, 0.0])}
    state = opt.init(params)
    updates, _ = opt.update(grads, state, params)
    # adamw normalizes magnitudes, but the clip stage must have seen a
    # 0.5-norm gradient: an unclipped 10.0 and a clipped 0.5 gradient
    # produce identical adamw updates only if clipping ran first
    opt_ref = make_optimizer(1.0, grad_clip=None)
    ref_updates, _ = opt_ref.update({"w": jnp.asarray([0.5, 0.0, 0.0, 0.0])},
                                    opt_ref.init(params), params)
    np.testing.assert_allclose(np.asarray(updates["w"]),
                               np.asarray(ref_updates["w"]), rtol=1e-6)


def test_make_optimizer_cosine_schedule_decays():
    import optax

    from lambdipy_tpu.train.step import make_optimizer

    opt = make_optimizer(1e-2, total_steps=10, warmup_steps=2,
                         schedule="cosine", grad_clip=None)
    params = {"w": jnp.ones(2)}
    grads = {"w": jnp.ones(2)}
    state = opt.init(params)
    sizes = []
    for _ in range(10):
        updates, state = opt.update(grads, state, params)
        sizes.append(float(optax.global_norm(updates)))
    assert sizes[0] < sizes[1]          # warmup ramps up
    assert sizes[-1] < sizes[2] / 5     # cosine decays toward 0


def test_make_optimizer_accumulates_gradients():
    from lambdipy_tpu.train.step import make_optimizer

    opt = make_optimizer(1e-2, accum_steps=2, grad_clip=None)
    params = {"w": jnp.ones(2)}
    grads = {"w": jnp.ones(2)}
    state = opt.init(params)
    u1, state = opt.update(grads, state, params)
    assert float(jnp.abs(u1["w"]).max()) == 0.0  # first micro-step: no update
    u2, state = opt.update(grads, state, params)
    assert float(jnp.abs(u2["w"]).max()) > 0.0   # second: params move


@pytest.mark.slow  # heavyweight parity; subsystem keeps a fast test
def test_trainer_with_accumulation_and_schedule(cpu_devices, tmp_path):
    """The full Trainer loop runs with the upgraded optimizer stack."""
    from lambdipy_tpu.data.loader import ShardedLoader, TokenSource
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.parallel.mesh import make_mesh
    from lambdipy_tpu.train.loop import Trainer, TrainerConfig

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    mesh = make_mesh({"dp": 2}, devices=cpu_devices[:2])
    tokens = np.tile(np.arange(50, dtype=np.int32), 40)
    loader = ShardedLoader(TokenSource(tokens, 16), 4, seed=0,
                           process_index=0, process_count=1)
    cfg = TrainerConfig(total_steps=6, log_every=2, grad_clip=0.5,
                        warmup_steps=2, schedule="cosine", accum_steps=2)
    with use_mesh(mesh):
        report = Trainer(adapter.forward, params, mesh, adapter.tp_rules,
                         loader, cfg).run()
    assert report.steps_run == 6
    assert all(np.isfinite(row["loss"]) for row in report.history)
