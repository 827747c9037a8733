"""Numerics debug checks (utils/debug.py; SURVEY.md §6 sanitizer row)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.utils.debug import apply_debug_env, debug_numerics


def test_debug_numerics_raises_at_producing_op():
    @jax.jit
    def bad(x):
        return jnp.sqrt(x) + 1.0  # sqrt(-1) -> nan

    # silently nan without the sanitizer...
    assert np.isnan(float(bad(jnp.float32(-1.0))))
    # ...raises with it
    with debug_numerics():
        with pytest.raises(FloatingPointError):
            jax.block_until_ready(bad(jnp.float32(-1.0)))


def test_debug_numerics_restores_flags():
    prior = jax.config.jax_debug_nans
    with debug_numerics():
        assert jax.config.jax_debug_nans is True
    assert jax.config.jax_debug_nans == prior


def test_apply_debug_env(monkeypatch):
    monkeypatch.setenv("LAMBDIPY_DEBUG_NANS", "1")
    try:
        assert apply_debug_env() == {"debug_nans": True}
        assert jax.config.jax_debug_nans is True
    finally:
        jax.config.update("jax_debug_nans", False)


@pytest.mark.slow  # heavyweight parity; subsystem keeps a fast test
def test_trainer_debug_numerics_catches_nan(cpu_devices):
    """A poisoned step fails fast under TrainerConfig.debug_numerics
    instead of logging nan losses forever."""
    from lambdipy_tpu.data.loader import ShardedLoader, TokenSource
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
    from lambdipy_tpu.train.loop import Trainer, TrainerConfig

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    # poison one weight: the forward nans immediately
    params["params"]["layer_0"]["q_proj"]["kernel"] = (
        params["params"]["layer_0"]["q_proj"]["kernel"].at[0, 0].set(jnp.nan))
    mesh = make_mesh({"dp": 2}, devices=cpu_devices[:2])
    tokens = np.tile(np.arange(50, dtype=np.int32), 40)
    loader = ShardedLoader(TokenSource(tokens, 16), 4, seed=0,
                           process_index=0, process_count=1)
    cfg = TrainerConfig(total_steps=2, log_every=1, debug_numerics=True)
    with use_mesh(mesh):
        trainer = Trainer(adapter.forward, params, mesh, adapter.tp_rules,
                          loader, cfg)
        with pytest.raises(FloatingPointError):
            trainer.run()
