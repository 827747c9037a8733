"""Pallas op tests: kernel (interpret mode) vs pure-jax oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.ops.attention import flash_attention, mha_reference


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    b, s, h, d = 2, 256, 2, 64
    q, k, v = (_rand((b, s, h, d), i) for i in range(3))
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3)


def test_flash_attention_gqa_broadcast():
    b, s, h, kvh, d = 1, 128, 4, 2, 64
    q = _rand((b, s, h, d), 0)
    k = _rand((b, s, kvh, d), 1)
    v = _rand((b, s, kvh, d), 2)
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3)


def test_flash_attention_untileable_raises():
    """A shape the kernel cannot tile is an error in the kernel's own name,
    never a quiet hand-off to the reference."""
    b, s, h, d = 1, 200, 2, 16  # s=200 doesn't tile by 128
    q, k, v = (_rand((b, s, h, d), i) for i in range(3))
    with pytest.raises(ValueError, match="do not tile"):
        flash_attention(q, k, v, causal=True, interpret=True)
