"""Pallas op tests: kernel (interpret mode) vs pure-jax oracle."""

import jax
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


# -- int8 weight-only matmul ------------------------------------------------


def _quant_weights(k, n, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    w = rng.normal(size=(k, n)).astype(np.float32)
    scale = np.abs(w).max(axis=0, keepdims=True) / 127.0
    w_i8 = np.round(w / scale).astype(np.int8)
    return jnp.asarray(w_i8), jnp.asarray(scale)


def test_int8_matmul_kernel_matches_reference():
    import numpy as np

    from lambdipy_tpu.ops.quant import int8_matmul, int8_matmul_reference

    m, k, n = 128, 256, 128
    x = jnp.asarray(np.random.default_rng(0).normal(size=(m, k)), jnp.float32)
    w_i8, scale = _quant_weights(k, n, 1)
    ref = int8_matmul_reference(x, w_i8, scale)
    out = int8_matmul(x, w_i8, scale, block_m=64, block_n=64, block_k=64,
                      interpret=True)
    # kernel applies scales on the f32 accumulator (more precise than the
    # reference's per-element bf16 dequant) -> bf16-rounding-sized deltas
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-2, atol=0.15)


def test_int8_matmul_decode_sized_m_and_odd_shapes():
    """m below one block is clamped (decode has m as small as 1) and runs
    the kernel; k/n that do not tile raise instead of returning the
    reference."""
    import numpy as np

    from lambdipy_tpu.ops.quant import int8_matmul, int8_matmul_reference

    m, k, n = 3, 256, 128
    x = jnp.asarray(np.random.default_rng(2).normal(size=(m, k)), jnp.float32)
    w_i8, scale = _quant_weights(k, n, 3)
    out = int8_matmul(x, w_i8, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(int8_matmul_reference(x, w_i8, scale)),
                               np.asarray(out), rtol=2e-2, atol=0.15)
    w_odd, scale_odd = _quant_weights(96, 80, 4)
    with pytest.raises(ValueError, match="does not tile"):
        int8_matmul(x[:, :96], w_odd, scale_odd, interpret=True)


def test_qdense_pallas_backend_matches_xla(monkeypatch):
    """QDense(int8, backend=pallas) routes through the kernel where kernels
    compile — steered here onto the interpreter, since Mosaic compiles
    only for a TPU backend — and matches the XLA dequant path."""
    import numpy as np

    import lambdipy_tpu.ops as ops
    import lambdipy_tpu.ops.quant as quant
    from lambdipy_tpu.models.llama import QDense

    calls = []
    real = quant.int8_matmul

    def interpreted(*args, **kwargs):
        calls.append(1)
        return real(*args, interpret=True, **kwargs)

    monkeypatch.setattr(ops, "kernels_compile_here", lambda: True)
    monkeypatch.setattr(quant, "int8_matmul", interpreted)

    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 64, 128)),
                    jnp.float32)
    ref_mod = QDense(256, "int8", jnp.float32, "xla")
    params = ref_mod.init(jax.random.PRNGKey(0), x)
    ref = ref_mod.apply(params, x)
    out = QDense(256, "int8", jnp.float32, "pallas").apply(params, x)
    assert calls, "pallas backend did not reach the kernel"
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-2, atol=0.1)
