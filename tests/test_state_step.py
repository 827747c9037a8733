"""``ops/state_step.py``: the kernel that steps a batch of recurrent states
in place (``stepped_in_place``, here in the Pallas interpreter) against its
reference (``stepped_reference``), against ``models/kda.py``'s step (the
delta rule on, a channel's decay) and against the Lightning step of
``models/linear_attn.py`` (the delta rule off, a head's decay).

Tolerance, and why. Everything is float32 in all of them; the kernel and
the reference are one function (the decay folded into ``k`` and ``q`` before
they meet the state) whose sums over ``d_k`` run in another order, and the
Lightning step takes ``q`` against the NEW state where they take it against
the old one and add ``(k . q) v``: 1e-5 at states, vectors and outputs of
order 1 (they differ by 1e-6 here). A state or a product in bfloat16 is
5e-3 away, a head's decay in place of a channel's is of order 1.

The last tests hold the two layers' choice between the forms: by
``kernels_compile_here`` and the shape alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.models import kda, linear_attn
from lambdipy_tpu.ops import state_step
from lambdipy_tpu.ops.state_step import (kernel_fits, kernel_vmem_bytes,
                                         stepped_in_place, stepped_reference)

TOY = dict(heads=4, d_k=16, d_v=128)
CELL = dict(heads=32, d_k=128, d_v=128)     # both cells' states
ATOL = 1e-5


def operands(rows, *, heads, d_k, d_v, seed=0, log_decay=None, beta=None,
             channel=True, delta=True):
    """States and vectors of order 1 (``k`` of norm 1 and ``q`` of norm
    ``d_k^-1/2`` a head, as the layers hand them); ``log_decay`` a value for
    every decay, else drawn over (-5, 0); ``beta`` a value, else drawn over
    (0, 1)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.normal(size=shape).astype(np.float32)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    shape = (rows, heads, d_k) if channel else (heads,)
    g = np.full(shape, log_decay, np.float32) if log_decay is not None \
        else rng.uniform(-5, 0, shape).astype(np.float32)
    b = None if not delta else np.full((rows, heads), beta, np.float32) \
        if beta is not None else rng.uniform(0, 1, (rows, heads)).astype(
            np.float32)
    return (jnp.asarray(normal(rows, heads, d_k, d_v)),
            jnp.asarray(unit(normal(rows, heads, d_k)) * d_k ** -0.5),
            jnp.asarray(unit(normal(rows, heads, d_k))),
            jnp.asarray(normal(rows, heads, d_v)), jnp.asarray(g),
            None if b is None else jnp.asarray(b))


def in_place(state, q, k, v, decay, beta):
    rows, heads, d_k, d_v = state.shape
    out, leaf = stepped_in_place(state.reshape(rows, 1, heads * d_k, d_v),
                                 q, k, v, decay, beta, interpret=True)
    assert out.dtype == leaf.dtype == jnp.float32
    assert leaf.shape == (rows, 1, heads * d_k, d_v)
    return np.asarray(out), np.asarray(leaf.reshape(state.shape))


def close(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, np.asarray(b), atol=ATOL, rtol=0)


@pytest.mark.parametrize("rows", [1, 3, 5, 16])
@pytest.mark.parametrize("log_decay,beta", [
    (-5.0, 0.0), (-5.0, 1.0), (-1e-6, 0.0), (-1e-6, 1.0), (None, None)],
    ids=["fast-off", "fast-on", "slow-off", "slow-on", "drawn"])
def test_the_kernel_is_the_kda_step(rows, log_decay, beta):
    """Both ends of the decay's range (-5, 0), ``beta`` 0 (the state only
    decays) and 1 (the whole correction), ragged row counts."""
    state, q, k, v, g, b = operands(rows, **TOY, seed=rows,
                                    log_decay=log_decay, beta=beta)
    want = kda.step(state, q, k, v, g, b)
    close(in_place(state, q, k, v, jnp.exp(g), b), want)
    if beta == 0.0:     # nothing is written but the decay
        np.testing.assert_allclose(
            np.asarray(want[1]), np.asarray(jnp.exp(g)[..., None] * state),
            atol=ATOL, rtol=0)


def lightning_step(state, q, k, v, heads):
    """``models/linear_attn.py attend``'s cache step, without the layer."""
    lam = jnp.exp(-linear_attn.slopes(heads))[None, :, None, None]
    state = lam * state + k[..., :, None] * v[..., None, :]
    return jnp.sum(q[..., :, None] * state, axis=-2), state


@pytest.mark.parametrize("rows", [1, 3, 8])
@pytest.mark.parametrize("shape", [TOY, dict(heads=8, d_k=128, d_v=128)],
                         ids=["toy", "wide"])
def test_the_kernel_is_the_lightning_step(shape, rows):
    state, q, k, v, _, _ = operands(rows, **shape, seed=10 + rows,
                                    delta=False)
    lam = jnp.exp(-linear_attn.slopes(shape["heads"]))
    close(in_place(state, q, k, v, lam, None),
          lightning_step(state, q, k, v, shape["heads"]))


@pytest.mark.parametrize("channel", [True, False], ids=["channel", "head"])
@pytest.mark.parametrize("delta", [True, False], ids=["delta", "plain"])
def test_the_kernel_is_its_reference_under_every_pair_of_flags(channel,
                                                               delta):
    state, q, k, v, g, b = operands(3, **TOY, seed=20, channel=channel,
                                    delta=delta)
    close(in_place(state, q, k, v, jnp.exp(g), b),
          stepped_reference(state, q, k, v, jnp.exp(g), b))


def test_the_kernel_is_its_reference_at_the_cells_widths():
    """32 heads of 128 x 128, two rows: a head's columns sit side by side
    in 96 lanes of one operand."""
    state, q, k, v, g, b = operands(2, **CELL, seed=30)
    close(in_place(state, q, k, v, jnp.exp(g), b),
          kda.step(state, q, k, v, g, b))


@pytest.mark.parametrize("wrong", ["bf16_state", "head_decay"])
def test_the_tolerance_sees_another_result(wrong):
    """What the issue calls a different result, not a faster one, lies far
    outside ``ATOL``: a state rounded to bfloat16, a head's mean decay where
    the model has one a channel."""
    state, q, k, v, g, b = operands(3, **TOY, seed=40)
    want = [np.asarray(a) for a in kda.step(state, q, k, v, g, b)]
    if wrong == "bf16_state":
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        got = in_place(state, q, k, v, jnp.exp(g), b)
    else:
        got = in_place(state, q, k, v,
                       jnp.exp(jnp.mean(g, axis=(0, 2))), b)
    assert max(np.abs(a - b).max() for a, b in zip(got, want)) > 100 * ATOL


@pytest.mark.parametrize("shape,named", [
    (dict(heads=4, d_k=16, d_v=64), "does not tile"),
    (dict(heads=4, d_k=12, d_v=128), "does not tile"),
    (dict(heads=512, d_k=128, d_v=128), "fast memory"),
], ids=["lanes", "sublanes", "vmem"])
def test_the_kernel_refuses_what_it_cannot_tile_or_hold(shape, named):
    heads, d_k, d_v = shape["heads"], shape["d_k"], shape["d_v"]
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, 1, heads * d_k, d_v), (1, heads, d_k), (1, heads, d_k),
        (1, heads, d_v), (1, heads, d_k), (1, heads))]
    assert not kernel_fits(heads, d_k, d_v)
    with pytest.raises(ValueError, match=named):
        jax.eval_shape(lambda *a: stepped_in_place(*a, interpret=True), *args)


def test_the_kernel_refuses_operands_that_disagree():
    state, q, k, v, g, b = operands(2, **TOY)
    with pytest.raises(ValueError, match="shapes disagree"):
        stepped_in_place(state, q, k, v, jnp.exp(g), b, interpret=True)


def test_the_fast_memory_it_asks_for_holds_a_rows_states_four_times():
    need = kernel_vmem_bytes(32, 128, 128)
    assert 4 * 32 * 128 * 128 * 4 < need <= state_step.VMEM_CEILING
    assert kernel_fits(32, 128, 128)


class _Cfg:
    kda_heads = lin_heads = 32
    kda_head_dim = lin_head_dim = 128


@pytest.mark.parametrize("module", [kda, linear_attn], ids=["kda", "linear"])
@pytest.mark.parametrize("compiles,dim,taken", [
    (False, 128, False), (True, 128, True), (True, 16, False)],
    ids=["no-mosaic", "mosaic", "mosaic-but-off-the-tiling"])
def test_a_layer_takes_the_kernel_where_mosaic_compiles_and_the_shape_fits(
        monkeypatch, module, compiles, dim, taken):
    cfg = _Cfg()
    cfg.kda_head_dim = cfg.lin_head_dim = dim
    monkeypatch.setattr(module, "kernels_compile_here", lambda: compiles)
    assert module.steps_in_place(cfg) is taken


@pytest.mark.parametrize("module", [kda, linear_attn], ids=["kda", "linear"])
def test_this_backend_takes_the_reference(module):
    assert not module.steps_in_place(_Cfg())
