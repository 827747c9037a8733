"""``ops/grouped_experts.py``: the kernel that fetches only the experts a
small call's rows picked (``picked_experts``, here in the Pallas
interpreter) against its reference, which runs every expert
(``streamed_experts``), and against a plain loop over the experts that
rounds where both say they round.

Tolerances, and why. In float32 the three are one function summed in
another order: 2e-5 at results of order 1, as ``tests/test_deepseek_v3.py``
holds the two XLA forms to. In bfloat16 the kernel is held to the loop,
which casts at the same places (tokens, kernels and ``silu(gate) * up`` to
bfloat16, everything else float32), so the two again differ by the order of
float32 sums only (1e-7 here): 2e-5 too, which is forty times under what
one rounding at another place shows (the all-float32 result differs from
the bfloat16 one by 9e-4 to 3e-3 here). XLA's CPU backend cannot run the
reference in bfloat16 (its docstring says which product), so there the loop
stands in for it, and the float32 cases tie the loop to the reference.

The last tests hold ``RoutedMLP``'s choice between the forms: by the call's
token count against the one constant and by ``kernels_compile_here`` alone,
and never while ``init`` is traced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.ops.grouped_experts import (distinct_experts,
                                              picked_experts, streamed_experts)

TOY = dict(e=16, h=128, m=256, k=3)
CELL = dict(e=8, h=2048, m=768, k=6)    # the kanana2-30b cell's widths


def operands(t, *, e, h, m, k, seed=0, quant=True, picks="random"):
    rng = np.random.default_rng(seed)
    stacks = []
    for shape in ((e, h, m), (e, h, m), (e, m, h)):
        if quant:
            stacks.append((
                jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                jnp.asarray(rng.uniform(0.5, 1.5, (e, 1, shape[2]))
                            / (127 * np.sqrt(shape[1])), jnp.float32)))
        else:
            stacks.append((jnp.asarray(
                rng.normal(size=shape) / np.sqrt(shape[1]), jnp.float32),
                None))
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    if picks == "same":         # every row the same k: one real group of slots
        chosen = np.tile(rng.permutation(e)[:k], (t, 1))
    elif picks == "distinct":   # no two picks alike: no padded slot
        chosen = rng.permutation(e)[:t * k].reshape(t, k)
    else:
        chosen = np.stack([rng.permutation(e)[:k] for _ in range(t)])
    w = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    return x, jnp.asarray(chosen, jnp.int32), w, stacks


def looped(x, chosen, w, valid, stacks, dtype):
    """Expert by expert in the order of their indices, rounding where the
    module's docstring says the two forms round."""
    def product(rows, stack, i):
        kernel, scale = stack
        out = jnp.dot(rows.astype(dtype), kernel[i].astype(dtype),
                      preferred_element_type=jnp.float32)
        return out if scale is None else out * scale[i]

    if valid is not None:
        w = w * valid[:, None]
    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(stacks[0][0].shape[0]):
        gate = jnp.sum(jnp.where(chosen == i, w, 0.0), axis=1)
        act = jax.nn.silu(product(x, stacks[0], i)) * product(x, stacks[1], i)
        out = out + product(act, stacks[2], i) * gate[:, None]
    return out


def distinct(chosen, valid=None):
    rows = np.asarray(chosen) if valid is None \
        else np.asarray(chosen)[np.asarray(valid)]
    return len(set(rows.ravel().tolist()))


@pytest.mark.parametrize("tokens", [1, 8, 16, 32])
@pytest.mark.parametrize("shape", [TOY, CELL], ids=["toy", "cell"])
def test_the_kernel_is_the_streamed_sum_in_float32(shape, tokens):
    x, chosen, w, stacks = operands(tokens, **shape, seed=tokens)
    want = streamed_experts(x, chosen, w, None, stacks, jnp.float32)
    got, count = picked_experts(x, chosen, w, None, stacks, jnp.float32,
                                interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(
        np.asarray(looped(x, chosen, w, None, stacks, jnp.float32)),
        np.asarray(want), atol=2e-5, rtol=0)
    assert int(count) == distinct(chosen)


@pytest.mark.parametrize("tokens", [1, 8, 16, 32])
@pytest.mark.parametrize("shape", [TOY, CELL], ids=["toy", "cell"])
def test_the_kernel_rounds_to_bfloat16_where_the_reference_does(shape,
                                                                tokens):
    x, chosen, w, stacks = operands(tokens, **shape, seed=100 + tokens)
    want = np.asarray(looped(x, chosen, w, None, stacks, jnp.bfloat16))
    got, count = picked_experts(x, chosen, w, None, stacks, jnp.bfloat16,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)
    assert int(count) == distinct(chosen)
    # and the tolerance would see a rounding at another place
    exact = np.asarray(looped(x, chosen, w, None, stacks, jnp.float32))
    assert np.abs(exact - want).max() > 10 * 2e-5


@pytest.mark.parametrize("picks,tokens,slots,real", [
    ("same", 8, 16, 3),         # 24 picks of the same 3: 13 padded slots
    ("distinct", 5, 15, 15),    # 15 picks, no two alike: no padded slot
    ("random", 32, 16, 16),     # more picks than experts: a slot an expert
])
def test_padded_slots_fetch_and_add_nothing(picks, tokens, slots, real):
    x, chosen, w, stacks = operands(tokens, **TOY, seed=7, picks=picks)
    ids, count = distinct_experts(chosen, None, TOY["e"], slots)
    assert int(count) == real == distinct(chosen)
    listed = np.asarray(ids).tolist()
    assert listed[:real] == sorted(set(np.asarray(chosen).ravel().tolist()))
    assert listed[real:] == [listed[real - 1]] * (slots - real)
    got, n = picked_experts(x, chosen, w, None, stacks, jnp.float32,
                            interpret=True)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(streamed_experts(x, chosen, w, None, stacks, jnp.float32)),
        atol=2e-5, rtol=0)
    assert int(n) == real


@pytest.mark.parametrize("live", [[True, False, True, True, False, True,
                                   False, False], [False] * 8],
                         ids=["some", "none"])
def test_an_invalid_row_picks_nothing_and_gets_zeros(live):
    x, chosen, w, stacks = operands(8, **TOY, seed=8)
    valid = jnp.asarray(live)
    got, count = picked_experts(x, chosen, w, valid, stacks, jnp.float32,
                                interpret=True)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(streamed_experts(x, chosen, w, valid, stacks,
                                    jnp.float32)), atol=2e-5, rtol=0)
    assert not np.asarray(got)[~np.asarray(live)].any()
    assert int(count) == distinct(chosen, valid)


def test_float_stacks_have_no_scale():
    x, chosen, w, stacks = operands(8, **TOY, seed=9, quant=False)
    got, _ = picked_experts(x, chosen, w, None, stacks, jnp.float32,
                            interpret=True)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(streamed_experts(x, chosen, w, None, stacks, jnp.float32)),
        atol=2e-5, rtol=0)


@pytest.mark.parametrize("shape,why", [
    (dict(e=16, h=64, m=24, k=3), "do not tile"),        # the toy twin's
    (dict(e=16, h=128, m=96, k=3), "do not tile"),
    (dict(e=2, h=8192, m=4096, k=1), "fast memory"),     # 96 MiB an expert
])
def test_a_shape_the_kernel_cannot_tile_raises(shape, why):
    k = shape["k"]
    stacks = [(jax.ShapeDtypeStruct(s, jnp.int8),
               jax.ShapeDtypeStruct((s[0], 1, s[2]), jnp.float32))
              for s in ((shape["e"], shape["h"], shape["m"]),) * 2
              + ((shape["e"], shape["m"], shape["h"]),)]
    with pytest.raises(ValueError, match=why):
        jax.eval_shape(
            lambda x, c, w, s: picked_experts(x, c, w, None, s, jnp.bfloat16,
                                              interpret=True),
            jax.ShapeDtypeStruct((8, shape["h"]), jnp.float32),
            jax.ShapeDtypeStruct((8, k), jnp.int32),
            jax.ShapeDtypeStruct((8, k), jnp.float32), stacks)


def test_operands_that_disagree_raise():
    x, chosen, w, stacks = operands(8, **TOY)
    with pytest.raises(ValueError, match="disagree"):
        picked_experts(x[:, :64], chosen, w, None, stacks, jnp.float32,
                       interpret=True)


# -- the dispatch in RoutedMLP -------------------------------------------------

def routed_mlp():
    from lambdipy_tpu.models.llama import LlamaConfig
    from lambdipy_tpu.models.moe import RoutedMLP

    return RoutedMLP(LlamaConfig(
        hidden=128, dtype=jnp.float32, quant="int8", ffn_kind="routed",
        moe_experts=16, moe_top_k=3, moe_intermediate=128, n_shared_experts=1,
        scoring_func="sigmoid"))


@pytest.fixture
def forms_taken(monkeypatch):
    """Which form each ``RoutedMLP`` call took, with the kernel run in the
    interpreter where a TPU backend would compile it."""
    from lambdipy_tpu.models import moe

    taken = []

    def spy(name, fn, **kw):
        def form(*args):
            taken.append(name)
            return fn(*args, **kw)
        return form

    monkeypatch.setattr(moe, "picked_experts",
                        spy("picked", picked_experts, interpret=True))
    monkeypatch.setattr(moe, "streamed_experts",
                        spy("streamed", streamed_experts))
    monkeypatch.setattr(moe, "grouped_experts",
                        spy("grouped", moe.grouped_experts))
    return taken


@pytest.mark.parametrize("backend_compiles", [False, True],
                         ids=["cpu", "tpu"])
def test_the_form_follows_the_token_count_and_the_backend(
        forms_taken, monkeypatch, backend_compiles):
    from lambdipy_tpu.models import moe

    asked = []
    monkeypatch.setattr(moe, "kernels_compile_here",
                        lambda: asked.append(1) or backend_compiles)
    mlp = routed_mlp()
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, moe.STREAM_ROWS, 128)), jnp.float32)
    params = mlp.init(jax.random.PRNGKey(0), x[:1, :8])
    # init is traced by processes that must not take the chip
    # (benchmark/weights.py, the builder): it never asks for the backend
    assert asked == [] and forms_taken == ["streamed"]
    small = "picked" if backend_compiles else "streamed"
    outs = {}
    for tokens in (8, moe.STREAM_ROWS, 2 * moe.STREAM_ROWS):
        del forms_taken[:]
        rows = x.reshape(1, -1, 128)[:, :tokens]
        (outs[tokens], sown) = mlp.apply(params, rows, mutable=["moe_reads"])
        assert forms_taken == [small if tokens <= moe.STREAM_ROWS
                               else "grouped"], tokens
        read = int(jax.tree.leaves(sown)[0])
        assert 3 <= read <= 16
    # one sum whatever form ran: the first 8 tokens of the larger calls
    for tokens in (moe.STREAM_ROWS, 2 * moe.STREAM_ROWS):
        np.testing.assert_allclose(np.asarray(outs[tokens][:, :8]),
                                   np.asarray(outs[8]), atol=2e-5, rtol=0)
