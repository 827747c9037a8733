"""Ring attention (sequence parallel) vs full attention on the 8-device
virtual mesh (SURVEY.md §5.4 pattern)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.ops.attention import mha_reference
from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
from lambdipy_tpu.parallel.ring import ring_attention


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(cpu_devices, causal):
    b, s, h, d = 2, 64, 2, 16  # s shards 8 ways -> 8 tokens per device
    q, k, v = (_rand((b, s, h, d), i) for i in range(3))
    ref = mha_reference(q, k, v, causal=causal)
    mesh = make_mesh({"sp": 8})
    with use_mesh(mesh):
        out = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_gqa(cpu_devices):
    b, s, h, kvh, d = 1, 32, 4, 2, 16
    q = _rand((b, s, h, d), 0)
    k = _rand((b, s, kvh, d), 1)
    v = _rand((b, s, kvh, d), 2)
    ref = mha_reference(q, k, v, causal=True)
    mesh = make_mesh({"sp": 8})
    with use_mesh(mesh):
        out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # heavyweight composition parity (tier-1 wall budget); fast siblings cover the mechanism
def test_ring_attention_composes_with_dp(cpu_devices):
    b, s, h, d = 4, 16, 2, 8
    q, k, v = (_rand((b, s, h, d), i) for i in range(3))
    ref = mha_reference(q, k, v, causal=True)
    mesh = make_mesh({"dp": 2, "sp": 4})
    from jax.sharding import NamedSharding, PartitionSpec as P

    with use_mesh(mesh):
        qs = jax.device_put(q, NamedSharding(mesh, P("dp", "sp")))
        ks = jax.device_put(k, NamedSharding(mesh, P("dp", "sp")))
        vs = jax.device_put(v, NamedSharding(mesh, P("dp", "sp")))
        out = ring_attention(qs, ks, vs, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # heavyweight parity; subsystem keeps a fast test
def test_llama_ring_backend_matches_dense(cpu_devices):
    """Llama prefill with attn_backend='ring' on an sp mesh must match the
    dense single-device forward — the long-context serving path."""
    import dataclasses

    from lambdipy_tpu.models.llama import LLAMA_TINY, LlamaModel
    from lambdipy_tpu.parallel.mesh import use_mesh

    cfg_dense = dataclasses.replace(LLAMA_TINY, max_len=64)
    cfg_ring = dataclasses.replace(cfg_dense, attn_backend="ring")
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 500, (1, 32)),
                         jnp.int32)
    model_d = LlamaModel(cfg_dense)
    params = model_d.init(jax.random.PRNGKey(0), tokens)
    ref, _ = model_d.apply(params, tokens)

    model_r = LlamaModel(cfg_ring)
    mesh = make_mesh({"sp": 8})
    with use_mesh(mesh):
        out, _ = model_r.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=5e-4, atol=5e-4)


def test_llama_flash_backend_matches_dense():
    import dataclasses

    from lambdipy_tpu.models.llama import LLAMA_TINY, LlamaModel

    cfg_dense = dataclasses.replace(LLAMA_TINY, max_len=256)
    cfg_flash = dataclasses.replace(cfg_dense, attn_backend="flash")
    tokens = jnp.asarray(np.random.default_rng(4).integers(0, 500, (1, 128)),
                         jnp.int32)
    model_d = LlamaModel(cfg_dense)
    params = model_d.init(jax.random.PRNGKey(0), tokens)
    ref, _ = model_d.apply(params, tokens)
    out, _ = LlamaModel(cfg_flash).apply(params, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=5e-4, atol=5e-4)


def test_ring_attention_respects_padding_mask(cpu_devices):
    """A padded batch attends identically under ring and dense backends —
    the kv mask rides the ring with its k/v block (VERDICT r2 weak #8)."""
    import numpy as np
    from lambdipy_tpu.models.llama import _attend
    from lambdipy_tpu.parallel.mesh import make_mesh
    from lambdipy_tpu.parallel.ring import ring_attention

    rng = np.random.default_rng(0)
    b, s, h, d = 2, 16, 4, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    lengths = np.array([11, 7])
    mask = jnp.asarray(np.arange(s)[None, :] < lengths[:, None])

    causal = jnp.tril(jnp.ones((s, s), dtype=jnp.bool_))
    dense = _attend(q, k, v, mask[:, None, :] & causal[None, :, :])

    mesh = make_mesh({"sp": 4}, devices=cpu_devices[:4])
    ring = ring_attention(q, k, v, mesh, causal=True, kv_mask=mask)
    # compare only valid query rows (pad-row outputs are garbage by design)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(dense)[row, :n],
                                   np.asarray(ring)[row, :n],
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# sequence-parallel DECODE (parallel/spdecode.py): the long-context decode
# path pairing with ring-attention prefill


def test_sp_decode_step_matches_dense_reference(cpu_devices):
    """One decode step over an sp-sharded cache == write-then-masked
    dense attention, for ragged per-row positions, including the
    updated cache blocks."""
    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.models.llama import _attend
    from lambdipy_tpu.parallel.mesh import make_mesh
    from lambdipy_tpu.parallel.spdecode import sp_decode_step

    rng = np.random.default_rng(0)
    b, T, kvh, d, h = 3, 32, 2, 16, 8
    mesh = make_mesh({"sp": 4}, devices=cpu_devices[:4])
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((b, 1, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((b, 1, kvh, d)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((b, T, kvh, d)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((b, T, kvh, d)), jnp.float32)
    idx = jnp.asarray([5, 17, 31], jnp.int32)
    with use_mesh(mesh):
        out, ncache = jax.jit(
            lambda *a: sp_decode_step(*a, mesh=mesh))(
            q, {"k": kn, "v": vn}, {"k": ck, "v": cv}, idx)
    rows = jnp.arange(b)
    rk = ck.at[rows, idx].set(kn[:, 0])
    rv = cv.at[rows, idx].set(vn[:, 0])
    valid = jnp.arange(T)[None, None, :] <= idx[:, None, None]
    ref = _attend(q, rk, rv, jnp.broadcast_to(valid, (b, 1, T)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(ncache["k"]), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(ncache["v"]), np.asarray(rv))


@pytest.mark.slow  # three meshed serves (~31 s); the sp_decode_step unit
# parity and the engine-over-sp test keep fast coverage
def test_sp_serve_decode_matches_unsharded(cpu_devices, count_sp_decode):
    """The full serving path with attn_backend='ring' over an sp mesh —
    ring prefill + sequence-sharded flash-decoding steps — produces the
    dense unsharded server's greedy tokens, rectangular and ragged,
    and composes with tp. The sp path is asserted to actually TRACE
    (code-review r5: the builder silently dropped extra and this test
    was dense-vs-dense)."""
    import jax

    from lambdipy_tpu.models import registry
    from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
    from lambdipy_tpu.parallel.sharding import shard_params

    calls = count_sp_decode

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    ref_server = adapter.make_server(params)
    ref = ref_server.generate([5, 6, 7, 8], max_new_tokens=8)
    ref_rag = ref_server.generate([[5, 6, 7, 8], [1, 2]],
                                  max_new_tokens=8)
    assert calls["n"] == 0  # the dense reference never touches sp

    ring = registry.get("llama-tiny").build(
        extra={"attn_backend": "ring"})
    assert ring.config.attn_backend == "ring"
    mesh = make_mesh({"sp": 2}, devices=cpu_devices[:2])
    with use_mesh(mesh):
        sp_params = shard_params(params, mesh, ring.tp_rules)
    server = ring.make_server(sp_params, mesh=mesh)
    np.testing.assert_array_equal(
        server.generate([5, 6, 7, 8], max_new_tokens=8), ref)
    assert calls["n"] > 0, "sp decode path never traced"
    np.testing.assert_array_equal(
        server.generate([[5, 6, 7, 8], [1, 2]], max_new_tokens=8),
        ref_rag)

    mesh2 = make_mesh({"sp": 2, "tp": 2}, devices=cpu_devices[:4])
    with use_mesh(mesh2):
        p2 = shard_params(params, mesh2, ring.tp_rules)
    server2 = ring.make_server(p2, mesh=mesh2)
    np.testing.assert_array_equal(
        server2.generate([5, 6, 7, 8], max_new_tokens=8), ref)


def test_sp_decode_strongly_negative_logits_with_empty_shards(cpu_devices):
    """Early decode (only position 0 valid -> most shards empty) with a
    strongly negative max logit: the combine must pmax raw maxima with
    the -inf sentinel, not the zero-filled safe maxima — otherwise the
    rescale underflows and the output collapses to 0/garbage."""
    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.models.llama import _attend
    from lambdipy_tpu.parallel.mesh import make_mesh
    from lambdipy_tpu.parallel.spdecode import sp_decode_step

    b, T, kvh, d, h = 1, 8, 1, 4, 2
    mesh = make_mesh({"sp": 4}, devices=cpu_devices[:4])
    q = jnp.zeros((b, 1, h, d), jnp.float32).at[..., 0].set(100.0)
    ck = jnp.zeros((b, T, kvh, d), jnp.float32)
    cv = jnp.asarray(
        np.arange(b * T * kvh * d, dtype=np.float32).reshape(
            b, T, kvh, d))
    # THIS STEP's key (written at pos 0, the only valid position) is
    # strongly anti-aligned: the one real logit is ~ -5000, far below
    # the 0.0 the zero-filled empty-shard maxima would clamp pmax to
    kn = jnp.zeros((b, 1, kvh, d), jnp.float32).at[..., 0].set(-100.0)
    vn = jnp.full((b, 1, kvh, d), 7.0, jnp.float32)
    idx = jnp.asarray([0], jnp.int32)  # writes pos 0; only pos 0 valid
    with use_mesh(mesh):
        out, _ = jax.jit(
            lambda *a: sp_decode_step(*a, mesh=mesh))(
            q, {"k": kn, "v": vn}, {"k": ck, "v": cv}, idx)
    rows = jnp.arange(b)
    rk = ck.at[rows, idx].set(kn[:, 0])
    rv = cv.at[rows, idx].set(vn[:, 0])
    valid = jnp.arange(T)[None, None, :] <= idx[:, None, None]
    ref = _attend(q, rk, rv, jnp.broadcast_to(valid, (b, 1, T)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow  # heavyweight composition parity (tier-1 wall budget); fast siblings cover the mechanism
def test_sp_decode_int8_kv_matches_replicated_int8(cpu_devices, count_sp_decode):
    """kv_quant='int8' composes with sp decode: the int8 cache leaves
    shard over sp, the sp path traces, and serve outputs match the
    REPLICATED int8-KV server (same quantization, different reduction
    layout)."""
    import dataclasses

    import jax

    from lambdipy_tpu.models.llama import (LLAMA_TINY, LlamaModel,
                                           LlamaServer)
    from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh

    calls = count_sp_decode

    cfg = dataclasses.replace(LLAMA_TINY, kv_quant="int8")
    module = LlamaModel(cfg)
    tokens = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    params = module.init(jax.random.PRNGKey(0), tokens)
    ref = LlamaServer(module, params).generate([5, 6, 7, 8],
                                               max_new_tokens=8)
    assert calls["n"] == 0

    ring_cfg = dataclasses.replace(cfg, attn_backend="ring")
    mesh = make_mesh({"sp": 2}, devices=cpu_devices[:2])
    # params replicated; the server enters the mesh itself
    server = LlamaServer(LlamaModel(ring_cfg), params, mesh=mesh)
    out = server.generate([5, 6, 7, 8], max_new_tokens=8)
    assert calls["n"] > 0, "int8 sp decode never traced"
    np.testing.assert_array_equal(out, ref)
