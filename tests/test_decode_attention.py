"""Length-aware blocked decode attention: reference vs oracle, Pallas
kernel (interpret) vs reference across lengths / window buckets / GQA
group sizes / int8 KV, and the ``attn_backend="blocked"`` model path's
BITWISE on/off parity with the dense decode path — solo, streamed, and
under concurrent continuous-engine traffic (the prefixstore on/off
pattern, applied to the decode side)."""

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.ops.decode_attention import (blocked_decode_attention,
                                               decode_attention,
                                               decode_attention_reference)


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def _masked_mha_oracle(q, k, v, active_len):
    """Independent oracle: broadcast GQA heads, mask by active_len, plain
    softmax attention."""
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    kk = jnp.repeat(k, h // kvh, axis=2)
    vv = jnp.repeat(v, h // kvh, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, kk).astype(jnp.float32)
    logits = logits / jnp.sqrt(d).astype(jnp.float32)
    valid = jnp.arange(t)[None, :] < active_len[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e9)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, vv)


@pytest.mark.parametrize("kvh", [1, 2, 4])
def test_reference_matches_masked_mha(kvh):
    b, h, d, t = 3, 4, 32, 96
    q = _rand((b, 1, h, d), 0)
    k = _rand((b, t, kvh, d), 1)
    v = _rand((b, t, kvh, d), 2)
    alen = jnp.asarray([1, 40, 96], jnp.int32)
    out = decode_attention_reference(q, k, v, alen)
    ref = _masked_mha_oracle(q, k, v, alen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_k", [32, 64, 128])
@pytest.mark.parametrize("kvh", [1, 2])
def test_kernel_matches_reference_across_lengths(block_k, kvh):
    """Interpret-mode kernel vs reference at every interesting active
    length: 1, mid-block, exact block boundary, full window — the
    early-exit masking must agree everywhere."""
    b, h, d, t = 4, 4, 32, 256
    q = _rand((b, 1, h, d), 3)
    k = _rand((b, t, kvh, d), 4)
    v = _rand((b, t, kvh, d), 5)
    alen = jnp.asarray([1, block_k // 2 + 1, block_k, t], jnp.int32)
    out = blocked_decode_attention(q, k, v, alen, block_k=block_k,
                                   interpret=True)
    ref = decode_attention_reference(q, k, v, alen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_int8_kv_matches_dequant_reference():
    b, h, kvh, d, t = 2, 4, 2, 32, 128

    def kvq(x):
        s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0, 1e-8)
        return jnp.round(x / s).astype(jnp.int8), s.astype(jnp.float32)

    q = _rand((b, 1, h, d), 6)
    k_i8, k_s = kvq(_rand((b, t, kvh, d), 7))
    v_i8, v_s = kvq(_rand((b, t, kvh, d), 8))
    alen = jnp.asarray([33, 128], jnp.int32)
    out = blocked_decode_attention(q, k_i8, v_i8, alen, k_scale=k_s,
                                   v_scale=v_s, block_k=64, interpret=True)
    kd = k_i8.astype(q.dtype) * k_s.astype(q.dtype)
    vd = v_i8.astype(q.dtype) * v_s.astype(q.dtype)
    ref = decode_attention_reference(q, kd, vd, alen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_untileable_and_multitoken_raise_in_the_kernel():
    """A window that does not tile, or a multi-token q, is an error in the
    kernel's own name; only the DISPATCHER picks the reference, and only
    off the TPU."""
    b, h, kvh, d = 1, 2, 1, 16
    alen = jnp.asarray([7], jnp.int32)
    q = _rand((b, 1, h, d), 9)
    k, v = _rand((b, 40, kvh, d), 10), _rand((b, 40, kvh, d), 11)
    with pytest.raises(ValueError, match="tiles by block_k"):
        blocked_decode_attention(q, k, v, alen, block_k=16, interpret=True)
    q2 = _rand((b, 2, h, d), 12)
    with pytest.raises(ValueError, match="single-token"):
        blocked_decode_attention(q2, k, v, alen, block_k=8, interpret=True)
    # the dispatcher on CPU routes to the reference outright
    ref = decode_attention_reference(q, k, v, alen)
    out3 = decode_attention(q, k, v, alen)
    assert (np.asarray(out3) == np.asarray(ref)).all()


def test_dispatchers_take_the_kernel_on_a_tpu_backend(monkeypatch):
    """On a TPU backend the dispatcher calls the kernel and lets it raise:
    an untileable window is refused there, never served by the reference
    (the kernel is stubbed — Mosaic does not compile on this CPU)."""
    import lambdipy_tpu.ops.decode_attention as da

    monkeypatch.setattr(da.jax, "default_backend", lambda: "tpu")
    b, h, kvh, d = 1, 2, 1, 16
    alen = jnp.asarray([7], jnp.int32)
    q = _rand((b, 1, h, d), 9)
    k, v = _rand((b, 40, kvh, d), 10), _rand((b, 40, kvh, d), 11)
    with pytest.raises(ValueError, match="tiles by block_k"):
        decode_attention(q, k, v, alen, block_k=16)
    q2 = _rand((b, 2, h, d), 12)
    k_pages, v_pages, tables = _paged_layout(
        _rand((b, 64, kvh, d), 13), _rand((b, 64, kvh, d), 14), 32)
    with pytest.raises(ValueError, match="single-token"):
        da.paged_decode_attention(q2, k_pages, v_pages, tables, alen)


# -- paged (block-table) decode attention ------------------------------------


def _paged_layout(k, v, page, seed=0, n_extra=3):
    """Scatter contiguous per-row KV into a shuffled page arena + the
    block tables naming it, with a zeroed null page at id 0 and a few
    garbage distractor pages — the layout the paged engine produces."""
    b, t, kvh, d = k.shape
    nb = t // page
    rng = np.random.default_rng(seed)
    perm = rng.permutation(b * nb) + 1 + n_extra
    n_pages = b * nb + 1 + n_extra
    k_pages = np.array(
        _rand((n_pages, page, kvh, d), seed + 50))   # garbage everywhere
    v_pages = np.array(_rand((n_pages, page, kvh, d), seed + 51))
    k_pages[0] = 0.0
    v_pages[0] = 0.0
    tables = np.zeros((b, nb), np.int32)
    kr = np.asarray(k).reshape(b * nb, page, kvh, d)
    vr = np.asarray(v).reshape(b * nb, page, kvh, d)
    for i in range(b * nb):
        pid = int(perm[i])
        k_pages[pid] = kr[i]
        v_pages[pid] = vr[i]
        tables[i // nb, i % nb] = pid
    return (jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(tables))


@pytest.mark.parametrize("kvh", [1, 2])
def test_paged_reference_bitwise_vs_dense(kvh):
    """The pure-jax paged oracle over a SHUFFLED page layout is bitwise
    the dense reference on the same values: the gather materializes
    exactly the contiguous KV, and masked positions contribute exact
    zeros either way — even when table entries past a row's length
    point at garbage pages."""
    from lambdipy_tpu.ops.decode_attention import (
        paged_decode_attention_reference)

    b, h, d, t, page = 3, 4, 32, 128, 32
    q = _rand((b, 1, h, d), 20)
    k = _rand((b, t, kvh, d), 21)
    v = _rand((b, t, kvh, d), 22)
    alen = jnp.asarray([1, 33, 128], jnp.int32)
    k_pages, v_pages, tables = _paged_layout(k, v, page, seed=23)
    # past-the-length table entries may point ANYWHERE: null them for
    # rows 0/1 to prove masking covers them
    tables = tables.at[0, 1:].set(0).at[1, 2:].set(0)
    out = paged_decode_attention_reference(q, k_pages, v_pages, tables,
                                           alen)
    ref = decode_attention_reference(q, k, v, alen)
    assert (np.asarray(out) == np.asarray(ref)).all()


@pytest.mark.parametrize("page", [32, 64])
def test_paged_kernel_matches_reference(page):
    """Interpret-mode block-table kernel vs the paged oracle across the
    interesting lengths (1, mid-page, page boundary, full window)."""
    from lambdipy_tpu.ops.decode_attention import (
        paged_blocked_decode_attention, paged_decode_attention_reference)

    b, h, kvh, d, t = 4, 4, 2, 32, 256
    q = _rand((b, 1, h, d), 30)
    k = _rand((b, t, kvh, d), 31)
    v = _rand((b, t, kvh, d), 32)
    alen = jnp.asarray([1, page // 2 + 1, page, t], jnp.int32)
    k_pages, v_pages, tables = _paged_layout(k, v, page, seed=33)
    out = paged_blocked_decode_attention(q, k_pages, v_pages, tables,
                                         alen, interpret=True)
    ref = paged_decode_attention_reference(q, k_pages, v_pages, tables,
                                           alen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_int8_kv_matches_dequant_reference():
    from lambdipy_tpu.ops.decode_attention import (
        paged_blocked_decode_attention, paged_decode_attention_reference)

    b, h, kvh, d, t, page = 2, 4, 2, 32, 128, 32

    def kvq(x):
        s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0,
                        1e-8)
        return jnp.round(x / s).astype(jnp.int8), s.astype(jnp.float32)

    q = _rand((b, 1, h, d), 40)
    k_i8, k_s = kvq(_rand((b, t, kvh, d), 41))
    v_i8, v_s = kvq(_rand((b, t, kvh, d), 42))
    alen = jnp.asarray([33, 128], jnp.int32)
    nb = t // page
    kp = k_i8.reshape(b * nb, page, kvh, d)
    vp = v_i8.reshape(b * nb, page, kvh, d)
    ksp = k_s.reshape(b * nb, page, kvh, 1)
    vsp = v_s.reshape(b * nb, page, kvh, 1)
    tables = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    out = paged_blocked_decode_attention(
        q, kp, vp, tables, alen, k_scale_pages=ksp, v_scale_pages=vsp,
        interpret=True)
    ref = paged_decode_attention_reference(
        q, kp, vp, tables, alen, k_scale_pages=ksp, v_scale_pages=vsp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_paged_dispatcher_serves_the_reference_off_the_tpu():
    """Off the TPU the dispatcher is the reference outright, for any q
    width (on a TPU backend a multi-token q raises in the kernel — see
    test_dispatchers_take_the_kernel_on_a_tpu_backend)."""
    from lambdipy_tpu.ops.decode_attention import (
        paged_decode_attention, paged_decode_attention_reference)

    b, h, kvh, d, t, page = 1, 2, 1, 16, 64, 32
    q = _rand((b, 2, h, d), 45)
    k = _rand((b, t, kvh, d), 46)
    v = _rand((b, t, kvh, d), 47)
    alen = jnp.asarray([40], jnp.int32)
    k_pages, v_pages, tables = _paged_layout(k, v, page, seed=48)
    out = paged_decode_attention(q, k_pages, v_pages, tables, alen)
    ref = paged_decode_attention_reference(q, k_pages, v_pages, tables,
                                           alen)
    assert (np.asarray(out) == np.asarray(ref)).all()


# -- model-path on/off parity ------------------------------------------------


@pytest.fixture(scope="module")
def param_servers():
    """(dense server, blocked server) sharing one set of weights, float
    KV — plus an int8-KV pair. One build per module: server construction
    compiles nothing, but params init is the slow part."""
    from lambdipy_tpu.models import registry

    out = {}
    for kv in (None, "int8"):
        extra = {} if kv is None else {"kv_quant": kv}
        dense = registry.get("llama-tiny").build(extra=dict(extra))
        params = dense.init_params(seed=0)
        blocked = registry.get("llama-tiny").build(
            extra=dict(extra, attn_backend="blocked"))
        out[kv] = (dense.make_server(params), blocked.make_server(params))
    return out


@pytest.mark.parametrize("kv", [None, "int8"])
def test_blocked_backend_bitwise_vs_dense(param_servers, kv):
    """The acceptance bar: blocked decode output equals dense decode
    output BITWISE — float and int8 KV (both read the same dequantized
    values through the same masked math on the reference path), greedy
    and seeded-sampled, ragged batches included."""
    dense, blocked = param_servers[kv]
    rows = [list(range(1, 25)), list(range(7, 14))]
    for kw in ({}, dict(temperature=0.9, seed=11, top_k=7, top_p=0.9)):
        off = dense.generate(rows, max_new_tokens=6, **kw)
        on = blocked.generate(rows, max_new_tokens=6, **kw)
        np.testing.assert_array_equal(on, off, err_msg=f"kv={kv} kw={kw}")


def test_blocked_backend_streaming_parity(param_servers):
    dense, blocked = param_servers[None]
    row = list(range(3, 40))
    off = dense.generate(row, max_new_tokens=6)
    chunks = list(blocked.generate_stream(row, max_new_tokens=6, segment=3))
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), off)


def test_blocked_backend_prefix_cache_parity(param_servers):
    """Blocked decode composes with the prefix-cache continuation: the
    suffix + decode from a cached prefix stays bitwise the dense run."""
    dense, blocked = param_servers[None]
    row = list(range(2, 50))
    off = dense.generate(row, max_new_tokens=6)
    on = blocked.generate(row[32:], prefix=row[:32], max_new_tokens=6)
    np.testing.assert_array_equal(on, off)


# -- windowed continuous engine ---------------------------------------------


def test_windowed_engine_parity_under_concurrent_traffic(param_servers):
    """Window-bucketed segments under concurrent mixed traffic: every
    row's tokens are bitwise its solo dense output, and the engine's
    decode-window counters show it actually read less than the full
    cache."""
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher

    dense, blocked = param_servers[None]
    cb = ContinuousBatcher(blocked, slots=4, segment=4)
    reqs = [
        dict(row=list(range(1, 20)), kw={}),
        dict(row=list(range(30, 70)), kw={}),
        dict(row=[9, 8, 7], kw=dict(temperature=1.1, top_k=3, seed=3)),
    ]
    solo = [dense.generate(r["row"], max_new_tokens=6, **r["kw"])
            for r in reqs]
    with ThreadPoolExecutor(max_workers=3) as ex:
        futs = [ex.submit(cb.generate, r["row"], max_new_tokens=6,
                          **r["kw"]) for r in reqs]
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(f.result(), solo[i],
                                          err_msg=str(reqs[i]))
    win = cb.stats()["decode_window"]
    assert win["segments"] > 0
    assert win["savings_ratio"] < 1.0
    assert win["window_tokens"] < win["full_tokens"]
    assert win["attended_tokens"] <= win["window_tokens"]


def test_windowed_engine_off_is_full_window(param_servers):
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher

    dense, _ = param_servers[None]
    cb = ContinuousBatcher(dense, slots=2, segment=4,
                           window_bucketing=False)
    row = list(range(1, 16))
    np.testing.assert_array_equal(
        cb.generate(row, max_new_tokens=6),
        dense.generate(row, max_new_tokens=6))
    win = cb.stats()["decode_window"]
    assert win["savings_ratio"] == 1.0
    assert list(win["buckets"]) == [str(cb.cache_len)]
