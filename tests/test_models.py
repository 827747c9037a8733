"""Model family tests on the virtual CPU mesh (SURVEY.md §5 plan items 3-4:
numerics + mesh logic without hardware)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.models import registry


def test_registry_lists_required_models():
    for name in ["resnet50", "bert-base", "llama3-8b", "tabular", "bert-base-torch"]:
        assert name in registry.names()


def test_registry_unknown_model():
    with pytest.raises(registry.ModelError, match="unknown model"):
        registry.get("gpt-17")


def test_resnet_tiny_forward():
    adapter = registry.get("resnet50-tiny").build()
    params = adapter.init_params(seed=0)
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    logits = jax.jit(adapter.forward)(params, x)
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_bert_tiny_forward_mask_matters():
    adapter = registry.get("bert-tiny").build()
    params = adapter.init_params(seed=0)
    cfg = adapter.config
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, cfg.max_len)), jnp.int32)
    full = jnp.ones((2, cfg.max_len), jnp.int32)
    half = full.at[:, cfg.max_len // 2:].set(0)
    out_full = jax.jit(adapter.forward)(params, ids, full)
    out_half = jax.jit(adapter.forward)(params, ids, half)
    assert out_full.shape == (2, cfg.num_classes)
    assert not np.allclose(np.asarray(out_full), np.asarray(out_half))


def test_llama_tiny_prefill_decode_consistency():
    """Teacher-forced prefill logits must match step-by-step decode logits —
    the KV-cache correctness invariant."""
    adapter = registry.get("llama-tiny").build()
    module = adapter.module
    params = adapter.init_params(seed=0)
    cfg = adapter.config
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)

    full_logits, _ = module.apply(params, tokens)

    from lambdipy_tpu.models.llama import init_decode_cache

    cache = init_decode_cache(cfg, batch=1, max_len=16)
    step_logits = []
    for t in range(8):
        positions = jnp.full((1, 1), t, jnp.int32)
        logits, cache = module.apply(params, tokens[:, t:t + 1],
                                     positions=positions, cache=cache)
        for entry in cache:
            entry["index"] = jnp.int32(t + 1)
        step_logits.append(np.asarray(logits[:, 0]))
    np.testing.assert_allclose(
        np.asarray(full_logits[0]), np.stack(step_logits, 1)[0],
        rtol=2e-4, atol=2e-4)


def test_llama_greedy_generate_shapes_and_determinism():
    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    out1 = adapter.generate(params, prompt, max_new_tokens=6)
    out2 = adapter.generate(params, prompt, max_new_tokens=6)
    assert out1.shape == (1, 6)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_llama_int8_quantize_params_close_to_float():
    import dataclasses

    from lambdipy_tpu.models.llama import LLAMA_TINY, LlamaModel, quantize_params

    cfg_f = LLAMA_TINY
    cfg_q = dataclasses.replace(LLAMA_TINY, quant="int8")
    model_f = LlamaModel(cfg_f)
    model_q = LlamaModel(cfg_q)
    tokens = jnp.asarray([[5, 6, 7]], jnp.int32)
    params_f = model_f.init(jax.random.PRNGKey(0), tokens)
    params_q = quantize_params(params_f)
    logits_f, _ = model_f.apply(params_f, tokens)
    logits_q, _ = model_q.apply(params_q, tokens)
    # int8 weight-only quant should track float logits closely on a tiny net
    err = np.max(np.abs(np.asarray(logits_f) - np.asarray(logits_q)))
    scale = np.max(np.abs(np.asarray(logits_f))) + 1e-6
    assert err / scale < 0.1, f"relative error {err / scale}"


def test_llama_tp_sharded_forward_matches_single_device(cpu_devices):
    """TP=4 sharded forward must be numerically identical (up to fp tolerance)
    to the unsharded run — XLA inserts the collectives (SURVEY.md §3.2)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
    from lambdipy_tpu.parallel.sharding import param_shardings, shard_params

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 500, (2, 8)), jnp.int32)
    ref = np.asarray(adapter.forward(params, tokens))

    mesh = make_mesh({"dp": 2, "tp": 4})
    sharded_params = shard_params(params, mesh, adapter.tp_rules)
    shardings = param_shardings(params, mesh, adapter.tp_rules)
    fwd = jax.jit(adapter.forward,
                  in_shardings=(shardings, NamedSharding(mesh, P("dp"))),
                  out_shardings=NamedSharding(mesh, P("dp")))
    with use_mesh(mesh):
        out = fwd(sharded_params, jax.device_put(tokens, NamedSharding(mesh, P("dp"))))
    np.testing.assert_allclose(ref, np.asarray(out), rtol=2e-3, atol=2e-3)


def test_llama_int8_tp_sharded_forward_matches_single_device(cpu_devices):
    """The same under int8 weights on four devices: QDense multiplies the
    ``[1, out]`` scale into the dot's float32 result, beside a kernel
    sharded over its columns (q/k/v/gate/up/lm_head: the scale is sharded
    with them) or over its rows (o/down: the scale is replicated and the
    multiply sits beside the all-reduce, which is linear)."""
    import dataclasses

    from jax.sharding import PartitionSpec as P

    from lambdipy_tpu.models.llama import LLAMA_TINY, LlamaModel, quantize_params
    from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
    from lambdipy_tpu.parallel.sharding import shard_params

    model = LlamaModel(dataclasses.replace(LLAMA_TINY, quant="int8"))
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 500, (2, 8)), jnp.int32)
    params = quantize_params(LlamaModel(LLAMA_TINY).init(jax.random.PRNGKey(0), tokens))
    forward = jax.jit(lambda p, t: model.apply(p, t)[0])
    ref = np.asarray(forward(params, tokens))

    mesh = make_mesh({"tp": 4}, devices=cpu_devices[:4])
    sharded = shard_params(params, mesh, registry.get("llama-tiny").build().tp_rules)
    block = sharded["params"]["layer_0"]
    assert block["q_proj"]["kernel_int8"].sharding.spec == P(None, "tp")
    assert block["q_proj"]["scale"].sharding.spec == P(None, "tp")
    assert block["o_proj"]["kernel_int8"].sharding.spec == P("tp", None)
    with use_mesh(mesh):
        out = forward(sharded, tokens)
    np.testing.assert_allclose(ref, np.asarray(out), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("lead", [(8,), (2, 4), (2, 96)],
                         ids=["2d", "3d", "compute_bound"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_qdense_int8_scale_after_dot(dtype, lead):
    """QDense's int8 path against float64 ``x @ (int8 * scale)`` on a
    non-square 4096 x 1536 kernel with a scale that differs per output
    channel (a wrong broadcast of the ``[1, out]`` scale cannot pass):
    the relative rms error is no larger than that of the expression the
    weight-bound form replaced, ``x @ (int8.astype(dtype) *
    scale.astype(dtype))``, which rounds every dequantized weight to
    ``dtype`` once more (bf16: 0.17 % against 0.27 %) and which the rows
    over ``WEIGHT_BOUND_ROWS`` still take."""
    from lambdipy_tpu.models.llama import WEIGHT_BOUND_ROWS, QDense

    assert (math.prod(lead) > WEIGHT_BOUND_ROWS) == (lead == (2, 96))

    rng = np.random.default_rng(7)
    n_in, n_out = 4096, 1536
    w = rng.integers(-127, 128, (n_in, n_out)).astype(np.int8)
    scale = rng.uniform(0.5, 2.0, (1, n_out)).astype(np.float32) / (127 * 64)
    w8, s = jnp.asarray(w), jnp.asarray(scale)
    x = jnp.asarray(rng.standard_normal((*lead, n_in)), dtype)
    out = QDense(n_out, "int8", dtype).apply(
        {"params": {"kernel_int8": w8, "scale": s}}, x)
    assert out.shape == (*lead, n_out) and out.dtype == dtype
    old = x @ (w8.astype(dtype) * s.astype(dtype))
    exact = np.asarray(x, np.float64) @ (w.astype(np.float64) * scale)

    def rel_rms(y):
        return float(np.sqrt(np.mean((np.asarray(y, np.float64) - exact) ** 2)
                             / np.mean(exact ** 2)))

    # float32 has no extra rounding to lose: both forms sit at its epsilon
    assert rel_rms(out) <= max(rel_rms(old), 1e-5), (rel_rms(out), rel_rms(old))
    assert rel_rms(out) < (4e-3 if dtype == jnp.bfloat16 else 1e-5)


def test_save_and_load_params_roundtrip_jax(tmp_path):
    info = registry.save_init_params("llama-tiny", tmp_path / "p", dtype="float32")
    assert info["format"] == "orbax+fpk" and info["n_params"] > 0
    params = registry.load_params("llama-tiny", tmp_path / "p")
    adapter = registry.get("llama-tiny").build()
    logits = adapter.forward(params, jnp.asarray([[1, 2]], jnp.int32))
    assert logits.shape[-1] == adapter.config.vocab_size


def test_flatpack_load_is_bitwise_equal_to_orbax(tmp_path):
    """The fast boot format and the canonical orbax checkpoint must hold
    identical tensors; removing the .fpk falls back to orbax."""
    import orbax.checkpoint as ocp

    registry.save_init_params("llama-tiny", tmp_path / "p", dtype="float32")
    fpk = registry.load_params("llama-tiny", tmp_path / "p")
    via_orbax = ocp.StandardCheckpointer().restore(
        (tmp_path / "p" / "orbax").resolve())
    flat_a = jax.tree_util.tree_leaves_with_path(fpk)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(via_orbax))
    assert len(flat_a) == len(flat_b) > 0
    for path, leaf in flat_a:
        ref = flat_b[path]
        assert np.asarray(leaf).dtype == np.asarray(ref).dtype
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))
    (tmp_path / "p" / "params.fpk").unlink()
    fallback = registry.load_params("llama-tiny", tmp_path / "p")
    assert len(jax.tree_util.tree_leaves(fallback)) == len(flat_a)


def test_flatpack_roundtrip_dtypes(tmp_path):
    """bf16 / int8 / f32 / scalar leaves survive the flat file bitwise."""
    import ml_dtypes

    from lambdipy_tpu.bundle import flatpack

    tree = {
        "a": {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
              "s": np.float32(3.5)},
        "q": {"kernel_int8": np.arange(-8, 8, dtype=np.int8).reshape(4, 4),
              "scale": np.ones((1, 4), np.float32)},
        "bf": np.arange(6, dtype=np.float32).astype(ml_dtypes.bfloat16),
    }
    stats = flatpack.save(tmp_path / "t.fpk", tree)
    assert stats["n_tensors"] == 5
    out = flatpack.load(tmp_path / "t.fpk")
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        got = out
        for k in path:
            got = got[k.key]
        assert np.asarray(got).dtype == np.asarray(leaf).dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(leaf))


@pytest.mark.slow  # heavyweight parity; subsystem keeps a fast test
def test_int8_kv_cache_decode_close_to_float(tmp_path):
    """kv_quant='int8' halves decode-cache HBM; its decode-step logits
    must stay within quantization tolerance of the float cache, and the
    full serve path (ragged rows, streaming) must run on it."""
    import dataclasses

    from lambdipy_tpu.models.llama import (
        LLAMA_TINY, LlamaModel, LlamaServer, prefill_into_cache)

    base = dataclasses.replace(LLAMA_TINY)
    quant = dataclasses.replace(LLAMA_TINY, kv_quant="int8")
    mf, mq = LlamaModel(base), LlamaModel(quant)
    prompt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7]], jnp.int32)
    params = mf.init(jax.random.PRNGKey(0), prompt)

    logits_f, pc_f = mf.apply(params, prompt)
    logits_q, pc_q = mq.apply(params, prompt)
    np.testing.assert_array_equal(np.asarray(logits_f), np.asarray(logits_q))

    step = jnp.asarray([[9]], jnp.int32)
    pos = jnp.asarray([[7]], jnp.int32)
    out = {}
    for name, (m, pc) in {"f": (mf, pc_f), "q": (mq, pc_q)}.items():
        cache = prefill_into_cache(m.cfg, pc, 1, 32, 7)
        lg, _ = m.apply(params, step, positions=pos, cache=cache)
        out[name] = np.asarray(lg[0, 0], np.float32)
    err = np.abs(out["f"] - out["q"]).max() / max(1e-6, np.abs(out["f"]).max())
    assert err < 0.05, err

    server = LlamaServer(mq, params)
    ragged = server.generate([[1, 2, 3], [4, 5, 6, 7, 8]], max_new_tokens=6)
    assert ragged.shape == (2, 6)
    chunks = list(server.generate_stream([1, 2, 3], max_new_tokens=6,
                                         segment=2))
    assert sum(c.shape[1] for c in chunks) == 6
    via_prefix = server.generate([9, 9], max_new_tokens=4, prefix=[1, 2, 3])
    assert via_prefix.shape == (1, 4)


def test_params_format_fpk_only(tmp_path):
    """params_format='fpk' writes only the flat file (big payloads must
    not ship their dominant bytes twice) and load_params still serves."""
    info = registry.save_init_params("llama-tiny", tmp_path / "p",
                                     dtype="float32", params_format="fpk")
    assert info["format"] == "fpk"
    assert (tmp_path / "p" / "params.fpk").is_file()
    assert not (tmp_path / "p" / "orbax").exists()
    params = registry.load_params("llama-tiny", tmp_path / "p")
    adapter = registry.get("llama-tiny").build()
    logits = adapter.forward(params, jnp.asarray([[1, 2]], jnp.int32))
    assert logits.shape[-1] == adapter.config.vocab_size


def test_serving_cast_applies_when_inert(tmp_path):
    """bf16-serving models whose modules cast params at compute (ResNet,
    BERT) get their f32 kernels stored as bf16 — with a bitwise forward
    parity gate, so the cast can never change served outputs."""
    info = registry.save_init_params("bert-tiny", tmp_path / "p",
                                     dtype="bfloat16")
    assert info["serving_cast"]["applied"], info
    assert info["serving_cast"]["bytes_saved"] > 0
    params = registry.load_params("bert-tiny", tmp_path / "p")
    adapter = registry.get("bert-tiny").build(dtype="bfloat16")
    out = adapter.forward(params, *adapter.example_batch(1))
    assert np.isfinite(np.asarray(out)).all()


def test_serving_cast_rejected_when_numerics_change(tmp_path):
    """A bf16-serving Llama computes its lm_head in f32: casting that
    kernel would change logits, so the parity gate must reject the cast
    and keep f32 weights wholesale."""
    info = registry.save_init_params("llama-tiny", tmp_path / "p",
                                     dtype="bfloat16")
    assert not info["serving_cast"]["applied"], info
    params = registry.load_params("llama-tiny", tmp_path / "p")
    leaves = jax.tree_util.tree_leaves(params)
    assert any(x.dtype == np.float32 and x.ndim >= 2 for x in leaves)


def test_save_and_load_params_sklearn(tmp_path):
    info = registry.save_init_params("tabular", tmp_path / "p")
    assert info["format"] == "joblib"
    clf = registry.load_params("tabular", tmp_path / "p")
    preds = clf.predict(np.zeros((3, info["n_features"])))
    assert preds.shape == (3,)


def test_torch_bert_cpu_smoke(tmp_path):
    import torch

    built = registry.get("bert-base-torch").build(
        extra={"hidden": 32, "layers": 1, "heads": 2, "vocab_size": 100, "max_len": 16})
    model = built["make_model"]()
    with torch.no_grad():
        out = model(torch.zeros(2, 16, dtype=torch.long),
                    torch.ones(2, 16, dtype=torch.long))
    assert out.shape == (2, 2)


def test_llama3_8b_builder_plumbs_backends():
    """Recipe extras select the prefill-attention backend for the
    config-5 model without touching model code; the int8-matmul key that
    once rode beside it is refused by name, for any value (its kernel
    went in PR 29: a bundle that still says it is not silently served)."""
    import pytest as _pytest

    from lambdipy_tpu.models import registry

    spec = registry.get("llama3-8b")
    cfg = spec.build(extra={"attn_backend": "flash",
                            "max_len": 4096}).config
    assert cfg.attn_backend == "flash"
    assert not hasattr(cfg, "matmul_backend")
    for value in ("pallas", "xla"):
        with _pytest.raises(ValueError, match="'matmul_backend'.*PR 29"):
            spec.build(extra={"attn_backend": "flash",
                              "matmul_backend": value})
    assert cfg.max_len == 4096 and cfg.quant == "int8"


def test_llama_builder_rejects_unknown_backend():
    import pytest as _pytest

    from lambdipy_tpu.models import registry

    with _pytest.raises(ValueError, match="attn_backend"):
        registry.get("llama3-8b").build(extra={"attn_backend": "Flash"})
    with _pytest.raises(ValueError, match="'matmul_backend'"):
        registry.get("llama-hf").build(extra={"matmul_backend": "cuda"})
    with _pytest.raises(ValueError, match="'matmul_backend'"):
        registry.get("deepseek-v3").build(extra={"matmul_backend": "xla"})


def test_flatpack_device_load_matches_host_load(tmp_path):
    """device_load (grouped single-buffer uploads + device-side unpack)
    returns bitwise the same tree as the host mmap load: identical-layout
    groups (transformer layers) share one compiled unpack program."""
    import ml_dtypes

    from lambdipy_tpu.bundle import flatpack

    rng = np.random.default_rng(0)
    tree = {"params": {
        "embed": {"embedding": rng.standard_normal((50, 8), np.float32)
                  .astype(ml_dtypes.bfloat16)},
        "final_norm": {"scale": rng.standard_normal((8,)).astype(np.float32)},
    }}
    for i in range(4):  # identical per-layer layout -> one shared program
        tree["params"][f"layer_{i}"] = {
            "q": {"kernel_int8": rng.integers(-127, 128, (8, 8), np.int8),
                  "scale": rng.standard_normal((1, 8)).astype(np.float32)},
            "norm": {"scale": np.ones((8,), np.float32)},
        }
    path = tmp_path / "p.fpk"
    flatpack.save(path, tree)

    host = flatpack.load(path)
    import jax

    def check(dev):
        flat_h = dict(flatpack._flatten(host))
        flat_d = dict(flatpack._flatten(jax.device_get(dev)))
        assert flat_h.keys() == flat_d.keys()
        for k in flat_h:
            assert flat_h[k].dtype == flat_d[k].dtype, k
            np.testing.assert_array_equal(
                np.asarray(flat_h[k]).view(np.uint8),
                np.asarray(flat_d[k]).view(np.uint8), err_msg=str(k))

    before = len(flatpack._unpack_cache)
    check(flatpack.device_load(path))
    # every leaf here is < 1 MB, so the default load rides the global
    # small-leaf buckets: one program per itemsize present (i8/bf16/f32)
    assert len(flatpack._unpack_cache) - before <= 3
    # force the BIG-leaf path (the 8B production route): small_leaf_bytes
    # 0 makes every leaf chunk by (subtree, itemsize), and a tiny
    # chunk_bytes forces intra-subtree splits — parity must hold and the
    # 4 identical layers must SHARE their per-width programs
    before = len(flatpack._unpack_cache)
    check(flatpack.device_load(path, chunk_bytes=256,
                               small_leaf_bytes=0))
    grown = len(flatpack._unpack_cache) - before
    # layers share signatures: programs grow by the distinct layouts of
    # (embed, final_norm, ONE layer's chunks), not by 4x layers
    assert 0 < grown <= 6, grown


def test_flatpack_device_load_64bit_falls_back_to_host(tmp_path):
    """64-bit leaves cannot ride the staged bitcast path (device_put
    would canonicalize the uint64 staging buffer to uint32 under default
    x64-off and silently corrupt values): device_load must return the
    host tree instead, bit-identical to load()."""
    from lambdipy_tpu.bundle import flatpack

    tree = {"a": np.arange(2**33, 2**33 + 8, dtype=np.int64),
            "b": np.ones((4, 4), np.float32)}
    path = tmp_path / "x64.fpk"
    flatpack.save(path, tree)
    out = flatpack.device_load(path)
    assert out["a"].dtype == np.int64
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(out["b"], tree["b"])


def test_int8_kv_error_bound_at_real_head_dims():
    """The int8 KV quantization error bound at the REAL 8B head layout
    (kv_heads=8, head_dim=128) rather than toy dims (VERDICT r5 #7):
    per-vector symmetric int8 keeps the K/V roundtrip within the
    ~0.4%-of-max bound the docs claim, and attention outputs through
    the real-dims _attend core stay within a small relative error of
    the float-cache path across realistic magnitude spreads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lambdipy_tpu.models.llama import (_attend, _kv_dequantize,
                                           _kv_quantize)

    b, t, kvh, d = 2, 256, 8, 128  # real 8B kv-head geometry, 1k-ish ctx
    rng = np.random.default_rng(0)
    for scale in (0.05, 1.0, 30.0):  # bf16-typical through outlier rows
        kv = jnp.asarray(rng.standard_normal((b, t, kvh, d)) * scale,
                         jnp.float32)
        q_i8, q_s = _kv_quantize(kv)
        back = _kv_dequantize(q_i8, q_s, jnp.float32)
        # round-to-nearest per-vector symmetric int8:
        # |err| <= 0.5 * scale = max|x|/254 per vector — the ~0.4%-of-
        # max bound the LlamaConfig.kv_quant docs claim (a regression
        # to truncation would double this and fail here)
        per_vec_max = np.max(np.abs(np.asarray(kv)), axis=-1,
                             keepdims=True)
        err = np.abs(np.asarray(back) - np.asarray(kv))
        assert (err <= per_vec_max / 254.0 + 1e-6).all()

    # attention-output error vs the float cache at real head dims
    h = kvh * 4  # 32 query heads (GQA group 4), the 8B layout
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kvh, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kvh, d)) * 0.3, jnp.float32)
    mask = jnp.ones((b, 1, t), jnp.bool_)
    ref = np.asarray(_attend(q, k, v, mask))
    k8 = _kv_dequantize(*_kv_quantize(k), jnp.float32)
    v8 = _kv_dequantize(*_kv_quantize(v), jnp.float32)
    got = np.asarray(_attend(q, k8, v8, mask))
    rel = np.abs(got - ref) / (np.abs(ref).mean() + 1e-9)
    assert float(rel.mean()) < 0.01, float(rel.mean())
    assert float(rel.max()) < 0.15, float(rel.max())
