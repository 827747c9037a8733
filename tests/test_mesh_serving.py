"""Tensor-parallel sharded serving (ISSUE 11 / ROADMAP direction 3).

The host-side pieces — the mesh-spec grammar, shape helpers,
sharding-rule path matching, the serving-mesh validation contract, the
cache placement math, the engine-level stand-down + metrics surfaces —
at unit-test cost, and at the end the bitwise parity matrix of the tp=2
engine against the unsharded server (greedy/sampled x cold/prefix-hit x
streamed x dense/paged x depths 1-2) at llama-tiny's dims.
"""

import numpy as np
import pytest

from lambdipy_tpu.models import registry
from lambdipy_tpu.parallel.mesh import (
    make_mesh,
    mesh_shape_for,
    parse_mesh_spec,
    use_mesh,
)
from lambdipy_tpu.parallel.sharding import (
    ShardingRules,
    device_bytes,
    shard_batch,
    shard_params,
)


# -- parse_mesh_spec ---------------------------------------------------------


def test_parse_mesh_spec_forms():
    assert parse_mesh_spec("tp=2") == {"tp": 2}
    assert parse_mesh_spec("tp=2,sp=1") == {"tp": 2}  # size-1 dropped
    assert parse_mesh_spec("dp=2 tp=4") == {"dp": 2, "tp": 4}
    assert parse_mesh_spec("2") == {"tp": 2}          # bare tp width
    assert parse_mesh_spec("2x4") == {"dp": 2, "tp": 4}
    assert parse_mesh_spec("TP=2") == {"tp": 2}       # case-insensitive


def test_parse_mesh_spec_off_forms():
    for s in ("", "0", "1", "off", "none", None):
        assert parse_mesh_spec(s) == {}
    assert parse_mesh_spec("tp=1") == {}  # degenerate = single-device


def test_parse_mesh_spec_rejects_garbage():
    with pytest.raises(ValueError, match="unknown mesh axis"):
        parse_mesh_spec("tq=2")
    with pytest.raises(ValueError, match="non-integer"):
        parse_mesh_spec("tp=two")
    with pytest.raises(ValueError):
        parse_mesh_spec("2x2x2")
    with pytest.raises(ValueError, match=">= 1"):
        parse_mesh_spec("tp=-2")
    with pytest.raises(ValueError):
        parse_mesh_spec("banana")


# -- mesh_shape_for ----------------------------------------------------------


def test_mesh_shape_for_defaults():
    # fill tp up to 4 (gcd with the device count), rest dp
    assert mesh_shape_for(8) == {"dp": 2, "pp": 1, "tp": 4, "sp": 1}
    assert mesh_shape_for(4) == {"dp": 1, "pp": 1, "tp": 4, "sp": 1}
    assert mesh_shape_for(2) == {"dp": 1, "pp": 1, "tp": 2, "sp": 1}
    assert mesh_shape_for(6) == {"dp": 3, "pp": 1, "tp": 2, "sp": 1}
    assert mesh_shape_for(1) == {"dp": 1, "pp": 1, "tp": 1, "sp": 1}


def test_mesh_shape_for_explicit_and_errors():
    assert mesh_shape_for(8, tp=2, sp=2) == {"dp": 2, "pp": 1, "tp": 2,
                                             "sp": 2}
    with pytest.raises(ValueError, match="not divisible"):
        mesh_shape_for(8, tp=3)


# -- ShardingRules.spec_for --------------------------------------------------


def test_sharding_rules_first_match_wins():
    from jax.sharding import PartitionSpec as P

    rules = ShardingRules(rules=(
        ("*o_proj/kernel*", P("tp", None)),
        ("*_proj/kernel*", P(None, "tp")),
    ))
    # o_proj matches its specific rule even though the general one
    # also globs it — order is the contract
    assert rules.spec_for("params/layer_0/o_proj/kernel") == P("tp", None)
    assert rules.spec_for("params/layer_0/q_proj/kernel") == P(None, "tp")
    # int8 layout rides the trailing glob
    assert rules.spec_for("params/layer_1/o_proj/kernel_int8") == \
        P("tp", None)
    # no match -> default (replicated)
    assert rules.spec_for("params/final_norm/scale") == P()


def test_llama_tp_rules_cover_the_serving_layout():
    from jax.sharding import PartitionSpec as P

    rules = registry.get("llama-tiny").build().tp_rules
    assert rules.spec_for("params/embed/embedding") == P("tp", None)
    assert rules.spec_for("params/layer_0/attn_norm/scale") == P()
    assert rules.spec_for("params/lm_head/kernel") == P(None, "tp")
    assert rules.spec_for("params/layer_0/down_proj/kernel") == \
        P("tp", None)


# -- shard_batch -------------------------------------------------------------


def test_shard_batch_leading_dim_over_dp(cpu_devices):
    import jax.numpy as jnp

    mesh = make_mesh({"dp": 2}, devices=cpu_devices[:2])
    batch = {"x": jnp.zeros((4, 6)), "y": jnp.zeros((4,))}
    sharded = shard_batch(batch, mesh)
    per, total = device_bytes(sharded)
    assert per == total // 2  # every leaf's leading dim split over dp
    np.testing.assert_array_equal(np.asarray(sharded["x"]),
                                  np.zeros((4, 6)))


def test_shard_batch_without_dp_axis_replicates(cpu_devices):
    import jax.numpy as jnp

    mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    sharded = shard_batch({"x": jnp.ones((4, 6))}, mesh)
    per, total = device_bytes(sharded)
    assert per == total  # dp absent from the mesh -> replicated no-op


# -- serving-mesh validation -------------------------------------------------


def test_tp_not_dividing_kv_heads_raises(cpu_devices):
    # llama-tiny: heads=4, kv_heads=2 — tp=4 can shard the query heads
    # but not the KV cache; serving must refuse loudly
    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    mesh = make_mesh({"tp": 4}, devices=cpu_devices[:4])
    with pytest.raises(ValueError, match="kv_heads"):
        adapter.make_server(params, mesh=mesh)


def test_odd_head_count_raises(cpu_devices):
    from lambdipy_tpu.models.llama import LlamaConfig, validate_serving_mesh

    cfg = LlamaConfig(vocab_size=64, hidden=60, layers=1, heads=3,
                      kv_heads=3, mlp=64, max_len=32)
    mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    with pytest.raises(ValueError, match="heads=3"):
        validate_serving_mesh(cfg, mesh)


def test_one_device_degenerate_mesh_is_exact_noop(cpu_devices):
    # mesh = "tp=1" parses to {} (no mesh); a literal 1-device Mesh on
    # the server must also serve byte-identically to no mesh at all
    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    ref = adapter.make_server(params).generate([5, 6, 7],
                                               max_new_tokens=6)
    mesh1 = make_mesh({"tp": 1}, devices=cpu_devices[:1])
    server = adapter.make_server(params, mesh=mesh1)
    np.testing.assert_array_equal(
        server.generate([5, 6, 7], max_new_tokens=6), ref)


# -- cache placement ---------------------------------------------------------


def test_shard_kv_cache_halves_per_device_bytes(cpu_devices):
    from lambdipy_tpu.models.llama import init_decode_cache, shard_kv_cache

    adapter = registry.get("llama-tiny").build()
    cfg = adapter.config
    mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    cache = init_decode_cache(cfg, 2, cfg.max_len)
    sharded = shard_kv_cache(cache, mesh)
    kv_only = [{n: v for n, v in e.items() if n != "index"}
               for e in sharded]
    per, total = device_bytes(kv_only)
    assert per == total // 2, (per, total)
    # index leaves replicate (host-global positions)
    idx_per, idx_total = device_bytes([e["index"] for e in sharded])
    assert idx_per == idx_total
    # values untouched by placement
    np.testing.assert_array_equal(np.asarray(sharded[0]["k"]),
                                  np.asarray(cache[0]["k"]))


def test_shard_page_arena_halves_per_device_bytes(cpu_devices):
    from lambdipy_tpu.models.llama import init_page_arena

    adapter = registry.get("llama-tiny").build()
    cfg = adapter.config
    mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    arena = init_page_arena(cfg, 5, 16, mesh=mesh)
    per, total = device_bytes(arena)
    assert per == total // 2, (per, total)


def test_concat_cache_blocks_preserves_tp_sharding(cpu_devices):
    from lambdipy_tpu.models.llama import (
        concat_cache_blocks,
        init_decode_cache,
        shard_kv_cache,
        slice_cache_blocks,
    )

    adapter = registry.get("llama-tiny").build()
    cfg = adapter.config
    mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    cache = shard_kv_cache(init_decode_cache(cfg, 1, cfg.max_len), mesh)
    with use_mesh(mesh):
        blocks = [slice_cache_blocks(cache, p, 16) for p in (0, 16)]
        out = concat_cache_blocks(cfg, blocks, cfg.max_len)
    kv_only = [{n: v for n, v in e.items() if n != "index"}
               for e in out]
    per, total = device_bytes(kv_only)
    assert per == total // 2, (per, total)


# -- engine surfaces ---------------------------------------------------------


def test_engine_mesh_stats_surface(cpu_devices):
    from lambdipy_tpu.parallel.sharding import shard_params as sp
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    ref = adapter.make_server(params).generate([1, 2, 3],
                                               max_new_tokens=6)
    mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    with use_mesh(mesh):
        sharded = sp(params, mesh, adapter.tp_rules)
    server = adapter.make_server(sharded, mesh=mesh)
    cb = ContinuousBatcher(server, slots=2, segment=4)
    np.testing.assert_array_equal(
        cb.generate([1, 2, 3], max_new_tokens=6), ref)
    stats = cb.stats()
    mb = stats["mesh"]
    assert mb["shape"] == {"tp": 2} and mb["devices"] == 2
    assert mb["segments_sharded"] > 0
    # live gauges: the B-slot carry reads half-per-device
    assert 0 < mb["kv_bytes_per_device"] <= 0.55 * mb["kv_bytes_replicated"]
    assert 0 < mb["param_bytes_per_device"] <= \
        0.55 * mb["param_bytes_total"]
    # analytic Megatron count: segment * (embed all-reduce + 2 per
    # layer + logits all-gather)
    cfg = adapter.config
    assert mb["collectives_per_segment"] == 4 * (2 * cfg.layers + 2)
    # an unsharded engine publishes NO mesh block
    assert "mesh" not in ContinuousBatcher(
        adapter.make_server(params), slots=2, segment=4).stats()


def test_handler_mesh_knob_end_to_end(cpu_devices, monkeypatch):
    """LAMBDIPY_MESH (the `lambdipy serve --mesh` bridge) resolves into
    a sharded continuous-engine handler: params placed by tp_rules,
    meta reports the mesh, batching.mesh rides /metrics stats, and the
    served tokens equal the unsharded handler's bitwise."""
    from types import SimpleNamespace

    from lambdipy_tpu.runtime.handlers import generate_handler

    ctx = SimpleNamespace(params_dir=None, bundle_dir=None, manifest=None)
    spec = {"model": "llama-tiny", "dtype": "float32",
            "extra": {"batch_mode": "continuous", "batch_max": "2",
                      "batch_segment": "4", "max_new_tokens": "6",
                      "prefix_cache_mb": "0", "warm_group_prefill": "0",
                      "serve_aot": "0"}}
    monkeypatch.delenv("LAMBDIPY_MESH", raising=False)
    plain = generate_handler(dict(spec), ctx)
    assert plain.meta["sharded"] is False and plain.meta["mesh"] is None
    ref = plain.invoke({"tokens": [1, 2, 3]})
    assert ref["ok"]

    monkeypatch.setenv("LAMBDIPY_MESH", "tp=2")
    sharded = generate_handler(dict(spec), ctx)
    assert sharded.meta["sharded"] is True
    assert sharded.meta["mesh"] == {"tp": 2}
    out = sharded.invoke({"tokens": [1, 2, 3]})
    assert out["ok"] and out["tokens"] == ref["tokens"]
    mesh_block = sharded.stats()["batching"]["mesh"]
    assert mesh_block["shape"] == {"tp": 2}
    assert 0 < mesh_block["kv_bytes_per_device"] <= \
        0.55 * mesh_block["kv_bytes_replicated"]
    # an explicit bundle extra WINS over the env, like every other
    # knob — and an explicit "off" REPLACES even a spec-level
    # [payload.mesh] (it must actually serve single-device, not
    # silently keep the declared mesh)
    monkeypatch.setenv("LAMBDIPY_MESH", "tp=4")  # would not divide kv
    off = generate_handler(
        {**spec, "mesh": {"tp": 2},
         "extra": {**spec["extra"], "mesh": "off"}}, ctx)
    assert off.meta["sharded"] is False


def test_engine_spec_k_stands_down_under_sp_mesh(cpu_devices):
    from lambdipy_tpu.parallel import spdecode
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher

    spdecode._reset_standdowns_for_tests()
    ring = registry.get("llama-tiny").build(extra={"attn_backend": "ring"})
    params = ring.init_params(seed=0)
    mesh = make_mesh({"sp": 2}, devices=cpu_devices[:2])
    with use_mesh(mesh):
        sp_params = shard_params(params, mesh, ring.tp_rules)
    server = ring.make_server(sp_params, mesh=mesh)
    cb = ContinuousBatcher(server, slots=2, segment=4, spec_k=4)
    assert cb.spec_k == 0, "spec_k must stand down under an sp mesh"
    stats = spdecode.standdown_stats()
    assert stats["reasons"].get("spec_k_under_sp_mesh") == 1
    # ...and the per-reason breakdown rides the /metrics spec report
    rep = server.spec_metrics.report()
    assert rep["sp_standdown_reasons"].get("spec_k_under_sp_mesh") == 1
    # a tp mesh (no sp axis) keeps speculation on
    tp_mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    with use_mesh(tp_mesh):
        tp_params = shard_params(params, tp_mesh, ring.tp_rules)
    dense = registry.get("llama-tiny").build()
    tp_server = dense.make_server(tp_params, mesh=tp_mesh)
    assert ContinuousBatcher(tp_server, slots=2, segment=4,
                             spec_k=4).spec_k == 4


# -- the tp=2 engine against the unsharded server ----------------------------

TP_NEW, TP_BLOCK = 12, 16
TP_SAMPLE = dict(temperature=0.8, top_k=32, seed=11)


@pytest.fixture(scope="module")
def tp_pair(cpu_devices):
    """An unsharded reference's tokens and the tp=2 server they are held
    against: three cold rows (greedy and seeded-sampled) and two rows that
    share a two-block prefix."""
    adapter = registry.get("llama-tiny").build()
    cfg = adapter.config
    params = adapter.init_params(seed=0)
    ref = adapter.make_server(params, prefix_cache_max=2)
    rng = np.random.default_rng(0)
    rows = [rng.integers(1, cfg.vocab_size, 4 + i).tolist() for i in range(3)]
    shared = rng.integers(1, cfg.vocab_size, 2 * TP_BLOCK).tolist()
    pfx_rows = [shared + rng.integers(1, cfg.vocab_size, 4).tolist()
                for _ in range(2)]
    refs = {tuple(r): ref.generate(r, max_new_tokens=TP_NEW)
            for r in rows + pfx_rows}
    refs_s = {tuple(r): ref.generate(r, max_new_tokens=TP_NEW, **TP_SAMPLE)
              for r in rows[:2]}
    mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    with use_mesh(mesh):
        sharded = shard_params(params, mesh, adapter.tp_rules)
    server = adapter.make_server(sharded, mesh=mesh, prefix_cache_max=2)
    return cfg, mesh, server, rows, pfx_rows, refs, refs_s


def tp_engine(tp_pair, paged, **engine_kw):
    """A 4-slot engine over the pair's tp=2 server, dense or over a page
    arena placed on its mesh; ``(engine, pool or None)``."""
    from lambdipy_tpu.models.llama import init_page_arena, page_kv_bytes
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher
    from lambdipy_tpu.runtime.pagepool import PagePool, page_width

    cfg, mesh, server = tp_pair[:3]
    slots, pool = 4, None
    if paged:
        page = page_width(cfg.max_len, TP_BLOCK)
        n_pages = slots * (cfg.max_len // page) + 1
        pool = PagePool(n_pages=n_pages, page=page,
                        page_bytes=page_kv_bytes(cfg, page),
                        make_arena=lambda: init_page_arena(
                            cfg, n_pages, page, mesh=mesh))
    return ContinuousBatcher(server, slots=slots, segment=4, page_pool=pool,
                             **engine_kw), pool


def assert_cold_rows_bitwise(eng, tp_pair):
    """Concurrent cold greedy rows, then seeded-sampled ones."""
    from concurrent.futures import ThreadPoolExecutor

    rows, _, refs, refs_s = tp_pair[3:]
    with ThreadPoolExecutor(max_workers=len(rows)) as ex:
        outs = list(ex.map(
            lambda r: eng.generate(r, max_new_tokens=TP_NEW), rows))
    for r, o in zip(rows, outs):
        np.testing.assert_array_equal(o, refs[tuple(r)])
    for r in rows[:2]:
        np.testing.assert_array_equal(
            eng.generate(r, max_new_tokens=TP_NEW, **TP_SAMPLE),
            refs_s[tuple(r)])


def idle_stats(eng, pool):
    """The engine's stats once its loop has gone idle; the pool's
    accounting checked on the way."""
    with eng._lock:
        while eng._engine_running:
            eng._lock.wait(0.05)
    if pool is not None:
        pool.check_invariants()
    return eng.stats()


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_tp2_engine_is_bitwise_the_unsharded_server(tp_pair, paged, depth):
    """The Megatron layout shards output channels, so reductions keep
    their order: the sharded engine's tokens ARE the single-device
    server's, for concurrent cold greedy rows, seeded-sampled rows, a
    prefix's cold walk and its (dense or zero-copy) hit, and a streamed
    hit; and after that traffic the LIVE gauges still read half the KV
    and half the parameters a device (a segment program that resharded
    the carry back to replicated would fail here, not at init)."""
    from lambdipy_tpu.runtime.prefixstore import PrefixStore

    server, pfx_rows, refs = tp_pair[2], tp_pair[4], tp_pair[5]
    eng, pool = tp_engine(tp_pair, paged, pipeline_depth=depth)
    store = PrefixStore(server, block=TP_BLOCK, budget_mb=64, pool=pool)
    if pool is not None:
        eng.prefix_pages_fn = store.acquire_pages

    def routed(row, stream=False):
        m = store.route(row)
        pfx = np.asarray(row[:m], np.int32) if m > 0 else None
        suf = np.asarray(row[m:], np.int32) if m > 0 else row
        if stream:
            return np.concatenate(list(eng.generate_stream(
                suf, max_new_tokens=TP_NEW, prefix=pfx)), axis=1)[:, :TP_NEW]
        return eng.generate(suf, max_new_tokens=TP_NEW, prefix=pfx)

    assert_cold_rows_bitwise(eng, tp_pair)
    for r in pfx_rows:              # the cold walk, then the hit
        np.testing.assert_array_equal(routed(r), refs[tuple(r)])
    np.testing.assert_array_equal(routed(pfx_rows[0], stream=True),
                                  refs[tuple(pfx_rows[0])])
    assert store.stats()["hits"] >= 2
    mb = idle_stats(eng, pool)["mesh"]
    assert mb["segments_sharded"] > 0
    assert 0 < mb["kv_bytes_per_device"] <= 0.55 * mb["kv_bytes_replicated"]
    assert 0 < mb["param_bytes_per_device"] <= 0.55 * mb["param_bytes_total"]


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_tp2_model_draft_engine_is_bitwise_the_unsharded_server(tp_pair,
                                                                paged):
    """Speculation composes with the mesh: the shallow-exit drafting
    engine (``spec_k`` 4, ``draft_mode`` model, pipeline depth 2) over the
    tp=2 server emits the unsharded plain server's tokens, greedy under
    concurrency, seeded-sampled and streamed: the accept rule is
    chain-deterministic, so a sharded draft can change how many tokens a
    step verifies and never which."""
    from lambdipy_tpu.runtime.metrics import SpecDecodeStats

    rows, refs = tp_pair[3], tp_pair[5]
    eng, pool = tp_engine(tp_pair, paged, pipeline_depth=2, spec_k=4,
                          draft_mode="model")
    eng.spec_metrics = SpecDecodeStats()
    assert_cold_rows_bitwise(eng, tp_pair)
    streamed = np.concatenate(list(eng.generate_stream(
        rows[0], max_new_tokens=TP_NEW)), axis=1)[:, :TP_NEW]
    np.testing.assert_array_equal(streamed, refs[tuple(rows[0])])
    stats = idle_stats(eng, pool)
    assert eng.spec_k == 4 and eng.spec_metrics.report()["steps"] > 0
    assert stats["mesh"]["segments_sharded"] > 0
