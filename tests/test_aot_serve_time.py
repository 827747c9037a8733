"""Serving programs enter later boots from the bundle's AOT exec tier: a
single-chip ``LlamaServer`` with a store snapshots every named program where
it first compiled (``models/llama.py`` ``_ServedProgram``), a second server
over the same directory loads it at its first use with no operands built for
a probe, and a boot preloads only what a boot runs."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark import families
from lambdipy_tpu.models import registry
from lambdipy_tpu.models.llama import LlamaServer
from lambdipy_tpu.runtime import spans
from lambdipy_tpu.runtime.aot import AotStore
from lambdipy_tpu.runtime.continuous import ContinuousBatcher
from lambdipy_tpu.utils.compile_cache import CompileCounters
from tests.test_boot_spans import close_stale_counters, now, programs_after

REPO = Path(__file__).resolve().parents[1]
# the toy configurations the family suites build: (model, rehearsal
# configuration, slots, segment)
TOYS = {"llama": ("llama-tiny", None, 4, 4),
        "latent": ("deepseek-v3", "rehearsal-mla-moe.json", 4, 8),
        "eva": ("evabyte", "rehearsal-eva.json", 2, 16),
        "sparse": ("deepseek-v32", "rehearsal-dsa.json", 4, 8)}
MODEL_PROGRAMS = ("jit(seg)", "jit(prefill)")
pytestmark = pytest.mark.usefixtures("fresh_compiles")


def toy(name):
    model, config, slots, segment = TOYS[name]
    if config is None:
        adapter, vocab = registry.get(model).build(), 256
    else:
        cfg = json.loads((REPO / "benchmark" / "configs" / config).read_text())
        adapter = registry.get(model).build(
            dtype="float32", quant="int8",
            extra=families.of(cfg).dims_of(cfg))
        vocab = cfg["vocab_size"]
    rng = np.random.default_rng(7)
    rows = [rng.integers(1, vocab, n).tolist() for n in (19, 23)]
    return adapter, adapter.init_params(seed=0), slots, segment, rows


def serve(server, slots, segment, rows):
    """Requests one after the other (a row prefill, window-bucketed
    segments) and one ragged group prefill's first tokens (which joiners
    meet in a group is the clock's choice; the programs and the keys here
    are not)."""
    eng = ContinuousBatcher(server, slots=slots, segment=segment)
    served = [eng.generate(row, max_new_tokens=8 + 8 * i)[0]
              for i, row in enumerate(rows)]
    group = eng._prefill_group(
        [dict(row=row, s=len(row), temperature=None, top_k=None, top_p=None,
              seed=None) for row in rows])
    return [*map(np.asarray, served), np.asarray(group[0])]


def has(store, name):
    return store._paths(name)["meta"].is_file()


def keyed(t):
    return [p for p in programs_after(t) if "key" in p]


@pytest.fixture()
def counters():
    close_stale_counters()
    c = CompileCounters()
    yield c
    c.close()


@pytest.mark.parametrize("name", sorted(TOYS))
def test_programs_saved_at_first_use_are_loaded_not_traced_by_the_next_boot(
        name, tmp_path, counters):
    adapter, params, slots, segment, rows = toy(name)
    want = serve(adapter.make_server(params), slots, segment, rows)

    t = now()
    first = adapter.make_server(params, aot=AotStore(tmp_path))
    got = serve(first, slots, segment, rows)
    first.aot_save_all()
    compiled = keyed(t)
    assert compiled and {p["source"] for p in compiled} == {"jit"}
    kinds = {p["key"][0] for p in compiled}
    assert kinds == {"stream", "seg_w"}
    assert any(p["key"][0] == "stream" and p["key"][1] > 1
               for p in compiled), "a group-prefill pair ran"
    # saved where they compiled: one artifact for each program that came
    # through jax, the exec tier alone, no other's operands synthesized
    assert first.aot_saved == len(compiled) and first.aot_hits == 0
    names = {p["name"] for p in compiled}
    metas = {f.name.removesuffix(".cpu.json"): json.loads(f.read_text())
             for f in (tmp_path / "aot").glob("*.json")}
    assert set(metas) == names
    assert all(m["tiers"] == ["exec"] for m in metas.values())
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)

    t = now()
    second = adapter.make_server(params, aot=AotStore(tmp_path))
    again = serve(second, slots, segment, rows)
    loaded = keyed(t)
    assert {p["source"] for p in loaded} == {"exec"}
    assert {p["name"] for p in loaded} == names
    assert all("aot_load" in p and "warm" in p for p in loaded)
    # no program of the model passed through jax: no trace, no lowering,
    # no compile request under the jitted functions' names
    assert not [p for p in programs_after(t) if p["name"] in MODEL_PROGRAMS]
    assert second.aot_hits == second.aot_lazy_loads == len(names)
    assert second.aot_saved == 0 and second.aot_fallbacks == 0
    assert second.compile_count >= len(names)
    for a, b in zip(again, want):
        np.testing.assert_array_equal(a, b)


@pytest.fixture(scope="module")
def tiny():
    adapter = registry.get("llama-tiny").build()
    return adapter, adapter.init_params(seed=0)


def run_tiny(server):
    eng = ContinuousBatcher(server, slots=4, segment=4)
    return eng.generate([1, 2, 3], max_new_tokens=8)[0]


def test_a_directory_that_cannot_be_written_serves_and_saves_nothing(
        tmp_path, tiny):
    """A read-only bundle (Lambda's ``/var/task``) keeps the jit path. The
    tests run as root, whom no mode stops: a FILE stands where the store's
    directory would go."""
    adapter, params = tiny
    (tmp_path / "aot").write_text("not a directory")
    store = AotStore(tmp_path)
    server = LlamaServer(adapter.module, params, aot=store)
    want = run_tiny(LlamaServer(adapter.module, params))
    np.testing.assert_array_equal(run_tiny(server), want)
    assert server.aot_save_all(boot_done=True) == 0
    assert server.aot_saved == 0 and not store.writable
    assert (tmp_path / "aot").read_text() == "not a directory"
    # the refusal is met once: later programs are not even serialised
    before = spans.report().get("boot.aot_save", {"count": 0})["count"]
    eng = ContinuousBatcher(server, slots=4, segment=4)
    eng.generate(list(range(1, 40)), max_new_tokens=4)
    server.aot_save_all()
    assert spans.report().get("boot.aot_save",
                              {"count": 0})["count"] == before


def test_an_artifact_of_another_generation_is_ignored(tmp_path, tiny,
                                                      monkeypatch):
    adapter, params = tiny
    monkeypatch.setattr(LlamaServer, "_AOT_GEN", "g0")
    old = LlamaServer(adapter.module, params, aot=AotStore(tmp_path))
    want = run_tiny(old)
    old.aot_save_all()
    stale = sorted(f.name for f in (tmp_path / "aot").iterdir())
    assert stale and all(n.startswith("srv-g0-") for n in stale)
    monkeypatch.undo()
    store = AotStore(tmp_path)
    assert store.preload(prefix=LlamaServer.aot_prefix())["names"] == []
    server = LlamaServer(adapter.module, params, aot=store)
    np.testing.assert_array_equal(run_tiny(server), want)
    server.aot_save_all()
    assert server.aot_hits == 0 and server.aot_saved == len(stale) // 2
    fresh = sorted(f.name for f in (tmp_path / "aot").iterdir()
                   if f.name not in stale)
    assert len(fresh) == len(stale)
    assert all(n.startswith(LlamaServer.aot_prefix()) for n in fresh)


def test_an_artifact_of_another_environment_is_replaced(tmp_path, tiny):
    """Another jaxlib's executable is not loaded, and its meta does not
    read as "tried here and pruned": this boot writes its own over it."""
    adapter, params = tiny
    first = LlamaServer(adapter.module, params, aot=AotStore(tmp_path))
    want = run_tiny(first)
    n = first.aot_save_all()
    for f in (tmp_path / "aot").glob("*.json"):
        f.write_text(json.dumps({**json.loads(f.read_text()),
                                 "jaxlib": "0.0.0-other"}))
    second = LlamaServer(adapter.module, params, aot=AotStore(tmp_path))
    np.testing.assert_array_equal(run_tiny(second), want)
    assert second.aot_save_all() == n
    assert (second.aot_hits, second.aot_fallbacks) == (0, 0)
    third = LlamaServer(adapter.module, params, aot=AotStore(tmp_path))
    np.testing.assert_array_equal(run_tiny(third), want)
    assert (third.aot_hits, third.aot_saved) == (n, 0)


def test_an_executable_whose_first_call_raises_falls_back_to_jit_and_is_pruned(
        tmp_path, tiny):
    adapter, params = tiny
    first = LlamaServer(adapter.module, params, aot=AotStore(tmp_path))
    want = run_tiny(first)
    first.aot_save_all()
    store = AotStore(tmp_path)
    prefill = LlamaServer._aot_part_names(("stream", 1, 16, 128, 4))[0]
    seg = LlamaServer._aot_name(("seg_w", 4, 128, 16, 4))
    assert has(store, prefill) and has(store, seg)
    # the segment's artifact now holds the PREFILL's executable: it loads,
    # and refuses the segment's operands at its first call
    store._paths(seg)["exec"].write_bytes(
        store._paths(prefill)["exec"].read_bytes())
    server = LlamaServer(adapter.module, params, aot=store)
    np.testing.assert_array_equal(run_tiny(server), want)
    assert server.aot_fallbacks == 1 and server.aot_hits == 1
    meta = json.loads(store._paths(seg)["meta"].read_text())
    assert meta["tiers"] == [] and not store._paths(seg)["exec"].exists()
    # the next boot neither loads nor writes the losing artifact again
    third = LlamaServer(adapter.module, params, aot=AotStore(tmp_path))
    np.testing.assert_array_equal(run_tiny(third), want)
    third.aot_save_all()
    assert (third.aot_fallbacks, third.aot_hits, third.aot_saved) == (0, 1, 0)


def test_a_load_at_serve_time_allocates_no_cache_shaped_array(tmp_path, tiny):
    """``_fn_cached`` on a store that HOLDS the key builds no operands (the
    old probe made a whole B-slot cache: a fourth copy inside a live
    engine); deserialising needs none, and the first real call is the
    probe."""
    adapter, params = tiny
    first = LlamaServer(adapter.module, params, aot=AotStore(tmp_path))
    run_tiny(first)
    first.aot_save_all()
    key = ("seg_w", 4, 128, 16, 4)
    store = AotStore(tmp_path)
    assert has(store, LlamaServer._aot_name(key))
    server = LlamaServer(adapter.module, params, aot=store)
    held = {id(a) for a in jax.live_arrays()}
    seg = server._windowed_seg_fn(*key[1:])
    hit = store.load_exec(seg.name)
    new = [a for a in jax.live_arrays() if id(a) not in held]
    assert hit is not None and hit[1] > 0
    assert new == [], [a.shape for a in new]
    assert server.aot_hits == 0          # nothing is settled before a call


def test_preload_loads_only_the_boot_set(tmp_path, tiny):
    """What a server compiles before its boot's warm-up has ended is the
    boot set; what the traffic asks for afterwards is saved too and left to
    its first use: a deploy does not wait for it."""
    adapter, params = tiny
    first = LlamaServer(adapter.module, params, aot=AotStore(tmp_path))
    eng = ContinuousBatcher(first, slots=4, segment=4)
    eng.generate([1, 2, 3], max_new_tokens=8)
    assert first.aot_save_all(boot_done=True) == 2
    eng.generate(list(range(1, 40)), max_new_tokens=8)
    assert first.aot_save_all() == 2
    flags = {f.name.removesuffix(".cpu.json"):
             json.loads(f.read_text())["boot"]
             for f in (tmp_path / "aot").glob("*.json")}
    boot = sorted(n for n, b in flags.items() if b)
    assert len(boot) == 2 and len(flags) == 4
    before = spans.report()
    store = AotStore(tmp_path)
    assert store.preload(prefix=LlamaServer.aot_prefix())["names"] == boot
    loads = spans.report()["boot.aot_load"]["count"] \
        - before.get("boot.aot_load", {"count": 0})["count"]
    assert loads == 2
    # the others come at first use, the preloaded ones cost no second load
    second = LlamaServer(adapter.module, params, aot=store)
    eng = ContinuousBatcher(second, slots=4, segment=4)
    eng.generate([1, 2, 3], max_new_tokens=8)
    assert (second.aot_hits, second.aot_lazy_loads) == (2, 0)
    eng.generate(list(range(1, 40)), max_new_tokens=8)
    assert (second.aot_hits, second.aot_lazy_loads) == (4, 2)
    assert second.aot_saved == 0
