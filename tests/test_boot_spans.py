"""Set-up's seconds under names: the boot's stages as contiguous ``boot.*``
spans that ``cold_start`` is filled from, every program's way through jax
(``jit.trace``, ``jit.lower``, ``jit.compile``, ``jit.cache_read``) booked
once from jax's own monitoring events, the bounded program record of
``GET /spans``, and the benchmark's seven readers of those spans."""

import threading

import pytest

from lambdipy_tpu.runtime import spans
from lambdipy_tpu.utils.compile_cache import CompileCounters
from tests.test_runtime import _get, make_model_bundle
from tests.test_spans import stream_completion, window

STAGES = ["manifest", "syspath", "compile_cache", "handler_import", "init",
          "warmup"]
JIT = ["jit.trace", "jit.lower", "jit.compile"]


def close_stale_counters():
    """A ``load_bundle`` whose report nobody closed (other tests of this
    worker) leaves its listeners registered, and each would book every
    ``jit.*`` span again."""
    from jax._src import monitoring

    for listener in monitoring.get_event_duration_listeners():
        owner = getattr(listener, "__self__", None)
        if isinstance(owner, CompileCounters):
            owner.close()


@pytest.fixture()
def counters():
    close_stale_counters()
    c = CompileCounters()
    yield c
    c.close()


def programs_after(t: float) -> list:
    return [p for p in spans.requests()["programs"] if p["t"] > t]


def now() -> float:
    import time

    return time.monotonic() - spans.T0


def test_a_jit_books_trace_lower_and_compile_once_and_a_warm_call_nothing(
        counters):
    import jax
    import jax.numpy as jnp

    x = jnp.arange(4.0) + 0          # its own small programs: before
    before, t, was = spans.report(), now(), counters.report()
    fn = jax.jit(lambda v: v * 2.0 + 1.0)
    jax.block_until_ready(fn(x))
    after = spans.report()
    for name in JIT:
        count, sum_s, _ = window(before, after, name)
        assert count == 1 and sum_s > 0, name
    got = counters.report()
    assert got["requests"] == was["requests"] + 1
    assert got["persistent_cache_hits"] - was["persistent_cache_hits"] \
        == window(before, after, "jit.cache_read")[0]
    assert got["compiled"] == got["requests"] - got["persistent_cache_hits"]
    entry, = programs_after(t)
    assert entry["source"] == "jit" and entry["name"].startswith("jit(")
    assert entry["thread"] == threading.current_thread().name
    assert {"trace", "lower", "compile", "cache_hit"} <= set(entry)
    assert entry["compile"] == pytest.approx(
        window(before, after, "jit.compile")[1], abs=1e-4)
    # a warm call passes none of it
    jax.block_until_ready(fn(x))
    assert spans.report() == after and counters.report() == got
    assert len(programs_after(t)) == 1


def test_traces_inside_a_trace_are_counted_once(counters):
    """jax times every jitted function it traces inside another (each
    ``jnp`` function is one) and those it meets while lowering: a program
    is still one ``jit.trace``, no longer than jax's own time for it."""
    import jax
    import jax.numpy as jnp

    seen = []

    def listen(event, duration, **kw):
        if event.endswith("jaxpr_trace_duration"):
            seen.append((kw.get("fun_name"), duration))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        @jax.jit
        def inner(v):
            return jnp.where(v > 0, v, 0.0) * 2

        def outer(v):
            return jax.lax.scan(lambda c, _: (inner(c) + 1, None), v, None,
                                length=3)[0]

        x = jnp.arange(5.0) + 0
        del seen[:]
        before = spans.report()
        jax.block_until_ready(jax.jit(outer)(x))
        after = spans.report()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(seen) > 3                       # jax did report the inner ones
    own = [d for name, d in seen if name == "outer"]
    count, sum_s, _ = window(before, after, "jit.trace")
    assert count == 1 and sum_s == pytest.approx(own[0], abs=1e-6)
    assert window(before, after, "jit.lower")[0] == 1
    assert window(before, after, "jit.compile")[0] == 1


def test_a_trace_that_no_lowering_follows_is_booked_with_the_next(counters):
    import jax
    import jax.numpy as jnp

    x = jnp.arange(6.0) + 0
    before = spans.report()
    jax.eval_shape(lambda v: jnp.tanh(v) * 3, x)    # traced, never lowered
    assert window(before, spans.report(), "jit.trace")[0] == 0
    jax.block_until_ready(jax.jit(lambda v: v - 7.0)(x))
    after = spans.report()
    assert window(before, after, "jit.trace")[0] == 2
    assert window(before, after, "jit.lower")[0] == 1


def test_a_span_never_starts_the_jax_import():
    """The import is seconds and belongs to the stage that needs jax
    (``boot.compile_cache``); a bundle that serves without jax stays
    without; and an import begun by a span on the boot thread collided
    with the benchmark's profiler thread importing jax (``KeyError:
    'jax'`` inside importlib, on the chip, PR 37 call A)."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from lambdipy_tpu.runtime import loader, spans\n"
            "with spans.span('boot.manifest', program='x') as sp:\n"
            "    sp.set(tier='exec')\n"
            "ph = spans.phases(); ph.enter('eng.wait'); ph.exit()\n"
            "assert spans.report()['boot.manifest']['count'] == 1\n"
            "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']\n"
            "import jax.profiler\n"
            "assert spans.span('x')._ann is not None\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


@pytest.fixture(scope="module")
def booted(tmp_path_factory):
    """The toy bundle of the span tests, booted once: the spans before the
    boot, the server, and the record's time just before it."""
    from lambdipy_tpu.runtime.server import BundleServer

    close_stale_counters()
    bundle = make_model_bundle(
        tmp_path_factory.mktemp("boot-spans-bundle"), model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"batch_mode": "continuous", "batch_max": "4",
               "batch_segment": "4", "max_new_tokens": "16"})
    before, t = spans.report(), now()
    server = BundleServer(bundle, port=0).start_background()
    yield before, server, t
    server.stop()


def test_the_boot_stages_are_contiguous_spans_and_cold_start_is_them(booted):
    before, server, _ = booted
    boot = server.boot
    after = _get(f"http://127.0.0.1:{server.port}/metrics")["spans"]
    assert [name for name, _, _ in boot.stage_spans] \
        == [f"boot.{s}" for s in STAGES]
    for (_, _, end), (_, begin, _) in zip(boot.stage_spans,
                                          boot.stage_spans[1:]):
        assert 0 <= begin - end < 1e-3
    health = _get(f"http://127.0.0.1:{server.port}/healthz")
    assert health["cold_start"] == boot.stages
    assert list(boot.stages) == STAGES + ["total"]
    for name, begin, end in boot.stage_spans:
        count, sum_s, _ = window(before, after, name)
        assert count == 1 and sum_s == pytest.approx(end - begin, abs=1e-9)
        assert boot.stages[name.removeprefix("boot.")] \
            == pytest.approx(sum_s, abs=5e-5)
    assert boot.stages["total"] == pytest.approx(
        sum(boot.stages[s] for s in STAGES), abs=1e-3)
    # the children lie inside boot.init, the backend's beside the stages
    count, params_s, _ = window(before, after, "boot.params")
    assert count == 1 and 0 < params_s < window(before, after,
                                                "boot.init")[1]
    assert window(before, after, "boot.backend")[0] == 1
    assert window(before, after, "jit.compile")[0] > 0


def test_spans_lists_every_program_the_boot_obtained(booted):
    _, server, t = booted
    base = f"http://127.0.0.1:{server.port}"
    body = _get(f"{base}/spans")
    programs = [p for p in body["programs"] if p["t"] > t]
    assert programs and len(body["programs"]) <= spans.PROGRAMS == 512
    assert all(p["source"] in ("exec", "hlo", "jit") for p in programs)
    assert all(p["thread"] and p["t"] > 0 for p in programs)
    assert [p["t"] for p in programs] == sorted(p["t"] for p in programs)
    # every key of the server's program cache was asked for once, and
    # every compile request of the boot has its entry
    asked = [tuple(p["key"]) if isinstance(p["key"], list) else p["key"]
             for p in programs if "key" in p]
    metrics = _get(f"{base}/metrics")
    held = [tuple(k) for k in metrics["handler"]["decode_buckets"]]
    assert sorted(asked, key=repr) == sorted(held, key=repr)
    compiled = [p for p in programs if "compile" in p]
    assert len(compiled) == metrics["compile"]["requests"]
    assert all("cache_hit" in p and p["name"] for p in compiled)
    assert any(p["name"] == "jit(seg)" and p.get("trace", 0) > 0
               for p in compiled)
    assert _get(f"{base}/spans?last=0")["programs"] == body["programs"]


def test_a_program_cache_hit_adds_no_entry(booted):
    _, server, _ = booted
    assert len(stream_completion(server.port, [1, 2, 3, 4, 5], 12)) == 12
    t = now()
    before = _get(f"http://127.0.0.1:{server.port}/metrics")["spans"]
    assert len(stream_completion(server.port, [5, 4, 3, 2, 1], 12)) == 12
    after = _get(f"http://127.0.0.1:{server.port}/metrics")["spans"]
    assert programs_after(t) == []
    assert all(window(before, after, name)[0] == 0
               for name in JIT + ["jit.cache_read", "boot.warm"])
    assert window(before, after, "eng.dispatch")[0] >= 3


def test_a_second_boot_lists_the_engines_programs_under_exec(tmp_path):
    """A bundle that has served its traffic once: the next boot's program
    record holds the engine's prefill and segment programs under ``exec``
    (no ``jit(prefill)``, no ``jit(seg)``), the ones the traffic asked for
    loaded at their first use, and ``boot.aot_preload`` holds the boot's
    own programs and no more: the deploy waits for nothing it may never
    run."""
    import time

    from lambdipy_tpu.runtime.server import BundleServer

    bundle = make_model_bundle(
        tmp_path, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"batch_mode": "continuous", "batch_max": "4",
               "batch_segment": "4", "max_new_tokens": "8",
               "serve_aot": "1", "warm_group_prefill": "1"})

    def boot():
        before, t = spans.report(), now()
        server = BundleServer(bundle, port=0).start_background()
        base = f"http://127.0.0.1:{server.port}"
        try:
            while not _get(f"{base}/healthz")["ready"]:
                time.sleep(0.05)
            at_ready = _get(f"{base}/metrics")
            served = stream_completion(server.port, list(range(1, 41)), 24)
            handler = _get(f"{base}/metrics")["handler"]
            record = [p for p in _get(f"{base}/spans?last=0")["programs"]
                      if p["t"] > t]
        finally:
            server.stop()
        loads = window(before, at_ready["spans"], "boot.aot_load")[0]
        return served, at_ready["handler"], handler, record, loads

    served1, ready1, after1, record1, loads1 = boot()
    engine = [p for p in record1 if "key" in p
              and p["key"][0] in ("stream", "seg_w")]
    assert engine and {p["source"] for p in engine} == {"jit"}
    assert loads1 == 0 and ready1["aot_saved"] > 0
    assert after1["aot_saved"] > ready1["aot_saved"]   # the traffic's own
    assert after1["aot_saved"] >= len(engine)  # + the fused decode program

    served2, ready2, after2, record2, loads2 = boot()
    assert served2 == served1
    engine2 = [p for p in record2 if "key" in p
               and p["key"][0] in ("stream", "seg_w")]
    assert {p["source"] for p in engine2} == {"exec"}
    assert sorted(p["name"] for p in engine2) \
        == sorted(p["name"] for p in engine)
    assert not [p for p in record2
                if p["name"] in ("jit(prefill)", "jit(seg)")]
    # the preload held the boot set: what the first boot had saved by the
    # time it was ready, and not what the traffic added
    assert ready2["aot_preload"]["programs"] == loads2 == ready1["aot_saved"]
    assert after2["aot_lazy_loads"] \
        == after1["aot_saved"] - ready1["aot_saved"] > 0
    assert after2["aot_saved"] == after2["aot_fallbacks"] == 0


def test_the_aot_store_writes_its_loads_and_first_runs(tmp_path):
    import jax.numpy as jnp

    from lambdipy_tpu.runtime.aot import AotStore

    x = jnp.arange(8.0)
    AotStore(tmp_path).save("srv-toy", lambda v: v * 3.0, (x,))
    before, t = spans.report(), now()
    store = AotStore(tmp_path)
    pre = store.preload(prefix="srv-")
    assert pre["names"] == ["srv-toy"]
    fn, tier = store.load("srv-toy", (x,), key=("toy", 8))
    assert float(fn(x)[1]) == 3.0
    after = spans.report()
    loaded, ran = [p for p in programs_after(t) if p["name"] == "srv-toy"]
    assert set(loaded) == {"t", "name", "source", "thread", "aot_load"}
    assert ran["source"] == tier and tuple(ran["key"]) == ("toy", 8)
    # where the preloaded tier runs (one device) only the first run is
    # left; where it fails its probe (this 8-device CPU refuses a one-device
    # executable) the next tier is loaded and run in one entry
    assert ("aot_load" in ran) == (tier != loaded["source"])
    assert window(before, after, "boot.aot_preload")[0] == 1
    assert pre["seconds"] == pytest.approx(
        window(before, after, "boot.aot_preload")[1], abs=1e-3)
    assert window(before, after, "boot.aot_load")[1] == pytest.approx(
        loaded["aot_load"] + ran.get("aot_load", 0.0), abs=2e-4)
    assert window(before, after, "boot.warm")[1] >= ran["warm"] - 1e-4
    # not preloaded: one entry holds both
    t = now()
    assert AotStore(tmp_path).load("srv-toy", (x,)) is not None
    both, = [p for p in programs_after(t) if p["name"] == "srv-toy"]
    assert {"aot_load", "warm"} <= set(both) and "key" not in both


def test_the_program_record_is_bounded():
    for i in range(spans.PROGRAMS + 40):
        spans.program(f"test.bound-{i}", "jit", key=("bound", i), trace=0.5)
    record = spans.requests()["programs"]
    assert len(record) == spans.PROGRAMS
    assert record[0]["name"] == "test.bound-40"
    assert record[-1] == {"t": record[-1]["t"], "name": record[-1]["name"],
                          "source": "jit", "key": ("bound", 551),
                          "thread": threading.current_thread().name,
                          "trace": 0.5}


SPANS = {"boot.init": {"count": 1, "sum_s": 25.5},
         "boot.params": {"count": 1, "sum_s": 17.25},
         "boot.warmup": {"count": 1, "sum_s": 15.5},
         "boot.aot_load": {"count": 7, "sum_s": 26.0},
         "boot.warm": {"count": 9, "sum_s": 6.5},
         "jit.trace": {"count": 75, "sum_s": 30.0},
         "jit.lower": {"count": 75, "sum_s": 12.5},
         "jit.compile": {"count": 82, "sum_s": 77.25},
         "req": {"count": 46, "sum_s": 90.0}}
WANT = {"boot_init_s": 25.5, "boot_params_s": 17.25, "boot_warmup_s": 15.5,
        "setup_aot_s": 32.5, "setup_trace_s": 42.5,
        "setup_cache_load_s": 77.25, "setup_programs": 89}
READS = {"boot_init_s": ["boot.init"], "boot_params_s": ["boot.params"],
         "boot_warmup_s": ["boot.warmup"],
         "setup_aot_s": ["boot.aot_load", "boot.warm"],
         "setup_trace_s": ["jit.trace", "jit.lower"],
         "setup_cache_load_s": ["jit.compile"],
         "setup_programs": ["jit.compile", "boot.aot_load"]}


@pytest.mark.parametrize("scrape", ["with", "no_block", "no_name", "parent"])
@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_set_up_reader_reads_the_opening_scrape(metric, scrape):
    """The value from a scrape that holds the spans, taken at the window's
    OPENING (the closing one is not looked at); None without the block or
    the names; on the parent (``boot.aot_load`` and ``boot.warm`` since PR
    24, nothing else) only ``setup_aot_s`` reads."""
    from benchmark import harness as H

    later = {k: {"count": v["count"] + 5, "sum_s": v["sum_s"] + 50.0}
             for k, v in SPANS.items()}
    have = {"with": SPANS, "no_block": None,
            "no_name": {k: v for k, v in SPANS.items()
                        if k not in READS[metric]},
            "parent": {k: SPANS[k] for k in ("boot.aot_load", "boot.warm",
                                             "req")}}[scrape]
    ctx = {"m_open": {"spans": have} if have is not None else {"sched": {}},
           "m_close": {"spans": later}}
    value = H.layer_metric(metric).read(ctx)
    if scrape == "with" or (scrape, metric) == ("parent", "setup_aot_s"):
        assert value == WANT[metric]
    else:
        assert value is None
    assert H.layer_metric(metric).read({"m_open": None}) is None


def test_setup_programs_counts_both_ways_in():
    from benchmark import harness as H

    read = H.layer_metric("setup_programs").read
    spans_ = {"jit.compile": {"count": 82, "sum_s": 77.0}}
    assert read({"m_open": {"spans": spans_}}) == 82     # no AOT store
    spans_["boot.aot_load"] = {"count": 7, "sum_s": 26.0}
    assert read({"m_open": {"spans": spans_}}) == 89
    listed = {m["name"]: m for m in H.load_cell(
        H.REPO / "BENCHMARK.json", "mistral7b.chat-steady")["manifest"][
            "per_layer"]}
    for name in WANT:
        assert listed[name]["layer"] == "boot"
        assert listed[name]["moves"] == "setup_s"
        assert "workloads" not in listed[name]
