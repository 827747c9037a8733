"""Serve-runtime integration: bundle boot, HTTP loop, deploy controller
(SURVEY.md §4 E — the rebuild's #1 new call stack; §6 failure rows)."""

import json
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from lambdipy_tpu.buildengine import build_recipe
from lambdipy_tpu.bundle import assemble_bundle
from lambdipy_tpu.recipes.schema import load_recipe_dict


def make_model_bundle(tmp_path, *, model="llama-tiny", handler, extra=None,
                      mesh=None):
    """Build a tiny model bundle end-to-end (vendor nothing; base layer
    provides jax; payload params initialized at build time). Serving-
    program AOT snapshots default OFF here — every warmed boot would pay
    exports + round-trip compiles on the 1-core box; the feature has its
    own test (test_aot) and stays default-ON in production bundles. The
    automatic prefix cache defaults OFF for the same reason (every
    33+-token prompt would compile block/continuation programs on the
    1-core box); it has its own tests (test_prefixstore, which opt in)
    and stays default-ON in production bundles."""
    extra = dict(extra or ())
    extra.setdefault("serve_aot", "0")
    extra.setdefault("prefix_cache_mb", "0")
    # the background group-prefill warm daemon compiles burst programs
    # CONCURRENTLY with whatever test runs next — pure CPU steal on the
    # 1-core box; its wiring has its own opt-in test
    # (test_handler_daemon_warms_group_prefill)
    extra.setdefault("warm_group_prefill", "0")
    doc = {
        "schema": 1,
        "name": f"test-{model}",
        "version": "0.1",
        "device": "any",
        "base_layer": "jax-tpu",
        "requires": [],
        "payload": {
            "model": model,
            "handler": handler,
            "params": "init",
            "dtype": "float32",
            **({"mesh": mesh} if mesh else {}),
            **({"extra": extra} if extra else {}),
        },
    }
    recipe = load_recipe_dict(doc)
    result = build_recipe(recipe, tmp_path / "work", run_smoke=False)
    out = tmp_path / "bundle"
    assemble_bundle(result, out, with_payload=True)
    return out


@pytest.fixture(scope="module")
def llama_bundle(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("llama-bundle")
    return make_model_bundle(
        tmp, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "4"})


def test_load_bundle_and_invoke(llama_bundle):
    from lambdipy_tpu.runtime.loader import load_bundle

    report = load_bundle(llama_bundle, warmup=True)
    assert report.warmup_result["ok"]
    assert {"manifest", "syspath", "compile_cache", "handler_import",
            "init", "warmup"} <= set(report.stages)
    out = report.handler.invoke(report.state, {"tokens": [1, 2, 3]})
    assert out["ok"] and out["n_new"] == 4
    assert (llama_bundle / "compile_cache").is_dir()


def test_resnet_bundle_image_handler(tmp_path):
    from lambdipy_tpu.runtime.loader import load_bundle

    bundle = make_model_bundle(
        tmp_path, model="resnet50-tiny",
        handler="lambdipy_tpu.runtime.handlers:image_classify_handler")
    report = load_bundle(bundle)
    out = report.handler.invoke(report.state, {"random": True})
    assert out["ok"] and len(out["top5"][0]) == 5


def test_hello_bundle_without_params(tmp_path):
    from lambdipy_tpu.runtime.loader import load_bundle

    bundle = make_model_bundle(
        tmp_path, model="hello",
        handler="lambdipy_tpu.runtime.handlers:hello_handler")
    report = load_bundle(bundle)
    out = report.handler.invoke(report.state, {"n": 16, "seed": 7})
    assert out["ok"] and isinstance(out["logdet"], float)


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def _post(url, payload):
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def test_http_server_full_loop(llama_bundle):
    from lambdipy_tpu.runtime.server import BundleServer

    server = BundleServer(llama_bundle, port=0).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        health = _get(f"{base}/healthz")
        assert health["ok"] and "init" in health["cold_start"]
        out = _post(f"{base}/invoke", {"tokens": [1, 2, 3], "max_new_tokens": 2})
        assert out["ok"] and out["n_new"] == 2
        metrics = _get(f"{base}/metrics")
        assert metrics["count"] >= 1 and metrics["p50_ms"] > 0
        # the decode server's live counters surface through /metrics
        assert metrics["handler"]["compile_count"] >= 1
        assert metrics["handler"]["decode_buckets"]
        # failure detection: bad payload shape -> 500, counted, server alive
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/invoke", {"tokens": "not-a-list"})
        assert e.value.code == 500
        assert _get(f"{base}/metrics")["errors"] >= 1
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(f"{base}/nope")
        assert e.value.code == 404
        assert _get(f"{base}/healthz")["ok"]  # still alive
    finally:
        server.stop()


@pytest.mark.slow
def test_local_deploy_subprocess_lifecycle(llama_bundle, tmp_path):
    """Full deploy path: subprocess server (CPU via LAMBDIPY_PLATFORM),
    readiness, invoke over HTTP, watchdog health, drain + stop."""
    from lambdipy_tpu.runtime.deploy import DeployError, LocalRuntime

    rt = LocalRuntime(tmp_path / "deployments.json")
    dep = rt.deploy("t1", llama_bundle, env={
        "LAMBDIPY_PLATFORM": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    })
    try:
        assert rt.health("t1")["ok"]
        out = rt.invoke("t1", {"tokens": [1, 2], "max_new_tokens": 2})
        assert out["ok"]
        with pytest.raises(DeployError, match="already exists"):
            rt.deploy("t1", llama_bundle)
        assert [d.name for d in rt.list()] == ["t1"]
    finally:
        rt.stop("t1")
    assert rt.list() == []


@pytest.mark.slow
def test_warm_populates_compile_cache_and_speeds_boot(tmp_path):
    """SURVEY.md §9.6: the bundle ships a warm XLA compile cache; a second
    boot's warmup must hit it (no recompile)."""
    import os
    import subprocess
    import sys as _sys

    bundle = make_model_bundle(
        tmp_path, model="resnet50-tiny",
        handler="lambdipy_tpu.runtime.handlers:image_classify_handler")
    env = dict(os.environ)
    env["LAMBDIPY_PLATFORM"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    repo_root = str(Path(__file__).resolve().parents[1])
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    r1 = subprocess.run(
        [_sys.executable, "-m", "lambdipy_tpu.runtime.warm", str(bundle)],
        capture_output=True, text=True, env=env, timeout=600)
    assert r1.returncode == 0, r1.stderr
    out1 = json.loads(r1.stdout.strip().splitlines()[-1])
    assert out1["cache_entries"] > 0
    # second warm run: compile stage should hit the shipped cache
    r2 = subprocess.run(
        [_sys.executable, "-m", "lambdipy_tpu.runtime.warm", str(bundle)],
        capture_output=True, text=True, env=env, timeout=600)
    out2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert out2["stages"]["warmup"] + out2["stages"]["init"] < \
        out1["stages"]["warmup"] + out1["stages"]["init"]


def test_profile_endpoint_captures_trace(llama_bundle):
    """``POST /profile {"seconds": s}`` traces that wall window of whatever
    traffic is live: the request sent meanwhile is in the trace. One mode:
    the old ``invokes`` body is refused."""
    import threading

    from jax.profiler import ProfileData

    from lambdipy_tpu.runtime.server import BundleServer

    server = BundleServer(llama_bundle, port=0).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        for bad in ({"invokes": 1}, {"seconds": 0}, {"seconds": 99},
                    {"seconds": "soon"}):
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(f"{base}/profile", bad)
            assert err.value.code == 400
        got = {}
        th = threading.Thread(target=lambda: got.update(
            _post(f"{base}/profile", {"seconds": 1.0})))
        th.start()
        time.sleep(0.3)
        assert _post(f"{base}/invoke", {"tokens": [1, 2, 3]})["ok"]
        th.join(timeout=120)
        assert got["ok"] and Path(got["dir"]).is_dir()
        trace = [f for f in got["files"] if f.endswith(".xplane.pb")]
        assert trace
        data = ProfileData.from_file(str(Path(got["dir"]) / trace[0]))
        names = {ev.name for plane in data.planes for line in plane.lines
                 for ev in line.events}
        assert any(n.startswith("PjitFunction(") for n in names)
    finally:
        server.stop()


@pytest.mark.slow
def test_watchdog_restarts_killed_server(llama_bundle, tmp_path):
    """Fault injection (SURVEY.md §6): SIGKILL the serving process mid-life;
    the supervisor must respawn it on the same port and invokes recover."""
    import os
    import signal
    import time

    from lambdipy_tpu.runtime.deploy import LocalRuntime

    rt = LocalRuntime(tmp_path / "deployments.json")
    dep = rt.deploy("wd", llama_bundle, env={
        "LAMBDIPY_PLATFORM": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    })
    try:
        first = rt.health("wd")
        assert first["ok"] and not first["draining"]
        server_pid = first["pid"]
        assert server_pid != dep.pid  # supervisor fronts a distinct worker
        os.kill(server_pid, signal.SIGKILL)  # crash the worker, not the sup
        deadline = time.monotonic() + 120
        second = None
        while time.monotonic() < deadline:
            try:
                second = rt.health("wd")
                if second["pid"] != server_pid:
                    break
            except Exception:
                pass
            time.sleep(0.5)
        assert second is not None and second["pid"] != server_pid, \
            "server was not respawned"
        out = rt.invoke("wd", {"tokens": [1, 2], "max_new_tokens": 2})
        assert out["ok"]
    finally:
        rt.stop("wd")
    assert rt.list() == []


def test_supervisor_gives_up_after_max_restarts(tmp_path):
    """A bundle that can never boot must not restart-loop forever."""
    import os
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    env["LAMBDIPY_MAX_RESTARTS"] = "1"
    repo_root = str(Path(__file__).resolve().parents[1])
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    r = subprocess.run(
        [_sys.executable, "-m", "lambdipy_tpu.runtime.supervisor",
         str(tmp_path / "not-a-bundle")],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1
    assert "giving up" in r.stderr


def test_server_drain_rejects_new_invokes(llama_bundle):
    import threading
    import urllib.error

    from lambdipy_tpu.runtime.server import BundleServer

    server = BundleServer(llama_bundle, port=0).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        assert _post(f"{base}/invoke", {"tokens": [1], "max_new_tokens": 1})["ok"]
        server.draining = True
        assert _get(f"{base}/healthz")["draining"]
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/invoke", {"tokens": [1]})
        assert e.value.code == 503
    finally:
        server.draining = False
        threading.Thread(target=server.stop, daemon=True).start()


def test_generate_handler_null_knobs(llama_bundle):
    """JSON null for every sampling knob (incl. max_new_tokens) means 'use
    the default' — it must not 500 (VERDICT r2 weak #7)."""
    from lambdipy_tpu.runtime.loader import load_bundle

    report = load_bundle(llama_bundle)
    out = report.handler.invoke(report.state, {
        "tokens": [1, 2, 3], "max_new_tokens": None, "temperature": None,
        "top_k": None, "top_p": None, "seed": None, "eos_id": None})
    assert out["ok"] and out["n_new"] == 4  # bundle default_new


def test_background_bucket_warm(tmp_path):
    """warm_buckets pre-compiles the listed prompt buckets on a daemon
    thread after init: once done, a first request in that bucket triggers
    zero new compiles, and progress is visible through stats()."""
    import time as _time

    from lambdipy_tpu.runtime.loader import load_bundle

    bundle = make_model_bundle(
        tmp_path, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        # the automatic prefix cache would route the 50-token probe into
        # continuation programs instead of the warmed fused bucket; this
        # test exercises the bucket-warm machinery, so keep it off
        extra={"max_new_tokens": "4", "warm_buckets": "64",
               "prefix_cache_mb": "0"})
    report = load_bundle(bundle, warmup=False)
    # the warm thread starts only after the FIRST invoke completes (so it
    # can never contend with the boot warmup); trigger it
    assert report.state.stats().get("warm_buckets", {}).get("done") in ([], None)
    assert report.handler.invoke(report.state, {"tokens": [1, 2]})["ok"]
    deadline = _time.monotonic() + 120
    while _time.monotonic() < deadline:
        wb = report.state.stats().get("warm_buckets", {})
        assert not wb.get("errors"), wb
        if wb.get("done") == [64]:
            break
        _time.sleep(0.5)
    else:
        raise AssertionError(f"bucket warm never finished: {report.state.stats()}")
    count = report.state.stats()["compile_count"]
    out = report.handler.invoke(report.state, {
        "tokens": list(range(1, 51)), "max_new_tokens": 4})  # 50 -> bucket 64
    assert out["ok"]
    assert report.state.stats()["compile_count"] == count  # warm hit


def test_openai_completions_endpoint(llama_bundle):
    """/v1/completions serves OpenAI-shaped requests over the generate
    handler: token-array prompts work without a tokenizer, greedy matches
    /invoke, eos sets finish_reason, bad requests get OpenAI-style
    errors, and stream=true emits SSE events closed by [DONE]."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    from lambdipy_tpu.runtime.server import BundleServer

    server = BundleServer(llama_bundle, warmup=False).start_background()
    base = f"http://127.0.0.1:{server.port}"

    def post(path, payload, timeout=60):
        req = urllib.request.Request(
            f"{base}{path}", data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        return urllib.request.urlopen(req, timeout=timeout)

    try:
        plain = _post(f"{base}/invoke",
                      {"tokens": [1, 2, 3], "max_new_tokens": 6})
        with post("/v1/completions", {"prompt": [1, 2, 3], "max_tokens": 6,
                                      "temperature": 0}) as resp:
            body = _json.loads(resp.read())
        assert body["object"] == "text_completion"
        choice = body["choices"][0]
        assert choice["tokens"] == plain["tokens"][0]
        assert choice["finish_reason"] == "length"
        assert body["usage"] == {"prompt_tokens": 3, "completion_tokens": 6,
                                 "total_tokens": 9}
        # eos latching -> finish_reason stop
        eos = plain["tokens"][0][1]
        with post("/v1/completions", {"prompt": [1, 2, 3], "max_tokens": 6,
                                      "temperature": 0, "eos_id": eos}) as resp:
            body = _json.loads(resp.read())
        assert body["choices"][0]["finish_reason"] == "stop"
        # string prompt without a tokenizer -> 400 with OpenAI error shape
        try:
            post("/v1/completions", {"prompt": "hello", "max_tokens": 4})
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "error" in _json.loads(e.read())
        # SSE streaming
        with post("/v1/completions", {"prompt": [1, 2, 3], "max_tokens": 6,
                                      "temperature": 0, "stream": True,
                                      "segment": 4}) as resp:
            assert resp.headers["Content-Type"] == "text/event-stream"
            events = [ln.decode().strip()[len("data: "):]
                      for ln in resp if ln.strip().startswith(b"data: ")]
        assert events[-1] == "[DONE]"
        toks = [t for e in events[:-1]
                for t in _json.loads(e)["choices"][0]["tokens"]]
        assert toks == plain["tokens"][0]
        # streamed logprobs ride each SSE chunk
        with post("/v1/completions", {"prompt": [1, 2, 3], "max_tokens": 6,
                                      "temperature": 0, "stream": True,
                                      "segment": 4, "logprobs": 1}) as resp:
            evs = [_json.loads(ln.decode().strip()[6:])
                   for ln in resp if ln.strip().startswith(b"data: ")
                   and not ln.strip().endswith(b"[DONE]")]
        tok_evs = [e for e in evs if e["choices"][0]["tokens"]]
        assert tok_evs, evs
        for e in tok_evs:
            ch = e["choices"][0]
            assert len(ch["logprobs"]["token_logprobs"]) == len(ch["tokens"])
        # logprobs: per-token model logprobs in OpenAI shape
        with post("/v1/completions", {"prompt": [1, 2, 3], "max_tokens": 4,
                                      "temperature": 0,
                                      "logprobs": 1}) as resp:
            body = _json.loads(resp.read())
        lp = body["choices"][0]["logprobs"]
        assert len(lp["token_logprobs"]) == len(body["choices"][0]["tokens"])
        assert all(x <= 1e-6 for x in lp["token_logprobs"])
        try:
            post("/v1/completions", {"prompt": [1], "logprobs": 5})
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
        # the shim shares /invoke's drain bracket: no new work while draining
        server.draining = True
        try:
            post("/v1/completions", {"prompt": [1], "max_tokens": 1})
            raise AssertionError("expected 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
        finally:
            server.draining = False
    finally:
        threading.Thread(target=server.stop, daemon=True).start()


def test_http_streaming_invoke(llama_bundle):
    """`stream: true` returns chunked ndjson whose concatenated tokens
    equal the non-streamed response; non-stream requests still work on
    the same server."""
    import json as _json
    import threading
    import urllib.request

    from lambdipy_tpu.runtime.server import BundleServer

    server = BundleServer(llama_bundle, warmup=False).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        plain = _post(f"{base}/invoke",
                      {"tokens": [1, 2, 3], "max_new_tokens": 8})
        req = urllib.request.Request(
            f"{base}/invoke",
            data=_json.dumps({"tokens": [1, 2, 3], "max_new_tokens": 8,
                              "stream": True, "segment": 3}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.headers.get("Content-Type") == "application/x-ndjson"
            lines = [_json.loads(ln) for ln in resp if ln.strip()]
        assert lines[-1].get("done") and lines[-1]["n_new"] == 8
        toks = []
        for ln in lines[:-1]:
            assert ln["ok"], ln
            toks.extend(ln["tokens"][0])
        assert toks == plain["tokens"][0]
    finally:
        threading.Thread(target=server.stop, daemon=True).start()


def test_generate_handler_ragged_json_rows(llama_bundle):
    """A JSON list of different-length prompt rows decodes as one ragged
    batch (each row from its own prompt end) and matches solo serving;
    equal-length rows still take the rectangular path."""
    import numpy as np

    from lambdipy_tpu.runtime.loader import load_bundle

    report = load_bundle(llama_bundle)
    ragged = report.handler.invoke(report.state, {
        "tokens": [[1, 2, 3], [4, 5, 6, 7, 8]], "max_new_tokens": 4})
    assert ragged["ok"] and len(ragged["tokens"]) == 2, ragged
    for row in ragged["tokens"]:
        assert len(row) == 4
    solo = report.handler.invoke(report.state, {
        "tokens": [4, 5, 6, 7, 8], "max_new_tokens": 4})
    assert ragged["tokens"][1] == solo["tokens"][0]
    rect = report.handler.invoke(report.state, {
        "tokens": [[1, 2, 3], [4, 5, 6]], "max_new_tokens": 4})
    assert rect["ok"] and np.asarray(rect["tokens"]).shape == (2, 4)
    empty = report.handler.invoke(report.state,
                                  {"tokens": [[1, 2], []]})
    assert not empty["ok"] and "empty" in empty["error"]


def test_generate_handler_prefix_caching(llama_bundle):
    """`prefix` requests reuse the cached prefix KV and match the
    concatenated-prompt response; streamed prefix requests consume the
    cached KV too (prefix_cached true) with identical tokens."""
    import numpy as np

    from lambdipy_tpu.runtime.loader import load_bundle

    report = load_bundle(llama_bundle)
    prefix, suffix = [1, 2, 3, 4, 5, 6, 7], [9, 8]
    full = report.handler.invoke(report.state,
                                 {"tokens": prefix + suffix,
                                  "max_new_tokens": 6})
    via = report.handler.invoke(report.state,
                                {"prefix": prefix, "tokens": suffix,
                                 "max_new_tokens": 6})
    assert via["ok"] and via["prefix_cached"], via
    assert via["tokens"] == full["tokens"]
    chunks = list(report.state.invoke_stream(
        {"prefix": prefix, "tokens": suffix, "max_new_tokens": 6}))
    streamed = [t for c in chunks if c.get("ok") and "tokens" in c
                for t in c["tokens"][0]]
    assert streamed == full["tokens"][0]
    summary = chunks[-1]
    assert summary.get("done") and summary["prefix_cached"] is True, summary
    assert summary["n_prompt"] == len(prefix) + len(suffix)
    bad = report.handler.invoke(report.state,
                                {"prefix": [], "tokens": suffix})
    assert not bad["ok"]


def test_generate_handler_serves_compile_once(llama_bundle):
    """The handler routes through LlamaServer: varied lengths and knobs in
    one bucket reuse a single compiled program."""
    from lambdipy_tpu.runtime.loader import load_bundle

    report = load_bundle(llama_bundle)
    r1 = report.handler.invoke(report.state, {"tokens": [1, 2, 3]})
    r2 = report.handler.invoke(report.state, {
        "tokens": [4, 5, 6, 7, 8], "temperature": 0.9, "top_k": 3,
        "seed": 5})
    assert r1["ok"] and r2["ok"]


def test_bundle_params_from_checkpoint_path(tmp_path):
    """payload.params may be a checkpoint PATH (the schema's third form —
    real deployments ship pre-built weights instead of build-time init):
    a params dir or a bare .fpk is linked/copied into the bundle and the
    served weights are EXACTLY the provided ones, not a fresh init."""
    import numpy as np

    from lambdipy_tpu.bundle.flatpack import save_checkpoint_files
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.recipes.schema import load_recipe_dict
    from lambdipy_tpu.buildengine import build_recipe
    from lambdipy_tpu.bundle import assemble_bundle
    from lambdipy_tpu.runtime.loader import load_bundle

    # distinctive weights: seed 7, not the handler default of 0
    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=7)
    src_dir = tmp_path / "ckpt"
    save_checkpoint_files(src_dir, params, "fpk")

    for src in (src_dir, src_dir / "params.fpk"):  # dir AND bare-file form
        doc = {
            "schema": 1, "name": "test-path-params", "version": "0.1",
            "device": "any", "base_layer": "jax-tpu", "requires": [],
            "payload": {
                "model": "llama-tiny",
                "handler": "lambdipy_tpu.runtime.handlers:generate_handler",
                "params": str(src), "dtype": "float32",
                "extra": {"max_new_tokens": "4"},
            },
        }
        work = tmp_path / f"w-{src.name}"
        result = build_recipe(load_recipe_dict(doc), work, run_smoke=False)
        bundle = work / "bundle"
        manifest = assemble_bundle(result, bundle, with_payload=True)
        assert manifest["payload"]["params_info"]["format"] == "external"
        report = load_bundle(bundle, warmup=False)
        out = report.handler.invoke(report.state,
                                    {"tokens": [1, 2, 3], "max_new_tokens": 4})
        assert out["ok"], out
        import jax.numpy as jnp

        expected = adapter.generate(params, jnp.asarray([[1, 2, 3]],
                                                        jnp.int32),
                                    max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                      np.asarray(expected))

    import pytest as _pytest
    doc["payload"]["params"] = str(tmp_path / "nope")
    with _pytest.raises(Exception, match="neither"):
        result = build_recipe(load_recipe_dict(doc), tmp_path / "w-bad",
                              run_smoke=False)
        assemble_bundle(result, tmp_path / "w-bad" / "bundle",
                        with_payload=True)


def test_min_bucket_recipe_knob_reaches_server(tmp_path):
    """[payload.extra] min_bucket = 1 must reach LlamaServer: a
    max_new_tokens=1 invoke then runs a ONE-step decode scan instead of
    the default 16-step bucket (~16 wasted weight reads at 8B for
    scoring workloads)."""
    from lambdipy_tpu.runtime.loader import load_bundle

    bundle = make_model_bundle(
        tmp_path, model="llama-tiny",
        handler="lambdipy_tpu.runtime.handlers:generate_handler",
        extra={"max_new_tokens": "4", "min_bucket": "1"})
    r = load_bundle(bundle, warmup=True)
    out = r.handler.invoke(r.state, {"tokens": [1, 2, 3],
                                     "max_new_tokens": 1})
    assert out["ok"] and len(out["tokens"][0]) == 1
    buckets = r.state.stats()["decode_buckets"]
    assert any(b[-1] == 1 for b in buckets), buckets
