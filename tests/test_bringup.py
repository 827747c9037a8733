"""The repairs that let build -> warm -> deploy -> serve run on an attached
chip without hiding it: platform pins that raise, child environments built
explicitly, a compile cache that can be placed from outside, a mesh that is
never served on fewer devices than it declares, a warm step that finishes
cleanly and fails the build when it matters, and a server that says which
device it holds."""

import json
import os
import subprocess
import sys

import pytest
from click.testing import CliRunner

from lambdipy_tpu.utils import compile_cache, platform
from tests.test_runtime import make_model_bundle

GENERATE = "lambdipy_tpu.runtime.handlers:generate_handler"


# -- platform ------------------------------------------------------------------


def _python(code: str, env: dict):
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)


def test_platform_override_raises_on_unknown_platform():
    """A pin that cannot be applied is an error, not a warning followed by
    whatever platform jax picked (fresh interpreter: this one already has
    a backend)."""
    code = ("from lambdipy_tpu.utils.platform import "
            "apply_platform_override as a, PlatformError\n"
            "try:\n    a()\nexcept PlatformError as e:\n"
            "    print('RAISED', e)\n")
    env = platform.child_env({"LAMBDIPY_PLATFORM": "no-such-platform",
                              "JAX_PLATFORMS": ""})
    proc = _python(code, env)
    assert "RAISED" in proc.stdout and "no-such-platform" in proc.stdout, \
        proc.stderr[-400:]


def test_platform_override_applies_and_reports(monkeypatch):
    import jax

    monkeypatch.delenv("LAMBDIPY_PLATFORM", raising=False)
    assert platform.apply_platform_override() is None
    monkeypatch.setenv("LAMBDIPY_PLATFORM", "cpu")
    assert platform.apply_platform_override() == "cpu"
    # a backend that already runs elsewhere cannot be re-pinned silently
    monkeypatch.setenv("LAMBDIPY_PLATFORM", "tpu")
    try:
        with pytest.raises(platform.PlatformError):
            platform.apply_platform_override()
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_child_env_drops_the_parents_pin_unless_passed(monkeypatch):
    """A parent that pins ITSELF to the CPU must not pin the server or warm
    child it starts; a caller that wants the child pinned says so."""
    monkeypatch.setenv("LAMBDIPY_PLATFORM", "cpu")
    monkeypatch.setenv("PYTHONPATH", "/somewhere/else")
    env = platform.child_env()
    assert "LAMBDIPY_PLATFORM" not in env
    assert env["PYTHONPATH"].split(os.pathsep) == [str(platform.REPO_ROOT),
                                                   "/somewhere/else"]
    assert platform.child_env({"LAMBDIPY_PLATFORM": "cpu"})[
        "LAMBDIPY_PLATFORM"] == "cpu"
    # the CLI speaks for the operator and forwards the pin explicitly
    assert platform.child_env(platform.operator_pin())[
        "LAMBDIPY_PLATFORM"] == "cpu"


def test_random_params_need_no_backend(tmp_path):
    """The seeded parameter generator the smoke and the measure scripts'
    parents use touches shapes only: under a platform that cannot start,
    any backend initialization would raise."""
    code = ("import sys\n"
            "from lambdipy_tpu.models import registry\n"
            "info = registry.save_random_params('llama3-8b', sys.argv[1], "
            "extra=dict(vocab_size=256, hidden=64, layers=2, heads=4, "
            "kv_heads=2, mlp=128), seed=3)\n"
            "print('WROTE', info['n_params'])\n")
    env = platform.child_env({"JAX_PLATFORMS": "no-such-platform"})
    proc = subprocess.run([sys.executable, "-c", code,
                           str(tmp_path / "p.fpk")], env=env,
                          capture_output=True, text=True, timeout=120)
    assert "WROTE" in proc.stdout, proc.stderr[-400:]
    from lambdipy_tpu.bundle import flatpack

    leaf = flatpack.load(tmp_path / "p.fpk")["params"]["layer_0"]["q_proj"]
    assert leaf["kernel_int8"].dtype == "int8" and leaf["scale"].shape == (1, 64)


# -- compile cache placement -----------------------------------------------------


def test_cache_dir_from_env_sets_nothing_in_code(monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.compile_cache_dir(tmp_path / "bundle") is None
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache(tmp_path / "bundle") == \
        tmp_path / "placed"
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "bundle").exists()


def test_cache_dir_unset_is_fixed_inside_the_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert first.is_relative_to(platform.REPO_ROOT)
    assert str(os.getpid()) not in str(first) and "tmp" not in first.parts
    # another process, another working directory: the same path
    code = ("from lambdipy_tpu.utils.compile_cache import compile_cache_dir\n"
            "print(compile_cache_dir())")
    env = platform.child_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=120)
    assert proc.stdout.strip() == str(first), proc.stderr[-400:]
    # a bundle carries its own, at the path it is served from
    assert compile_cache.compile_cache_dir(tmp_path / "b") == \
        tmp_path / "b" / "compile_cache"


def test_the_cache_key_changes_with_the_names_generation(monkeypatch,
                                                        tmp_path):
    """jax leaves a program's names out of the persistent cache's key, so
    the program salts the key with ``NAMES_GEN``: the same computation
    under another generation of scope names is another entry."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src import cache_key, compiler

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    monkeypatch.setattr(cache_key, "custom_hook", cache_key.custom_hook)
    compile_cache.enable_compile_cache()
    assert cache_key.custom_hook() == compile_cache.NAMES_GEN

    def key_of(fn):
        module = jax.jit(fn).lower(jnp.ones((4,))).compiler_ir()
        devices = np.array(jax.devices()[:1])
        options = compiler.get_compile_options(num_replicas=1,
                                               num_partitions=1)
        return cache_key.get(module, devices, options, devices[0].client)

    def plain(x):
        return x * 2

    def scoped(x):
        with jax.named_scope("mlp"):
            return x * 2

    scoped.__name__ = "plain"
    # names alone do not move the key ...
    assert key_of(plain) == key_of(scoped)
    first = key_of(plain)
    # ... the generation does
    monkeypatch.setattr(compile_cache, "NAMES_GEN", "names-other")
    assert key_of(plain) != first


# -- mesh ----------------------------------------------------------------------------


def test_declared_mesh_larger_than_visible_devices_raises(cpu_devices):
    """A mesh is never degraded to single-device serving: the operator who
    declared 16 devices and has 8 gets an error that says so."""
    from lambdipy_tpu.models import registry
    from lambdipy_tpu.runtime.handlers import _maybe_shard

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    with pytest.raises(ValueError, match="needs 16 devices but only 8"):
        _maybe_shard(adapter, params, {"mesh": {"tp": 16}})
    _, mesh = _maybe_shard(adapter, params, {"mesh": {"tp": 2}})
    assert dict(mesh.shape) == {"tp": 2}


# -- warm step -------------------------------------------------------------------------


def test_warm_subprocess_exits_zero_with_background_warm(tmp_path):
    """The warm subprocess waits out the handler's background warm (the
    engine's group-prefill programs) before it exits: exit code 0 — it used
    to abort at interpreter shutdown with a thread still compiling, rc 134,
    and every build recorded warm.ok = false — and those programs are in
    the cache it reports."""
    bundle = make_model_bundle(
        tmp_path, handler=GENERATE,
        extra={"max_new_tokens": "4", "batch_mode": "continuous",
               "batch_max": "2", "warm_group_prefill": "1"})
    proc = subprocess.run(
        [sys.executable, "-m", "lambdipy_tpu.runtime.warm", str(bundle)],
        env=platform.child_env({"LAMBDIPY_PLATFORM": "cpu"}),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-600:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["warmed"] is True
    assert out["background_warm"]["in_flight"] is False
    assert out["background_warm"]["done"] and not out["background_warm"]["errors"]
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert out["cache_dir"] == str(bundle / "compile_cache")
    assert out["cache_entries"] == out["compile"]["compiled"] > 0


@pytest.mark.parametrize("device,exit_code", [("tpu-v5e-1", 1), ("any", 0)])
def test_build_fails_on_failed_warm_only_for_tpu_recipes(tmp_path, monkeypatch,
                                                         device, exit_code):
    """A warm step that fails (here: times out at once) is recorded in the
    manifest either way; for a recipe that targets a TPU it also fails the
    build instead of shipping a bundle that compiles everything at boot."""
    from lambdipy_tpu.cli import main

    recipes = tmp_path / "recipes"
    recipes.mkdir()
    (recipes / "tiny-llm.toml").write_text(
        f'schema = 1\nname = "tiny-llm"\nversion = "0.1"\n'
        f'device = "{device}"\nbase_layer = "jax-tpu"\nrequires = []\n'
        f'[payload]\nmodel = "llama-tiny"\nhandler = "{GENERATE}"\n'
        'params = "init"\ndtype = "float32"\n')
    monkeypatch.setenv("LAMBDIPY_WARM_TIMEOUT", "0.01")
    out = tmp_path / "bundle"
    r = CliRunner().invoke(main, ["build", "tiny-llm", "--recipe-dir",
                                  str(recipes), "--out", str(out)])
    assert r.exit_code == exit_code, r.output
    warm = json.loads((out / "manifest.json").read_text())["warm"]
    assert warm["ok"] is False and "timeout" in warm["error"]
    if exit_code:
        assert "warm step failed for tpu-v5e-1 recipe" in r.output


# -- what the server says about its device -------------------------------------------------


def test_healthz_and_metrics_carry_the_device_block(tmp_path):
    """/healthz names the device the SERVING process holds (platform, kind,
    count from jax.devices()), so a parent that must stay off the chip can
    check its child; /metrics adds allocator statistics and the compile
    counters."""
    import urllib.request

    from lambdipy_tpu.runtime.server import BundleServer

    bundle = make_model_bundle(tmp_path, handler=GENERATE,
                               extra={"max_new_tokens": "4"})
    server = BundleServer(bundle, port=0).start_background()
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}{path}", timeout=30) as r:
                return json.loads(r.read())

        assert get("/healthz")["device"] == {"platform": "cpu", "kind": "cpu",
                                             "count": 8}
        metrics = get("/metrics")
        assert metrics["device"]["platform"] == "cpu"
        assert len(metrics["device"]["memory"]) == 8
        assert metrics["compile"]["requests"] >= metrics["compile"][
            "persistent_cache_hits"] >= 0
        assert metrics["compile"]["requests"] > 0
    finally:
        server.stop()
