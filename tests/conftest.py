"""Test configuration.

Runs the suite on a virtual 8-device CPU mesh (SURVEY.md §5.4): multi-chip
mesh/pjit/collective logic is exercised without TPU hardware and the same
code runs unmodified on a real slice. Both settings are read when the first
backend starts, which is after conftest import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_asset_cache(tmp_path, monkeypatch):
    """Keep the release-asset download cache out of the real HOME."""
    monkeypatch.setenv("LAMBDIPY_CACHE_DIR", str(tmp_path / "asset-cache"))


@pytest.fixture()
def tmp_registry(tmp_path):
    from lambdipy_tpu.resolve.registry import ArtifactRegistry

    return ArtifactRegistry(tmp_path / "registry")


@pytest.fixture(scope="session")
def tiny_server():
    """One shared llama-tiny LlamaServer for the engine test modules:
    its compiled-program cache is the expensive part, and the continuous
    and pipelined-engine suites exercise the same program families —
    building per-module would recompile them all. Tests that mutate
    server state (prefix registry, custom caps) build their own."""
    from lambdipy_tpu.models import registry

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    return adapter.make_server(params)


@pytest.fixture()
def fresh_compiles():
    """No persistent compile cache while the test runs. A worker that
    booted a bundle earlier keeps that bundle's cache directory set (jax's
    configuration is the process's), and XLA:CPU serialises an executable
    the persistent cache ANSWERED without its kernels: it loads, and its
    first call fails (``Function ... not found``). A test that snapshots
    what it compiles into an AOT store compiles it afresh."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual CPU devices, got {devices}"
    return devices


@pytest.fixture()
def count_sp_decode(monkeypatch):
    """Counts sp_decode_step TRACES so sp-path tests can assert the
    sequence-parallel decode actually ran (code-review r5: a silently
    dropped backend override once made those tests dense-vs-dense)."""
    import lambdipy_tpu.parallel.spdecode as spd

    calls = {"n": 0}
    real = spd.sp_decode_step

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(spd, "sp_decode_step", counting)
    return calls
