"""bench.py's default path: staged subprocesses, no fallback. With no TPU
it prints an error line stamped with the platform jax found and exits
non-zero; only an operator's explicit LAMBDIPY_PLATFORM=cpu pin makes it
measure on the CPU, and then the line says so."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run_bench(extra_env, timeout=900):
    env = {k: v for k, v in os.environ.items()
           if k not in ("LAMBDIPY_PLATFORM", "XLA_FLAGS")}
    env.update({"LAMBDIPY_BENCH_MODEL": "resnet50-tiny", **extra_env})
    proc = subprocess.run([sys.executable, BENCH], capture_output=True,
                          text=True, env=env, timeout=timeout)
    line = proc.stdout.strip().splitlines()[-1]
    return proc.returncode, json.loads(line)


def test_bench_without_pin_or_chip_fails_with_platform_stamped():
    """No CPU attempt, no borrowed device record: the line names what jax
    found and the exit code is non-zero after the devices stage alone."""
    rc, out = _run_bench({})
    assert rc == 1
    assert out["platform"] == "cpu" and out["device_kind"] == "cpu"
    assert "no TPU" in out["error"] and out["value"] == -1.0
    assert out["stages"] == {"devices": "ok"}
    assert "last_published_device" not in out and "probe_log_tail" not in out


def test_bench_names_the_stage_that_hung():
    """A stage that outlives its timeout is killed, named in the stages
    log, and fails the run (a chip held by another process hangs like
    this)."""
    rc, out = _run_bench({"LAMBDIPY_BENCH_PROBE_TIMEOUT": "0.01"})
    assert rc == 1
    assert "hung (timeout" in out["stages"]["devices"]
    assert out["error"] == "device enumeration failed"


@pytest.mark.slow  # three stage subprocesses
def test_bench_pinned_to_cpu_reports_stages():
    rc, out = _run_bench({"LAMBDIPY_PLATFORM": "cpu"})
    assert rc == 0
    assert out["metric"] == "resnet50-tiny_b1_fwd_p50"
    assert out["value"] > 0 and out["platform"] == "cpu"
    assert out["stages"] == {"devices": "ok", "matmul": "ok", "model": "ok"}
    # no utilization against an assumed peak on a device with no peaks entry
    assert not any(k.startswith("model_") for k in out)


@pytest.mark.slow  # two full staged runs
def test_bench_stages_share_the_placed_compile_cache(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the stages fill THAT directory
    (the program sets none in code), and a second run's model compile is a
    cache hit."""
    env = {"LAMBDIPY_PLATFORM": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
    rc_cold, cold = _run_bench(env)
    assert rc_cold == 0 and any((tmp_path / "cc").iterdir())
    rc, out = _run_bench(env)
    assert rc == 0
    assert out["first_compile_s"] <= max(0.5, cold["first_compile_s"] / 2)
