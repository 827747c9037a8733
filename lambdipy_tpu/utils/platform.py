"""Which device a process runs on, and what it tells its children.

A TPU chip belongs to one process at a time: a parent that has started a
JAX backend on it holds it, and a child that needs it then fails or
hangs. So every entry point decides its platform here, once, before any
backend starts:

- ``LAMBDIPY_PLATFORM`` pins the process that READS it (CPU drives and
  tests set ``cpu``). :func:`apply_platform_override` applies it and
  raises when the backend that comes up is not the one asked for.
- :func:`prefer_cpu_backend` keeps a build-time step (param init, weight
  conversion) off the accelerator, leaving the chip to the warm
  subprocess that must own it.
- :func:`child_env` builds a child's environment explicitly, so a pin
  meant for the parent does not follow the child onto the CPU.
"""

from __future__ import annotations

import os
from pathlib import Path

PLATFORM_ENV = "LAMBDIPY_PLATFORM"
REPO_ROOT = Path(__file__).resolve().parents[2]


class PlatformError(RuntimeError):
    """The platform named by LAMBDIPY_PLATFORM could not be applied."""


def apply_platform_override() -> str | None:
    """Pin jax to the platform named by LAMBDIPY_PLATFORM, if set, start
    that backend and return its name. Raises :class:`PlatformError` when
    the name is unknown to this installation, the backend fails to start,
    or another backend already started first — a process that asked for
    one device must never continue on another."""
    platform = os.environ.get(PLATFORM_ENV)
    if not platform:
        return None
    import jax

    jax.config.update("jax_platforms", platform)
    try:
        got = jax.devices()[0].platform
    except RuntimeError as e:
        raise PlatformError(
            f"{PLATFORM_ENV}={platform!r} could not be applied: {e}") from e
    if got != platform:
        raise PlatformError(
            f"{PLATFORM_ENV}={platform!r} asked for, but jax already runs on "
            f"{got!r} (a backend started before the override)")
    return platform


def prefer_cpu_backend() -> None:
    """Keep this process off the accelerator: pin jax to the CPU, or to
    LAMBDIPY_PLATFORM where the operator named one. A no-op once a backend
    has started (jax ignores the update), so call it before the first jax
    computation."""
    import jax

    jax.config.update("jax_platforms", os.environ.get(PLATFORM_ENV) or "cpu")


def child_env(overrides: dict | None = None) -> dict:
    """Environment for a child process (server, warm step, a benchmark run's deploy).

    The parent's environment minus LAMBDIPY_PLATFORM, plus ``overrides``,
    with the repo root first on PYTHONPATH so the child imports this
    framework. The pin is dropped because it names the platform of the
    process that set it: a parent that keeps itself on the CPU while its
    child serves on the chip is the normal arrangement, and an inherited
    pin would put the child on the CPU without complaint. A caller that
    wants the child pinned passes the pin in ``overrides``."""
    env = {k: v for k, v in os.environ.items() if k != PLATFORM_ENV}
    env.update(overrides or {})
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO_ROOT)]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
           if p and p != str(REPO_ROOT)])
    return env


def operator_pin() -> dict:
    """The operator's own LAMBDIPY_PLATFORM as an explicit ``child_env``
    override — for the CLI commands, which speak for the operator and so
    do forward the pin to the servers they start."""
    pin = os.environ.get(PLATFORM_ENV)
    return {PLATFORM_ENV: pin} if pin else {}


def device_identity() -> dict:
    """What jax runs on in THIS process (starts the backend if none has):
    platform, kind and count as ``jax.devices()`` reports them."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def device_report() -> dict:
    """:func:`device_identity` plus each device's allocator statistics,
    where the backend keeps them."""
    import jax

    memory = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        memory.append({k: int(stats[k]) for k in
                       ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                       if k in stats})
    return {**device_identity(), "memory": memory}


def probe_device(timeout_s: float = 300.0) -> dict:
    """:func:`device_identity` as a CHILD process sees it — for a parent
    that must not start a backend itself (the child has exited, and
    released the chip, on return). Raises ``RuntimeError`` when the child
    fails and ``subprocess.TimeoutExpired`` when it hangs (a chip held by
    another process does that)."""
    import json
    import subprocess
    import sys

    code = ("import json\n"
            "from lambdipy_tpu.utils.platform import device_identity\n"
            "print(json.dumps(device_identity()))")
    proc = subprocess.run([sys.executable, "-c", code], env=child_env(),
                          capture_output=True, text=True, timeout=timeout_s)
    if proc.returncode != 0:
        raise RuntimeError(f"device probe failed rc={proc.returncode}: "
                           f"{proc.stderr.strip()[-400:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])
