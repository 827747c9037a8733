"""One deployment of a bundle for one run: start, wait for ready, scrape,
stop. ``--trace 0`` goes through ``LocalRuntime.deploy(..., watchdog=False)``
unchanged; ``--trace 1`` starts the same server entry point through
``benchmark/traced_server.py``, because only the process that holds the
chip can trace it."""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

from benchmark.bundle import REPO, BenchFailure, note


def http_json(url: str, payload: dict | None = None, timeout: float = 60.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method="GET" if payload is None else "POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class Served:
    """A context manager around one server process."""

    def __init__(self, bundle: Path, work: Path, *, traced: bool,
                 env: dict | None = None, ready_timeout: float = 1100.0):
        from lambdipy_tpu.utils.platform import child_env

        self.traced = traced
        self.trace_dir = work / "trace"
        self.proc = None
        self.rt = None
        self.state = None
        t0 = time.monotonic()
        if traced:
            self._spawn_traced(bundle, work, child_env(env), ready_timeout)
        else:
            from lambdipy_tpu.runtime.deploy import LocalRuntime

            self.state = work / "deployments.json"
            self.state.unlink(missing_ok=True)
            self.rt = LocalRuntime(self.state)
            dep = self.rt.deploy("bench", bundle, ready_timeout=ready_timeout,
                                 env=env, watchdog=False)
            self.port, self.pid = dep.port, dep.pid
        self.url = f"http://127.0.0.1:{self.port}"
        deadline = time.monotonic() + ready_timeout
        while True:
            self.health = http_json(f"{self.url}/healthz")
            if self.health.get("ready"):
                break
            if time.monotonic() > deadline:
                self.stop()
                raise BenchFailure(f"never became ready: {self.health}")
            time.sleep(0.25)
        self.ready_s = time.monotonic() - t0
        self.device = self.health.get("device") or {}

    def _spawn_traced(self, bundle, work, env, ready_timeout):
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True)
        log = open(work / "bench.serve.log", "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.traced_server", str(bundle), "0",
             str(self.trace_dir)],
            cwd=str(REPO), stdout=subprocess.PIPE, stderr=log, text=True,
            env=env, start_new_session=True)
        log.close()
        deadline = time.monotonic() + ready_timeout
        while time.monotonic() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                if self.proc.poll() is not None:
                    raise BenchFailure(
                        f"traced server exited rc={self.proc.returncode}: "
                        + (work / "bench.serve.log").read_text()[-800:])
                time.sleep(0.05)
                continue
            try:
                parsed = json.loads(line)
            except json.JSONDecodeError:
                continue
            if parsed.get("ready"):
                self.port, self.pid = parsed["port"], self.proc.pid
                return
        self.stop()
        raise BenchFailure("traced server not ready in time")

    def metrics(self) -> dict:
        return http_json(f"{self.url}/metrics")

    def trace(self, on: bool) -> None:
        """Start or stop the profiler in the traced server, and wait until
        it has."""
        want = self.trace_dir / ("start" if on else "stop")
        want.touch()
        ack = self.trace_dir / ("started" if on else "stopped")
        deadline = time.monotonic() + 280
        while not ack.exists():
            if time.monotonic() > deadline:
                raise BenchFailure(f"profiler did not acknowledge {want.name}")
            time.sleep(0.02)

    def _reaped(self, seconds: float) -> bool:
        """Wait for the server — this process's child on either path — and
        reap it: an unreaped child is a zombie that still answers signal 0
        (which is why ``LocalRuntime.stop`` from the deploying process waits
        out both its grace periods; not used here)."""
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            try:
                if os.waitpid(self.pid, os.WNOHANG)[0]:
                    return True
            except ChildProcessError:
                return True
            time.sleep(0.05)
        return False

    def stop(self) -> None:
        """Drain through ``/shutdown``, then SIGTERM, then SIGKILL the group.
        The chip is free only when the process is gone."""
        if getattr(self, "pid", None) is None:
            return
        try:
            http_json(f"{self.url}/shutdown", {}, timeout=10)
        except (OSError, ValueError, AttributeError):
            pass
        for sig in (None, signal.SIGTERM, signal.SIGKILL):
            if sig is not None:
                try:
                    os.killpg(self.pid, sig)
                except (ProcessLookupError, PermissionError):
                    pass
            if self._reaped(30.0):
                break
        else:
            note(stage="stop", error=f"server {self.pid} did not exit")
        self.pid = None
        if self.state is not None:  # the runtime's table names a dead server
            self.state.unlink(missing_ok=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
