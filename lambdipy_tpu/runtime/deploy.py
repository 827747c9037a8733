"""Deploy layer: local-process stand-in for the TPU serverless runtime.

The reference's publish layer uploads artifacts to GitHub Releases and
leaves deployment to the user (SURVEY.md §2 publish row); the rebuild gains
a real deploy target (SURVEY.md §9.9). ``LocalRuntime`` spawns a bundle
server subprocess, waits for the readiness line, health-checks it, and
records the deployment — the same control-plane contract a Cloud-Run-on-TPU
target would implement (deploy/list/invoke/stop against a URL).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request
from dataclasses import dataclass
from pathlib import Path

from lambdipy_tpu.utils.fsutil import atomic_write_text
from lambdipy_tpu.utils.logs import get_logger, log_event
from lambdipy_tpu.utils.platform import child_env

log = get_logger("lambdipy.deploy")

DEFAULT_STATE = Path.home() / ".lambdipy-tpu" / "deployments.json"


class DeployError(RuntimeError):
    pass


@dataclass
class Deployment:
    name: str
    bundle_dir: str
    pid: int
    port: int
    cold_start: dict

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"


def _http_json(url: str, payload: dict | None = None, timeout: float = 30.0) -> dict:
    if payload is None:
        req = urllib.request.Request(url)
    else:
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class LocalRuntime:
    """Process-per-function local runtime with a persisted deployment table."""

    def __init__(self, state_path: Path | None = None):
        self.state_path = Path(state_path) if state_path else DEFAULT_STATE
        self.state_path.parent.mkdir(parents=True, exist_ok=True)

    def _load(self) -> dict:
        if self.state_path.exists():
            return json.loads(self.state_path.read_text())
        return {}

    def _save(self, state: dict) -> None:
        atomic_write_text(self.state_path, json.dumps(state, indent=1))

    def deploy(self, name: str, bundle_dir: Path, *, port: int = 0,
               ready_timeout: float = 300.0, env: dict | None = None,
               watchdog: bool = True) -> Deployment:
        """Spawn a server for the bundle and wait until it reports ready.

        ``watchdog`` (default) runs the server under the restart supervisor
        (SURVEY.md §6 failure-detection row): a crashed server is respawned
        on the same port with backoff, so the deployment URL self-heals.
        ``ready_timeout`` is generous because cold start includes PJRT init
        + first compile on a cold compile cache. The server's environment
        is built by ``utils.platform.child_env``: the caller's
        LAMBDIPY_PLATFORM pin is NOT inherited — pass it in ``env`` to pin
        the server too.
        """
        bundle_dir = Path(bundle_dir).resolve()
        state = self._load()
        if name in state:
            raise DeployError(f"deployment {name!r} already exists; stop it first")
        # surface a failed build-time warm before paying for it: this boot
        # will trace+compile from scratch instead of hitting the cache
        try:
            from lambdipy_tpu.bundle.format import load_manifest

            warm_info = load_manifest(bundle_dir).get("warm")
            if isinstance(warm_info, dict) and not warm_info.get("ok"):
                log_event(log, "bundle warm step failed at build time; expect "
                               "a cold first compile", name=name,
                          warm_error=warm_info.get("error", ""))
        except Exception:
            pass  # advisory only — never blocks a deploy
        module = ("lambdipy_tpu.runtime.supervisor" if watchdog
                  else "lambdipy_tpu.runtime.server")
        cmd = [sys.executable, "-m", module, str(bundle_dir), str(port)]
        # server stderr goes to a per-deployment log so a boot failure is
        # diagnosable (`serve.log` beside the state file)
        log_path = self.state_path.parent / f"{name}.serve.log"
        log_path.parent.mkdir(parents=True, exist_ok=True)
        stderr_f = open(log_path, "w")
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=stderr_f,
                                text=True, env=child_env(env),
                                start_new_session=True)
        stderr_f.close()

        def _log_tail() -> str:
            try:
                return log_path.read_text(errors="replace")[-800:]
            except OSError:
                return ""

        deadline = time.monotonic() + ready_timeout
        ready_line = None
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                if proc.poll() is not None:
                    raise DeployError(
                        f"server for {name!r} exited rc={proc.returncode} before "
                        f"ready; log tail ({log_path}):\n{_log_tail()}")
                time.sleep(0.05)
                continue
            try:
                parsed = json.loads(line)
            except json.JSONDecodeError:
                continue
            if parsed.get("ready"):
                ready_line = parsed
                break
        if ready_line is None:
            # group-kill: with the watchdog a supervisor fronts the server,
            # and killing only the supervisor would orphan the booting child
            _signal_group(proc.pid, signal.SIGKILL)
            raise DeployError(
                f"deployment {name!r} not ready within {ready_timeout}s; "
                f"log tail ({log_path}):\n{_log_tail()}")
        dep = Deployment(name=name, bundle_dir=str(bundle_dir), pid=proc.pid,
                         port=ready_line["port"],
                         cold_start=ready_line.get("cold_start", {}))
        state[name] = dep.__dict__
        self._save(state)
        log_event(log, "deployed", name=name, port=dep.port,
                  cold_start=dep.cold_start)
        return dep

    def list(self) -> list[Deployment]:
        return [Deployment(**v) for v in self._load().values()]

    def get(self, name: str) -> Deployment:
        state = self._load()
        if name not in state:
            raise DeployError(f"no deployment named {name!r}")
        return Deployment(**state[name])

    def invoke(self, name: str, request: dict, timeout: float = 60.0) -> dict:
        dep = self.get(name)
        return _http_json(f"{dep.url}/invoke", request, timeout=timeout)

    def invoke_stream(self, name: str, request: dict, timeout: float = 60.0):
        """Streaming invoke: sets ``stream: true`` and yields one dict per
        ndjson line as the server emits decode segments."""
        dep = self.get(name)
        req = urllib.request.Request(
            f"{dep.url}/invoke",
            data=json.dumps({**request, "stream": True}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            for line in resp:  # urllib de-chunks; one JSON object per line
                line = line.strip()
                if line:
                    yield json.loads(line)

    def health(self, name: str) -> dict:
        return _http_json(f"{self.get(name).url}/healthz")

    def metrics(self, name: str) -> dict:
        return _http_json(f"{self.get(name).url}/metrics")

    def restart(self, name: str, *, ready_timeout: float = 300.0,
                env: dict | None = None, watchdog: bool = True,
                grace: float = 5.0) -> Deployment:
        """Drain + stop, then redeploy the same bundle pinned to the SAME
        port, so anything holding the deployment's URL (the fleet
        router's replica table) stays valid across the restart. This is
        the rolling-restart primitive ``ReplicaPool.rolling_restart``
        drains the fleet with."""
        dep = self.get(name)
        self.stop(name, grace=grace)
        return self.deploy(name, Path(dep.bundle_dir), port=dep.port,
                           ready_timeout=ready_timeout, env=env,
                           watchdog=watchdog)

    def stop(self, name: str, *, grace: float = 5.0) -> None:
        """Drain via /shutdown, escalate to SIGTERM, then SIGKILL the whole
        process group (deploys start a new session, so this reaps the
        supervisor AND its server child — a bare SIGKILL on the supervisor
        would orphan the serving process)."""
        dep = self.get(name)
        try:
            _http_json(f"{dep.url}/shutdown", {})
        except Exception:
            pass
        if not _wait_dead(dep.pid, grace):
            _signal_group(dep.pid, signal.SIGTERM)
            if not _wait_dead(dep.pid, grace):
                _signal_group(dep.pid, signal.SIGKILL)
        state = self._load()
        state.pop(name, None)
        self._save(state)
        # the per-deployment serve.log dies with its deployment entry —
        # otherwise one orphan file per deployment name accumulates forever
        try:
            (self.state_path.parent / f"{name}.serve.log").unlink(missing_ok=True)
        except OSError:
            pass
        log_event(log, "stopped", name=name)


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except (ProcessLookupError, PermissionError):
        return False


def _wait_dead(pid: int, grace: float) -> bool:
    deadline = time.monotonic() + grace
    while time.monotonic() < deadline:
        if not _pid_alive(pid):
            return True
        time.sleep(0.1)
    return not _pid_alive(pid)


def _signal_group(pid: int, sig: int) -> None:
    """Signal the deployment's process group, falling back to the single
    pid if the group is gone."""
    try:
        os.killpg(pid, sig)
    except (ProcessLookupError, PermissionError):
        try:
            os.kill(pid, sig)
        except (ProcessLookupError, PermissionError):
            pass
