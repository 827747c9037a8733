"""From a profiler trace (``*.xplane.pb``) to device busy time, the programs
and operations that took most of it, and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is
one named ``/device:TPU:<n>``; its operations are the events of its
``XLA Ops`` line (the other lines — steps, modules — span many operations
and would count the same time twice). Busy time is the union of the
operation intervals, so operations that overlap count once. It is averaged
over the device planes. A trace without a device plane — a CPU rehearsal —
reduces to None: there is no device number to report.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that only hold others (their time is their body's)
CONTAINERS = ("%while", "%conditional", "%call")


def short(name: str) -> str:
    """An operation's own name: the trace gives the whole HLO line."""
    return name.split(" = ")[0].split("(")[0][:80]


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _largest(seconds_by_name: dict, k: int) -> list:
    return sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:k]


def _ops_line(plane):
    lines = list(plane.lines)
    for line in lines:
        if line.name == OPS_LINE:
            return line
    return None


def _host_events(data) -> list:
    out = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) or not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def find_trace(trace_dir: Path) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def reduce(path: Path, window_s: float | None = None, top: int = 10,
           gaps: int = 5) -> dict | None:
    """``{"busy_s", "window_s", "devices", "device_ops": [[name, s]...],
    "idle_gaps": [[what, s]...]}`` or None when no device plane has an
    operation. ``window_s``: the traced window as the harness timed it; the
    trace's own span is used where that is longer (or not given)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    per_device, by_name, by_module = [], {}, {}
    first_plane_busy = None
    lo, hi = None, None
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        line = _ops_line(plane)
        if line is None:
            continue
        spans = []
        for ev in line.events:
            if ev.duration_ns <= 0:
                continue
            end = ev.start_ns + ev.duration_ns
            spans.append((ev.start_ns, end))
            name = short(ev.name)
            if not name.startswith(CONTAINERS):
                by_name[name] = by_name.get(name, 0.0) + ev.duration_ns / 1e9
        if not spans:
            continue
        for mline in plane.lines:
            if mline.name == MODULES_LINE:
                for ev in mline.events:
                    key = "module " + short(ev.name)
                    by_module[key] = by_module.get(key, 0.0) \
                        + ev.duration_ns / 1e9
        busy = _union(spans)
        per_device.append(sum(e - s for s, e in busy) / 1e9)
        lo = busy[0][0] if lo is None else min(lo, busy[0][0])
        hi = busy[-1][1] if hi is None else max(hi, busy[-1][1])
        if first_plane_busy is None:
            first_plane_busy = busy
    if not per_device:
        return None
    span_s = (hi - lo) / 1e9
    n = len(per_device)
    # the programs (XLA modules) that took most time, then the single
    # operations: half of the list each
    mods = _largest(by_module, top // 2)
    ops = mods + _largest(by_name, top - len(mods))
    host = _host_events(data)
    idle = []
    for (_, e0), (s1, _) in zip(first_plane_busy, first_plane_busy[1:]):
        idle.append((s1 - e0, e0, s1))
    idle_named = []
    for length, g0, g1 in sorted(idle, reverse=True)[:gaps]:
        best, best_overlap = "host: nothing traced", 0
        for s, e, name in host:
            overlap = min(e, g1) - max(s, g0)
            if overlap > best_overlap:
                best, best_overlap = f"host: {name}", overlap
        idle_named.append([best, length / 1e9])
    return {"busy_s": sum(per_device) / n,
            "window_s": max(span_s, window_s or 0.0),
            "devices": n,
            # per-name seconds are summed over devices; give the mean
            "device_ops": [[name, s / n] for name, s in ops],
            "idle_gaps": idle_named}
