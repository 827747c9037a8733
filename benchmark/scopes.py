"""The decode segment's device time, split by the scope names the program
gives its operations (``jax.named_scope`` in the model's module). Which names
those are is the family's to say (``SCOPES`` and ``WITNESS`` of the cell's
family, ``benchmark/families``): every function here that reads names takes
them from the family it is handed.

From the same ``.xplane.pb`` the reducer reads. A device plane's
``XLA Modules`` line holds one event per program run; the decode segment is
the program ``jit_seg`` (every window variant carries that name, followed by
its program id: ``jit_seg(7280407790217281831)``). Its operations are the
events of the ``XLA Ops`` line that start inside one of its runs; of three
runs or more the first and the last are left out, because the profiler
records the runs in flight at its start and stop cut off. The HLO
``op_name`` of an operation — ``jit(seg)/while/body/closed_call/LlamaModel/
layer_3/mlp/down_proj/dot_general`` — is NOT among the stats of its events
(``jax.profiler.ProfileData`` shows three: offset, duration, time scale).
It is the stat ``tf_op`` of the event's METADATA, beside ``program_id``,
``flops`` and ``bytes_accessed`` (TPU v5e, jax 0.9: looked at by hand, PR
24). ``ProfileData`` does not expose metadata stats, so ``op_names`` reads
those two from the file's protobuf wire format itself (the XSpace / XPlane /
XEventMetadata / XStat messages of tsl's ``xplane.proto``): a few thousand
metadata entries, never the events. An operation is counted under the
INNERMOST path component of its op_name that is one of the family's scopes, or
under ``""`` when none is. Operations that only hold others (``%while`` ...)
are left out: their time is their body's.

Operations the compiler made have no op_name at all. Nearly all of their
time is ``%copy-done`` / ``%slice-done``: the core waiting for an
asynchronous copy it started earlier — a weight matrix or a cache window on
its way from HBM — and the compiler puts the wait right in front of the
operation that needs the data. So such a wait is charged to the next
operation of the same run that has a scope (the weight stream of a matmul
counts as that matmul's); ``waited_s`` says how much was charged so. Seconds
are averaged over the device planes.

``for_run(family)`` finds the trace of the run in progress the way ``run.py``
does (``--work-dir``, else the default work directory, then ``trace/``) and
reduces it once per process: the readers in ``layer_metrics/`` share it.
"""

from __future__ import annotations

import bisect
import functools
import re
import sys
from pathlib import Path

from benchmark import xplane
from benchmark.bundle import DEFAULT_WORK

SEGMENT_MODULE = "jit_seg"
PROGRAM_ID = re.compile(r"\((\d+)\)\s*$")
CONTAINER, WAIT = "<container>", "<wait>"


def scope_of(op_name: str, names) -> str:
    """The innermost component of an op_name path that is in ``names``."""
    for part in reversed(op_name.split("/")):
        if part in names:
            return part
    return ""


# -- the metadata stats, from the protobuf wire format -----------------------
# field numbers of xplane.proto: XSpace.planes 1; XPlane.name 2,
# .event_metadata 4, .stat_metadata 5 (maps: entry.key 1, .value 2);
# XEventMetadata.name 2, .stats 5; XStatMetadata.name 2; XStat.metadata_id 1,
# .uint64_value 3, .int64_value 4, .str_value 5, .ref_value 7

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_values(entries: list) -> dict:
    """A protobuf map's entries -> {key: value message}."""
    out = {}
    for entry in entries:
        pair = dict(_fields(entry))
        out[pair.get(1, 0)] = pair.get(2, b"")
    return out


def op_names(path: Path) -> dict:
    """``{(program id, operation's full name): op_name}`` over the device
    planes of the trace; the program id is '' where the metadata has none."""
    out: dict = {}
    space = memoryview(Path(path).read_bytes())
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], []
        for f, value in _fields(plane):
            if f == 2:
                name = _text(value)
            elif f == 4:
                events.append(value)
            elif f == 5:
                stat_names.append(value)
        if not xplane.DEVICE_PLANE.match(name):
            continue
        names = {k: next((_text(v) for f, v in _fields(m) if f == 2), "")
                 for k, m in _map_values(stat_names).items()}
        for meta in _map_values(events).values():
            op, tf_op, program = "", "", ""
            for f, value in _fields(meta):
                if f == 2:
                    op = _text(value)
                elif f == 5:
                    stat = dict(_fields(value))
                    what = names.get(stat.get(1))
                    if what == "tf_op":
                        tf_op = (_text(stat[5]) if 5 in stat
                                 else names.get(stat.get(7), ""))
                    elif what == "program_id":
                        program = str(stat.get(3, stat.get(4, "")))
            if tf_op:
                # "<op_name>:<op type>", the type often empty
                out[(program, op)] = tf_op.rsplit(":", 1)[0]
    return out


# -- the split ---------------------------------------------------------------

def whole_runs(runs: list) -> list:
    """Of a device's runs in time order, those recorded whole. The runs in
    flight when the profiler started and stopped are recorded cut off;
    counted as whole runs they read a step a fifth too short (nine "runs"
    in 2 s of 250 ms segments: my chip run, PR 24). Fewer than three runs
    stay as they are: a cut fixture holds one."""
    return runs[1:-1] if len(runs) >= 3 else runs


def segment_split(path: Path, family) -> dict | None:
    """``{"runs", "run_s", "op_s", "by_scope": {scope: s}, "waited_s",
    "scoped"}`` for the segment program, split by ``family.SCOPES``, or
    None when no device plane ran it. ``runs`` is the number of segment
    runs traced (mean over devices), ``run_s`` the sum of their durations,
    ``op_s`` the sum of their operations' durations (what ``by_scope`` adds
    up to), ``waited_s`` the part of it that was waits charged to the
    operation behind them, ``scoped`` whether the program's own scope names
    (``family.WITNESS``) were found."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    named = None
    devices = 0
    runs, run_s, waited_s = 0, 0.0, 0.0
    by_scope: dict = {}
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = lines.get(xplane.OPS_LINE)
        modules = lines.get(xplane.MODULES_LINE)
        if ops is None or modules is None:
            continue
        spans = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in modules.events
            if xplane.short(ev.name) == SEGMENT_MODULE and ev.duration_ns > 0)
        spans = whole_runs(spans)
        if not spans:
            continue
        if named is None:
            named = op_names(path)
        devices += 1
        runs += len(spans)
        run_s += sum(e - s for s, e, _ in spans) / 1e9
        starts = [s for s, _, _ in spans]
        programs = [(PROGRAM_ID.search(m) or [None, ""])[1]
                    for _, _, m in spans]
        seen: dict = {}   # (program, operation) -> scope
        run, waiting = -1, 0.0
        for start, ns, name in sorted(
                (ev.start_ns, ev.duration_ns, ev.name) for ev in ops.events
                if ev.duration_ns > 0):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= spans[i][1]:
                continue
            if i != run:   # a wait nothing followed stays unnamed
                by_scope[""] = by_scope.get("", 0.0) + waiting
                run, waiting = i, 0.0
            key = (programs[i], name)
            scope = seen.get(key)
            if scope is None:
                if xplane.short(name).startswith(xplane.CONTAINERS):
                    scope = CONTAINER
                else:
                    op_name = named.get(key) or named.get(("", name))
                    scope = WAIT if op_name is None \
                        else scope_of(op_name, family.SCOPES)
                seen[key] = scope
            if scope == CONTAINER:
                continue
            if scope == WAIT:
                waiting += ns / 1e9
                continue
            by_scope[scope] = by_scope.get(scope, 0.0) + ns / 1e9
            if scope and waiting:
                by_scope[scope] += waiting
                waited_s += waiting
                waiting = 0.0
        by_scope[""] = by_scope.get("", 0.0) + waiting
    if not devices:
        return None
    by_scope = {k: v / devices for k, v in sorted(by_scope.items())}
    return {"runs": runs / devices, "run_s": run_s / devices,
            "op_s": sum(by_scope.values()), "by_scope": by_scope,
            "waited_s": waited_s / devices,
            "scoped": any(by_scope.get(w, 0.0) > 0
                          for w in family.WITNESS)}


def work_dir() -> Path:
    """Where this run keeps its trace: as ``run.py`` decides it."""
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == "--work-dir" and i + 1 < len(argv):
            return Path(argv[i + 1])
        if arg.startswith("--work-dir="):
            return Path(arg.split("=", 1)[1])
    return DEFAULT_WORK


@functools.cache
def for_run(family) -> dict | None:
    found = xplane.find_trace(work_dir() / "trace")
    return segment_split(found, family) if found else None


def step_ms(ctx: dict, scopes: tuple | None = None) -> float | None:
    """Milliseconds of device time a decode step took in the traced slice:
    all of the segment program (``scopes`` None: the runs' own durations),
    or only its operations under ``scopes`` (names of the cell's family,
    ``ctx["family"]``). A step is one of the ``handler.batching.segment``
    steps of a run. None where the run has no device trace, ran no segment,
    or (for a part) the program names no scopes."""
    if not ctx.get("trace"):
        return None
    try:
        segment = int(ctx["m_close"]["handler"]["batching"]["segment"])
    except (KeyError, TypeError, ValueError):
        return None
    split = for_run(ctx["family"])
    if not split or split["runs"] <= 0 or segment <= 0:
        return None
    steps = split["runs"] * segment
    if scopes is None:
        return 1e3 * split["run_s"] / steps
    if not split["scoped"]:
        return None
    return 1e3 * sum(split["by_scope"].get(s, 0.0) for s in scopes) / steps
