"""Spans: one primitive for "how long did this take, and for whom".

Always on; no flag. The state is the process's, like the profiler session
the spans are written into (one server a process is what is deployed; two
servers in one test process share the aggregates). Three things share one
aggregate:

- :func:`span` — a context manager for work that starts and ends on one
  thread (the engine loop's phases ``eng.*``, the boot's ``boot.*``). It
  opens a ``jax.profiler.TraceAnnotation``, so while a profiler session
  runs (``POST /profile``) the span lies in the profiler's own trace, on
  the same clock as the device's operations; while none runs that costs
  under a microsecond. On exit the duration joins the aggregate of its
  name.
- :class:`phases` — the leaf phases of a loop on one thread, one open at
  a time, built on :func:`span`.
- request records — :func:`begin_request` hands out the ``rid`` that rides
  the request context into the scheduler ticket and the engine's entry;
  :func:`mark` closes one tile of the request (from its previous stamp to
  now, ``time.monotonic()``) under a span name, from whichever thread
  reaches the boundary; :func:`end_request` records the root ``req`` and
  moves the record into a bounded ring (``GET /spans``: "why was this
  request slow"). The request spans ``req.*`` go into aggregate and
  record only, not into the profiler's trace: a ``req`` annotation would
  overlap every idle gap of the device whole, and a reader that names a
  gap by the host event overlapping it most would read ``req``
  everywhere. In a trace a request is followed by the ``rids`` argument
  of the ``eng.*`` phases that worked for it.
- durations measured elsewhere — :func:`add` books one under a span name
  (jax's own clock round a trace, a lowering, a compile: ``jit.*``, from
  ``utils/compile_cache.CompileCounters``).

Beside the request ring, :func:`program` keeps a bounded record of how each
program entered the process (``GET /spans`` → ``programs``: "why did this
replica take a minute to be ready", "which program was loaded twice").

The aggregate of a name is ``count``, ``sum_s`` and counts in power-of-two
millisecond buckets. All three only grow, so any two scrapes of
``/metrics`` → ``spans`` give the window between them.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import deque

# upper edges in ms of the first 14 buckets; the 15th counts what is over
BUCKET_EDGES_MS = tuple(2 ** i for i in range(14))  # 1 ... 8192
RING = 1024
PROGRAMS = 512


def _process_start() -> float:
    """``time.monotonic()`` when this process started: from the kernel's
    record where there is one (Linux), else this module's import."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return now
    return now - max(0.0, age)


T0 = _process_start()

_lock = threading.Lock()
_agg: dict = {}       # name -> [count, sum_s, [bucket counts]]
_live: dict = {}      # rid -> open request record
_ring: deque = deque(maxlen=RING)
_programs: deque = deque(maxlen=PROGRAMS)
_rids = itertools.count(1)
_annotation = None    # jax.profiler.TraceAnnotation, once jax is imported


def _bucket(seconds: float) -> int:
    ms = seconds * 1e3
    for i, edge in enumerate(BUCKET_EDGES_MS):
        if ms <= edge:
            return i
    return len(BUCKET_EDGES_MS)


def _add_locked(name: str, seconds: float) -> None:
    a = _agg.get(name)
    if a is None:
        a = _agg[name] = [0, 0.0, [0] * (len(BUCKET_EDGES_MS) + 1)]
    a[0] += 1
    a[1] += seconds
    a[2][_bucket(seconds)] += 1


def rids_arg(rids) -> str:
    """Several rids as ONE annotation argument. The profiler splits an
    annotation's arguments at commas, so the ids are joined by ``/``."""
    return "/".join(str(r) for r in rids if r is not None)


class span:
    """``with span("eng.dispatch", rids="3/7") as sp: ... sp.set(window=256)``"""

    __slots__ = ("name", "_ann", "t0", "seconds")

    def __init__(self, name: str, **args):
        global _annotation
        if _annotation is None:
            # taken from jax once SOMEBODY ELSE has imported it: a span
            # never starts that import itself (seconds, which belong to
            # the stage that needs jax; a bundle that serves without jax
            # stays without; and an import begun here can collide with
            # another thread's), and no profiler session runs before it
            _annotation = getattr(sys.modules.get("jax.profiler"),
                                  "TraceAnnotation", None)
        self.name = name
        self._ann = _annotation(name, **args) if _annotation else None

    def set(self, **args) -> None:
        """Arguments known only once the work is under way."""
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.seconds = time.monotonic() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        with _lock:
            _add_locked(self.name, self.seconds)
        return False


def add(name: str, seconds: float) -> None:
    """A duration somebody else measured, into the aggregate of ``name``."""
    with _lock:
        _add_locked(name, seconds)


def program(name: str | None, source: str, **fields) -> None:
    """One program's way into the process, into the bounded record:
    ``name`` (the AOT program's, or the jitted function's), ``source``
    (``exec`` / ``hlo``: deserialized from the bundle's AOT store; ``jit``:
    traced, lowered and compiled or read from the persistent cache), the
    ``key`` where the program cache knew it, and the seconds of whichever
    of ``aot_load``, ``warm``, ``trace``, ``lower``, ``compile``,
    ``cache_read`` happened (``cache_hit`` beside ``compile``). ``t`` is
    seconds since :data:`T0` when the entry was written, i.e. at the END of
    what it times."""
    entry = {"t": time.monotonic() - T0, "name": name, "source": source,
             "thread": threading.current_thread().name, **fields}
    entry = {k: round(v, 4) if isinstance(v, float) else v
             for k, v in entry.items() if v is not None or k == "name"}
    with _lock:
        _programs.append(entry)


class phases:
    """The leaf phases of one loop on one thread: ``enter`` closes the
    open phase and opens the next, so no two ever overlap and none
    encloses another (a reader that names a device's idle gap by the host
    event overlapping it most then reads the phase, never a wrapper)."""

    __slots__ = ("_open",)

    def __init__(self):
        self._open = None

    def enter(self, name: str, **args) -> span:
        self.exit()
        self._open = span(name, **args)
        return self._open.__enter__()

    def exit(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def _tile_locked(rec: dict, name: str, t0: float, t1: float,
                 parent: str | None = "req") -> None:
    """One finished span of a request: into the aggregate of ``name`` and
    into the request's record (seconds from the request's start)."""
    _add_locked(name, max(0.0, t1 - t0))
    rec["spans"].append({"name": name, "parent": parent,
                         "t0": t0 - rec["t0"], "t1": t1 - rec["t0"]})


def begin_request(t0: float | None = None) -> int:
    """A new request: its ``rid``. ``t0`` is when its body had been read."""
    now = time.monotonic()
    rid = next(_rids)
    rec = {"rid": rid, "t0": now if t0 is None else t0, "last": now,
           "first": None, "args": {}, "spans": []}
    with _lock:
        _live[rid] = rec
    return rid


def request_args(rid, **args) -> None:
    with _lock:
        rec = _live.get(rid)
        if rec is not None:
            rec["args"].update(args)


def mark(rid, name: str | None) -> None:
    """Close the request's current tile — from its previous stamp to now —
    as span ``name`` (None: move the stamp, record nothing). Any thread
    may stamp: the tiles of one request are contiguous by construction.
    An unknown or finished ``rid`` is ignored."""
    if rid is None:
        return
    now = time.monotonic()
    with _lock:
        rec = _live.get(rid)
        if rec is None:
            return
        if name is not None:
            _tile_locked(rec, name, rec["last"], now)
        rec["last"] = now


def first_frame(rid) -> None:
    """The request's first chunk has been written: ``req.ttft`` from the
    request's start, stamped on its own (so the tiles below it are a
    check, not a sum), and the tile ``req.first`` when the engine packed
    this request (a request served outside the engine has no such tile).
    Only the first call of a request counts."""
    if rid is None:
        return
    now = time.monotonic()
    with _lock:
        rec = _live.get(rid)
        if rec is None or rec["first"] is not None:
            return
        rec["first"] = now
        if rec["spans"] and rec["spans"][-1]["name"] == "req.prefill":
            _tile_locked(rec, "req.first", rec["last"], now)
        _tile_locked(rec, "req.ttft", rec["t0"], now)
        rec["last"] = now


def end_request(rid) -> None:
    """The last frame has been written (or the request was refused):
    ``req.decode`` since the first frame, the root ``req``, and the record
    moves to the ring."""
    if rid is None:
        return
    now = time.monotonic()
    with _lock:
        rec = _live.pop(rid, None)
        if rec is None:
            return
        if rec["first"] is not None:
            _tile_locked(rec, "req.decode", rec["first"], now)
        _tile_locked(rec, "req", rec["t0"], now, parent=None)
        _ring.append({"rid": rid, "args": rec["args"],
                      "spans": rec["spans"]})


def report() -> dict:
    """``/metrics`` → ``spans``: ``{name: {"count", "sum_s", "buckets"}}``."""
    with _lock:
        return {name: {"count": a[0], "sum_s": a[1], "buckets": list(a[2])}
                for name, a in sorted(_agg.items())}


def requests(last: int | None = None) -> dict:
    """``GET /spans``: the finished request records, oldest first (times
    in seconds from the request's start), the bucket edges, and the
    program record, oldest first (``last`` cuts the requests only)."""
    with _lock:
        done = list(_ring)
        programs = list(_programs)
    if last is not None:
        done = done[-last:] if last > 0 else []
    return {"bucket_edges_ms": list(BUCKET_EDGES_MS), "ring": RING,
            "requests": done, "programs": programs}
