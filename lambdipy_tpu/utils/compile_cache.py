"""Where JAX's persistent compilation cache lives — decided in one place.

The directory is part of how a cached program is found again, so a cache
written under one path and read under another never hits. Hence:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the program
  sets no directory in code at all — the operator (or the machine) owns
  the placement.
- unset, with a bundle: ``<bundle>/compile_cache``, shipped warm by the
  builder (``lambdipy build`` warms the bundle at the path it is served
  from).
- unset, no bundle (today only the soak's own process, ``python -m
  lambdipy_tpu.chaos.soak``): one fixed directory inside the checkout —
  never a temporary, per-process or home directory.
"""

from __future__ import annotations

import os
import threading
import time
from pathlib import Path

from lambdipy_tpu.utils.platform import REPO_ROOT

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# The cache's key leaves a program's names out (jax strips debug info before
# it hashes), so a program whose computation is unchanged would be served
# the executable compiled under its OLD scope names, and a trace would
# show those. Bump this when a ``jax.named_scope`` of ``models/`` or
# ``ops/`` is renamed, added or moved, or a jitted function is renamed
# (``LlamaServer._AOT_GEN`` is the same switch for the bundle's AOT tier).
NAMES_GEN = "names-1"
CHECKOUT_CACHE = REPO_ROOT / ".lambdipy_cache" / "compile"


def compile_cache_dir(bundle_dir: Path | None = None) -> Path | None:
    """The directory this program would set in code, or None when the
    environment already places the cache."""
    if os.environ.get(CACHE_ENV):
        return None
    if bundle_dir is not None:
        return Path(bundle_dir) / "compile_cache"
    return CHECKOUT_CACHE


def enable_compile_cache(bundle_dir: Path | None = None) -> Path:
    """Turn the persistent cache on for every program, however small or
    quick to compile, and return the directory in force."""
    import jax

    cache_dir = compile_cache_dir(bundle_dir)
    if cache_dir is not None:
        cache_dir.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from jax._src import cache_key

    if not callable(getattr(cache_key, "custom_hook", None)):
        raise RuntimeError("this jax has no cache_key.custom_hook: the "
                           "persistent cache could serve programs compiled "
                           "under other scope names")
    cache_key.custom_hook = lambda: NAMES_GEN
    return cache_dir or Path(os.environ[CACHE_ENV])


class _PerThread(threading.local):
    """What one thread's jax events have said of the program on its way."""

    traces: tuple = ()   # (began, seconds) of the traces no lowering followed
    entry: dict | None = None   # the program record's entry, until its compile


class CompileCounters:
    """Counts the XLA compile requests made in this process while the
    object is open, and how many of them the persistent cache answered,
    from jax's own monitoring events. The owner calls :meth:`close`.

    The same listeners book every program's way through jax into the span
    aggregate (``runtime/spans.py``), by jax's own clock: ``jit.trace``,
    ``jit.lower``, ``jit.compile`` (with a persistent hit: key hashing,
    cache read, deserialise, load onto the device) and ``jit.cache_read``
    (a part of ``jit.compile``), and write one entry of the program record
    a compile. They fire only where something is traced or compiled: a
    warm call of a jitted program passes none of this.

    jax times every jitted function traced INSIDE another one too (each
    ``jnp`` function is one), and those it meets while it lowers. So a
    trace is held back, per thread, until the lowering that follows it:
    only traces that lie inside no other and began before the lowering did
    are booked, each once."""

    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _REQUEST = "/jax/core/compile/backend_compile_duration"
    _CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        from lambdipy_tpu.runtime import spans

        self._spans = spans
        self._lock = threading.Lock()
        self._thread = _PerThread()
        self.requests = 0
        self.request_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration_secs: float,
                     fun_name: str | None = None, **_kw) -> None:
        spans, th = self._spans, self._thread
        if event == self._TRACE:
            # the events of one thread come in the order their work ENDED:
            # what began after this trace did lies inside it
            began = time.monotonic() - duration_secs
            th.traces = (*(t for t in th.traces if t[0] < began),
                         (began, duration_secs))
        elif event == self._LOWER:
            began = time.monotonic() - duration_secs
            traces = [t for t in th.traces if t[0] < began]
            th.traces = ()
            for _, seconds in traces:
                spans.add("jit.trace", seconds)
            spans.add("jit.lower", duration_secs)
            # the last trace before a lowering is that program's own; a
            # program whose jaxpr jax still held has none
            th.entry = {"name": fun_name, "lower": duration_secs,
                        **({"trace": traces[-1][1]} if traces else {})}
        elif event == self._CACHE_READ:
            spans.add("jit.cache_read", duration_secs)
            if th.entry is not None:
                th.entry["cache_read"] = duration_secs
        elif event == self._REQUEST:
            with self._lock:
                self.requests += 1
                self.request_s += duration_secs
            spans.add("jit.compile", duration_secs)
            entry, th.entry = th.entry or {"name": fun_name}, None
            entry.setdefault("cache_hit", False)
            spans.program(entry.pop("name"), "jit", **entry,
                          compile=duration_secs)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            with self._lock:
                self.cache_hits += 1
            if self._thread.entry is not None:
                self._thread.entry["cache_hit"] = True

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def report(self) -> dict:
        with self._lock:
            return {"requests": self.requests,
                    "seconds": round(self.request_s, 3),
                    "persistent_cache_hits": self.cache_hits,
                    "compiled": self.requests - self.cache_hits}
