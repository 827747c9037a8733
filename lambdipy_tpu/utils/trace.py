"""Profiler capture for a serving process.

:func:`profile_trace` captures a ``jax.profiler`` trace into a directory
(an ``.xplane.pb`` under ``plugins/profile/<time>/``, read with
``jax.profiler.ProfileData`` or opened in tensorboard-plugin-profile /
xprof). ``POST /profile {"seconds": s}`` of the serve loop wraps a wall
window of live traffic in it: device operations under their scope names
(``models/llama.py``) and the program's ``eng.*`` / ``boot.*`` spans
(``runtime/spans.py``) on one clock.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from pathlib import Path


class TraceCapture:
    """Handle yielded by :func:`profile_trace`; ``started`` records whether
    the profiler actually engaged (callers must surface this — an untraced
    capture must not masquerade as a trace)."""

    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)
        self.started = False
        self.error: str | None = None


@contextmanager
def profile_trace(out_dir: Path):
    """Capture a jax profiler trace into ``out_dir``. Host events are
    recorded at level 1 (the program's spans, jit dispatches); the
    python tracer is off: its frames are large and nothing reads them.
    Never raises — serving must not die to tracing — but the yielded
    :class:`TraceCapture` reports whether the profiler engaged (it won't
    if jax is absent or another trace is already active)."""
    capture = TraceCapture(out_dir)
    capture.out_dir.mkdir(parents=True, exist_ok=True)
    try:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(capture.out_dir), profiler_options=opts)
        capture.started = True
    except Exception as e:
        capture.error = f"{type(e).__name__}: {e}"
    t0 = time.monotonic()
    try:
        yield capture
    finally:
        if capture.started:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:
                capture.error = f"stop_trace: {type(e).__name__}: {e}"
        (capture.out_dir / "capture_meta.json").write_text(
            '{"wall_s": %.4f, "started": %s}'
            % (time.monotonic() - t0, "true" if capture.started else "false"))


def latest_trace_files(out_dir: Path) -> list[str]:
    out_dir = Path(out_dir)
    if not out_dir.is_dir():
        return []
    return sorted(str(p.relative_to(out_dir))
                  for p in out_dir.rglob("*") if p.is_file())[:50]
