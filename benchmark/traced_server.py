"""``python -m benchmark.traced_server <bundle> <port> <trigger dir>``: the
program's own server entry point, in-process, plus one thread that starts
and stops the jax profiler when the harness touches ``start`` / ``stop`` in
the trigger directory (and answers with ``started`` / ``stopped``). The
program has no wall-window trace endpoint, and only the process that holds
the chip can trace it. Used for ``--trace 1`` runs only."""

from __future__ import annotations

import sys
import threading
import time
from pathlib import Path


def _watch(trigger: Path) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # host python frames: large, and not read
    opts.host_tracer_level = 1
    while True:
        if (trigger / "start").exists() and not (trigger / "started").exists():
            jax.profiler.start_trace(str(trigger / "xplane"),
                                     profiler_options=opts)
            (trigger / "started").touch()
        if (trigger / "stop").exists() and not (trigger / "stopped").exists():
            jax.profiler.stop_trace()
            (trigger / "stopped").touch()
            return
        time.sleep(0.01)


def main(argv: list) -> int:
    from lambdipy_tpu.runtime import server

    threading.Thread(target=_watch, args=(Path(argv[2]),), daemon=True,
                     name="bench-profiler").start()
    return server.main(argv[:2])


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
