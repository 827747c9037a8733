"""Build-time bundle warming: pre-populate the persistent compile cache.

Cold start is interpreter + PJRT init + first compile (tens of seconds for
real models). The builder runs this module as a subprocess against the
freshly assembled bundle (same interpreter/platform as the serve runtime),
so the XLA compilation cache the bundle ships is already hot and the serve
boot's "first" compile is a cache hit — SURVEY.md §9.6: "persistent
compilation cache shipped *inside* the bundle".

What is warmed is what a BOOT of the bundle runs before it is ready: the
warm-up invoke (on a generate bundle: the row prefill, the engine's
start-window segment, the streaming pair) and the handler's warm daemon
(``warm_buckets``, the group-prefill programs at the two ends of the bucket
range). A single-chip server snapshots each of those into ``<bundle>/aot``
as it compiles (``models/llama.py`` ``_ServedProgram``), marked as the
bundle's boot set: every later boot loads them beside the weights. What the
TRAFFIC adds (the other prompt buckets, joiner counts and decode windows)
is not run here. A first serve from a writable bundle compiles each such
program once, against this cache, and saves it beside the others, to be
loaded at its first use from then on; a read-only bundle (Lambda's
``/var/task``) saves nothing and pays the cache hit at every boot until its
build runs the traffic's envelope too — the follow-up, which needs a recipe
field that states the envelope (ROADMAP queue 2, R12).

Usage: ``python -m lambdipy_tpu.runtime.warm <bundle_dir>``
(honors LAMBDIPY_PLATFORM like the server).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path


def warm_bundle(bundle_dir: Path) -> dict:
    from lambdipy_tpu.runtime.loader import load_bundle

    t0 = time.monotonic()
    report = load_bundle(Path(bundle_dir), warmup=True)
    try:
        # the warmup invoke releases the handler's background warm (listed
        # buckets, the engine's group-prefill programs) on a daemon
        # thread. Wait it out: those compiles belong in the cache this
        # step exists to fill, and an interpreter that exits while a
        # thread is still inside an XLA compile aborts (rc 134). The
        # builder's warm timeout bounds the wait.
        warming = getattr(report.state, "warming_fn", None)
        while warming is not None and warming():
            time.sleep(0.1)
        stats = getattr(report.state, "stats", dict)()
        background = stats.get("warm_buckets") or {}
        cache_dir = report.compile_cache_dir
        out = {
            "warmed": not background.get("errors"),
            "background_warm": background,
            "wall_s": round(time.monotonic() - t0, 2),
            "stages": report.stages,
            "device": report.device,
            "compile": (report.compile_counters.report()
                        if report.compile_counters is not None else None),
            "cache_dir": str(cache_dir) if cache_dir is not None else None,
            "cache_entries": (sum(1 for f in cache_dir.rglob("*")
                                  if f.is_file())
                              if cache_dir is not None and cache_dir.is_dir()
                              else 0),
        }
    finally:
        report.close()
    return out


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if not argv:
        print("usage: warm <bundle_dir>", file=sys.stderr)
        return 2
    from lambdipy_tpu.utils.platform import apply_platform_override

    apply_platform_override()
    out = warm_bundle(Path(argv[0]))
    print(json.dumps(out), flush=True)
    if not out["warmed"]:
        print(f"background warm failed: {out['background_warm']['errors']}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
