"""Model programs layer: the share of the window the device spent OUTSIDE
the decode segments, in percent: in a sparse-attention cell that is the
prompts' prefill (one body a block of queries inside each block of keys,
each block scoring, selecting and attending: ``jit_prefill``), beside the
pack program and whatever the device idled (``device_idle_pct``).

100 x (1 - decode steps run in the window x ``decode_step_ms`` / the
window's seconds): the steps from /metrics (``handler.batching``:
``segments_run`` x ``segment``, deltas between the scrapes at the window's
ends), a step's device time from the traced slice's whole ``jit_seg`` runs
(``benchmark/scopes.py``), the seconds the run's own ``--seconds``. Over the
WHOLE window, because the 2 s traced slice is shorter than one prefill of
this cell and holds either most of one or none. None where the cell's
family names no ``dsa_index`` scope, the run has no device trace or the
program ran no segment."""

import sys

from benchmark import scopes


def window_seconds() -> float | None:
    """``--seconds`` as ``run.py`` was given it."""
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == "--seconds" and i + 1 < len(argv):
            return float(argv[i + 1])
        if arg.startswith("--seconds="):
            return float(arg.split("=", 1)[1])
    return None


def read(ctx):
    if "dsa_index" not in ctx["family"].SCOPES:
        return None
    step_ms = scopes.step_ms(ctx, None)
    try:
        seconds = window_seconds()
        a, b = (ctx[k]["handler"]["batching"] for k in ("m_open", "m_close"))
        steps = (b["segments_run"] - a["segments_run"]) * int(b["segment"])
    except (KeyError, TypeError, ValueError):
        return None
    if not step_ms or not seconds or steps <= 0:
        return None
    return 100.0 * (1.0 - steps * step_ms / 1e3 / seconds)
