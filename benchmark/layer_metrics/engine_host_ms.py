"""Engine layer: the host's own work per decode segment over the window, in
ms: the engine loop's phases ``eng.barrier``, ``eng.pack``,
``eng.dispatch``, ``eng.fetch`` and ``eng.book`` (window deltas of their
``sum_s``) over the segments run (``handler.batching.segments_run``).
``eng.wait`` (blocked on the device) and ``eng.prefill`` (the prefill
programs) are left out."""

from benchmark import span_delta

PHASES = ("eng.barrier", "eng.pack", "eng.dispatch", "eng.fetch", "eng.book")


def read(ctx):
    try:
        a, b = (ctx[k]["handler"]["batching"] for k in ("m_open", "m_close"))
        segments = b["segments_run"] - a["segments_run"]
    except (KeyError, TypeError):
        return None
    deltas = [span_delta.delta(ctx, name) for name in PHASES]
    if segments <= 0 or any(d is None for d in deltas):
        return None
    return 1e3 * sum(d[1] for d in deltas) / segments
