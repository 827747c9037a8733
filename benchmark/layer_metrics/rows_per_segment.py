"""Engine layer: mean live rows per decode segment over the window
(/metrics `handler.batching`: rows_in_segments / segments_run, deltas)."""


def read(ctx):
    try:
        a, b = (ctx[k]["handler"]["batching"] for k in ("m_open", "m_close"))
        segs = b["segments_run"] - a["segments_run"]
        return (b["rows_in_segments"] - a["rows_in_segments"]) / segs \
            if segs > 0 else None
    except (KeyError, TypeError):
        return None
