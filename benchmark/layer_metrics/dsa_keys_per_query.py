"""Model programs layer: the cached positions one query of a
sparse-attention decode step attended, mean over the window (/metrics
``handler.dsa``: ``keys_selected`` / ``row_steps``, deltas), from the
segment programs' own masks, for the rows the engine booked:
``min(context, index_topk)`` exactly, so anything else is a fault.
``visible`` is the same mean of ``keys_visible``: the context the keys were
chosen from. None where the program has no such counter."""


def means(ctx):
    """(keys selected, keys visible) a booked row-step, or None."""
    try:
        a, b = (ctx[k]["handler"]["dsa"] for k in ("m_open", "m_close"))
        steps = b["row_steps"] - a["row_steps"]
        if steps <= 0:
            return None
        return ((b["keys_selected"] - a["keys_selected"]) / steps,
                (b["keys_visible"] - a["keys_visible"]) / steps)
    except (KeyError, TypeError):
        return None


def read(ctx):
    got = means(ctx)
    return None if got is None else got[0]
