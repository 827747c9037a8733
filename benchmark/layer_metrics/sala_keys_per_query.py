"""Model programs layer: the keys one query of a block-sparse decode step
attended, mean over the window (/metrics ``handler.sala``: ``keys_attended``
/ ``row_steps``, deltas), from the segment programs' own masks, for the rows
the engine booked: the context while it lies inside ``dense_len``, at most
``topk x block_size`` (4096) past it, so anything over that is a fault.
``visible`` is the same mean of ``keys_visible``: the context the blocks were
chosen from. None where the program has no such counter."""


def means(ctx):
    """(keys attended, keys visible) a booked row-step, or None."""
    try:
        a, b = (ctx[k]["handler"]["sala"] for k in ("m_open", "m_close"))
        steps = b["row_steps"] - a["row_steps"]
        if steps <= 0:
            return None
        return ((b["keys_attended"] - a["keys_attended"]) / steps,
                (b["keys_visible"] - a["keys_visible"]) / steps)
    except (KeyError, TypeError):
        return None


def read(ctx):
    got = means(ctx)
    return None if got is None else got[0]
