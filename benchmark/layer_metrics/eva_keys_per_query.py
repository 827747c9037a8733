"""Model programs layer: the keys one query of an EVA decode step attended,
mean over the window (/metrics ``handler.eva``: ``keys_attended`` /
``row_steps``, deltas): the ring rows of the query's own window plus one
summary for every chunk of the earlier ones, as the segment programs' masks
had them, for the rows the engine booked. Beside the rows' mean context it
is the compression the traffic really got (plain attention reads the
context). None where the program has no such counter."""


def read(ctx):
    try:
        a, b = (ctx[k]["handler"]["eva"] for k in ("m_open", "m_close"))
        steps = b["row_steps"] - a["row_steps"]
        return (b["keys_attended"] - a["keys_attended"]) / steps \
            if steps > 0 else None
    except (KeyError, TypeError):
        return None

