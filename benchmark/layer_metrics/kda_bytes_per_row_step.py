"""Model programs layer: the bytes of state and conv tail one booked row's
decode step moved in ONE Kimi-Delta-Attention layer, mean over the window
(/metrics ``handler.kda``: ``state_bytes`` / ``row_steps``, deltas; the
program books both from its cache's own shapes and dtypes). It reads the
shape's value (``ling3-flash``: 2 x (2097152 + 73728) = 4341760) if and only
if every booked layer-step moved each state and tail once each way: a leaf
of another dtype or width shows as another number. None where the program
has no such counter."""


def read(ctx):
    try:
        a, b = (ctx[k]["handler"]["kda"] for k in ("m_open", "m_close"))
        steps = b["row_steps"] - a["row_steps"]
        return (b["state_bytes"] - a["state_bytes"]) / steps \
            if steps > 0 else None
    except (KeyError, TypeError):
        return None
