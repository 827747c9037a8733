"""Model programs layer: the busiest routed expert's share of the window's
assignments, in percent (/metrics ``handler.moe.load``, deltas over the
window: booked rows only, summed over the routed layers). Even routing
reads 100 / experts (0.78 at 128); the expert stream of a decode step grows
with the number of DISTINCT experts its rows pick, so a skewed load reads
fewer bytes a step and an even one more. None where the program counts no
expert load."""


def read(ctx):
    try:
        a, b = (ctx[k]["handler"]["moe"]["load"] for k in ("m_open", "m_close"))
    except (KeyError, TypeError):
        return None
    delta = [y - x for x, y in zip(a or [0] * len(b), b)]
    total = sum(delta)
    return 100.0 * max(delta) / total if total > 0 else None
