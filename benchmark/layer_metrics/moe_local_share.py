"""Model programs layer: of the window's expert assignments, the share that
went to the routed experts THIS chip holds, in percent (/metrics
``handler.moe``: ``local_assignments`` / ``assignments``, deltas; booked
rows only). 100 where the chip holds every expert; where it holds a share,
what routing really gave it: 6.25 for 16 of 256 under even routing. None
where the program has no such counter."""


def read(ctx):
    try:
        a, b = (ctx[k]["handler"]["moe"] for k in ("m_open", "m_close"))
        total = b["assignments"] - a["assignments"]
        return 100.0 * (b["local_assignments"] - a["local_assignments"]) \
            / total if total > 0 else None
    except (KeyError, TypeError):
        return None
