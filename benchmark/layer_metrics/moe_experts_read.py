"""Kernels layer: the distinct routed experts one routed layer's call picked
in one decode step, mean over the window (/metrics ``handler.moe``:
``experts_read`` / ``layer_steps``, deltas). The program counts them over
all the slots' rows of every layer-step of every fetched segment, so this is
what a form that fetches only the picked experts reads (the kernel
``ops/grouped_experts.py picked_experts``): about 41 of 128 at 8 rows x 6
picks under even routing, fewer under a skewed load; a streamed form reads
them all whatever this says. None where the program has no such counter."""


def read(ctx):
    try:
        a, b = (ctx[k]["handler"]["moe"] for k in ("m_open", "m_close"))
        steps = b["layer_steps"] - a["layer_steps"]
        return (b["experts_read"] - a["experts_read"]) / steps \
            if steps > 0 else None
    except (KeyError, TypeError):
        return None
