"""Engine layer: mean ``req.admit`` over the window: ticket granted -> the
entry queued as a joiner. The handler's own work and, for a prompt over the
group-prefill limit, its prefill on the request thread."""

from benchmark import span_delta


def read(ctx):
    return span_delta.mean_ms(ctx, "req.admit")
