"""Engine layer: mean ``req.first`` over the window: packed -> the first
chunk's frame written by the handler (the joiner's own first segment)."""

from benchmark import span_delta


def read(ctx):
    return span_delta.mean_ms(ctx, "req.first")
