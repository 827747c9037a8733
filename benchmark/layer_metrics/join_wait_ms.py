"""Engine layer: mean ``req.join`` over the window: queued as a joiner -> a
drain barrier gives it a slot."""

from benchmark import span_delta


def read(ctx):
    return span_delta.mean_ms(ctx, "req.join")
