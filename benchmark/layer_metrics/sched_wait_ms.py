"""HTTP + admission layer: mean ``req.sched`` over the window: ``sched.admit``
-> the ticket granted a run slot."""

from benchmark import span_delta


def read(ctx):
    return span_delta.mean_ms(ctx, "req.sched")
