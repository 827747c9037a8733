"""HTTP + admission layer: mean of the program's own ``req.ttft`` spans over the
window: request body read -> the first chunk's frame written. The client's
time to the first token less this is HTTP, the socket and how late the
generator ran."""

from benchmark import span_delta


def read(ctx):
    return span_delta.mean_ms(ctx, "req.ttft")
