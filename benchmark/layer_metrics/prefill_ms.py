"""Model-programs layer: mean ``req.prefill`` over the window: slot given ->
the row packed into the batch carry (group, solo or prefix prefill, and
the pack program)."""

from benchmark import span_delta


def read(ctx):
    return span_delta.mean_ms(ctx, "req.prefill")
