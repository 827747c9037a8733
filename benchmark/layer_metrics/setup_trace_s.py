"""Boot layer: BUSY seconds, process start -> the window's opening, that
jax spent tracing and lowering programs (``jit.trace`` + ``jit.lower``; a
trace inside another is counted once), on whichever thread."""

from benchmark import span_total


def read(ctx):
    return span_total.total(ctx, "jit.trace", "jit.lower")
