"""Boot layer: seconds of the boot thread's ``boot.warmup`` stage (the
warm-up invoke: the first programs obtained and run)."""

from benchmark import span_total


def read(ctx):
    return span_total.total(ctx, "boot.warmup")
