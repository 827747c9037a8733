"""Boot layer: seconds of the boot thread's ``boot.init`` stage (the
handler's ``init``: weights, the AOT preload beside them, server and engine),
process start -> the window's opening."""

from benchmark import span_total


def read(ctx):
    return span_total.total(ctx, "boot.init")
