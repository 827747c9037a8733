"""Boot layer: programs that entered the process before the window's
opening, by either way in: compile requests (``jit.compile``: compiled, or
read from the persistent cache) + AOT loads (``boot.aot_load``)."""

from benchmark import span_total


def read(ctx):
    compiles = span_total.total(ctx, "jit.compile", field="count")
    if compiles is None:
        return None
    return compiles + (span_total.total(ctx, "boot.aot_load", field="count")
                       or 0)
