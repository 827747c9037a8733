"""Boot layer: BUSY seconds, process start -> the window's opening, under
``jit.compile``: with a persistent-cache hit the key's hashing, the cache
read, deserialising and loading onto the device; with a miss the compile."""

from benchmark import span_total


def read(ctx):
    return span_total.total(ctx, "jit.compile")
