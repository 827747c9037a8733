"""Boot layer: BUSY seconds, process start -> the window's opening, of
obtaining programs from the bundle's AOT store and first running a program:
``boot.aot_load`` (deserialise and load, mostly on the preload thread beside
the weights) + ``boot.warm`` (the first run, also of the warm daemons')."""

from benchmark import span_total


def read(ctx):
    return span_total.total(ctx, "boot.aot_load", "boot.warm")
