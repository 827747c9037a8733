"""Boot layer: seconds under ``boot.params`` (weights, file -> device as
far as the handler's call goes), a part of ``boot.init``."""

from benchmark import span_total


def read(ctx):
    return span_total.total(ctx, "boot.params")
