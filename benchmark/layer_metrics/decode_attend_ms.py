"""Kernels layer: of ``decode_step_ms``, the operations under ``attend``
(cache read, scores, softmax, values), ``kv_write`` and ``kv_window`` (the
window bucket's two copies a segment)."""

from benchmark import scopes


def read(ctx):
    return scopes.step_ms(ctx, ("attend", "kv_write", "kv_window"))
