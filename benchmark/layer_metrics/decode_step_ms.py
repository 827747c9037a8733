"""Kernels layer: device ms per decode step over the traced slice: the
durations of the ``jit_seg`` runs / (runs x ``handler.batching.segment``)."""

from benchmark import scopes


def read(ctx):
    return scopes.step_ms(ctx, None)
