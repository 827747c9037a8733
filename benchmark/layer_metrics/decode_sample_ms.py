"""Kernels layer: of ``decode_step_ms``, the operations under ``sample``: rng
split, greedy argmax or the top-k/top-p filter and draw, the token's logprob."""

from benchmark import scopes


def read(ctx):
    return scopes.step_ms(ctx, ("sample",))
