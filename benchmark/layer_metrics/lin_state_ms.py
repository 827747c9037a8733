"""Kernels layer: of ``decode_step_ms``, the operations under the scope
``lin_state``: a linear-attention decode step's recurrence, every such
layer's: the float32 state read, scaled by its head's decay, one outer
product added, written back, one ``q S`` and the output norm
(``benchmark/scopes.py``). None where the cell's family names no such scope
or the program ran none."""

from benchmark import scopes


def read(ctx):
    if "lin_state" not in ctx["family"].SCOPES:
        return None
    return scopes.step_ms(ctx, ("lin_state",)) or None
