"""Kernels layer: of ``decode_step_ms``, the operations under the scope
``eva_summarize``: an EVA decode step's pooling of the chunk its position
lies in (two softmaxes over the chunk's ring rows, a head) and the write of
the two summary rows, which lands one step in ``chunk_size`` a row and drops
otherwise (``benchmark/scopes.py``). None where the cell's family names no
such scope or the program ran none."""

from benchmark import scopes


def read(ctx):
    if "eva_summarize" not in ctx["family"].SCOPES:
        return None
    return scopes.step_ms(ctx, ("eva_summarize",)) or None
