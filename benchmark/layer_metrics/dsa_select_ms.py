"""Kernels layer: of ``decode_step_ms``, the operations under the scope
``dsa_select``: the exact selection of a sparse-attention decode step, the
``index_topk`` positions of largest index score found as a MASK over the
window (a bit-by-bit threshold: no sort and no gather; the masked read of
the window lies under ``attend``) (``benchmark/scopes.py``). None where the
cell's family names no such scope or the program ran none."""

from benchmark import scopes


def read(ctx):
    if "dsa_select" not in ctx["family"].SCOPES:
        return None
    return scopes.step_ms(ctx, ("dsa_select",)) or None
