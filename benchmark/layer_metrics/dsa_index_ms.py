"""Kernels layer: of ``decode_step_ms``, the operations under the scope
``dsa_index``: a sparse-attention decode step's lightning indexer: its
three projections, the key's norm and rope, the write of the indexer key
into its cache leaf, and the score of every cached position of the window
(``benchmark/scopes.py``). None where the cell's family names no such scope
or the program ran none."""

from benchmark import scopes


def read(ctx):
    if "dsa_index" not in ctx["family"].SCOPES:
        return None
    return scopes.step_ms(ctx, ("dsa_index",)) or None
