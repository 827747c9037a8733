"""Kernels layer: of ``decode_step_ms``, the operations under the scopes
``sala_compress`` and ``sala_select``: a block-sparse decode step's compressed
key (the mean of the last ``kernel_size`` rows, written one step in
``kernel_stride`` a row and dropped otherwise), the scores of every visible
compressed key, their softmax a head, the sum over a KV group, the max-pool
to blocks and the exact threshold that picks the ``topk`` blocks as a MASK
(no sort, no gather; the masked read of the rows lies under ``attend``)
(``benchmark/scopes.py``). None where the cell's family names no such scope
or the program ran none."""

from benchmark import scopes


def read(ctx):
    if "sala_select" not in ctx["family"].SCOPES:
        return None
    return scopes.step_ms(ctx, ("sala_compress", "sala_select")) or None
