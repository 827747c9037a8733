"""Kernels layer: of ``decode_step_ms``, the operations under the scope
``mla_absorb``: the two per-head products of a latent-attention decode step
with the up-projection's halves (into the query, out of the weighted sum of
latents), which stand where re-expanding the cached window would
(``benchmark/scopes.py``). None where the cell's family names no such scope
or the program ran none."""

from benchmark import scopes


def read(ctx):
    if "mla_absorb" not in ctx["family"].SCOPES:
        return None
    return scopes.step_ms(ctx, ("mla_absorb",)) or None
