"""Kernels layer: of ``decode_step_ms``, the operations under the scopes
``router``, ``experts`` and ``shared_expert``: a routed FFN's float32 router
and top-k, the grouped expert products with the waits for their weights, and
the always-on shared experts (``benchmark/scopes.py``). None where the
cell's family names no such scopes or the program ran none."""

from benchmark import scopes

MOE_SCOPES = ("router", "experts", "shared_expert")


def read(ctx):
    if not set(MOE_SCOPES) <= set(ctx["family"].SCOPES):
        return None
    return scopes.step_ms(ctx, MOE_SCOPES) or None
