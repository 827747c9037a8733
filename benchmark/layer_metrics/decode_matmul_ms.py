"""Kernels layer: of ``decode_step_ms``, the operations under the scopes
``qkv_proj``, ``o_proj``, ``mlp`` and ``lm_head``: the weight matmuls with their
norms, rope, and the waits for their weights (``benchmark/scopes.py``)."""

from benchmark import scopes


def read(ctx):
    return scopes.step_ms(ctx, ("qkv_proj", "o_proj", "mlp", "lm_head"))
