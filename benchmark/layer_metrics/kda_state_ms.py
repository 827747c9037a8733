"""Kernels layer: of ``decode_step_ms``, the operations under the scopes
``kda_conv``, ``kda_gate`` and ``kda_state``: a Kimi-Delta-Attention decode
step's short convolution over the conv tail, its decay and ``beta``, and the
delta rule on the float32 state (read twice, written once) with the output
norm, every such layer's (``benchmark/scopes.py``). None where the cell's
family names no such scopes or the program ran none."""

from benchmark import scopes

KDA_SCOPES = ("kda_conv", "kda_gate", "kda_state")


def read(ctx):
    if not set(KDA_SCOPES) <= set(ctx["family"].SCOPES):
        return None
    return scopes.step_ms(ctx, KDA_SCOPES) or None
