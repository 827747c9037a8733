"""Attention kind ``linear``: Lightning linear attention, a layer's module.

A layer of this kind keeps NO row a token. Its cache entry is one leaf,
``state`` ``[rows, 1, heads x d, d]`` float32 (a head's ``[d_k, d_v]`` matrix
with its ``d_v`` axis last, as ``models/kda.py`` keeps its state and for its
reason: the step's ``[rows, heads, d_k, d_v]`` view is the leaf's own
tiling): a head's running sum
``S_t = lambda S_t-1 + k_t^T v_t`` with a fixed decay a head, ``lambda_i =
exp(-2^(-8 i / heads))`` (``i`` = 1 .. heads: Lightning Attention's
data-independent schedule), and a token's output is ``d^-1/2 q_t S_t``. A
decode step reads the state, scales it, adds one outer product, writes it and
takes one ``q S`` (scope ``lin_state``; where Mosaic compiles, the kernel of
``ops/state_step.py`` in place: its step with the delta rule off and a head's
decay); a prefill runs the CHUNKED form
(scope ``lin_scan``): inside a chunk of ``LIN_CHUNK`` positions ``(Q K^T * D)
V`` with ``D_ts = lambda^(t-s)`` for ``s <= t``, across chunks ``Lambda Q
S_prev`` and ``S_next = lambda^C S_prev + (K * lambda^(C-1-s))^T V``: one scan
over the chunks whose carry is the state, so nothing is ``[s, s]`` and the
state a right-padded row hands on is the one at its own length (the decay
exponents stop there). States, decays and every product that touches a state
are float32 (``highest`` on a TPU); ``Q K^T`` and ``A V`` inside a chunk take
the activations' precision with float32 accumulation, as every attention
here.

The interface is ``llama.ATTN_KINDS``'. Keys: ``lin_heads``, ``lin_head_dim``,
``lin_rope``, ``lin_output_norm``, ``qk_norm``, ``attn_output_gate``
(``LlamaConfig``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from lambdipy_tpu.models.llama import (Counters, QDense, RMSNorm,
                                       require_own_leaves, rope,
                                       whole_prompt_blocks)
from lambdipy_tpu.ops import kernels_compile_here
from lambdipy_tpu.ops.state_step import kernel_fits, stepped_in_place

NAME = "linear"
PLACES = ("layer_kinds",)
# positions one turn of the prefill's scan takes: the [heads, C, C] float32
# scores of a turn are 8 MB at 32 heads, and a 20480 prompt is 80 turns
LIN_CHUNK = 256


def validate(cfg) -> None:
    require_own_leaves(cfg, NAME)
    if min(cfg.lin_heads, cfg.lin_head_dim) <= 0 or (
            cfg.lin_rope and cfg.lin_head_dim % 2):
        raise ValueError("linear attention needs lin_heads and lin_head_dim "
                         "(even under lin_rope)")


def cache_layout(cfg) -> dict:
    return {"state": (cfg.lin_heads * cfg.lin_head_dim, cfg.lin_head_dim)}


def cache_positions(cfg, max_len: int) -> dict:
    """One slot whatever the length: the state is no row of any position."""
    return {"state": 1}


def cache_dtypes(cfg) -> dict:
    return {"state": jnp.float32}


def cache_slot(cfg, leaf: str, position):
    return position * 0


def refusal(cfg, holder: str) -> str:
    return (f"{holder} keeps one cache row a token on one position axis and "
            "cannot take a linear-attention layer, whose cache is one "
            f"recurrent state a slot ({cfg.lin_heads} heads x "
            f"{cfg.lin_head_dim} x {cfg.lin_head_dim} float32) with no "
            "position axis: a span of positions is no slice of it (PERF.md "
            "section 7)")


def steps_in_place(cfg) -> bool:
    """Whether a decode step takes the kernel that steps the state leaf in
    place (``ops/state_step.py``): where Mosaic compiles, the one thing this
    code can observe, as ``RoutedMLP`` chooses its experts' kernel."""
    return kernels_compile_here() and kernel_fits(
        cfg.lin_heads, cfg.lin_head_dim, cfg.lin_head_dim)


prompt_block = whole_prompt_blocks


def counters(cfg) -> tuple:
    """This kind's share of the ``handler.sala`` block on ``/metrics``, only
    growing, from shapes. ``state_bytes``: the recurrent state the linear
    layers read and wrote, once each way: booked rows x segment steps x
    linear layers x a head's float32 ``[d, d]`` x heads x 2.
    ``kernel_row_steps``: the row-steps whose linear states the kernel
    stepped in place (:func:`steps_in_place`, the layers' own static
    choice): booked rows x segment steps where Mosaic compiles, 0
    elsewhere."""
    layers = tuple(cfg.layer_kinds).count(NAME)
    a_step = 2 * 4 * cfg.lin_heads * cfg.lin_head_dim ** 2 * layers
    kernel = steps_in_place(cfg)

    def segment(sown, rows: int, steps: int) -> dict:
        return {"state_bytes": rows * steps * a_step,
                "kernel_row_steps": rows * steps * kernel}

    return (Counters("sala", "a model with linear-attention layers",
                     {"state_bytes": 0, "kernel_row_steps": 0},
                     segment=segment),)


def slopes(heads: int):
    """``-ln lambda_i`` a head, float32 ``[heads]``: ``2^(-8 i / heads)``."""
    return jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                    / heads)


def _state_dot(a, b, spec: str):
    """A float32 product that touches a state: never at the MXU's default
    precision."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def chunked_scan(q, k, v, lengths, chunk: int = 0):
    """The chunked form over a whole (right-padded) sequence: ``q``, ``k``,
    ``v`` ``[b, s, heads, d]``, ``lengths`` ``[b]`` int32. Returns ``(o [b,
    s, heads, d] float32, the state at each row's length [b, heads, d, d]
    float32)``; ``o`` past a row's length is of no use to anybody."""
    b, s, heads, d = q.shape
    c = min(chunk or LIN_CHUNK, s)
    n = -(-s // c)
    slope = slopes(heads)                                   # [h]
    t = jnp.arange(c, dtype=jnp.float32)
    # D_ts = lambda^(t-s), s <= t; computed from the difference: a product
    # of two powers would overflow one of them
    diff = t[:, None] - t[None, :]
    decay = jnp.where(diff >= 0, jnp.exp(-slope[:, None, None]
                                         * jnp.maximum(diff, 0.0)), 0.0)
    into = jnp.exp(-slope[:, None] * (t[None, :] + 1.0))    # Lambda [h, c]

    def cut(x):
        x = jnp.pad(x, ((0, 0), (0, n * c - s), (0, 0), (0, 0)))
        return jnp.moveaxis(x.reshape(b, n, c, heads, d), 1, 0)

    def turn(state, args):
        i, q_c, k_c, v_c = args
        # positions of this chunk a row still has: C, a part, or none
        r = jnp.clip(lengths - i * c, 0, c).astype(jnp.float32)   # [b]
        scores = jnp.einsum("bthd,bshd->bhts", q_c, k_c,
                            preferred_element_type=jnp.float32) * decay
        inside = jnp.einsum("bhts,bshd->bthd", scores.astype(v_c.dtype), v_c,
                            preferred_element_type=jnp.float32)
        q32 = q_c.astype(jnp.float32) * jnp.transpose(into)[None, :, :, None]
        across = _state_dot(q32, state, "bthd,bhde->bthe")
        # w_s = lambda^(r-1-s) for s < r: the keys the row still has
        left = r[:, None, None] - 1.0 - t[None, None, :]          # [b, 1, c]
        w = jnp.where(left >= 0, jnp.exp(-slope[None, :, None]
                                         * jnp.maximum(left, 0.0)), 0.0)
        k32 = k_c.astype(jnp.float32) * jnp.transpose(w, (0, 2, 1))[..., None]
        state = jnp.exp(-slope[None, :] * r[:, None])[:, :, None, None] \
            * state + _state_dot(k32, v_c.astype(jnp.float32),
                                 "bshd,bshe->bhde")
        return state, inside + across

    state, out = jax.lax.scan(
        turn, jnp.zeros((b, heads, d, d), jnp.float32),
        (jnp.arange(n), cut(q), cut(k), cut(v)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, n * c, heads, d)[:, :s]
    return out * jnp.float32(d ** -0.5), state


def attend(block, x, positions, mask, cache, lengths):
    """The layer's attention inside ``block`` (a ``LlamaBlock`` under its
    ``nn.compact`` call): ``(the heads' outputs [b, s, heads x d] after
    norm and gate, the new cache entry)``. ``mask`` is not read: padding is
    told by ``lengths`` (right-padded rows), and a row's padding changes
    nothing a real position reads."""
    cfg = block.cfg
    heads, d = cfg.lin_heads, cfg.lin_head_dim
    b, s, _ = x.shape
    scope = "lin_scan" if cache is None else "lin_state"
    with jax.named_scope("qkv_proj"):
        h = RMSNorm(cfg.norm_eps, name="attn_norm")(x)
        q, k, v = (QDense(heads * d, cfg.quant, cfg.dtype, name=name)(h)
                   .reshape(b, s, heads, d)
                   for name in ("q_proj", "k_proj", "v_proj"))
        if cfg.qk_norm:
            q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        if cfg.lin_rope:
            q, k = rope(q, k, positions, cfg.rope_theta, cfg.rope_scaling)
    if cache is not None and s != 1:
        raise NotImplementedError(
            "a linear-attention state is stepped one token a row: a chunk of "
            f"{s} positions against it (a prefix continued, a draft verified "
            "and rolled back) is not written (PERF.md section 7)")
    with jax.named_scope(scope):
        if cache is None:
            if lengths is None:
                lengths = jnp.full((b,), s, jnp.int32)
            out, state = chunked_scan(q, k, v, lengths)
        else:
            lam = jnp.exp(-slopes(heads))
            q32, k32, v32 = (a[:, 0].astype(jnp.float32) for a in (q, k, v))
            if steps_in_place(cfg):
                out, state = stepped_in_place(cache["state"], q32, k32, v32,
                                              lam)
            else:
                state = lam[None, :, None, None] \
                    * cache["state"].reshape(b, heads, d, d) \
                    + k32[..., :, None] * v32[..., None, :]
                # a multiply-reduce in float32: 8 rows x 32 heads x 128 x 128
                out = jnp.sum(q32[..., :, None] * state, axis=-2)
            out = out[:, None] * jnp.float32(d ** -0.5)
        if cfg.lin_output_norm:
            out = RMSNorm(cfg.norm_eps, name="o_norm")(out)
        out = out.astype(cfg.dtype).reshape(b, s, heads * d)
    if cfg.attn_output_gate:
        with jax.named_scope("qkv_proj"):
            out = out * jax.nn.sigmoid(QDense(
                heads * d, cfg.quant, cfg.dtype, name="out_gate_proj")(h))
    return out, {"state": state.reshape(b, 1, heads * d, d)}
