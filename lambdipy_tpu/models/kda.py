"""Attention kind ``kda``: Kimi Delta Attention, a layer's module.

A layer of this kind keeps NO row a token. Its cache entry is two leaves:
``state`` ``[rows, 1, heads x d, d]`` float32, a head's ``[d_k, d_v]`` matrix
``S`` with its ``d_v`` axis last (so that the step's ``[rows, heads, d_k,
d_v]`` view is the leaf's own tiling: with ``d_k x d_v`` flattened into the
last axis the compiler re-tiled every state on its way in and out of every
step, compiled text for a v5e and my chip run, PR 41), and ``conv`` ``[rows,
kda_conv - 1, 3, heads x d]`` in the activations' dtype, the last ``kda_conv -
1`` PRE-convolution query, key and value vectors (q, k, v on the head axis).
With ``a`` the normed input:

1. ``q~, k~, v~ = W a``; a causal depthwise convolution of ``kda_conv``
   positions (``conv_weight`` ``[kda_conv, 3, heads x d]``: tap j multiplies
   position ``t - (kda_conv - 1) + j``; zeros before the prompt), then SiLU.
2. ``q <- d^-1/2 q / |q|``, ``k <- k / |k|`` a head (``x rsqrt(sum x^2 +
   1e-6)``); no rope.
3. ``log alpha = kda_lower_bound x sigmoid(exp(A_log[head]) x (W_f a +
   dt_bias))``, a head and CHANNEL; ``beta = sigmoid(W_b a)``, a head.
4. ``S_t = Diag(alpha_t) S_t-1 + k_t u_t^T`` with ``u_t = beta_t (v_t -
   S_t-1^T (alpha_t * k_t))``: the delta rule on a state decayed channel by
   channel; ``o_t = S_t^T q_t``.
5. ``o <- rmsnorm(o)`` over all ``heads x d`` values (``o_norm``), then the
   block's output gate and ``o_proj``.

A decode step (scopes ``kda_conv``, ``kda_gate``, ``kda_state``) takes ``S^T
(alpha * k)`` and ``S^T (alpha * q)`` together, then writes ``alpha * S + k
u^T``; ``o`` is ``S^T (alpha * q) + (k . q) u``. Where Mosaic compiles, one
kernel does both in the fast memory and steps the state leaf in place: every
state read once and written once (``ops/state_step.py``); XLA's form, served
elsewhere, reads the state twice and writes it once. A prefill runs the
CHUNKED form (scope
``kda_scan``) over chunks of ``KDA_CHUNK`` positions. With ``G_t`` the
running sum of ``log alpha`` inside a chunk and ``S_0`` the state it begins
with, the ``u_t`` solve the unit lower-triangular system ``u_t = beta_t [v_t
- S_0^T (e^G_t * k_t) - sum_{s<t} A_kk[t, s] u_s]``, ``A_kk[t, s] = sum_c k_s,c
k_t,c e^(G_t,c - G_s,c)``; ``o_t = S_0^T (e^G_t * q_t) + sum_{s<=t} A_qk[t, s]
u_s``; ``S_C = Diag(e^G_C) S_0 + sum_s (e^(G_C - G_s) * k_s) u_s^T``. Three
passes: ``A_kk`` and ``A_qk`` a chunk (every decay is the exponential of a
DIFFERENCE of running sums, never above 1: a quotient of two exponentials
would overflow one of them at ``kda_lower_bound`` x 18 positions); ``T = (I +
Diag(beta) A_kk)^-1`` by forward substitution, every chunk at once (it does
not read the state); ONE scan over the chunks whose carry is the state: ``U =
T (beta V) - T (beta e^G * K) S_0`` and the two sums above. A position past a
right-padded row's length has ``log alpha`` 0 and ``beta`` 0: it leaves the
state as it was, so the state a row hands on is the one at ITS length, and
its conv tail is gathered there. States, gates, running sums and every
product that touches a state are float32 (``highest`` on a TPU).

The interface is ``llama.ATTN_KINDS``'. Keys: ``kda_heads``, ``kda_head_dim``,
``kda_conv``, ``kda_lower_bound``, ``attn_output_gate``,
``attn_gate_headwise`` (``LlamaConfig``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from lambdipy_tpu.models.llama import (Counters, QDense, RMSNorm, output_gate,
                                       require_own_leaves, whole_prompt_blocks)
from lambdipy_tpu.ops import kernels_compile_here
from lambdipy_tpu.ops.state_step import (kernel_fits, stepped_in_place,
                                         stepped_reference)

NAME = "kda"
PLACES = ("layer_kinds",)
# positions one turn of the prefill's scan takes: a turn's [heads, C, C, d]
# decays are 17 MB a row at 32 heads of 128, and a 1536 prompt is 48 turns
KDA_CHUNK = 32
L2_EPS = 1e-6


def validate(cfg) -> None:
    require_own_leaves(cfg, NAME)
    if min(cfg.kda_heads, cfg.kda_head_dim) <= 0 or cfg.kda_conv < 2 \
            or not cfg.kda_lower_bound < 0:
        raise ValueError("kda attention needs kda_heads, kda_head_dim, "
                         "kda_conv >= 2 and kda_lower_bound < 0")


def cache_layout(cfg) -> dict:
    wide = cfg.kda_heads * cfg.kda_head_dim
    return {"state": (wide, cfg.kda_head_dim), "conv": (3, wide)}


def cache_positions(cfg, max_len: int) -> dict:
    """Whatever the length: the state is no row of any position, and the
    tail holds the ``kda_conv - 1`` positions before the next one."""
    return {"state": 1, "conv": cfg.kda_conv - 1}


def cache_dtypes(cfg) -> dict:
    return {"state": jnp.float32, "conv": cfg.dtype}


def cache_slot(cfg, leaf: str, position):
    return position * 0


def refusal(cfg, holder: str) -> str:
    return (f"{holder} keeps one cache row a token on one position axis and "
            "cannot take a kda layer, whose cache is a gated delta-rule "
            f"state a slot ({cfg.kda_heads} heads x {cfg.kda_head_dim} x "
            f"{cfg.kda_head_dim} float32) and the {cfg.kda_conv - 1} "
            "positions behind its short convolution: a span of positions is "
            "no slice of either (PERF.md section 7)")


def state_bytes_a_step(cfg) -> int:
    """Bytes one row's decode step moves in ONE kda layer: state and conv
    tail, each read once and written once."""
    wide = cfg.kda_heads * cfg.kda_head_dim
    return 2 * (4 * wide * cfg.kda_head_dim
                + (cfg.kda_conv - 1) * 3 * wide * jnp.dtype(cfg.dtype).itemsize)


def steps_in_place(cfg) -> bool:
    """Whether a decode step takes the kernel that steps the state leaf in
    place, one read and one write of every state (``ops/state_step.py``):
    where Mosaic compiles, the one thing this code can observe, as
    ``RoutedMLP`` chooses its experts' kernel."""
    return kernels_compile_here() and kernel_fits(
        cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)


def scan_chunks(s: int) -> int:
    """Turns the chunked form takes over ``s`` (padded) positions."""
    return -(-s // min(KDA_CHUNK, s))


prompt_block = whole_prompt_blocks


def counters(cfg) -> tuple:
    """The ``handler.kda`` block on ``/metrics``, only growing, from shapes.
    ``row_steps``: booked rows x segment steps x kda layers: the
    layer-steps that stepped a state for somebody. ``scan_chunks``: the
    chunks the prefills' chunked form scanned, every row and kda layer of
    every prefill program run (:func:`scan_chunks`). ``state_bytes``: the
    state and conv tail those layer-steps read and wrote, once each way
    (:func:`state_bytes_a_step` a row's step in one layer).
    ``kernel_row_steps``: the layer-steps whose state the kernel stepped in
    place (:func:`steps_in_place`, the layers' own static choice):
    ``row_steps`` where Mosaic compiles, 0 elsewhere."""
    layers = tuple(cfg.layer_kinds).count(NAME)
    a_step, kernel = state_bytes_a_step(cfg), steps_in_place(cfg)

    def segment(sown, rows: int, steps: int) -> dict:
        took = rows * steps * layers
        return {"row_steps": took, "state_bytes": took * a_step,
                "kernel_row_steps": took * kernel}

    def prefill(lengths, rows: int, s: int) -> dict:
        return {"scan_chunks": rows * layers * scan_chunks(s)}

    return (Counters(
        "kda", "a model with kda layers",
        dict.fromkeys(("row_steps", "scan_chunks", "state_bytes",
                       "kernel_row_steps"), 0),
        segment=segment, prefill=prefill),)


def _state_dot(a, b, spec: str):
    """A float32 product that touches a state: never at the MXU's default
    precision."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def chunked_scan(q, k, v, g, beta, state0=None, chunk: int = 0):
    """The chunked form over a whole sequence: ``q``, ``k`` (normed), ``v``
    ``[b, s, heads, d]``, ``g`` = ``log alpha`` ``[b, s, heads, d]`` (<= 0),
    ``beta`` ``[b, s, heads]``, all float32; a position that must leave the
    state alone (padding) has ``g`` 0 and ``beta`` 0. Returns ``(o [b, s,
    heads, d] float32, the state after the last position [b, heads, d, d]
    float32)``."""
    b, s, heads, d = q.shape
    c = min(chunk or KDA_CHUNK, s)
    n = -(-s // c)

    def cut(x):     # [b, s, heads, ...] -> [n, b, heads, c, ...]
        x = jnp.pad(x, ((0, 0), (0, n * c - s)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    q, k, v, g, beta = (cut(x) for x in (q, k, v, g, beta))
    run = jnp.cumsum(g, axis=-2)                      # G_t, inclusive
    t = jnp.arange(c)
    below, upto = t[:, None] > t[None, :], t[:, None] >= t[None, :]

    def pairs(args):
        """A chunk's two [c, c] matrices, from the differences G_t - G_s."""
        q_c, k_c, run_c = args                        # [b, heads, c, d]
        diff = run_c[..., :, None, :] - run_c[..., None, :, :]
        decay = jnp.exp(jnp.minimum(diff, 0.0)) * k_c[..., None, :, :]
        a_kk = jnp.sum(decay * k_c[..., :, None, :], axis=-1)
        a_qk = jnp.sum(decay * q_c[..., :, None, :], axis=-1)
        return jnp.where(below, a_kk, 0.0), jnp.where(upto, a_qk, 0.0)

    a_kk, a_qk = jax.lax.map(pairs, (q, k, run))      # [n, b, heads, c, c]
    low = beta[..., None] * a_kk

    def row(i, inv):
        """Row i of (I + low)^-1 from the rows above it."""
        new = -_state_dot(jax.lax.dynamic_index_in_dim(low, i, -2, False),
                          inv, "...s,...sj->...j")
        return jax.lax.dynamic_update_index_in_dim(
            inv, new + (t == i), i, -2)

    inv = jax.lax.fori_loop(
        1, c, row, jnp.broadcast_to(jnp.eye(c, dtype=jnp.float32), low.shape))
    into = jnp.exp(run)                               # e^G_t
    u_v = _state_dot(inv, beta[..., None] * v, "...ts,...sd->...td")
    u_k = _state_dot(inv, beta[..., None] * into * k, "...ts,...sd->...td")
    # e^(G_C - G_s) * k_s: what of key s is left at the chunk's end
    left = jnp.exp(run[..., -1:, :] - run) * k

    def turn(state, args):
        a_qk_c, u_v_c, u_k_c, q_in, left_c, out_c = args
        u = u_v_c - _state_dot(u_k_c, state, "bhtk,bhkv->bhtv")
        o = _state_dot(q_in, state, "bhtk,bhkv->bhtv") \
            + _state_dot(a_qk_c, u, "bhts,bhsv->bhtv")
        state = out_c[..., None] * state \
            + _state_dot(left_c, u, "bhsk,bhsv->bhkv")
        return state, o

    if state0 is None:
        state0 = jnp.zeros((b, heads, d, d), jnp.float32)
    state, out = jax.lax.scan(
        turn, state0, (a_qk, u_v, u_k, into * q, left, into[..., -1, :]))
    out = jnp.moveaxis(jnp.moveaxis(out, 2, 3), 0, 1)  # [b, n, c, heads, d]
    return out.reshape(b, n * c, heads, d)[:, :s], state


def step(state, q, k, v, g, beta):
    """One position: ``state`` ``[b, heads, d, d]``, ``q``, ``k``, ``v``,
    ``g`` ``[b, heads, d]``, ``beta`` ``[b, heads]``, float32. Returns ``(o
    [b, heads, d], the new state)``: XLA's form, two reads and a write of
    every state (``ops/state_step.py``)."""
    return stepped_reference(state, q, k, v, jnp.exp(g), beta)


def attend(block, x, positions, mask, cache, lengths):
    """The layer's attention inside ``block`` (a ``LlamaBlock`` under its
    ``nn.compact`` call): ``(the heads' outputs [b, s, heads x d] after
    norm and gate, the new cache entry)``. ``mask`` and ``positions`` are
    not read: padding is told by ``lengths`` (right-padded rows), and
    position enters through the decay and the convolution."""
    cfg = block.cfg
    heads, d, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
    wide = heads * d
    b, s, _ = x.shape
    if cache is not None and s != 1:
        raise NotImplementedError(
            "a kda state is stepped one token a row: a chunk of "
            f"{s} positions against it (a prefix continued, a draft verified "
            "and rolled back) is not written (PERF.md section 7)")
    with jax.named_scope("qkv_proj"):
        h = RMSNorm(cfg.norm_eps, name="attn_norm")(x)
        raw = jnp.stack([QDense(wide, cfg.quant, cfg.dtype, name=name)(h)
                         for name in ("q_proj", "k_proj", "v_proj")], axis=2)
    # a prefill's operations all lie under kda_scan, a step's under three
    def scope(name):
        return jax.named_scope("kda_scan" if cache is None else name)

    with scope("kda_conv"):
        w = block.param("conv_weight", nn.initializers.lecun_normal(),
                        (taps, 3, wide), jnp.float32)
        if cache is None:
            if lengths is None:
                lengths = jnp.full((b,), s, jnp.int32)
            seen = jnp.pad(raw, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
            # the tail a row hands on: positions length - 3 .. length - 1
            tail = jnp.take_along_axis(
                seen, (lengths[:, None] + jnp.arange(taps - 1)[None, :])
                [:, :, None, None], axis=1)
            live = jnp.arange(s)[None, :] < lengths[:, None]
        else:
            seen = jnp.concatenate([cache["conv"].astype(raw.dtype), raw],
                                   axis=1)
            tail = seen[:, 1:]
        conv = sum(w[j] * seen[:, j:j + s].astype(jnp.float32)
                   for j in range(taps))
        q, k, v = (a.reshape(b, s, heads, d)
                   for a in jnp.moveaxis(nn.silu(conv), 2, 0))
        q, k = _l2(q) * jnp.float32(d ** -0.5), _l2(k)
    with scope("kda_gate"):
        rate = jnp.exp(block.param("A_log", nn.initializers.zeros, (heads,),
                                   jnp.float32))
        f = QDense(wide, cfg.quant, jnp.float32, name="f_proj")(h) \
            + block.param("dt_bias", nn.initializers.zeros, (wide,),
                          jnp.float32)
        g = jnp.float32(cfg.kda_lower_bound) * jax.nn.sigmoid(
            rate[:, None] * f.reshape(b, s, heads, d))
        beta = jax.nn.sigmoid(
            QDense(heads, cfg.quant, jnp.float32, name="b_proj")(h))
    with scope("kda_state"):
        if cache is None:
            out, state = chunked_scan(
                q, k, v, jnp.where(live[..., None, None], g, 0.0),
                jnp.where(live[..., None], beta, 0.0))
        else:
            if steps_in_place(cfg):
                out, state = stepped_in_place(
                    cache["state"], q[:, 0], k[:, 0], v[:, 0],
                    jnp.exp(g[:, 0]), beta[:, 0])
            else:
                out, state = step(cache["state"].reshape(b, heads, d, d),
                                  q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0])
            out = out[:, None]
        out = RMSNorm(cfg.norm_eps, name="o_norm")(out.reshape(b, s, wide))
        out = out.astype(cfg.dtype)
    out = output_gate(cfg, out.reshape(b, s, heads, d), h)
    return out.reshape(b, s, wide), {
        "state": state.reshape(b, 1, wide, d),
        "conv": tail.astype(cfg.dtype)}
