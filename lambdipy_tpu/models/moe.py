"""Sparse Mixture-of-Experts FFNs. Two forms, for two uses.

**The dropless form** (``RoutedMLP``, further down; ``ffn_kind="routed"``,
the ``deepseek-v3`` model) is what a SERVED model routes with: no capacity,
no dropped token, a token's result independent of what it is batched with;
sigmoid or softmax scores, a routing bias that moves the choice and not the
weight, scaled weights, shared experts, int8 expert stacks; for a call of
few tokens (a decode step) a kernel that fetches only the experts its rows
picked (``ops/grouped_experts.py``).

**The capacity form** (``MoEMLP``, ``route_topk``; ``moe_experts > 0`` on a
dense-kind config: ``llama-moe-tiny``, ``train/``) is the GShard
formulation for training over an ``ep`` mesh axis: it DROPS what overflows
an expert's capacity, so it is served only at a seat-every-token capacity
(the benchmark's rehearsal family) and never as a cell.

The capacity form: new TPU-first surface (the reference has no model code at all — SURVEY.md
§3.2); this is the Mixtral-style sparse FFN for the Llama family
(models/llama.py wires it in when ``LlamaConfig.moe_experts > 0``).

TPU-first choices:
- **Dense dispatch** (GShard/Switch formulation): routing becomes one-hot
  einsums over a *static* expert-capacity dim — [tokens, experts, capacity]
  dispatch/combine tensors, no gather/scatter, no dynamic shapes, everything
  tiles onto the MXU and jits cleanly. Overflow tokens are dropped (their
  residual path carries them), the standard capacity-factor trade.
- **Expert parallelism is annotation**: expert-stacked weights [E, ...]
  shard ``P("ep", ...)`` via the registry rules, and the dispatched
  activations get a ``with_sharding_constraint`` so XLA inserts the
  all-to-all over ICI (scaling-book recipe; no hand-rolled transport).
- fp32 router and softmax (routing is precision-sensitive), bf16 expert
  matmuls; the load-balance auxiliary loss (Switch eq. 4 shape) is sown as
  an intermediate for the train step to read.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from lambdipy_tpu.ops import kernels_compile_here
from lambdipy_tpu.ops.grouped_experts import (VMEM_CEILING,
                                              kernel_vmem_bytes,
                                              picked_experts,
                                              streamed_experts)


def route_topk(probs, top_k: int, capacity: int, valid=None):
    """GShard-style top-k routing with a static per-expert capacity.

    probs: [t, e] fp32 router probabilities. Returns
    (dispatch [t, e, c] {0,1}, combine [t, e, c] fp32, aux_loss scalar).
    Slot priority: all tokens' first choices are seated before any second
    choice, so a token's top expert is the last to drop it on overflow.
    ``valid``: optional [t] bool — invalid (padding) tokens are never
    seated and are excluded from the balance loss.
    """
    t, e = probs.shape
    gates, idx = jax.lax.top_k(probs, top_k)  # [t, k]
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [t, k, e]
    if valid is not None:
        onehot = onehot * valid.astype(jnp.float32)[:, None, None]

    # accumulate per slot (static tiny top_k loop) so peak memory stays at
    # the [t, e, c] of the result tensors instead of top_k times that
    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)  # queue length after prior slots
    for slot in range(top_k):
        oh = onehot[:, slot, :]  # [t, e]
        pos = jnp.cumsum(oh, axis=0) - 1.0 + counts[None, :]
        keep = (pos < capacity) & (oh > 0)
        seated = jnp.where(
            keep[..., None],
            jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32),
            0.0)  # [t, e, c]
        dispatch = dispatch + seated
        combine = combine + seated * gates[:, slot][:, None, None]
        counts = counts + jnp.sum(oh, axis=0)

    # Switch-Transformer load-balance loss: E * <frac tokens per expert> ·
    # <mean router prob per expert>; minimized at uniform routing
    w = (jnp.ones((t,), jnp.float32) if valid is None
         else valid.astype(jnp.float32))
    n = jnp.maximum(jnp.sum(w), 1.0)
    frac_tokens = jnp.sum(onehot[:, 0, :], axis=0) / n  # first-choice assignment
    mean_probs = jnp.sum(probs * w[:, None], axis=0) / n
    aux = e * jnp.sum(frac_tokens * mean_probs)
    return dispatch, combine, aux


def _expert_stack(module, name: str, shape, quant, dtype):
    """An expert-stacked weight ``[E, in, out]`` of ``module`` as ``(kernel,
    scale)``: int8 ``<name>_int8`` with float32 ``<name>_scale`` [E, 1, out]
    per (expert, output channel) under quant="int8" (the layout
    ``llama.quantize_params`` writes), else the float ``<name>`` and None."""
    if quant != "int8":
        return module.param(
            name, nn.initializers.lecun_normal(batch_axis=(0,)), shape,
            dtype), None

    def init_int8(key, shape, _dtype):
        w = nn.initializers.lecun_normal(batch_axis=(0,))(
            key, shape, jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=1, keepdims=True) / 127.0
        return jnp.round(w / jnp.maximum(scale, 1e-8)).astype(jnp.int8)

    return (module.param(f"{name}_int8", init_int8, shape, jnp.int8),
            module.param(f"{name}_scale", nn.initializers.constant(
                1.0 / (127.0 * shape[1] ** 0.5)),
                (shape[0], 1, shape[2]), jnp.float32))


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU experts, expert dim sharded over ``ep``.

    ``quant="int8"``: expert weights stored int8 with per-(expert, output-
    channel) fp32 scales — the experts are the dominant parameters of an
    MoE model, so they must join the 1-byte/param HBM budget that int8
    serving relies on (same scheme as llama.py QDense; real weights come
    through llama.quantize_params which handles the 3-D expert stacks).
    """

    num_experts: int
    mlp: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    quant: str | None = None
    # Routing-group size (GShard): tokens route within fixed-size groups,
    # so per-group capacity is a CONSTANT and the dispatch/combine tensors
    # are [g, gs, e, c] — linear in total tokens, not the O(t^2) of a
    # single global group whose capacity grows with t.
    group_size: int = 256

    def _expert_weight(self, name: str, shape):
        w, scale = _expert_stack(self, name, shape, self.quant, self.dtype)
        if scale is None:
            return w
        return w.astype(self.dtype) * scale.astype(self.dtype)

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        e, m = self.num_experts, self.mlp
        tokens = x.reshape(b * s, hidden)
        t = tokens.shape[0]
        gs = min(t, self.group_size)
        g = -(-t // gs)
        pad = g * gs - t
        capacity = max(1, int(self.capacity_factor * self.top_k * gs / e))

        router = self.param("router", nn.initializers.lecun_normal(),
                            (hidden, e), jnp.float32)
        probs = jax.nn.softmax(tokens.astype(jnp.float32) @ router, axis=-1)
        valid = jnp.ones((t,), jnp.bool_)
        if pad:
            tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
            probs = jnp.pad(probs, ((0, pad), (0, 0)))
            valid = jnp.pad(valid, (0, pad))
        vg = valid.reshape(g, gs)
        dispatch, combine, aux = jax.vmap(
            lambda p, v: route_topk(p, self.top_k, capacity, valid=v))(
                probs.reshape(g, gs, e), vg)
        # combine per-group balance losses weighted by valid-token count —
        # an unweighted mean would let a mostly-padding tail group's few
        # tokens dominate the gradient
        n_g = jnp.sum(vg.astype(jnp.float32), axis=-1)
        self.sow("intermediates", "moe_aux_loss",
                 jnp.sum(aux * n_g) / jnp.maximum(jnp.sum(n_g), 1.0))

        w_gate = self._expert_weight("experts_gate", (e, hidden, m))
        w_up = self._expert_weight("experts_up", (e, hidden, m))
        w_down = self._expert_weight("experts_down", (e, m, hidden))

        from lambdipy_tpu.parallel.sharding import shard_hint

        # dispatch all-to-all: token groups (dp-sharded) -> expert shards
        # (ep); [g, e, c, h] with c constant per group => linear in tokens
        xe = jnp.einsum("gtec,gth->gech", dispatch.astype(self.dtype),
                        tokens.reshape(g, gs, hidden).astype(self.dtype))
        xe = shard_hint(xe, None, "ep")
        gate = jnp.einsum("gech,ehm->gecm", xe, w_gate)
        up = jnp.einsum("gech,ehm->gecm", xe, w_up)
        ye = jnp.einsum("gecm,emh->gech", nn.silu(gate) * up, w_down)
        ye = shard_hint(ye, None, "ep")
        # combine all-to-all back to token order, weighted by router gates
        out = jnp.einsum("gtec,gech->gth", combine.astype(self.dtype), ye)
        out = out.reshape(g * gs, hidden)[:t]
        return out.reshape(b, s, hidden).astype(x.dtype)


# -- the dropless form: what a served model routes with ----------------------

def route_dropless(logits, bias, top_k: int, *, scoring: str, norm: bool,
                   scaling: float, n_group: int = 1, topk_group: int = 1):
    """Top-k routing as DeepSeek-V3's ``noaux_tc`` gate publishes it, with
    no capacity: ``logits`` [t, e] float32 -> (experts [t, k] int32,
    weights [t, k] float32). Scores are ``sigmoid`` (or ``softmax``) of the
    logits; the k experts with the largest ``score + bias`` are chosen
    (ties to the lowest index); their weights are the scores WITHOUT the
    bias, divided by their sum when ``norm``, times ``scaling``. The bias
    (``e_score_correction_bias``) moves the choice, never the weight.
    ``n_group`` > 1 limits the choice to groups: the experts lie in
    ``n_group`` groups of consecutive ids, a group's score is the sum of
    its two largest ``score + bias``, and the k are chosen inside the
    ``topk_group`` groups of largest score (ties to the lowest group)."""
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    if n_group > 1:
        t, e = scores.shape
        choice = (scores + bias).reshape(t, n_group, e // n_group)
        _, kept = jax.lax.top_k(
            jnp.sum(jax.lax.top_k(choice, 2)[0], axis=-1), topk_group)
        inside = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None],
                         axis=1)
        _, experts = jax.lax.top_k(
            jnp.where(inside[:, :, None], choice, -jnp.inf).reshape(t, e),
            top_k)
    else:
        _, experts = jax.lax.top_k(scores + bias, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scaling


def _expert_block(num_assignments: int, num_experts: int) -> int:
    """Rows of one grouped-matmul block: a power of two near twice the mean
    group, between 8 (a decode step's groups are one or two rows) and 128."""
    want = max(1, -(-2 * num_assignments // num_experts))
    block = 8
    while block < min(want, 128):
        block *= 2
    return block


def grouped_experts(tokens, experts, weights, valid, expert_fn,
                    num_experts: int):
    """``sum_k weights[t, k] * expert_fn(experts[t, k], tokens[t])`` without
    a capacity: the t x k assignments are sorted by expert and cut into
    blocks of rows that belong to ONE expert each; a loop over the blocks
    that exist (its trip count is the data's: an expert nobody chose costs
    nothing, and none of its weights is read) gathers a block's rows, runs
    that expert on them and adds the weighted result to the rows' tokens.
    A token's six results are added in the order of their experts' indices,
    whatever else is in the batch. ``valid`` [t] bool or None: an invalid
    token has no assignment. ``expert_fn(e, rows [block, h]) -> [block, h]``
    float32. Returns [t, h] float32."""
    t, k = experts.shape
    a = t * k
    block = _expert_block(a, num_experts)
    flat_e = experts.reshape(a)
    if valid is not None:
        # past every real group: sorted last, counted nowhere
        flat_e = jnp.where(jnp.repeat(valid, k), flat_e, num_experts)
    order = jnp.argsort(flat_e, stable=True)
    sorted_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)[order]
    sorted_w = weights.reshape(a)[order]
    counts = jnp.zeros((num_experts + 1,), jnp.int32).at[flat_e].add(1)[
        :num_experts]
    starts = jnp.cumsum(counts) - counts          # group e in the sorted rows
    blocks = (counts + block - 1) // block        # blocks of group e
    block_ends = jnp.cumsum(blocks)

    def body(j, out):
        e = jnp.searchsorted(block_ends, j, side="right").astype(jnp.int32)
        first = starts[e] + (j - (block_ends[e] - blocks[e])) * block
        at = first + jnp.arange(block, dtype=jnp.int32)
        live = at < starts[e] + counts[e]
        at = jnp.minimum(at, a - 1)
        rows = sorted_t[at]
        y = expert_fn(e, jnp.take(tokens, rows, axis=0))
        y = jnp.where(live[:, None], y * sorted_w[at][:, None], 0.0)
        return out.at[rows].add(y)

    return jax.lax.fori_loop(
        0, block_ends[-1], body,
        jnp.zeros((t, tokens.shape[-1]), jnp.float32))


def expert_by_index(stacks, dtype):
    """``expert(i, rows)`` for :func:`grouped_experts` over the three stacks
    of SwiGLU experts (gate, up, down), each ``(kernel [E, in, out], scale
    [E, 1, out] or None)``: expert ``i``'s kernels are sliced out of the
    stacks, cast to ``dtype`` (an int8 kernel exactly), the products
    accumulate in float32 and the scale multiplies the dot's result."""
    def product(rows, stack, i):
        w, scale = stack
        out = jnp.matmul(
            rows.astype(dtype),
            jax.lax.dynamic_index_in_dim(w, i, 0, False).astype(dtype),
            preferred_element_type=jnp.float32)
        if scale is not None:
            out = out * jax.lax.dynamic_index_in_dim(scale, i, 0, False)
        return out

    def expert(i, rows):
        act = nn.silu(product(rows, stacks[0], i)) \
            * product(rows, stacks[1], i)
        return product(act, stacks[2], i)

    return expert


# Tokens of one RoutedMLP call up to which the experts' sum runs every token
# through an expert it visits (the kernel ``picked_experts`` where Mosaic
# compiles: only the experts the rows picked; elsewhere ``streamed_experts``:
# all of them) instead of grouping the assignments by expert
# (``grouped_experts``): the measured crossovers. One v5e, the routed sum of
# a layer at 128 experts of 3 x 2048 x 768 int8, top-6, bfloat16, ms a
# layer (``scripts/moe_forms.py``; my chip run, PR 28,
# ``chiprun_out/c1/forms.out``; the router and a norm, 0.01 ms, in all):
#
#     tokens          8      16     32     64     128    256
#     distinct      40.5   67.7   99.2  121.2  127.4  128.0
#     picked        0.302  0.477  0.682  0.826  0.892  1.675
#     streamed      0.851  0.854  0.863  0.884  1.313  2.107
#     grouped       0.917  1.492  2.181  2.674  2.908  3.198
#
# and above (PR 27, ``chiprun_out/callA/forms.out``, one call of four layers
# a reading, so about 0.1 ms a layer of dispatch in each), streamed /
# grouped: 4.32 / 4.06 at 512 tokens, 8.50 / 5.40 at 1024, 16.29 / 5.78 at
# 2048.
#
# The kernel is ahead at every power of two through 256, so it has no bound
# of its own: at 8 tokens it fetches 40 experts where streaming reads 128
# (6.2 us an expert, 93 % of the HBM roofline, beside 0.05 ms a call that
# does not depend on the picks; a kernel that only touches its blocks runs
# as long: it is bound by its DMA); from 64 tokens the picks cover the
# experts and what is left is XLA's three batched products against one
# fused pass. Streaming and the loop cross near 460 tokens and a call's
# token count is a power of two (slots x 1, or joiners x prompt bucket):
# 256 is the last the every-token form wins, 512 the first the loop wins,
# by 1.6 x at 1024 and 2.8 x at 2048, where streaming runs 21 times the
# arithmetic a top-6 of 128 needs; the loop pays about 25 us for each block
# it visits; streaming in chunks of 256 tokens to bound its float32
# intermediates (0.6 GB at 1024, 1.2 GB at 2048) costs 2-12 % more than
# streaming whole and wins nowhere.
STREAM_ROWS = 256


class RoutedMLP(nn.Module):
    """The dropless routed FFN of a served model (``ffn_kind="routed"``):
    float32 router (``route_dropless``), ``moe_experts`` SwiGLU experts of
    width ``moe_intermediate`` (one sum, three forms: up to ``STREAM_ROWS``
    tokens a call the kernel ``picked_experts`` where Mosaic compiles and
    ``streamed_experts`` elsewhere, ``grouped_experts`` above; ``init``,
    which returns parameters only, never asks for the backend: it is traced
    by processes that must not take the chip), and ``n_shared_experts``
    always-on experts as ONE :class:`QDense` SwiGLU of their summed width.
    No capacity, no dropped token, and a token's result does not depend on
    what it is batched with. On a TPU the expert widths must tile by 128
    lanes (``picked_experts`` raises otherwise: it is never served by
    another form under its name).

    ``quant="int8"``: the expert stacks are int8 ``[E, in, out]`` with
    float32 scales ``[E, 1, out]`` applied to the dot's float32 result, as
    :class:`QDense` does below its weight-bound row count (models/llama.py
    ``quantize_params`` writes this layout). The router and its bias stay
    float32. Sows ``moe_stats/load``: this call's assignments per row and
    expert, int32 [b, E], and ``moe_reads/experts``: the distinct experts
    its valid rows picked, one int32: ``picked_experts``'s own count of
    what it fetched where it ran, the same number from ``load`` elsewhere
    (the engine's segment programs sum both over layers and steps for
    ``handler.moe``; nobody else asks for the collections)."""

    cfg: Any  # LlamaConfig

    @nn.compact
    def __call__(self, x, valid=None):
        """``x`` [b, s, hidden]: the FFN's normed input, float32 where the
        caller has it (the router reads it as it comes; the experts'
        products cast it to ``cfg.dtype``). Returns ``cfg.dtype``."""
        from lambdipy_tpu.models.llama import QDense

        cfg = self.cfg
        b, s, hidden = x.shape
        e, m = cfg.moe_experts, cfg.moe_intermediate
        tokens = x.reshape(b * s, hidden)
        if valid is not None:
            valid = valid.reshape(b * s)

        with jax.named_scope("router"):
            router = self.param("router", nn.initializers.lecun_normal(),
                                (hidden, e), jnp.float32)
            bias = self.param("e_score_correction_bias",
                              nn.initializers.zeros, (e,), jnp.float32)
            # float32 all the way: a top-6 of 128 has near-ties, and a TPU
            # runs a float32 matmul in bfloat16 passes unless told not to
            logits = jnp.matmul(tokens.astype(jnp.float32), router,
                                precision=jax.lax.Precision.HIGHEST)
            experts, weights = route_dropless(
                logits, bias, cfg.moe_top_k, scoring=cfg.scoring_func,
                norm=cfg.norm_topk_prob, scaling=cfg.routed_scaling_factor,
                n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group)
            load = jnp.zeros((b * s, e), jnp.int32).at[
                jnp.arange(b * s)[:, None], experts].add(1)
            if valid is not None:
                load = load * valid[:, None]
            if not self.is_initializing():  # init returns parameters only
                self.sow("moe_stats", "load",
                         load.reshape(b, s, e).sum(axis=1))
            first, held = cfg.moe_held
            if held != e:
                # a chip's share: the stacks hold experts first .. first +
                # held - 1 under LOCAL ids; an assignment to an absent
                # expert gets the id past the last, which every form of
                # the sum skips (no group, no fetch, a dropped scatter).
                # Its weight stays in the normalisation above
                experts = experts - first
                experts = jnp.where((experts >= 0) & (experts < held),
                                    experts, held)
                load = load[:, first:first + held]

        with jax.named_scope("experts"):
            stacks = [_expert_stack(self, name, shape, cfg.quant, cfg.dtype)
                      for name, shape in (("experts_gate", (held, hidden, m)),
                                          ("experts_up", (held, hidden, m)),
                                          ("experts_down", (held, m, hidden)))]

            # the distinct experts (of those held here) the valid rows
            # picked; the kernel reports its own count of what it fetched
            read = jnp.sum(jnp.any(load > 0, axis=0).astype(jnp.int32))
            if b * s > STREAM_ROWS:
                out = grouped_experts(tokens, experts, weights, valid,
                                      expert_by_index(stacks, cfg.dtype),
                                      held)
            elif not self.is_initializing() and kernels_compile_here():
                if kernel_vmem_bytes(b * s, hidden, m, stacks[0][0].dtype,
                                     cfg.dtype) > VMEM_CEILING:
                    # an expert too wide for the kernel to hold whole: the
                    # loop over the blocks that exist reads the picked
                    # experts alone too, a block at a time
                    out = grouped_experts(
                        tokens, experts, weights, valid,
                        expert_by_index(stacks, cfg.dtype), held)
                else:
                    out, read = picked_experts(tokens, experts, weights,
                                               valid, stacks, cfg.dtype)
            else:
                out = streamed_experts(tokens, experts, weights, valid,
                                       stacks, cfg.dtype)
            out = out.astype(cfg.dtype).reshape(b, s, hidden)
            if not self.is_initializing():
                self.sow("moe_reads", "experts", read)

        if cfg.n_shared_experts:
            with jax.named_scope("shared_expert"):
                width = cfg.n_shared_experts * m
                dense = [QDense(n, cfg.quant, cfg.dtype,
                                name=f"shared_{name}_proj")
                         for name, n in (("gate", width), ("up", width),
                                         ("down", hidden))]
                out = out + dense[2](nn.silu(dense[0](x)) * dense[1](x))
        return out


def counters(cfg) -> tuple:
    """The ``handler.moe`` block on ``/metrics`` of a routed-FFN model's
    decode segments, only growing (``llama.Counters``; :class:`RoutedMLP`
    sows ``moe_stats`` and ``moe_reads``, and a segment program returns
    their sums over the routed layers and its steps). ``assignments``:
    (token, expert) pairs the booked rows' steps sent to routed experts,
    summed over the layers: with dropless routing exactly booked rows x
    segment steps x routed layers x experts per token, so a dropped or
    doubled assignment shows as a difference. ``load``: the same count per
    expert. The engine's collector adds each booked row's vector from the
    segment's own fetch; rows the device stepped for nobody (empty slots,
    over-decode) are left out. ``experts_read`` / ``layer_steps``: the mean
    number of DISTINCT experts one routed layer's call picked in one decode
    step, as sum and count: what a form that fetches only the picked experts
    reads (``ops/grouped_experts.py picked_experts``; one that streams reads
    them all, whatever this says). Counted by the program over ALL the
    slots' rows, because a cache step routes every slot, live or empty;
    ``layer_steps`` is fetched segments x segment steps x routed layers.
    ``local_assignments``: of ``assignments``, those to the experts this
    chip holds (``LlamaConfig.moe_held``): all of them where it holds every
    expert, the share routing really gave it where it holds a share."""
    from lambdipy_tpu.models.llama import Counters

    if cfg.ffn_kind != "routed":
        return ()
    first, held = cfg.moe_held
    layers = cfg.layers - cfg.first_dense_layers

    def segment(sown, rows: int, steps: int) -> dict:
        add = {"experts_read": sown["moe_reads"],
               "layer_steps": steps * layers}
        if rows:
            load = sown["moe_stats"].sum(axis=0)
            add.update(assignments=load.sum(), load=load,
                       local_assignments=load[first:first + held].sum())
        return add

    return (Counters(
        "moe", "a routed-FFN model",
        {"assignments": 0, "local_assignments": 0, "load": [],
         "experts_read": 0, "layer_steps": 0},
        {"moe_stats": lambda b: jnp.zeros((b, cfg.moe_experts), jnp.int32),
         "moe_reads": lambda b: jnp.int32(0)}, segment),)


def moe_aux_loss(intermediates) -> jax.Array:
    """Sum every sown ``moe_aux_loss`` in an intermediates collection."""
    leaves = [
        jnp.sum(jnp.asarray(v))
        for path, v in jax.tree_util.tree_leaves_with_path(intermediates)
        if any(getattr(k, "key", None) == "moe_aux_loss" for k in path)
    ]
    return sum(leaves, jnp.float32(0.0))
