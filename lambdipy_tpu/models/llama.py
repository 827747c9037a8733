"""Llama-3-style decoder-only LM: GQA + RoPE + RMSNorm + SwiGLU, with int8
weight-only quantization and a functional KV cache for ``lax.scan`` decode.

BASELINE.json config 5: Llama-3-8B int8 generate on v5e-4, weights tensor-
parallel over the ``tp`` mesh axis (sharding rules in
:func:`llama_tp_rules`; the module itself is sharding-agnostic).

TPU-first choices:
- decode loop is ``lax.scan`` over a static-shape KV cache
  (``dynamic_update_slice`` at the position index) — no Python control flow
  under jit, one compiled step reused for every token;
- int8 weight-only quant: weights stored int8 + per-output-channel fp32
  scale, dequantized into bf16 at the matmul (HBM-bandwidth win: 8B params
  fit v5e-4's 64 GB HBM with room for cache);
- fp32 RMSNorm/softmax accumulation, bf16 MXU matmuls.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math
import threading
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from lambdipy_tpu.utils.logs import get_logger

log = get_logger("lambdipy.llama")


class LayerSpec(NamedTuple):
    attn: str  # one of ATTN_KINDS
    ffn: str   # "dense" | "capacity" | "routed"


# The attention kinds: ``{kind: its module}``, in the order their counters
# leave a segment program; a new kind is its file and its line here. What
# the block, the cache constructors, the segment programs and the engine ask
# of a kind's module, and nothing else of a kind:
#
#   NAME, PLACES                      where it may stand: "attn_kind" (the
#                                     model's one), "layer_kinds", or both
#   validate(cfg)                     raise for a wrong description
#   cache_layout(cfg)                 {leaf: (heads, width)}
#   cache_positions(cfg, max_len)     {leaf: slots}
#   cache_dtypes(cfg)                 {leaf: dtype}
#   cache_slot(cfg, leaf, position)   where a position lies in a leaf
#   refusal(cfg, holder)              why a holder of per-head K/V rows, one
#                                     a token and every one attended, cannot
#                                     take it (None: it can)
#   attend(block, x, positions, mask, cache, lengths)
#                                     -> (heads' outputs, new cache entry)
#
# and, each OPTIONAL (:func:`_ask`):
#
#   FORMS                   which of the block's static forms, ``sp_prefill``
#                           and ``band``, ``attend`` takes as keywords; any
#                           other is refused
#   absent(cfg)             raise for a model WITHOUT the kind that sets
#                           what only the kind reads
#   row_a_token(cfg)        whether a holder that only cuts, joins or extends
#                           ONE position axis can take it all the same
#   scales_softmax(cfg)     whether it applies YaRN's ``attn_scale_mult``
#   prompt_block(cfg)       (block, past): a prompt longer than ``past``
#                           prefills at whole multiples of ``block``
#   counters(cfg)           its :class:`Counters`
#   keeps_tail(cfg), tail_fits(cfg, steps, spans), tail_init(cfg, frozen,
#   base, steps), tail_step(cfg, frozen, tails, base, j), tail_merge(cfg,
#   full, tails, base, steps)
#                           a decode segment whose cache is read-only inside
#                           its scan (:func:`_scan_decode`, ``tail_window``):
#                           what the scan carries in the cache's place, what
#                           a step's layers read, the one write after it
ATTN_KINDS = {"kv": "lambdipy_tpu.models.kv",
              "eva": "lambdipy_tpu.models.eva",
              "latent": "lambdipy_tpu.models.latent",
              "sparse_kv": "lambdipy_tpu.models.sparse_kv",
              "linear": "lambdipy_tpu.models.linear_attn",
              "kda": "lambdipy_tpu.models.kda"}


class Counters(NamedTuple):
    """What one kind (an attention kind, or the routed FFN) counts for one
    block of ``/metrics`` -> ``handler``: its own declaration, which
    :func:`_scan_decode`, the engine's collector and its recorder
    (``runtime/metrics.py KindCounters``) follow without knowing the kind.
    Kinds that name the same block share it, their fields in the kinds'
    order."""

    block: str    # the /metrics block: handler.<block>
    what: str     # "a ... model", for a refusal's words
    fields: dict  # {report key: 0, or [] for a vector}, in the report's order
    # {collection: zero(b)}: what the kind sows into a segment program's
    # steps, and where each sum starts for ``b`` rows; the program returns
    # the sums behind its tokens, in this order
    sown: dict = {}
    # (sown, rows, steps) -> {field: increment} of one fetched plain segment.
    # ``sown``: the kind's collections as host arrays, those with a row axis
    # cut to the ``rows`` rows the collector books
    segment: Any = None
    # (lengths, rows, s) -> {field: increment} of one dispatched prefill of
    # ``rows`` rows padded to ``s`` positions (``lengths``: the real rows')
    prefill: Any = None


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden: int = 4096
    layers: int = 32
    heads: int = 32
    kv_heads: int = 8
    mlp: int = 14336
    max_len: int = 8192
    rope_theta: float = 500000.0
    # RoPE frequency scaling for long-context checkpoints, as a hashable
    # tuple (the config is a flax module attribute): None,
    # ("linear", factor), or ("llama3", factor, low_freq_factor,
    # high_freq_factor, original_max_position_embeddings) — the Llama-3.1+
    # scheme. Populated from HF configs by models/convert.py.
    rope_scaling: tuple | None = None
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    quant: str | None = None  # None | "int8"
    # KV-cache quantization: None (cache in ``dtype``) or "int8"
    # (per-token-per-head symmetric int8 + f32 scale). The decode cache is
    # the dominant HBM object of long-context serving (8B at 8k context:
    # 1 GB/row in bf16) and decode re-reads all of it every step — int8
    # halves that traffic and capacity for ~0.4% attention error; XLA
    # fuses the dequant into the attention einsum.
    kv_quant: str | None = None
    # Attention backend: "dense" (XLA-fused, default), "flash" (Pallas
    # kernel when shapes tile), "blocked" (length-aware blocked DECODE
    # attention, ops/decode_attention.py: single-token decode steps read
    # KV bytes proportional to each row's actual context instead of the
    # full static window — per-row active_len early exit on the TPU
    # kernel, dense-bitwise pure-jax reference elsewhere; prefill and
    # multi-token chunks stay dense, sharded/sp decode stands down to
    # the existing path), or "ring" — the LONG-CONTEXT pair:
    # sequence-parallel ring attention for prefill AND sequence-sharded
    # flash-decoding for decode steps over the ambient mesh's sp axis
    # (parallel/ring.py + parallel/spdecode.py; the KV cache never
    # gathers, per-step collectives are O(b*h*d)). Every cell of the
    # benchmark runs "dense"; the others have never run on the attached
    # v5e (docs/kernels.md); flash is the O(S)-memory fallback for
    # contexts whose dense score tensor would not fit.
    attn_backend: str = "dense"
    # Sparse MoE FFN (Mixtral-style): >0 replaces the dense SwiGLU with
    # moe_experts top-k routed experts (models/moe.py), expert dim sharded
    # over the mesh's ep axis.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 256  # routing-group size (models/moe.py)
    # -- the per-layer description: what the ONE block, the cache
    # constructors and the registry read, so that an architecture is a
    # setting of these fields and a kind's module (``ATTN_KINDS``), not a
    # fork of this file. A kind's settings are flat fields here because
    # recipes' TOML, bundle manifests and ``registry.py`` name them (ROADMAP
    # D21); only the kind's module reads them.
    # The model's one attention kind: "kv" (models/kv.py), "latent"
    # (models/latent.py: ``qk_nope``, ``qk_rope``, ``v_head``,
    # ``kv_lora_rank``) or "eva" (models/eva.py).
    attn_kind: str = "kv"
    qk_nope: int = 0
    qk_rope: int = 0
    v_head: int = 0
    kv_lora_rank: int = 0
    # the rotary dims arrive as (even, odd) pairs and are de-interleaved
    # to halves before the usual rotate-half
    rope_interleave: bool = False
    # FFN kind of the layers from ``first_dense_layers`` on: "dense"
    # (SwiGLU of width ``mlp``; with ``moe_experts`` > 0 the capacity form
    # above, which drops) or "routed" (models/moe.py RoutedMLP: dropless
    # top-``moe_top_k`` of ``moe_experts`` experts of width
    # ``moe_intermediate`` beside ``n_shared_experts`` always-on ones).
    # The leading layers are dense SwiGLUs of width ``mlp``.
    ffn_kind: str = "dense"
    first_dense_layers: int = 0
    moe_intermediate: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "softmax"  # "softmax" | "sigmoid"
    # attn_kind "eva": a ring of ``window_size`` rows beside one pooled
    # summary every ``chunk_size`` positions (models/eva.py)
    window_size: int = 0
    chunk_size: int = 0
    # prediction heads: the head has ``pred_heads x vocab_size`` columns
    # (EvaByte's multi-byte heads); the programs compute them all and
    # serve the first ``vocab_size``, the next token's
    pred_heads: int = 1
    # RMSNorm multiplies by (1 + gain) and its gain starts at zero
    norm_unit_offset: bool = False
    # -- the latent kind as DeepSeek-V3.2 publishes it (models/latent.py):
    # ``q_lora_rank`` > 0 compresses the query; ``index_topk`` > 0 is
    # DeepSeek Sparse Attention, a lightning indexer of ``index_heads`` heads
    # of ``index_head_dim`` that picks the positions a query attends
    q_lora_rank: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Group-limited routing (DeepSeek-V3's ``n_group`` / ``topk_group``):
    # the experts lie in ``moe_n_group`` groups of consecutive ids, a
    # group's score is the sum of its two largest choice scores, and the
    # top-k is taken inside the ``moe_topk_group`` best groups.
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # A chip's share of a layer's routed experts: ``moe_experts_held`` > 0
    # holds the stacks of experts ``moe_first_expert`` .. + held - 1 only.
    # Router, bias, groups and top-k stay ``moe_experts`` wide and the
    # weights are normalised over all picks; what the absent experts would
    # add is computed by nobody here (the exchange between chips is not
    # written: PERF.md section 7).
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    # -- the attention kind a LAYER, in place of the model's one
    # ``attn_kind`` (which stays "kv"): of the kinds whose ``PLACES`` say so
    # (``ATTN_KINDS``): "kv", "latent" without ``index_topk``, "sparse_kv"
    # (``sparse_*``), "linear" (``lin_*``), "kda" (``kda_*``). A layer's
    # cache entry, its prefill, its step and what refuses it are its
    # kind's; ``cache_layout`` / ``cache_positions`` / ``cache_slot`` take
    # the layer.
    layer_kinds: tuple = ()
    # RMSNorm with a learned gain over each head's query and key
    qk_norm: bool = False
    # the heads' outputs x sigmoid(out_gate_proj h) before o_proj: one gate
    # a channel, or (``attn_gate_headwise``: the kda and latent layers) one
    # a head
    attn_output_gate: bool = False
    attn_gate_headwise: bool = False
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_dense_len: int = 8192
    lin_heads: int = 0
    lin_head_dim: int = 0
    lin_rope: bool = True
    lin_output_norm: bool = True
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    # MiniCPM's three scalars: the embedding x ``embed_scale``, each
    # sublayer's output x ``residual_scale`` before it joins the residual,
    # the final norm's output / ``logit_divisor`` before the head
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0

    def __post_init__(self):
        modules = {kind: attn_kind_module(kind) for kind in ATTN_KINDS}
        one = [kind for kind, m in modules.items() if "attn_kind" in m.PLACES]
        if self.attn_kind not in one:
            raise ValueError(f"unknown attn_kind {self.attn_kind!r}; "
                             f"supported: {', '.join(one)}")
        if self.layer_kinds:
            kinds = tuple(self.layer_kinds)
            each = [kind for kind, m in modules.items()
                    if "layer_kinds" in m.PLACES]
            if len(kinds) != self.layers or self.attn_kind != "kv" \
                    or not set(kinds) <= set(each):
                raise ValueError(
                    f"layer_kinds {kinds!r}: one kind for each of the "
                    f"{self.layers} layers, of {', '.join(each)} "
                    "(attn_kind stays kv: the others are one a model)")
        for kind, module in sorted(modules.items()):
            if kind in self.attn_kinds:
                module.validate(self)
            else:
                _ask(module, "absent", None, self)
        if self.pred_heads < 1:
            raise ValueError("pred_heads must be >= 1")
        if self.ffn_kind not in ("dense", "routed"):
            raise ValueError(f"unknown ffn_kind {self.ffn_kind!r}; "
                             "supported: dense, routed")
        if self.rope_scaling and self.rope_scaling[0] == "yarn" and not all(
                _ask(attn_kind_module(kind), "scales_softmax", False, self)
                for kind in self.attn_kinds):
            # only the sparse paths multiply the softmax scale by
            # attn_scale_mult: elsewhere prefill and decode would disagree
            raise NotImplementedError(
                "yarn rope scaling is served only under sparse attention "
                "(the latent kind with index_topk): no other attention "
                "path applies its mscale to the softmax scale")
        if self.ffn_kind == "routed":
            if self.moe_experts < self.moe_top_k or self.moe_top_k < 1 \
                    or self.moe_intermediate <= 0:
                raise ValueError(
                    "a routed FFN needs moe_experts >= moe_top_k >= 1 and "
                    "moe_intermediate")
            if self.scoring_func not in ("softmax", "sigmoid"):
                raise ValueError(
                    f"unknown scoring_func {self.scoring_func!r}; "
                    "supported: softmax, sigmoid")
            if self.moe_experts % self.moe_n_group \
                    or not 1 <= self.moe_topk_group <= self.moe_n_group:
                raise ValueError(
                    "group-limited routing needs moe_n_group to divide "
                    "moe_experts and 1 <= moe_topk_group <= moe_n_group")
            if self.moe_experts_held < 0 or self.moe_first_expert < 0 \
                    or self.moe_first_expert + self.moe_experts_held \
                    > self.moe_experts:
                raise ValueError(
                    "moe_first_expert .. + moe_experts_held must lie inside "
                    "the moe_experts routed experts")

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def attn_kinds(self) -> tuple:
        """The attention kinds the layers have, each once."""
        return tuple(dict.fromkeys(self.layer_kinds or (self.attn_kind,)))

    def layer_spec(self, layer: int) -> LayerSpec:
        """What layer ``layer`` is made of."""
        if self.ffn_kind == "routed":
            ffn = "dense" if layer < self.first_dense_layers else "routed"
        else:
            ffn = "capacity" if self.moe_experts else "dense"
        return LayerSpec(self.layer_kinds[layer] if self.layer_kinds
                         else self.attn_kind, ffn)

    def first_layer_of(self, kind: str) -> int:
        """The first layer of attention kind ``kind`` (the one that sows the
        kind's counters: every layer's are the same), -1 where none is."""
        return next((i for i in range(self.layers)
                     if self.layer_spec(i).attn == kind), -1)

    def kind_of(self, layer: int = 0):
        """The module of layer ``layer``'s attention kind."""
        return attn_kind_module(self.layer_spec(layer).attn)

    def cache_layout(self, layer: int = 0) -> dict:
        """The cache row of one token of layer ``layer``: ``{leaf: (heads,
        width)}`` in storage order. Every leaf is 4-D ``[rows, positions,
        heads, width]`` (a leaf without heads has a head axis of 1, a
        recurrent state a position axis of 1), so whatever iterates a cache
        entry's leaves -- slicing, copying, window buckets, the engine's
        pack -- never asks which kind it holds."""
        return self.kind_of(layer).cache_layout(self)

    def cache_positions(self, max_len: int, layer: int = 0) -> dict:
        """``{leaf: slots}``: the length of each leaf's position axis in a
        cache that serves absolute positions ``0 .. max_len - 1``:
        ``max_len`` where a leaf holds one row a token; a ring, a leaf of
        pooled or compressed rows or a state says its own."""
        return self.kind_of(layer).cache_positions(self, max_len)

    def cache_dtypes(self, layer: int = 0) -> dict:
        """``{leaf: dtype}`` of layer ``layer``'s entry: ``dtype`` but for a
        kind that says otherwise (a recurrent state is float32)."""
        return self.kind_of(layer).cache_dtypes(self)

    def cache_slot(self, leaf: str, position, layer: int = 0):
        """The slot of ``leaf``'s position axis (in layer ``layer``'s entry)
        that holds absolute position ``position`` (an int or an int
        array): for a compressed key or a summary, the one whose span the
        position BEGINS in; a state has one slot."""
        return self.kind_of(layer).cache_slot(self, leaf, position)

    def prompt_bucket(self, s: int, lo: int) -> int:
        """The padded length a prompt of ``s`` tokens prefills at: the next
        power of two from ``lo``, but for a long prompt of a model whose
        kinds prefill a block a turn (their ``prompt_block``: whole blocks,
        so that the prefill's cost follows the prompt and not the power of
        two above it)."""
        asked = [block for block in (
            _ask(attn_kind_module(kind), "prompt_block", None, self)
            for kind in self.attn_kinds) if block]
        if asked and s > max(past for _, past in asked):
            block = math.lcm(*(block for block, _ in asked))
            return -(-s // block) * block
        return _next_bucket(s, lo)

    def counters(self) -> tuple:
        """What this model's kinds count for ``/metrics`` (:class:`Counters`),
        in the order their sums leave a segment program: the routed FFN's,
        then the attention kinds' in ``ATTN_KINDS``' order."""
        from lambdipy_tpu.models import moe

        found = list(moe.counters(self))
        for kind in ATTN_KINDS:
            if kind in self.attn_kinds:
                found += _ask(attn_kind_module(kind), "counters", (), self)
        return tuple(found)

    @property
    def moe_held(self) -> tuple:
        """``(first, count)``: the routed experts whose stacks this chip
        holds."""
        if self.moe_experts_held:
            return self.moe_first_expert, self.moe_experts_held
        return 0, self.moe_experts

    @property
    def attn_scale_mult(self) -> float:
        """What YaRN multiplies the softmax scale by: ``mscale`` squared,
        ``mscale = 0.1 x mscale x ln(factor) + 1`` (DeepSeek's inference
        code), 1 without it."""
        if self.rope_scaling and self.rope_scaling[0] == "yarn":
            factor, mscale = float(self.rope_scaling[1]), \
                float(self.rope_scaling[5])
            return (0.1 * mscale * math.log(factor) + 1.0) ** 2
        return 1.0


# prompts past two of these prefill at whole multiples of it (the kinds that
# stand in ``layer_kinds`` alone: their ``prompt_block``): the keys of one
# block of a block-sparse prefill (``sparse_kv.SPARSE_KEY_BLOCK``); a state's
# prefill is a scan over chunks. A 20k prompt must not pad to 32k
SALA_PROMPT_BLOCK = 4096


def whole_prompt_blocks(cfg) -> tuple:
    return SALA_PROMPT_BLOCK, 2 * SALA_PROMPT_BLOCK


def attn_kind_module(kind: str):
    """The module of attention kind ``kind``: its cache layout, its prefill
    and step, its refusals, its counters (the table above ``ATTN_KINDS``).
    Imported on demand: the modules import this one's layers."""
    module = ATTN_KINDS[kind]
    return importlib.import_module(module) if isinstance(module, str) \
        else module


def _ask(module, word: str, default, *args):
    """What a kind's module says under an OPTIONAL word of the interface,
    ``default`` where it has none."""
    said = getattr(module, word, None)
    return default if said is None else said(*args)


def require_kv_cache(cfg: LlamaConfig, holder: str) -> None:
    """Raise for a cache holder that knows only per-head K/V leaves (no
    silent fallback: the holder would store, ship or page rows of the
    wrong layout), in the words of the first kind that is none."""
    for kind in getattr(cfg, "attn_kinds", ()):
        words = attn_kind_module(kind).refusal(cfg, holder)
        if words:
            raise NotImplementedError(words)


def require_row_a_token(cfg: LlamaConfig, holder: str) -> None:
    """Raise for a holder that cuts, joins or extends a cache along ONE
    position axis, every row of which a query may attend (a prefix carried
    over, a chunk continued, a draft verified and rolled back), in the words
    of the first kind that keeps no such axis (its ``row_a_token``): a ring
    that forgets beside summaries that pool; a selection by content that
    only the whole-prompt prefill and the one-token step compute; a
    recurrent state, which has no position axis at all."""
    for kind in getattr(cfg, "attn_kinds", ()):
        module = attn_kind_module(kind)
        if not _ask(module, "row_a_token", False, cfg):
            raise NotImplementedError(module.refusal(cfg, holder))


def require_own_leaves(cfg: LlamaConfig, kind: str) -> None:
    """A kind's ``validate``: raise for the options that belong to per-head
    K/V rows, for a kind that keeps leaves of its own."""
    if cfg.kv_quant is not None or cfg.attn_backend != "dense":
        raise NotImplementedError(
            f"kv_quant={cfg.kv_quant!r} / attn_backend="
            f"{cfg.attn_backend!r}: the int8 cache layout and "
            "the flash, blocked and ring backends hold one "
            f"per-head K/V row a token; a {kind} layer runs "
            "the dense backend over its own leaves")


def block_method(fn):
    """``fn(block, ...)``, a part of a kind's ``attend``, under the scope a
    ``LlamaBlock`` method of its name had while the kind lived in the block
    (flax names a method's operations ``<module>.<method>``): the op_names
    of the device operations, which a trace is read by, stay what programs
    compiled before PR 44 carry (``utils/compile_cache.NAMES_GEN``)."""
    @functools.wraps(fn)
    def scoped(block, *args, **kwargs):
        with jax.named_scope(
                f"{block.name or type(block).__name__}.{fn.__name__}"):
            return fn(block, *args, **kwargs)

    return scoped


def __getattr__(name: str):
    """``LLAMA3_8B`` and ``LLAMA_TINY``, built when first asked for: a
    config asks its kinds' modules to validate it, and they import this
    module's layers."""
    if name not in _PRESETS:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    if not isinstance(_PRESETS[name], LlamaConfig):
        _PRESETS[name] = LlamaConfig(**_PRESETS[name])
    return _PRESETS[name]


_PRESETS = {"LLAMA3_8B": {},
            "LLAMA_TINY": dict(vocab_size=512, hidden=64, layers=2, heads=4,
                               kv_heads=2, mlp=128, max_len=128,
                               dtype=jnp.float32)}



class RMSNorm(nn.Module):
    eps: float = 1e-5
    # the gain is stored as its offset from one (``norm_add_unit_offset``)
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        init = nn.initializers.zeros if self.unit_offset \
            else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        if self.unit_offset:
            scale = 1.0 + scale
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(dtype)


# Rows (tokens) of one QDense call up to which the matmul is bound by
# reading its int8 kernel, not by the MXU: 2 flops a row for every weight
# byte against the v5e's 197 TFLOP/s over 819 GB/s = 240 flops a byte.
# Decode, speculative verify and the smallest prefill buckets lie below
# it. Above it a one-off dequantization is amortized over the rows, and
# the float32 result the weight-bound form hands a row-parallel kernel's
# all-reduce doubles its bytes: tp=4 group prefill of 8 x 256 tokens
# 38.4 -> 46.9 ms with that form at every row count (PERF.md, PR 25).
WEIGHT_BOUND_ROWS = 128


def _init_int8(key, shape, _dtype):
    """A lecun-normal kernel [in, out] rounded to int8 under its own
    per-output-channel scale (random init only: real weights come through
    :func:`quantize_params`, which computes true scales)."""
    w = nn.initializers.lecun_normal()(key, shape, jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(scale, 1e-8)).astype(jnp.int8)


class QDense(nn.Module):
    """Linear layer with optional int8 weight-only quantization.

    quant=None: a plain bf16 kernel. quant="int8": kernel stored as int8
    with per-output-channel fp32 scales; dequantized at the matmul so HBM
    traffic (the serving bottleneck) is 1 byte/param while the MXU still
    sees bf16.
    """

    features: int
    quant: str | None = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        in_features = x.shape[-1]
        if self.quant == "int8":
            w_i8 = self.param("kernel_int8", _init_int8,
                              (in_features, self.features), jnp.int8)
            # random-init scale approximates lecun magnitude; real weights
            # come through quantize_params() which computes true scales
            scale = self.param(
                "scale", nn.initializers.constant(1.0 / (127.0 * in_features ** 0.5)),
                (1, self.features), jnp.float32)
            if math.prod(x.shape[:-1]) <= WEIGHT_BOUND_ROWS:
                # the dot consumes the converted int8 kernel and nothing
                # else (int8 -> dtype is exact), accumulates in float32,
                # and the per-output-channel scale, constant along the
                # contraction, multiplies the result: (x @ W8) * s ==
                # x @ (W8 * s), less one rounding of every weight. A
                # scale on the weight lets XLA split the dequantization
                # off the dot: inside the decode loop it then writes a
                # dtype copy of the kernel every step, and transposes
                # the kernels at the head of every segment (PERF.md
                # section 6, PR 25)
                acc = jnp.matmul(x.astype(self.dtype),
                                 w_i8.astype(self.dtype),
                                 preferred_element_type=jnp.float32)
                return (acc * scale).astype(self.dtype)
            w = w_i8.astype(self.dtype) * scale.astype(self.dtype)
        else:
            w = self.param("kernel", nn.initializers.lecun_normal(),
                           (in_features, self.features), self.dtype)
        return x.astype(self.dtype) @ w


class QKernel(nn.Module):
    """A linear layer's weights without the product: ``(kernel, scale)``
    under :class:`QDense`'s names, layout and initializers (``kernel_int8``
    [in, out] + float32 ``scale`` [1, out] per output channel under
    quant="int8", else ``kernel`` and None). For the one weight that is
    multiplied two ways: latent attention's up-projection, expanded at
    prefill and absorbed per head into the query and the output when a
    cache is attended."""

    in_features: int
    features: int
    quant: str | None = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self):
        shape = (self.in_features, self.features)
        if self.quant != "int8":
            return self.param("kernel", nn.initializers.lecun_normal(),
                              shape, self.dtype), None
        return (self.param("kernel_int8", _init_int8, shape, jnp.int8),
                self.param("scale", nn.initializers.constant(
                    1.0 / (127.0 * self.in_features ** 0.5)),
                    (1, self.features), jnp.float32))


def output_gate(cfg, out, h):
    """``out`` ``[b, s, heads, d]`` x ``sigmoid(out_gate_proj h)`` where the
    model gates its heads' outputs (``attn_output_gate``): one gate a head
    under ``attn_gate_headwise``, else one a channel; in float32, as every
    gate. For the kinds whose gate is not written into their own module (the
    kda and latent layers); called under the block's ``nn.compact``."""
    if not cfg.attn_output_gate:
        return out
    b, s, heads, d = out.shape
    with jax.named_scope("qkv_proj"):
        gate = jax.nn.sigmoid(QDense(
            heads if cfg.attn_gate_headwise else heads * d, cfg.quant,
            jnp.float32, name="out_gate_proj")(h)).reshape(b, s, heads, -1)
        return (out.astype(jnp.float32) * gate).astype(out.dtype)


def _scaled_rope_freqs(freqs, scaling, theta: float = 0.0):
    """Apply RoPE frequency scaling (inverse frequencies in, out).

    "llama3" is the Llama-3.1 scheme: low-frequency (long-wavelength)
    components are slowed by ``factor``, high-frequency ones kept, with a
    smooth ramp between the two wavelength thresholds derived from the
    original context length. "yarn" (``("yarn", factor, original
    positions, beta_fast, beta_slow, mscale)``, as DeepSeek's inference
    code computes it): pair d keeps its frequency
    below the correction dim of ``beta_fast`` rotations over the original
    positions, is slowed by ``factor`` above that of ``beta_slow``, and is
    blended linearly between; cos and sin are not scaled (the softmax
    scale is: ``LlamaConfig.attn_scale_mult``)."""
    if scaling is None:
        return freqs
    kind = scaling[0]
    if kind == "yarn":
        factor, orig, fast, slow = map(float, scaling[1:5])
        dim = 2 * freqs.shape[0]

        def correction_dim(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        lo = max(math.floor(correction_dim(fast)), 0)
        hi = min(math.ceil(correction_dim(slow)), dim - 1)
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                        / (hi - lo if hi != lo else 0.001), 0.0, 1.0)
        return freqs / factor * ramp + freqs * (1.0 - ramp)
    if kind == "linear":
        return freqs / jnp.float32(scaling[1])
    if kind == "llama3":
        factor, low_f, high_f, orig = map(float, scaling[1:])
        wavelen = 2.0 * jnp.pi / freqs
        smooth = (orig / wavelen - low_f) / (high_f - low_f)
        mid = (1.0 - smooth) * freqs / factor + smooth * freqs
        return jnp.where(wavelen > orig / low_f, freqs / factor,
                         jnp.where(wavelen < orig / high_f, freqs, mid))
    raise ValueError(f"unsupported rope scaling kind {kind!r}")


def rope(q, k, positions, theta: float, scaling: tuple | None = None):
    """Rotary position embeddings, fp32 trig, applied per head-dim pair."""
    head_dim = q.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = _scaled_rope_freqs(freqs, scaling, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, s, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]

    def rot(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def _deinterleave(x):
    """Rotary dims stored as (even, odd) pairs -> the two halves that
    :func:`rope`'s rotate-half expects (``rope_interleave`` checkpoints)."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _kv_quantize(x):
    """[..., d] float -> (int8 values, f32 scale [..., 1]) per-vector
    symmetric quantization (one scale per token per kv-head)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0,
                        1e-8)
    return jnp.round(x32 / scale).astype(jnp.int8), scale


def _kv_dequantize(q_i8, scale, dtype):
    return q_i8.astype(dtype) * scale.astype(dtype)


def cache_width(cache) -> int:
    """Sequence capacity of a decode/prefix cache (float or int8
    layout) — the ONE layout probe shared by the server's bucket math
    and the continuous engine's pack gate. Of a cache whose leaves hold
    one row a token; the holders that ask refuse any other
    (:func:`require_row_a_token`)."""
    return next(val for name, val in cache[0].items()
                if name != "index").shape[1]


def _kv_store(cfg, k, v, *more, layer: int = 0) -> dict:
    """This step's (or chunk's) K/V in the cache's storage layout: the
    float leaves, or int8 values + scales under ``cfg.kv_quant``. The
    ONE place the layout is built — the dense decode path, the sp
    decode path, and prefill embedding all consume it. ``k`` and ``v``
    (and ``more``) are the parts of ``cfg.cache_layout(layer)`` in its order
    (a latent cache: the compressed latent, the shared rotary key and, under
    sparse attention, the indexer's key). ``kv_quant`` is the per-head K/V
    rows' alone: every other kind's ``validate`` refuses it."""
    if cfg.kv_quant == "int8":
        k_q, k_s = _kv_quantize(k)
        v_q, v_s = _kv_quantize(v)
        return {"k_int8": k_q, "k_scale": k_s,
                "v_int8": v_q, "v_scale": v_s}
    return {name: part.astype(cfg.dtype)
            for name, part in zip(cfg.cache_layout(layer), (k, v, *more))}


def _active_sp_mesh():
    """The ambient mesh when sequence parallelism is usable: an ``sp``
    axis > 1 and not inside a manual (shard_map / pipeline-stage) region
    where a nested whole-mesh shard_map cannot trace. The ONE gate
    shared by ring prefill and sp decode — they must agree, or prefill
    would shard what decode then replicates."""
    from lambdipy_tpu.parallel.mesh import current_mesh
    from lambdipy_tpu.parallel.sharding import shard_hints_suppressed

    mesh = current_mesh()
    if (mesh is not None and mesh.shape.get("sp", 1) > 1
            and not shard_hints_suppressed()):
        return mesh
    return None


def resolve_sp_prefill(mode: str, mesh) -> int:
    """Resolve the usable whole-prompt sp-prefill factor for
    ``prefill_mode``: 0 under ``chunked``; under ``sp`` the mesh's
    sp-axis size when >= 2, else 0 with a counted stand-down
    (``sp_prefill_without_sp_mesh``) — the ``spec_k_under_sp_mesh``
    idiom: the operator's ask is impossible on this mesh, so the serial
    path runs and the condition is visible on /metrics, never silent."""
    if mode != "sp":
        return 0
    sp = int(mesh.shape.get("sp", 1)) if mesh is not None else 1
    if sp >= 2:
        return sp
    from lambdipy_tpu.parallel.spdecode import note_standdown

    note_standdown("sp_prefill_without_sp_mesh")
    return 0


def _attend(q, k, v, mask, tail=None):
    """Grouped-query attention core. q: [b,s,h,d]; k/v: [b,t,kvh,d].

    The shard_hints pin ONE layout through softmax and its jvp/transpose —
    batch over dp, kv-heads over tp, query seq over sp, key seq gathered
    (replicated over sp) — so the SPMD partitioner never falls back to
    involuntary full rematerialization bouncing between dp- and sp-sharded
    logits (ring attention is the layout that never gathers k/v).

    ``tail``: ``(k2, v2, seen [t2])``, further keys of the same rows (a
    decode segment's own positions, :func:`_tail_write`), attended under
    the ONE softmax: the same float32 logits and probabilities as if they
    lay in ``k`` / ``v``, summed in two parts. Positions not ``seen`` are
    never READ as numbers: their keys are masked like any other and their
    values selected to zero, because the TPU compiler hands the scan a
    tail it has not initialised (``_scan_decode``), and 0 x NaN is NaN."""
    from lambdipy_tpu.parallel.sharding import shard_hint

    b, s, h, d = q.shape  # values may be narrower than keys (latent)
    kvh = k.shape[2]
    group = h // kvh
    q = shard_hint(q.reshape(b, s, kvh, group, d), "dp", "sp", "tp")

    def scores(k, mask):
        logits = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
        logits = shard_hint(logits / jnp.sqrt(d).astype(jnp.float32),
                            "dp", "tp", None, "sp", None)
        return jnp.where(mask[:, None, None, :, :], logits, jnp.float32(-1e9))

    k = shard_hint(k, "dp", None, "tp")
    v = shard_hint(v, "dp", None, "tp")
    logits = scores(k, mask)
    if tail is not None:
        k2, v2, seen = tail
        k2 = shard_hint(k2, "dp", None, "tp")
        v2 = shard_hint(jnp.where(seen[None, :, None, None], v2, 0),
                        "dp", None, "tp")
        logits = jnp.concatenate(
            [logits, scores(k2, seen[None, None, :])], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    probs = shard_hint(probs, "dp", "tp", None, "sp", None)
    if tail is None:
        out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    else:
        t = k.shape[1]
        out = (jnp.einsum("bkgst,btkd->bskgd", probs[..., :t], v,
                          preferred_element_type=jnp.float32)
               + jnp.einsum("bkgst,btkd->bskgd", probs[..., t:], v2,
                            preferred_element_type=jnp.float32)
               ).astype(v.dtype)
    return shard_hint(out.reshape(b, s, h, v.shape[-1]), "dp", "sp", "tp")


# The float32 scores of one turn that the v5e compiler keeps in its fast
# memory through every pass of the softmax (``tests/test_chip_compile.py``:
# 24 MiB it keeps, 32 it spills): the prefills that select their keys by
# content (``models/latent.py``, ``models/sparse_kv.py``) run their heads a
# group at a time under it (:func:`_head_group`) and pick by the same exact
# threshold (:func:`_dsa_select_mask`).
DSA_SCORE_BYTES = 24 << 20


def _head_group(heads: int, elements_a_head: int) -> int:
    """The most heads (a divisor of ``heads``) whose float32 scores of
    ``elements_a_head`` each stay within DSA_SCORE_BYTES; at least one."""
    group = heads
    while group > 1 and (heads % group
                         or 4 * group * elements_a_head > DSA_SCORE_BYTES):
        group -= 1
    return group


def _dsa_select_mask(scores, visible, k: int):
    """``[..., t]`` bool: the ``k`` ``visible`` positions of largest float32
    ``scores``, ties to the lowest position, all the visible ones where
    they are fewer: the set ``jax.lax.top_k`` picks, as a mask and without
    a sort. The k-th largest score is found exactly, bit by bit, on the
    order-preserving integer image of the floats (32 counts over the row);
    positions equal to it are taken from the lowest on until k are."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    # sign-magnitude floats -> integers of the same order, then unsigned
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)
    key = jnp.where(visible, key, jnp.uint32(0))

    def bit(i, kth):
        cand = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    equal = key == kth[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    taken = equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
                     <= room[..., None])
    return (above | taken) & visible


def _cache_write(cache, store, idx, b: int, s: int, band: int = 0):
    """Write this step's (or chunk's) ``store`` leaves into the layer's
    cache entry at ``idx`` (an int32 scalar, or ``[b]`` per-row positions)
    and return ``(new cache entry, valid [.., s, t], t)``: which of the
    ``t`` cached positions each of the ``s`` queries may attend. The ONE
    write path of every cache kind: it iterates the leaves it is given."""
    from lambdipy_tpu.parallel.sharding import shard_hint

    new_cache = {}
    if jnp.ndim(idx) == 0:
        for name, val in store.items():
            new_cache[name] = jax.lax.dynamic_update_slice(
                cache[name], val, (0, idx, 0, 0))
        # chunk query j attends keys <= idx + j — causal within the
        # chunk, everything before it. s == 1 is the familiar decode-step
        # mask; s > 1 is a multi-token continuation chunk (prefix-cache
        # suffix prefill).
        t = new_cache[next(iter(store))].shape[1]
        valid = (jnp.arange(t)[None, None, :]
                 <= (idx + jnp.arange(s))[None, :, None])
        if band:
            # long-context sliding band: query at cache position p sees
            # keys from the start of the PREVIOUS band block — exactly the
            # window the serial window/2 slide schedule leaves resident
            # when p's chunk runs
            qpos = idx + jnp.arange(s)
            band_start = jnp.maximum(0, (qpos // band - 1) * band)
            valid = valid & (jnp.arange(t)[None, None, :]
                             >= band_start[None, :, None])
    else:
        # ragged batch (rows decode from different prompt lengths):
        # per-row scatter of this step's (or chunk's) positions. s == 1 is
        # the familiar decode step; s > 1 is a SPECULATIVE VERIFY CHUNK —
        # row r's chunk lands at idx[r]..idx[r]+s-1 and query j attends
        # keys <= idx[r]+j (causal within the chunk). Out-of-bounds scatter
        # indices DROP (jax .at[] default), which is exactly the engine's
        # over-decode/rollback contract: a rejected tail or
        # past-the-window write lands nowhere a kept token can read.
        rows = jnp.arange(b)
        cols = idx[:, None] + jnp.arange(s)[None, :]  # [b, s]
        for name, val in store.items():
            new_cache[name] = cache[name].at[rows[:, None], cols].set(val)
        t = new_cache[next(iter(store))].shape[1]
        valid = jnp.arange(t)[None, None, :] <= cols[:, :, None]  # [b, s, t]
    new_cache = {name: shard_hint(val, "dp", None, "tp")
                 for name, val in new_cache.items()}
    return new_cache, valid, t


class LlamaBlock(nn.Module):
    cfg: LlamaConfig
    layer: int = 0  # which layer of the model: cfg.layer_spec(layer)

    @nn.compact
    def __call__(self, x, positions, mask, cache, sp_prefill: int = 0,
                 band: int = 0, lengths=None):
        """cache: None (prefill over full x) or the layer's cache entry
        (the leaves of ``cfg.cache_layout()`` plus ``index``) for decode.
        Returns (y, new_cache_entry). What the layer is made of is
        ``cfg.layer_spec(self.layer)``.

        sp_prefill: static int — when >= 2, this is a whole-prompt
        sequence-parallel prefill program: the no-cache branch
        ring-shards the prompt's attention, the scalar-index
        continuation branch (s > 1) shards the chunk's queries over the
        sp axis (:func:`sp_chunk_attention`). 0 keeps every existing
        program byte-identical.
        band: static int — when > 0, restrict each scalar-index query at
        cache position p to keys in [max(0, (p//band - 1)*band), p]: the
        long-context SLIDING-WINDOW band, so one multi-chunk sp round
        attends exactly what the serial window/2 slide schedule would
        have exposed chunk by chunk."""
        cfg = self.cfg
        spec = cfg.layer_spec(self.layer)
        # the scope names (o_proj, mlp; embed, lm_head, sample further
        # down; qkv_proj, kv_write, attend and a kind's own in its module;
        # router, experts, shared_expert of the routed FFN) reach each
        # device operation's op_name: the trace is split by them (PERF.md).
        # Renaming or moving one: bump utils/compile_cache.NAMES_GEN
        # and LlamaServer._AOT_GEN
        b, s, _ = x.shape
        kind = attn_kind_module(spec.attn)
        forms = {name: form for name, form in (("sp_prefill", sp_prefill),
                                               ("band", band)) if form}
        if not set(forms) <= set(getattr(kind, "FORMS", ())):
            raise NotImplementedError(
                f"a {spec.attn} layer under a sliding band or the "
                "sequence-parallel prefill is not written (PERF.md "
                "section 7)")
        out, new_cache = kind.attend(self, x, positions, mask, cache,
                                     lengths, **forms)

        # (1.0 adds nothing to the program; the product in float32: as a
        # bfloat16 constant 1.4 / sqrt(32) would be off by 0.17 %, in every
        # sublayer alike)
        def scaled(y):
            if cfg.residual_scale == 1.0:
                return y
            return (y.astype(jnp.float32)
                    * jnp.float32(cfg.residual_scale)).astype(y.dtype)

        with jax.named_scope("o_proj"):
            out = out.reshape(b, s, -1)
            x = x + scaled(QDense(cfg.hidden, cfg.quant, cfg.dtype,
                                  name="o_proj")(out))

        with jax.named_scope("mlp"):
            if spec.ffn == "routed":
                from lambdipy_tpu.models.moe import RoutedMLP

                # the router reads the norm's float32 result, before the
                # cast that the experts' products take: a top-k of many
                # has near-ties. Padding rows of a ragged group prefill
                # route nowhere; a cache step's rows are all the device
                # can know of
                h = RMSNorm(cfg.norm_eps, name="mlp_norm")(
                    x.astype(jnp.float32))
                x = x + RoutedMLP(cfg, name="moe")(
                    h, mask if cache is None else None).astype(x.dtype)
                return x, new_cache
            h = RMSNorm(cfg.norm_eps, cfg.norm_unit_offset, name="mlp_norm")(x)
            if spec.ffn == "capacity":
                from lambdipy_tpu.models.moe import MoEMLP

                x = x + MoEMLP(cfg.moe_experts, cfg.mlp, cfg.moe_top_k,
                               cfg.moe_capacity_factor, cfg.dtype, cfg.quant,
                               group_size=cfg.moe_group_size, name="moe")(h)
            else:
                gate = QDense(cfg.mlp, cfg.quant, cfg.dtype, name="gate_proj")(h)
                up = QDense(cfg.mlp, cfg.quant, cfg.dtype, name="up_proj")(h)
                x = x + scaled(QDense(cfg.hidden, cfg.quant, cfg.dtype,
                                      name="down_proj")(nn.silu(gate) * up))
        return x, new_cache


class LlamaModel(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, positions=None, mask=None, cache=None,
                 logit_positions=None, exit_layer=None, sp_prefill=0,
                 band=0, lengths=None):
        """Returns (logits, new_cache).

        prefill: cache=None, tokens [b, s] -> cache entries sized s.
        decode:  cache=list of {k,v,index} (static max_len), tokens [b, 1].
        logit_positions: optional [b] int32 — compute lm_head only at that
        position per row (logits [b, 1, v]). Serving prefill needs one
        row of logits, not s: the full [b, s, vocab] f32 tensor is the
        largest activation of the whole serve path (8B at 8k context:
        4 GB) and s unneeded lm_head matmuls.
        lengths: optional [b] int32 — each right-padded row's true length,
        for a layer whose prefill entry depends on it (an eva ring).
        exit_layer: optional int — a SHALLOW-EXIT forward: run only
        layers 0..exit_layer-1, then final_norm + the TIED lm_head over
        that early hidden state (the self-drafting head for the
        speculative draft tier). Params for the skipped layers are
        simply never looked up, so the same param tree serves both
        depths; ``cache`` (when given) holds one entry per RUN layer.
        """
        cfg = self.cfg
        n_layers = (cfg.layers if exit_layer is None
                    else max(1, min(int(exit_layer), cfg.layers)))
        b, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if mask is None:
            mask = jnp.ones((b, s), dtype=jnp.bool_)
        emb = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                       param_dtype=cfg.dtype, name="embed")
        with jax.named_scope("embed"):
            x = emb(tokens)
            if cfg.embed_scale != 1.0:
                x = x * cfg.embed_scale
        new_cache = []
        for i in range(n_layers):
            layer_cache = None if cache is None else cache[i]
            x, c = LlamaBlock(cfg, i, name=f"layer_{i}")(
                x, positions, mask, layer_cache, sp_prefill=sp_prefill,
                band=band, lengths=lengths)
            new_cache.append(c)
        with jax.named_scope("lm_head"):
            x = RMSNorm(cfg.norm_eps, cfg.norm_unit_offset,
                        name="final_norm")(x)
            if logit_positions is not None:
                x = jnp.take_along_axis(
                    x, jnp.broadcast_to(logit_positions[:, None, None],
                                        (b, 1, x.shape[-1])), axis=1)
            if cfg.logit_divisor != 1.0:
                x = x / cfg.logit_divisor
            logits = QDense(cfg.vocab_size * cfg.pred_heads, cfg.quant,
                            jnp.float32, name="lm_head")(x)
            if cfg.pred_heads > 1:
                # the further heads predict the bytes after the next one;
                # serving them as drafts is not written (PERF.md section 7)
                logits = logits[..., :cfg.vocab_size]
        return logits, new_cache


def _empty_cache_entry(cfg: LlamaConfig, batch: int, max_len: int,
                       layer: int = 0) -> dict:
    if cfg.kv_quant == "int8":  # per-head K/V rows alone (_kv_store)
        shape = (batch, max_len, cfg.kv_heads, cfg.head_dim)
        return {"k_int8": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.full(shape[:3] + (1,), 1e-8, jnp.float32),
                "v_int8": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.full(shape[:3] + (1,), 1e-8, jnp.float32)}
    slots = cfg.cache_positions(max_len, layer)
    dtypes = cfg.cache_dtypes(layer)
    return {name: jnp.zeros((batch, slots[name], heads, width), dtypes[name])
            for name, (heads, width) in cfg.cache_layout(layer).items()}


def init_decode_cache(cfg: LlamaConfig, batch: int, max_len: int):
    """Static-shape KV cache for decode (one entry per layer)."""
    return [{**_empty_cache_entry(cfg, batch, max_len, layer),
             "index": jnp.int32(0)} for layer in range(cfg.layers)]


# The ONE KV-cache layout rule for tensor-parallel serving: every
# store-layout leaf is [..., seq, kv_heads, d-or-1], so the kv-head dim
# (axis 2 for both the [b, t, kvh, *] decode cache and the
# [n_pages, page, kvh, *] arena) shards over ``tp`` and everything else
# replicates. Matches the in-program ``shard_hint(..., "dp", None,
# "tp")`` the decode write path pins, so host-placed caches and
# program-produced caches agree on layout — per-device KV HBM drops
# ~1/tp and XLA never round-trips the cache through a gather.
def _kv_leaf_sharding(mesh, ndim: int):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lambdipy_tpu.parallel.sharding import _filter_spec

    return NamedSharding(mesh, _filter_spec(P(None, None, "tp"), mesh, ndim))


def shard_kv_cache(cache, mesh):
    """Place a host-built decode cache (list of per-layer dicts, as
    :func:`init_decode_cache` / :func:`concat_cache_blocks` return) on
    ``mesh``: KV leaves kv-head-sharded over ``tp``, ``index`` leaves
    replicated. A mesh without a ``tp`` axis places everything
    replicated — the 1-device degenerate mesh is an exact no-op."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    return [{name: jax.device_put(
                 val, rep if name == "index"
                 else _kv_leaf_sharding(mesh, val.ndim))
             for name, val in entry.items()}
            for entry in cache]


def shard_page_arena(arena, mesh):
    """Place a paged KV arena (:func:`init_page_arena`) on ``mesh`` —
    same kv-head-over-``tp`` rule as :func:`shard_kv_cache`, applied to
    the ``[n_pages, page, kv_heads, *]`` leaves."""
    return [{name: jax.device_put(val, _kv_leaf_sharding(mesh, val.ndim))
             for name, val in entry.items()}
            for entry in arena]


def validate_serving_mesh(cfg: LlamaConfig, mesh) -> None:
    """Reject serving meshes the TP layout cannot honor. ``shard_hint``
    silently DROPS an axis that does not divide the dim it would split —
    correct for a training forward, but a serving bundle that declared
    ``tp=8`` over 4 kv heads would then pay an 8-chip mesh to replicate
    its dominant HBM object. Raise loudly instead."""
    shape = dict(getattr(mesh, "shape", {}) or {})
    if (cfg.attn_kinds != ("kv",) or cfg.ffn_kind != "dense"
            or cfg.layer_kinds) \
            and any(int(n) > 1 for n in shape.values()):
        require_kv_cache(
            cfg, f"mesh {shape}: the cache layout sharded by kv head")
        raise NotImplementedError(
            f"mesh {shape}: no sharding is written yet for kinds chosen a "
            "layer or the dropless routed FFN (a chip's share of the "
            "experts): serve this model on one device (PERF.md section 7)")
    tp = int(shape.get("tp", 1))
    if tp <= 1:
        return
    bad = []
    if cfg.kv_heads % tp:
        bad.append(f"kv_heads={cfg.kv_heads}")
    if cfg.heads % tp:
        bad.append(f"heads={cfg.heads}")
    if cfg.mlp % tp:
        bad.append(f"mlp={cfg.mlp}")
    if bad:
        raise ValueError(
            f"mesh tp={tp} does not divide {', '.join(bad)}: the "
            "tensor-parallel layout shards attention heads and the MLP "
            "hidden dim over tp, and the KV cache over kv_heads — pick "
            "a tp that divides all three (or drop the mesh)")


def slice_cache_blocks(cache, start: int, width: int):
    """Store-layout ``[start, start + width)`` sequence slices of a decode
    cache, one dict per layer (``index`` dropped) — the block-granular
    unit the radix prefix store (runtime/prefixstore.py) keeps. Slices
    are fresh buffers, so they stay valid when the source cache is later
    donated to an extension program."""
    return [{name: jax.lax.dynamic_slice_in_dim(val, start, width, 1)
             for name, val in entry.items() if name != "index"}
            for entry in cache]


def concat_cache_blocks(cfg: LlamaConfig, blocks, cache_len: int):
    """Assemble per-layer block slices (as :func:`slice_cache_blocks`
    returns, one list entry per block, in sequence order) back into a
    full ``cache_len`` decode cache with ``index`` = total assembled
    width — the inverse of slicing at block boundaries. KV values are
    position-dependent (RoPE is applied before the cache store), so the
    caller must place blocks at the absolute positions they were sliced
    from; a radix path does that by construction."""
    require_row_a_token(cfg, "concat_cache_blocks")
    from lambdipy_tpu.parallel.mesh import current_mesh

    total = sum(next(iter(b[0].values())).shape[1] for b in blocks)
    # sharding-preserving under an ambient tp mesh: the assembled
    # full-window buffer is the big allocation here — place the fresh
    # dest kv-head-sharded BEFORE the updates, so the eager
    # dynamic_update_slice of (tp-sharded) block slices never gathers
    # and the registered cache costs 1/tp per device like its sources
    mesh = current_mesh()
    shard = (mesh is not None and mesh.shape.get("tp", 1) > 1)
    out = []
    for i in range(cfg.layers):
        dest = _empty_cache_entry(cfg, 1, cache_len)
        if shard:
            dest = {name: jax.device_put(
                        val, _kv_leaf_sharding(mesh, val.ndim))
                    for name, val in dest.items()}
        for name in blocks[0][i]:
            merged = jnp.concatenate([b[i][name] for b in blocks], axis=1)
            dest[name] = jax.lax.dynamic_update_slice(
                dest[name], merged.astype(dest[name].dtype), (0, 0, 0, 0))
        dest["index"] = jnp.int32(total)
        out.append(dest)
    return out


def init_page_arena(cfg: LlamaConfig, n_pages: int, page: int, mesh=None):
    """The paged KV arena (runtime/pagepool.py): per layer, the decode
    cache's store-layout leaves re-shaped page-major —
    ``[n_pages, page, kv_heads, head_dim]`` — with NO ``index`` leaf
    (positions live in the per-row block tables, not the storage).
    Page 0 is the reserved null page; it starts zero like everything
    else and only ever accumulates unread garbage. With ``mesh`` the
    arena is placed kv-head-sharded over ``tp``
    (:func:`shard_page_arena`): per-device arena HBM drops ~1/tp and
    the paged gather/scatter programs keep the layout end to end."""
    require_kv_cache(cfg, "the paged KV arena (init_page_arena)")
    shape = (n_pages, page, cfg.kv_heads, cfg.head_dim)
    if cfg.kv_quant == "int8":
        arena = [{"k_int8": jnp.zeros(shape, jnp.int8),
                  "k_scale": jnp.full(shape[:3] + (1,), 1e-8, jnp.float32),
                  "v_int8": jnp.zeros(shape, jnp.int8),
                  "v_scale": jnp.full(shape[:3] + (1,), 1e-8, jnp.float32)}
                 for _ in range(cfg.layers)]
    else:
        arena = [{"k": jnp.zeros(shape, cfg.dtype),
                  "v": jnp.zeros(shape, cfg.dtype)}
                 for _ in range(cfg.layers)]
    return arena if mesh is None else shard_page_arena(arena, mesh)


def page_kv_bytes(cfg: LlamaConfig, page: int) -> int:
    """Exact stored bytes of ONE page across all layers and leaves — the
    page-granular unit of the pool's byte accounting (host arithmetic,
    no device access)."""
    import numpy as np

    require_kv_cache(cfg, "the page pool's byte accounting (page_kv_bytes)")
    per_pos = cfg.kv_heads * cfg.head_dim
    if cfg.kv_quant == "int8":
        # int8 k + v values, f32 per-position-per-head scales
        per_layer = page * (2 * per_pos + 2 * cfg.kv_heads * 4)
    else:
        per_layer = page * 2 * per_pos * np.dtype(cfg.dtype).itemsize
    return int(cfg.layers * per_layer)


@jax.named_scope("attend")
def _gather_page_cache(arena, tables, window: int, page: int, index):
    """Materialize each row's first ``window`` positions from its block
    table into a contiguous decode cache (one dict per layer, ``index``
    attached) — the XLA twin of the paged kernel's table-lookup DMA.
    tables: [b, >= window/page] int32 page ids; entries past a row's
    allocation point at the null page, whose values are only ever read
    masked. The gathered values are bitwise the pages' values, so every
    downstream program (the shared ``_scan_decode``, the continuation)
    sees exactly what a dense contiguous cache would hold."""
    from lambdipy_tpu.parallel.sharding import shard_hint

    nb = window // page
    b = tables.shape[0]
    cols = tables[:, :nb].reshape(-1)
    out = []
    for entry in arena:
        # the hint keeps the gathered working cache in the arena's
        # kv-head-over-tp layout (no-op without a mesh): the page gather
        # touches only the pages/seq dims, so the head dim never moves
        e = {name: shard_hint(
                 jnp.take(val, cols, axis=0).reshape(
                     b, nb * page, *val.shape[2:]),
                 "dp", None, "tp")
             for name, val in entry.items()}
        e["index"] = index
        out.append(e)
    return out


@jax.named_scope("kv_write")
def _scatter_page_cache(arena, tables, cache, page: int):
    """Write a contiguous per-row cache back into its block-table pages
    (the inverse of :func:`_gather_page_cache`; ``index`` dropped).
    Pages shared between rows (frozen prefix pages) receive their own
    values back — decode never writes inside a row's matched prefix, so
    the round trip is bitwise a no-op there — and null-page duplicates
    may land in any order because nothing reads the null page
    unmasked."""
    b = tables.shape[0]
    new = []
    for aentry, centry in zip(arena, cache):
        e = {}
        for name, val in aentry.items():
            c = centry[name]
            nb = c.shape[1] // page
            pages = c.reshape(b * nb, page, *c.shape[2:]).astype(val.dtype)
            e[name] = val.at[tables[:, :nb].reshape(-1)].set(pages)
        new.append(e)
    return new


def arena_page_slices(arena, pid: int, page: int):
    """One arena page's per-layer KV as block slices shaped like
    :func:`slice_cache_blocks` returns (``[1, page, kv_heads, d-or-1]``
    per leaf) — the KV-EXPORT read primitive for paged prefix stores
    (runtime/kvwire.py framing): a shipped page leaves the arena in the
    exact block-slice layout a dense import would insert. Host fetch;
    the caller must hold a pool ref on ``pid`` so a concurrent release
    cannot recycle the page mid-read."""
    import numpy as np

    return [{name: np.asarray(val[int(pid)])[None, ...]
             for name, val in entry.items()}
            for entry in arena]


def copy_cache(cache):
    """Fresh-buffer copy of a decode cache: safe to feed a DONATING
    program (``_prefix_ext_fn``) while the original stays live in a
    shared store — donation would otherwise invalidate the stored
    buffers under every reader."""
    return [{name: jnp.copy(val) for name, val in entry.items()}
            for entry in cache]


def prefill_into_cache(cfg: LlamaConfig, prefill_cache, batch: int, max_len: int,
                       prompt_len: int):
    """Embed a prefill cache (float entries sized prompt_len) into a
    static max_len decode cache (quantizing when cfg.kv_quant). The
    shard_hint pins the embedded cache to the serving KV layout
    (kv-heads over tp) so prefill-produced caches — the prefix store's
    full-window entries included — leave their program tp-sharded
    instead of whatever replicated layout propagation falls back to
    (no-op without an ambient mesh). A kind whose leaves are not one row a
    token hands on a slot's entry already (a ring and every chunk's
    summaries, a state: its ``attend``), which is cut to the slots the
    cache has: what lies past a row's length there is masked, or
    overwritten, before anything may see it."""
    from lambdipy_tpu.parallel.sharding import shard_hint

    out = []
    for layer, entry in enumerate(prefill_cache):
        if cfg.kv_quant:
            store = _kv_store(cfg, *(entry[name]
                                     for name in cfg.cache_layout(layer)),
                              layer=layer)
        else:
            slots = cfg.cache_positions(max_len, layer)
            dtypes = cfg.cache_dtypes(layer)
            store = {name: entry[name][:, :slots[name]].astype(dtypes[name])
                     for name in cfg.cache_layout(layer)}
        dest = _empty_cache_entry(cfg, batch, max_len, layer)
        for name, val in store.items():
            dest[name] = shard_hint(
                jax.lax.dynamic_update_slice(dest[name], val, (0, 0, 0, 0)),
                "dp", None, "tp")
        dest["index"] = jnp.int32(prompt_len)
        out.append(dest)
    return out


_MOE_EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")


def quantize_params(float_params):
    """Convert a float LlamaModel params pytree (quant=None) into the int8
    layout (quant="int8"): each QDense ``kernel`` becomes ``kernel_int8`` +
    per-output-channel ``scale``, and each 3-D MoE expert stack becomes
    ``<name>_int8`` + per-(expert, channel) ``<name>_scale``. Embeddings,
    norms and the router stay float."""

    def convert(tree):
        if isinstance(tree, dict):
            if "kernel" in tree and getattr(tree["kernel"], "ndim", 0) == 2:
                w = jnp.asarray(tree["kernel"], jnp.float32)
                scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
                scale = jnp.maximum(scale, 1e-8)
                out = dict(tree)
                del out["kernel"]
                out["kernel_int8"] = jnp.round(w / scale).astype(jnp.int8)
                out["scale"] = scale
                return out
            if any(k in tree and getattr(tree[k], "ndim", 0) == 3
                   for k in _MOE_EXPERT_KEYS):
                out = dict(tree)
                for k in _MOE_EXPERT_KEYS:
                    if k in out and getattr(out[k], "ndim", 0) == 3:
                        w = jnp.asarray(out[k], jnp.float32)  # [e, in, out]
                        scale = jnp.max(jnp.abs(w), axis=1, keepdims=True) / 127.0
                        scale = jnp.maximum(scale, 1e-8)
                        del out[k]
                        out[f"{k}_int8"] = jnp.round(w / scale).astype(jnp.int8)
                        out[f"{k}_scale"] = scale
                return {k: convert(v) if isinstance(v, dict) else v
                        for k, v in out.items()}
            return {k: convert(v) for k, v in tree.items()}
        return tree

    return convert(float_params)


def pipeline_forward(model: LlamaModel, params, tokens, mesh, *,
                     num_microbatches: int):
    """Forward scoring with the transformer blocks pipeline-parallel over
    the mesh's ``pp`` axis (GPipe microbatching, parallel/pipeline.py).

    Embedding and the final norm/lm_head run replicated outside the
    pipeline (they are a small fraction of FLOPs); the ``layers`` blocks are
    split into ``pp`` equal stages. Layer count must divide by pp size.
    """
    from lambdipy_tpu.parallel.pipeline import (
        merge_microbatches, pipeline_apply, split_microbatches,
        stack_stage_params)

    cfg = model.cfg
    if cfg.ffn_kind != "dense" or cfg.layer_kinds:
        raise NotImplementedError(
            "pipeline_forward stacks the layers' trees, so they must be "
            "alike: a model with leading dense layers before routed ones, "
            "or with an attention kind a layer, is not")
    p = params["params"]
    n_stages = mesh.shape["pp"]
    if cfg.layers % n_stages:
        raise ValueError(f"{cfg.layers} layers not divisible by pp={n_stages}")
    per_stage = cfg.layers // n_stages
    layer_trees = [p[f"layer_{i}"] for i in range(cfg.layers)]
    stage_trees = [
        jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                               *layer_trees[s * per_stage:(s + 1) * per_stage])
        for s in range(n_stages)
    ]
    stacked = stack_stage_params(stage_trees)  # leading dims [pp, per_stage, ...]

    b, s = tokens.shape
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by {num_microbatches} microbatches")
    block = LlamaBlock(cfg)
    # batch dim 1: broadcasts against any local microbatch size, so the
    # replicated const stays valid when pipeline_apply also shards the
    # microbatch dim over dp/fsdp
    const = {
        "positions": jnp.arange(s)[None, :],
        "mask": jnp.ones((1, s), jnp.bool_),
    }

    def stage_fn(stage_params, h, const):
        for j in range(per_stage):
            layer = jax.tree_util.tree_map(lambda q, j=j: q[j], stage_params)
            h, _ = block.apply({"params": layer}, h, const["positions"],
                               const["mask"], None)
        return h

    x = jnp.take(p["embed"]["embedding"], tokens, axis=0)
    x = merge_microbatches(pipeline_apply(
        stage_fn, stacked, split_microbatches(x, num_microbatches), mesh,
        const=const))
    x = RMSNorm(cfg.norm_eps).apply({"params": p["final_norm"]}, x)
    return QDense(cfg.vocab_size, cfg.quant, jnp.float32).apply(
        {"params": p["lm_head"]}, x)


def filter_logits(logits, *, top_k: int | None = None, top_p: float | None = None):
    """Mask logits outside the top-k / nucleus (top-p) sets to -inf.

    logits: [b, v] fp32. Static top_k/top_p (compile-time), the standard
    serving knobs. The highest-probability token is always kept.
    """
    neg = jnp.float32(-1e30)
    if top_k is not None and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p is not None and top_p < 1.0:
        sort = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sort, axis=-1)
        # keep while cumulative prob *before* this token is < top_p; the
        # head token is kept unconditionally so top_p <= 0 degrades to
        # greedy instead of masking the whole vocabulary
        keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
        keep = keep.at[..., 0].set(True)
        thresh = jnp.min(jnp.where(keep, sort, jnp.float32(jnp.inf)),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < thresh, neg, logits)
    return logits


@jax.named_scope("sample")
def filter_logits_runtime(logits, top_k, top_p):
    """:func:`filter_logits` with the knobs as RUNTIME operands, so one
    compiled program serves every request (VERDICT r2 #3: static knobs
    forced a multi-second re-trace per novel sampling combination).

    top_k (int32) and top_p (f32) may be scalars or PER-ROW ``[b]``
    vectors — batcher-fused rows each filter under their own request's
    knobs (VERDICT r5 #2). <= 0 disables top_k, >= 1 disables top_p,
    per row. Same sequential semantics as the static version (top-k
    filter, then nucleus over the filtered distribution); the extra
    vocab-sized sort per emitted token is noise next to the per-step
    matmuls.
    """
    neg = jnp.float32(-1e30)
    v = logits.shape[-1]
    rows = logits.shape[:-1]
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), rows)
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), rows)
    srt = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        srt, jnp.clip(top_k - 1, 0, v - 1)[..., None], axis=-1)
    logits = jnp.where((top_k > 0)[..., None] & (logits < kth), neg, logits)
    srt = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p[..., None]
    keep = keep.at[..., 0].set(True)
    thresh = jnp.min(jnp.where(keep, srt, jnp.float32(jnp.inf)),
                     axis=-1, keepdims=True)
    return jnp.where((top_p < 1.0)[..., None] & (logits < thresh), neg,
                     logits)


@jax.named_scope("sample")
def _split_rows(keys):
    """Advance per-row PRNG chains one step: ``[b, 2]`` uint32 keys ->
    (new keys ``[b, 2]``, per-row subkeys ``[b, 2]``). Each row's walk is
    a function of ITS key alone — a row splits identically whether it
    decodes solo or packed next to arbitrary traffic, which is what
    makes sampled requests batchable (VERDICT r5 #2)."""
    pair = jax.vmap(lambda k: jax.random.split(k, 2))(keys)  # [b, 2, 2]
    return pair[:, 0], pair[:, 1]


def segment_keeps_tail(cfg: LlamaConfig) -> bool:
    """Whether a decode segment leaves its cache unwritten until its end
    (:func:`_scan_decode`, ``tail_window``): where every layer is of ONE
    kind and that kind says so under the model's shapes (its ``keeps_tail``,
    which holds the measurements: what the v5e compiler does with the
    per-step write of such leaves). A kind without the word keeps the
    per-step write: a recurrent state is a carry of the scan by nature, and
    rows whose per-step write is in place gain nothing. So does a model of
    several kinds a layer, whatever they say: the tail's init, what a step
    reads and the merge are one kind's over all the layers. Asked while a
    segment program is traced, under its mesh."""
    if len(cfg.attn_kinds) != 1:
        return False
    return _ask(cfg.kind_of(0), "keeps_tail", False, cfg)


def _leaf_spans(cfg: LlamaConfig, entry: dict, positions: int,
                layer: int = 0) -> dict:
    """``{leaf: slots}`` for the leaves of layer ``layer``'s cache entry
    ``entry``: the slots of each that ``positions`` consecutive positions
    from 0 lie in: ``positions`` itself where a leaf holds one row a
    token (and for the leaves ``kv_quant`` stores in a row's place)."""
    spans = cfg.cache_positions(positions, layer)
    return {name: spans.get(name, positions)
            for name in entry if name != "index"}


def _segment_decode(model: LlamaModel, params, select, first, lp, cache, pos,
                    done, keys, eos_id, segment: int, window: int):
    """One segment of the continuous engine's plain programs: ``segment``
    steps whose attention reads the first ``window`` positions of the
    B-slot cache; returns ``(emitted, carry)`` with the FULL cache in the
    carry, advanced. Where the segment keeps no tail, each layer's leaves
    are cut to THEIR spans of the window (rows a token; a ring, compressed
    keys or summaries by their own arithmetic; a state has one slot whatever
    the window and is handed through), the scan writes every step, and the
    advanced windows go back into the full carry."""
    cfg = model.cfg

    def scan(cache, **form):
        return _scan_decode(model, params, select, first, lp, cache, pos,
                            done, keys, eos_id, segment, return_carry=True,
                            counters=cfg.counters(), **form)

    spans = [_leaf_spans(cfg, entry, window, layer)
             for layer, entry in enumerate(cache)]
    if segment_keeps_tail(cfg) and _ask(cfg.kind_of(0), "tail_fits", True,
                                        cfg, segment, spans[0]):
        return scan(cache, tail_window=window)

    def short(layer, name, val):     # whether the window cuts this leaf
        return name != "index" and spans[layer][name] < val.shape[1]

    if not any(short(i, name, val) for i, entry in enumerate(cache)
               for name, val in entry.items()):
        return scan(cache)
    # the window's two copies per segment have a scope of their own,
    # apart from the step's kv_write and attend
    with jax.named_scope("kv_window"):
        win = [{name: (jax.lax.slice_in_dim(val, 0, spans[i][name], axis=1)
                       if short(i, name, val) else val)
                for name, val in entry.items()}
               for i, entry in enumerate(cache)]
    out, carry = scan(win)
    f2, lp2, wcache, pos2, done2, keys2 = carry
    with jax.named_scope("kv_window"):
        merged = [{name: (jax.lax.dynamic_update_slice_in_dim(
                              cache[i][name], val, 0, axis=1)
                          if short(i, name, cache[i][name]) else val)
                   for name, val in entry.items()}
                  for i, entry in enumerate(wcache)]
    return out, (f2, lp2, merged, pos2, done2, keys2)


def _scan_decode(model: LlamaModel, params, select_fn, first, lp0, cache,
                 start, done0, keys, eos_id, decode_steps: int,
                 return_carry: bool = False, pos_offset=None,
                 counters: tuple = (), tail_window: int | None = None):
    """The decode scan shared by the exact-shape path (:func:`_decode`),
    the bucketed serving path (:func:`_serve_decode`) and the streaming
    segment path: one compiled step per token over a static-shape cache.
    ``eos_id`` is an int32 scalar or per-row ``[b]`` operand; < 0
    disables eos latching for that row (``done`` then never becomes
    True, so the filler value is never emitted). ``keys`` is the per-row
    ``[b, 2]`` PRNG operand (:func:`_split_rows`). Emits ``(tokens,
    logprobs)`` — each token's raw model logprob rides along (one
    logsumexp per step, noise next to the forward); filler tokens after
    eos carry logprob 0. ``return_carry`` additionally returns the final
    (tok, lp, cache, pos, done, keys) carry so a later segment can
    continue the decode exactly where this one stopped.

    ``pos_offset`` (int32 scalar or ``[b]``, default None) splits the
    LOGICAL position from the cache-local one: the carry's ``pos`` stays
    the LOCAL frame (cache writes and the validity mask key off it — the
    windowed long-context path gathers a sliding view whose slot 0 is
    logical token ``pos_offset``), while RoPE sees ``pos + pos_offset``,
    the token's true logical position. None keeps every existing path
    byte-identical (no extra operand is traced).

    ``counters`` (the engine's plain segments: ``cfg.counters()``, what the
    model's kinds count for ``/metrics``, :class:`Counters`): the emitted
    tuple gains, behind tokens and logprobs, one member for every
    collection the declarations name, in their order: the sum, over the
    steps and over whatever layers sow it, of what the model sows into that
    collection, from the declaration's zero for ``b`` rows on; the carry is
    what it was.

    ``tail_window`` (the engine's plain segments, where
    :func:`segment_keeps_tail`): inside the scan the cache is READ-ONLY,
    its first ``tail_window`` positions a loop invariant. What the scan
    carries in the cache's place, what a step's layers read and the ONE
    write of the full cache after the scan are the kind's (``tail_init``,
    ``tail_step``, ``tail_merge``; ``models/kv.py`` says it for per-head K/V
    rows: a tail ``[b, decode_steps, ...]`` a leaf; ``models/eva.py`` for a
    ring tail and a summary tail). The same keys, values and probabilities
    as the per-step write, the sum's order apart; done rows step as garbage
    into their own row's tail as they did into their own row. Why: a cache
    the loop writes is prefetched whole, updated and written back WHOLE
    every layer of every step (PERF.md section 6, PR 30); a scan of
    hundreds of steps keeps the per-step write, its tail would be a second
    cache."""
    b = first.shape[0]
    has_eos = eos_id >= 0
    cfg = model.cfg
    if tail_window is not None:
        kind = cfg.kind_of(0)   # every layer's (segment_keeps_tail)
        full, base = cache, jnp.broadcast_to(start, (b,))
        spans = _leaf_spans(cfg, full[0], tail_window)
        with jax.named_scope("kv_window"):
            frozen = [{name: jax.lax.slice_in_dim(val, 0, spans[name], axis=1)
                       for name, val in entry.items() if name != "index"}
                      for entry in full]
        cache = (kind.tail_init(cfg, frozen, base, decode_steps),
                 jnp.int32(0))

    # what the program counts beside its tokens: the collections the model
    # sows, and where each one's sum starts
    counted = {name: zero(b) for kind_counters in counters
               for name, zero in kind_counters.sown.items()}

    def step(carry, _):
        if counted:
            carry, counts = carry
        tok, lp, cache, pos, done, keys = carry  # pos: int32 scalar or [b]
        rope_pos = pos if pos_offset is None else pos + pos_offset
        positions = (rope_pos[:, None] if jnp.ndim(rope_pos)
                     else jnp.broadcast_to(rope_pos[None, None], (b, 1)))
        if tail_window is not None:
            tails, j = cache
            cache = kind.tail_step(cfg, frozen, tails, base, j)
        if counted:
            (logits, new_cache), sown = model.apply(
                params, tok[:, None], positions=positions, cache=cache,
                mutable=list(counted))
            counts = tuple(c + sum(jax.tree.leaves(sown[name]))
                           for name, c in zip(counted, counts))
        else:
            logits, new_cache = model.apply(params, tok[:, None],
                                            positions=positions, cache=cache)
        if tail_window is not None:
            new_cache = (new_cache, j + 1)  # each layer's tail, grown by one
        else:
            for entry in new_cache:
                entry["index"] = pos + 1
        keys, subs = _split_rows(keys)
        nxt, nlp = select_fn(logits[:, -1, :].astype(jnp.float32), subs)
        nxt = jnp.where(done, eos_id, nxt)
        nlp = jnp.where(done, jnp.float32(0.0), nlp)
        done = done | (has_eos & (nxt == eos_id))
        carry = (nxt, nlp, new_cache, pos + 1, done, keys)
        if counted:
            carry = (carry, counts)
        return carry, (tok, lp)

    init = (first, lp0, cache, start, done0, keys)
    if counted:
        init = (init, tuple(counted.values()))
    carry, (toks, lps) = jax.lax.scan(step, init, None, length=decode_steps)
    out = (jnp.transpose(toks), jnp.transpose(lps))  # [b, decode_steps] x2
    if counted:
        carry, counts = carry
        out = (*out, *counts)
    if tail_window is not None:
        tok, lp, (tails, _), pos, done, keys = carry
        merged = kind.tail_merge(cfg, full, tails, base, decode_steps)
        for entry in merged:
            entry["index"] = pos
        carry = (tok, lp, merged, pos, done, keys)
    return (out, carry) if return_carry else out


def _serve_decode(model: LlamaModel, params, prompt, length, temperature,
                  top_k, top_p, rng, eos_id, *, decode_steps: int,
                  cache_len: int):
    """Serving decode with every request knob as a runtime operand.

    prompt: [b, sb] int32, right-padded to the bucket size sb; length:
    int32 scalar or [b] — PER-ROW true prompt lengths, so one program
    serves a ragged batch of different-length prompts (each row decodes
    from its own prompt end). Right padding is safe under causal
    attention — real positions never attend pad keys, and the decode loop
    overwrites each row's pad cache slots at index ``length[r] + j``
    before the validity mask (``pos <= index``) ever exposes them. The
    first sampled token reads row r's logits at ``length[r] - 1``.

    temperature (f32, <= 0 = greedy), top_k (int32, <= 0 = off), top_p
    (f32, >= 1 = off), eos_id (int32, < 0 = none) and the PRNG keys are
    all PER-ROW ``[b]`` traced operands (keys ``[b, 2]``): one compiled
    (sb, decode_steps) program serves every sampling configuration and
    every prompt length in the bucket, and batcher-fused rows each
    decode under their own request's knobs and their own seed-derived
    PRNG chain (VERDICT r5 #2).
    """
    select = _serve_select(temperature, top_k, top_p)
    carry = _serve_prefill(model, params, prompt, length, select, rng,
                           eos_id, cache_len=cache_len)
    return _scan_decode(model, params, select, *carry, eos_id, decode_steps)


@jax.named_scope("sample")
def _token_logprob(lg, tok):
    """Raw model logprob of ``tok`` under fp32 logits ``lg`` [b, v] —
    log_softmax at the chosen index (knob-independent: what the MODEL
    assigned, not the sampling distribution)."""
    logz = jax.nn.logsumexp(lg, axis=-1)
    return jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0] - logz


def _serve_select(temperature, top_k, top_p):
    """Token-selection closure over PER-ROW runtime knob operands
    (scalar or ``[b]``; batcher-fused rows each select under their own
    request's knobs). ``select(lg [b, v] f32, keys [b, 2])`` returns
    ``(token [b], raw model logprob of token [b])`` — row r's draw uses
    row r's subkey alone, so its tokens are independent of what shares
    the batch (VERDICT r5 #2)."""

    @jax.named_scope("sample")
    def select(lg, keys):
        lg = lg.astype(jnp.float32)
        t_row = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32),
                                 lg.shape[:-1])
        greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)

        def sampled(args):
            lg, keys = args
            t = jnp.maximum(t_row, jnp.float32(1e-6))[:, None]
            filt = filter_logits_runtime(lg / t, top_k, top_p)
            draw = jax.vmap(
                lambda k, row: jax.random.categorical(k, row))(keys, filt)
            # greedy rows inside a mixed batch keep their argmax
            return jnp.where(t_row > 0, draw.astype(jnp.int32), greedy_tok)

        # cond, not where: an all-greedy batch (the bulk of serving
        # load) must not pay the sampling path's two vocab-sized sorts
        # per emitted token — they dominate small-model decode steps
        tok = jax.lax.cond(jnp.any(t_row > jnp.float32(0.0)), sampled,
                           lambda args: greedy_tok, (lg, keys))
        return tok, _token_logprob(lg, tok)

    return select


def _serve_prefill(model: LlamaModel, params, prompt, length, select, rng,
                   eos_id, *, cache_len: int, sp_prefill: int = 0):
    """Bucketed serving prefill: embed the prompt into a ``cache_len``
    decode cache and select the first token. Returns the decode carry
    ``(first, lp0, cache, pos, done, rng)`` consumed by
    :func:`_scan_decode` —
    either fused into one program (:func:`_serve_decode`) or as its own
    compiled program for streaming segments."""
    cfg = model.cfg
    b, sb = prompt.shape
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    # lm_head only at each row's last real position: [b, 1, v], never the
    # [b, sb, v] full-prefill logits tensor; a layer whose prefill entry
    # depends on the rows' lengths (an eva ring) reads them
    logits, prefill_cache = model.apply(params, prompt,
                                        logit_positions=length - 1,
                                        sp_prefill=sp_prefill,
                                        lengths=length)
    cache = prefill_into_cache(cfg, prefill_cache, b, cache_len, 0)
    for entry in cache:
        entry["index"] = length
    keys, subs = _split_rows(rng)
    first, lp0 = select(logits[:, 0, :].astype(jnp.float32), subs)
    done0 = (eos_id >= 0) & (first == eos_id)
    return first, lp0, cache, length, done0, keys


def _continue_prefill(model: LlamaModel, params, cache, suffix, suffix_len,
                      select, rng, eos_id, sbs: int, pos_offset=None,
                      sp_prefill: int = 0, band: int = 0):
    """Continuation prefill from a cached prefix KV: embed the suffix
    chunk at positions after the cache index, select the first token, and
    return the decode carry ``(first, lp0, cache, pos, done, rng)``. The
    SINGLE source of the prefix-continuation math — the fused prefix path
    feeds this carry straight into :func:`_scan_decode`, the streaming
    prefix path returns it to segment programs, and their bitwise parity
    rests on this being one function. ``pos_offset`` is the windowed
    long-context split (see :func:`_scan_decode`): cache writes stay in
    the LOCAL frame (``index``), RoPE sees the logical position."""
    idx = cache[0]["index"]
    rope0 = idx if pos_offset is None else idx + pos_offset
    positions = (rope0 + jnp.arange(sbs))[None, :]
    logits, new_cache = model.apply(
        params, suffix, positions=positions, cache=cache,
        logit_positions=jnp.broadcast_to(suffix_len - 1, (1,)),
        sp_prefill=sp_prefill, band=band)
    # The carry must come out in the SEG-PROGRAM family's shapes: per-row
    # (1,) index/pos, matching what _serve_prefill produces. The prefix
    # cache's scalar index fed model.apply above (the multi-token chunk
    # needs the scalar-index branch), but a scalar carry here would make
    # the shared ('stream', ...) segment program silently retrace — and
    # FAIL against its shape-strict AOT-loaded executable (ADVICE r4).
    start = jnp.broadcast_to(idx + suffix_len, (1,))
    for entry in new_cache:
        entry["index"] = start
    keys, subs = _split_rows(rng)
    first, lp0 = select(logits[:, 0, :].astype(jnp.float32), subs)
    done0 = (eos_id >= 0) & (first == eos_id)
    return first, lp0, new_cache, start, done0, keys


def _next_bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


@jax.named_scope("sample")
def _spec_accept_resample(probs, draft, keys):
    """The deterministic-draft rejection-sampling core of SAMPLED
    speculative decoding (the delta-proposal case of Leviathan-style
    speculative sampling).

    probs: [kb, v] target distributions per chunk position (post
    temperature/top-k/top-p); draft: [kb-1] proposed tokens; keys:
    [kb, 2] — one uniform per accept test plus one for the final draw.
    Position i accepts draft_i with probability p_i(draft_i); the
    first rejection resamples position m from the RESIDUAL (p_m with
    the rejected token zeroed, renormalized), and a full accept draws
    position kb-1 fresh from p_{kb-1}. Emitting
    ``[pending, draft[:m]]`` with ``new_tok`` as the next pending is
    exactly ancestral sampling from the target chain — the identity
    ``p = q * min(1, p/q) + (1 - accept) * residual`` with q a delta.
    Returns (m accepted-draft count 0..kb-1, new_tok)."""
    kb, v = probs.shape
    p_draft = jnp.take_along_axis(probs[: kb - 1], draft[:, None],
                                  1)[:, 0]
    u = jax.vmap(lambda key: jax.random.uniform(key))(keys[: kb - 1])
    acc = (u < p_draft).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(acc))  # 0..kb-1
    pm = probs[m]
    rejected = m < kb - 1
    # v is out of range -> no zeroing on a full accept
    dm = jnp.where(rejected, draft[jnp.clip(m, 0, kb - 2)], v)
    pm = jnp.where(jnp.arange(v) == dm, 0.0, pm)
    pm = pm / jnp.maximum(pm.sum(), 1e-30)
    new_tok = jax.random.categorical(
        keys[kb - 1], jnp.log(jnp.maximum(pm, 1e-38)))
    return m, new_tok.astype(jnp.int32)


@jax.named_scope("sample")
def _spec_chain_verify(select, lg, draft, lp_in, keys):
    """Chain-deterministic draft verification — the continuous engine's
    accept/rollback core (the batched counterpart of the solo verify
    fns, specialized to the engine's bitwise contract).

    lg: [b, kb, v] f32 logits of the verify chunk (position i
    conditioned on the pending token + drafts before i); draft:
    [b, kb-1] proposals; lp_in: [b] the pending token's logprob carry;
    keys: [b, 2] the per-row PRNG chains as of the pending token.

    The target here is not a distribution but the CHAIN itself: given a
    row's seed, ``_scan_decode`` emits a deterministic sequence (greedy
    rows by argmax, sampled rows by categorical draws along the row's
    own split-per-step key walk). Verification re-derives that chain's
    next token at every chunk position — advancing the key walk exactly
    as the one-token scan would — and accepts the longest draft prefix
    that MATCHES it. Emitted tokens are therefore bitwise the
    non-speculative engine's for greedy AND seeded-sampled rows alike
    (speculation changes how many tokens each weight read verifies,
    never which tokens) — what ``tests/test_spec_engine.py`` holds.
    Relative to :func:`_spec_accept_resample`'s rejection sampling (the
    solo sampled path's distributional contract) the accept test is
    stricter — token equality instead of probability mass — costing
    some acceptance on high-entropy sampled rows and buying exact
    replay/parity. The rejected tail's key splits roll back: the
    returned chain state is the walk after exactly ``count``
    selections, so a later segment continues precisely where plain
    decode would.

    Returns ``(lps_block [b, kb], count [b] in 1..kb, tok' [b],
    lp' [b], keys' [b, 2])``; ``lps_block[:, 0]`` is the pending
    token's logprob and column j >= 1 the (j-1)'th selection's — only
    the first ``count`` columns are meaningful, like the token block."""
    b, kb, _ = lg.shape
    tgt, tlp, kstack = [], [], [keys]
    cur = keys
    for i in range(kb):
        cur, subs = _split_rows(cur)
        t_i, l_i = select(lg[:, i, :], subs)
        tgt.append(t_i)
        tlp.append(l_i)
        kstack.append(cur)
    tgt = jnp.stack(tgt)          # [kb, b]
    tlp = jnp.stack(tlp)          # [kb, b]
    kstack = jnp.stack(kstack)    # [kb + 1, b, 2]
    ok = (tgt[: kb - 1] == jnp.transpose(draft)).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(ok, axis=0), axis=0)   # [b] 0..kb-1
    count = m + 1
    tok2 = jnp.take_along_axis(tgt, m[None, :], axis=0)[0]
    lp2 = jnp.take_along_axis(tlp, m[None, :], axis=0)[0]
    keys2 = jnp.take_along_axis(
        kstack, jnp.broadcast_to(count[None, :, None], (1, b, 2)),
        axis=0)[0]
    lps_block = jnp.concatenate(
        [lp_in[:, None], jnp.transpose(tlp[: kb - 1])], axis=1)
    return lps_block, count, tok2, lp2, keys2


def _lookup_draft(context, k: int, ngram_max: int = 3) -> list:
    """Prompt-lookup drafting (host-side): propose the k tokens that
    followed the most recent earlier occurrence of the context's current
    suffix n-gram, falling back to repeating the last token.

    No draft model exists or is needed: the draft source is the sequence
    itself, which makes this free and surprisingly effective exactly
    where speculative decoding pays off — repetitive continuations
    (copying, templated output, and the cycles greedy decodes fall
    into). A wrong draft costs nothing beyond the verify chunk whose
    weight read was the point of the step anyway. An EMPTY context
    (nothing to look up in) drafts zeros — a draft is only ever a
    proposal, so a content-free one is safe, just never accepted."""
    return _lookup_draft_hit(context, k, ngram_max)[0]


def _lookup_draft_hit(context, k: int, ngram_max: int = 3) -> tuple:
    """:func:`_lookup_draft` plus whether an n-gram match was FOUND:
    ``(draft list of k, hit bool)``. ``hit=False`` marks the fallback
    (repeat-last-token, or zeros on an empty context) — the engine's
    per-row draft-miss accounting (``SpecDecodeStats.draft_misses``)
    keys off it, and ISSUE's "no match falls back to k=1" degeneracy is
    the observable consequence: a fallback draft usually verifies 0
    proposals, so the step emits exactly the 1 token plain decode
    would."""
    import numpy as np

    ctx = np.asarray(context, np.int64).reshape(-1)
    n = ctx.size
    if n == 0:
        return [0] * k, False
    for g in range(min(ngram_max, n - 1), 0, -1):
        suffix = ctx[n - g:]
        windows = np.lib.stride_tricks.sliding_window_view(ctx, g)[:n - g]
        hits = np.nonzero((windows == suffix).all(axis=1))[0]
        if hits.size:
            start = int(hits[-1]) + g
            cand = ctx[start:start + k]
            out = np.full(k, ctx[-1], np.int64)
            out[:cand.size] = cand
            return out.tolist(), True
    return [int(ctx[-1])] * k, False


def _shallow_draft(model, params, tok, cache, pos, kb: int,
                   exit_layer: int):
    """Self-drafting shallow-exit chain (device-side, traced INSIDE a
    verify program): run ``kb - 1`` sequential one-token forwards through
    only the first ``exit_layer`` layers + final_norm + the tied lm_head
    (:class:`LlamaModel`'s ``exit_layer`` path), each greedy-argmax token
    feeding the next step — the Medusa/EAGLE-style "cheap head over the
    target's own early hidden state" draft source, costing roughly
    ``exit_layer / layers`` of a full forward per proposed token.

    The chain reads/writes a SCRATCH alias of the early layers' windowed
    KV entries: each functional ``.at[].set`` write lands in throwaway
    arrays the caller discards, so the real cache the verify chunk runs
    over is untouched — the draft can never poison verification, and
    acceptance stays chain-deterministic whatever the drafts are. Because
    it runs in-program off the device-true carry token, the drafts are
    never stale at pipeline depth >= 2 (unlike host lookup, which must
    extrapolate across in-flight steps). Returns ``d_model [b, kb-1]``
    int32."""
    dcache = [dict(entry) for entry in cache[:exit_layer]]
    cur = tok
    drafts = []
    for j in range(kb - 1):
        step_pos = pos + j
        for entry in dcache:
            entry["index"] = step_pos
        lg, dcache = model.apply(params, cur[:, None],
                                 positions=step_pos[:, None],
                                 cache=dcache, exit_layer=exit_layer)
        nxt = jnp.argmax(lg[:, -1, :].astype(jnp.float32),
                         axis=-1).astype(jnp.int32)
        drafts.append(nxt)
        cur = nxt
    return jnp.stack(drafts, axis=1)


class _ServedProgram:
    """One named serving program of a single-chip server whose bundle has an
    AOT store: where it comes from is settled at its FIRST CALL, with that
    call's own operands and no others.

    - The store holds it (``AotStore.load_exec``: preloaded at boot, or
      deserialised now): the first real call is the probe. An executable
      that raises there is pruned and counted (``aot_fallbacks``), and the
      jit wrapper serves the key.
    - The store has never seen it: ``lower(*args).compile()`` obtains the
      executable ONCE (the persistent compile cache is read and written on
      that path as on a plain call), that ``Compiled`` runs this call and
      every later one, and the same object goes to the store's saver thread
      (``AotStore.save_later``), so the next boot of the bundle loads what
      this one traced, lowered and compiled or read from the cache.

    Afterwards a call is one attribute read more than the program's own
    (both a ``Compiled`` and a jit wrapper dispatch from C++). A program
    that is never called (the unused half of a streaming pair) is neither
    loaded nor compiled."""

    __slots__ = ("_server", "key", "name", "_jitted", "_fn", "_lock")

    def __init__(self, server: "LlamaServer", key: tuple, name: str, jitted):
        self._server = server
        self.key = key
        self.name = name
        self._jitted = jitted
        self._fn = None
        self._lock = threading.Lock()

    def __call__(self, *args):
        fn = self._fn
        if fn is None:
            return self._first_call(args)
        return fn(*args)

    def _cache_size(self) -> int:
        """``compile_count``'s question of a jit wrapper: programs held."""
        fn = self._fn
        if fn is None:
            return 0
        return getattr(fn, "_cache_size", lambda: 1)()

    def _first_call(self, args):
        from lambdipy_tpu.runtime import spans

        server, store = self._server, self._server._aot
        with self._lock:
            if self._fn is None:
                hit = store.load_exec(self.name)
                if hit is not None:
                    fn, load_s = hit
                    try:
                        with spans.span("boot.warm", program=self.name,
                                        tier="exec") as warm:
                            out = jax.block_until_ready(fn(*args))
                    except Exception as e:  # noqa: BLE001 — the probe
                        log.warning("aot %s: the loaded executable failed "
                                    "its first call (%s): pruned, serving "
                                    "from jit", self.name, e)
                        store.drop_tier(self.name, "exec")
                        with server._fns_lock:
                            server.aot_fallbacks += 1
                    else:
                        spans.program(self.name, "exec", key=self.key,
                                      aot_load=load_s, warm=warm.seconds)
                        with server._fns_lock:
                            server.aot_hits += 1
                            server.aot_lazy_loads += load_s is not None
                        self._fn = fn
                        return out
                # its trace, lowering and compile follow under the jitted
                # function's own name
                spans.program(self.name, "jit", key=self.key)
                if store.pruned(self.name):
                    # saving it again would write the same losing artifact
                    self._fn = self._jitted
                else:
                    compiled = self._jitted.lower(*args).compile()
                    out = compiled(*args)
                    self._fn = compiled
                    store.save_later(self.name, compiled, server._aot_boot)
                    return out
        return self._fn(*args)


class LlamaServer:
    """Compile-once decode serving: prompt-length bucketing (pad right to a
    power of two) + sampling knobs as runtime operands.

    One jitted ``_serve_decode`` per (batch, prompt-bucket, decode-bucket)
    triple serves every request that falls in it; a second request with a
    different prompt length, temperature, top-k/p, seed, or eos triggers
    ZERO new compiles (VERDICT r2 #3). Ragged batches are first-class:
    per-row length operands let rows of different prompt lengths decode
    together, each from its own prompt end. ``compile_count`` exposes the
    number of distinct compiled programs for tests and metrics.
    """

    def __init__(self, model: LlamaModel, params, *, mesh=None,
                 min_bucket: int = 16, decode_cap: int | None = None,
                 prefix_cache_max: int = 4, program_cache_max: int = 64,
                 prefill_chunk: int | None = None, aot=None):
        self.model = model
        self.params = params
        self.mesh = mesh
        if mesh is not None:
            # serving is strict where the training forward is lenient: a
            # tp that can't shard the heads must error, not silently
            # replicate the KV cache the operator paid a mesh to shard
            validate_serving_mesh(model.cfg, mesh)
        self.min_bucket = min_bucket
        # optional runtime/aot.AotStore: serving programs are loaded from
        # the bundle's serialized-executable tier instead of compiled
        # (the 8B boot pays ~40 s of compile PER program without this, and
        # 8-10 s of trace, lowering and cache read where the persistent
        # cache holds it). Every NAMED program (_aot_name) is a
        # _ServedProgram: loaded at its first call if the store has it,
        # else compiled there and snapshotted, with no example operands.
        # The exec tier is single-chip by AotStore's own rule, so a meshed
        # server takes no store (the handler offers it none): its programs
        # come through jit and the persistent cache.
        self._aot = aot if mesh is None else None
        self.aot_hits = 0  # programs served from the AOT store this boot
        self.aot_lazy_loads = 0  # of them, deserialised at first use
        self.aot_fallbacks = 0   # loaded executables whose first call failed
        # True until the boot's own warm-up has ended (aot_save_all): what
        # is compiled till then is the bundle's boot set (AotStore.preload)
        self._aot_boot = True
        self._aot_saved_seen = 0
        # Speculative-decoding counters. ``spec_stats`` (the legacy bare
        # dict — last call's counters, single-threaded convenience only)
        # is kept for back-compat; the LOCKED, cumulative,
        # /metrics-surfaced object is ``spec_metrics`` — ONE
        # SpecDecodeStats instance that both the solo
        # ``generate_speculative`` path and the continuous engine's
        # spec mode record into, so acceptance reporting has a single
        # source of truth under threaded serving.
        from lambdipy_tpu.runtime.metrics import SpecDecodeStats

        self.spec_stats: dict = {}  # last generate_speculative counters
        self.spec_metrics = SpecDecodeStats()
        # chunked prefill: prompts longer than this prefill through
        # fixed-width chunks against the growing KV cache instead of one
        # wide program. Memory for dense attention drops from O(s^2) to
        # O(chunk x s) — an 8k dense prefill's [h, s, s] f32 scores are
        # 8.6 GB in one shot but bounded at chunk width chunked — and
        # program count stays O(1) in prompt length. None = off.
        # The chunk width MUST divide max_len: every chunk (padded last
        # one included) writes its full width at a multiple-of-chunk
        # offset, and a write window crossing max_len would be CLAMPED by
        # dynamic_update_slice — silently overwriting real prefix KV.
        # Halve until it divides; disable if nothing >= min_bucket does.
        self.prefill_chunk = None
        if prefill_chunk:
            require_row_a_token(model.cfg, "chunked prefill (prefill_chunk)")
            ck = max(self.min_bucket, _next_bucket(prefill_chunk, 16))
            while ck >= self.min_bucket and model.cfg.max_len % ck:
                ck //= 2
            if ck >= self.min_bucket:
                self.prefill_chunk = ck
        # default: anything the context window allows is servable (power-
        # of-two bucketing bounds distinct compiles at log2(max_len))
        self.decode_cap = decode_cap or model.cfg.max_len
        # Compiled-program cache. Bucketing bounds prompt/decode keys to
        # log2 counts, but ("continue", ...) keys multiply across prefix
        # lengths x suffix buckets x step buckets — a long-lived
        # multi-tenant server must not accrete programs without bound, so
        # the cache is LRU-capped (VERDICT r3 weak #8). The lock also
        # serializes check-then-insert: serving threads, streams, prefix
        # prefills, and the bucket-warm thread all race here, and an
        # unlocked miss makes each racer pay a duplicate compile.
        from collections import OrderedDict

        self._fns: "OrderedDict[tuple, Any]" = OrderedDict()
        self._fns_lock = threading.Lock()
        self._fns_max = max(1, program_cache_max)
        self._fn_evictions = 0
        # prefix KV cache (shared system prompts): key -> (cache, length).
        # The KV cache is FUNCTIONAL (immutable jax arrays), so serving
        # from a cached prefix never copies or locks it — each request's
        # programs produce fresh buffers. LRU-bounded: a full-window
        # cache entry is max_len * kv_heads * head_dim * 2 * layers bytes.
        from collections import OrderedDict

        self._prefix_cache_max = max(1, prefix_cache_max)
        self._prefixes: "OrderedDict[str, tuple]" = OrderedDict()
        # the jax arrays are immutable, but the LRU BOOKKEEPING is not:
        # serving threads insert/refresh/evict concurrently. _inflight
        # collapses a thundering herd of first requests for the SAME new
        # prefix to one device prefill (key -> Event the rest wait on).
        self._prefix_lock = threading.Lock()
        self._prefix_inflight: dict[str, Any] = {}

    @property
    def buckets(self) -> list[tuple]:
        """Snapshot of the bucket keys compiled so far — (batch, prompt,
        decode) for fused programs, ("stream", batch, prompt, cache_len,
        segment) for streaming pairs (repr-keyed sort tolerates the mixed
        tuple shapes)."""
        with self._fns_lock:
            return sorted(self._fns, key=repr)

    @property
    def compile_count(self) -> int:
        with self._fns_lock:
            fns = list(self._fns.values())
        # AOT-loaded executables are not jit objects; count each as one
        # compiled program
        return sum(getattr(f, "_cache_size", lambda: 1)()
                   for fn in fns
                   for f in (fn if isinstance(fn, tuple) else (fn,)))

    @property
    def program_evictions(self) -> int:
        """Programs LRU-evicted from the compiled cache (a rising count on
        a steady workload means program_cache_max is too small and the
        server is recompiling hot buckets)."""
        return self._fn_evictions

    def _fn_cached(self, key: tuple, build):
        """LRU get-or-build under the cache lock. ``build()`` only wraps
        with ``jax.jit`` (lazy — tracing/compiling happens at first call),
        so holding the lock through it is cheap; what the lock buys is
        that at most one wrapper per key ever exists, so concurrent racers
        share one compiled program instead of each tracing their own.
        With an AOT store attached, each part of a NAMED key is a
        :class:`_ServedProgram` round its jit wrapper: the bundle's
        executable or a compile, settled at the part's first call. A miss
        leaves the key and what answered it in the program record (``GET
        /spans``): a served program writes its own entry at that call, an
        un-named key's jit wrapper is written here (its trace, lowering
        and compile follow at first call, under the jitted function's
        name)."""
        with self._fns_lock:
            fn = self._fns.get(key)
            if fn is not None:
                self._fns.move_to_end(key)
                return fn
            names = self._aot_part_names(key) \
                if self._aot is not None else None
            fn = build()
            if names is None:
                from lambdipy_tpu.runtime import spans as _spans

                _spans.program(self._aot_name(key), "jit", key=key)
            else:
                parts = fn if isinstance(fn, tuple) else (fn,)
                served = [_ServedProgram(self, key, n, part)
                          for n, part in zip(names, parts)]
                fn = served[0] if len(served) == 1 else tuple(served)
            self._fns[key] = fn
            while len(self._fns) > self._fns_max:
                self._fns.popitem(last=False)
                self._fn_evictions += 1
            return fn

    # -- AOT snapshot/restore of compiled serving programs -------------------

    # Serving-program AOT generation: bump when any serving program's
    # SIGNATURE or carry shape changes, or its text changes under an
    # unchanged key (family, shapes), so a pre-change bundle's aot/
    # dir (which persists across in-place upgrade) orphans its stale
    # executables instead of loading them. g2 = round 5: per-row knob /
    # PRNG operands + the (1,)-shaped prefix-continuation carry.
    # g3 = PR 24: scope names inside the programs (an executable keeps
    # the names it was compiled with; utils/compile_cache.NAMES_GEN is
    # the same switch for the persistent cache). g4 = PR 25: QDense
    # applies the int8 scale after the dot where the kernel's bytes
    # bound it (names unchanged: the persistent cache's key hashes the
    # new computation by itself). g5 = PR 30: the two plain segment
    # programs write a segment-long tail and merge it once
    # (_scan_decode, tail_window); same signature, same carry. g6 = PR
    # 34: so do an eva model's (a ring tail and a summary tail,
    # _eva_tail_attend), and their counter has a third column. g7 = PR
    # 38: the set of names changes (``seg_w`` has one) and a server saves
    # at first use, so an executable is trusted by its NAME in many more
    # places: from here on a change of a window-bucketed segment's text
    # bumps this too. g8 = PR 42: on a TPU a kda or linear layer's decode
    # step is a Mosaic call that steps the state leaf in place
    # (ops/state_step.py), and the linear state leaf is [slots, 1, heads x
    # d, d]: an executable of the old text would take the old leaf. g9 =
    # PR 43: a sparse prefill's loops take their trip counts from the rows'
    # length operand and walk key blocks of 2048 (_sparse_prefill_attend):
    # same operands, another text. (PR 44 moved the kv, latent and eva
    # kinds out of the block into modules: no text moved and, under
    # ``block_method``, no op_name, so no generation.)
    _AOT_GEN = "g9"

    @classmethod
    def aot_prefix(cls) -> str:
        """Artifact-name prefix for THIS generation's serving programs.
        The generation tag sits in the prefix so boot-time bulk
        operations (AotStore.preload) can glob exactly the loadable
        artifacts — a stale generation's executables must not be
        device-loaded just to sit unconsumed (code-review r5)."""
        return f"srv-{cls._AOT_GEN}-"

    @classmethod
    def _aot_name(cls, key: tuple) -> str | None:
        """Artifact name(s) for a program-cache key; None = not AOT-able."""
        if isinstance(key[0], int):  # fused decode (b, sb, steps)
            return cls.aot_prefix() + "dec-" + "-".join(map(str, key))
        kind = key[0]
        if kind in ("stream", "seg_w", "prefix", "continue", "stream_prefix",
                    "spec", "spec_s"):
            return cls.aot_prefix() + f"{kind}-" + "-".join(map(str, key[1:]))
        # un-named, so jit alone serves them: "prefix_ext" and
        # "sp_prefill_ext" donate their cache operand; the speculative
        # segments ("spec_seg", "mspec_seg") and the paged families are
        # what no measured cell runs. Naming one is a line here.
        return None

    @classmethod
    def _aot_part_names(cls, key: tuple) -> list[str] | None:
        """One artifact name for each callable the key maps to (a
        streaming pair is two parts, ``-p0`` the prefill and ``-p1`` the
        segment); None = not AOT-able."""
        name = cls._aot_name(key)
        if name is None:
            return None
        if key[0] == "stream":
            return [f"{name}-p0", f"{name}-p1"]
        return [name]

    def _aot_examples(self, key: tuple):
        """Synthesized example operand tuples (excluding params) matching
        the traced shapes of the key's program(s). Returns a list — one
        per callable the key maps to (streaming keys map to a pair).
        Serving builds none of these (a :class:`_ServedProgram` settles
        with its first call's own operands); they describe a key's
        programs to whoever lowers one without a request: the program-text
        and chip-compile tests, under ``jax.eval_shape``."""
        cfg = self.model.cfg

        def knobs_for(b):
            return self._knob_operands(0.0, None, None, 0, None, b=b)

        def prompt_ops(b, sb):
            return (jnp.zeros((b, sb), jnp.int32),
                    jnp.ones((b,), jnp.int32))

        def prefix_cache(cache_len):
            cache = init_decode_cache(cfg, 1, cache_len)
            for entry in cache:
                entry["index"] = jnp.int32(1)  # prefix cache: scalar index
            return cache

        if isinstance(key[0], int):
            b, sb, _steps = key
            return [(*prompt_ops(b, sb), *knobs_for(b))]
        kind = key[0]
        if kind == "seg_w":
            # the stream pair's segment half's: the window is sliced
            # inside the program, the carry is the full cache's
            _, b, cache_len, _window, segment = key
            return self._aot_examples(
                ("stream", b, self.min_bucket, cache_len, segment))[1:]
        if kind == "stream":
            _, b, sb, cache_len, _segment = key
            t, k, p, rng, eos = knobs_for(b)
            index = jnp.ones((b,), jnp.int32)  # per-row, like the prefill
            cache = init_decode_cache(cfg, b, cache_len)
            for entry in cache:
                entry["index"] = index
            seg_ex = (t, k, p,
                      jnp.zeros((b,), jnp.int32),    # first token
                      jnp.zeros((b,), jnp.float32),  # lp
                      cache, index,                  # pos
                      jnp.zeros((b,), jnp.bool_),    # done
                      rng, eos)
            return [(*prompt_ops(b, sb), t, k, p, rng, eos), seg_ex]
        if kind == "prefix":
            _, sb, _cache_len = key
            return [(jnp.zeros((1, sb), jnp.int32), jnp.int32(1))]
        if kind == "continue":
            _, sbs, _steps, cache_len = key
            return [(prefix_cache(cache_len), jnp.zeros((1, sbs), jnp.int32),
                     jnp.int32(1), *knobs_for(1))]
        if kind == "stream_prefix":
            # 2-tuple: full-window continuation (the prefix path);
            # 3-tuple: continuation over a capped engine cache
            sbs = key[1]
            cache_len = key[2] if len(key) > 2 else cfg.max_len
            return [(prefix_cache(cache_len),
                     jnp.zeros((1, sbs), jnp.int32), jnp.int32(1),
                     *knobs_for(1))]
        if kind == "spec":
            # verify inputs are scalar-index (generate_speculative
            # normalizes the prefill carry before the first call)
            _, kb, cache_len = key
            return [(jnp.zeros((1, kb), jnp.int32),
                     jnp.zeros((1,), jnp.int32), prefix_cache(cache_len))]
        if kind == "spec_s":
            _, kb, cache_len = key
            return [(jnp.zeros((1, kb), jnp.int32),
                     jnp.zeros((1,), jnp.int32), prefix_cache(cache_len),
                     jnp.float32(1.0), jnp.int32(0), jnp.float32(1.0),
                     jnp.zeros((kb, 2), jnp.uint32))]
        return None

    @property
    def aot_saved(self) -> int:
        """Artifacts this boot has written to the bundle's AOT store."""
        return self._aot.saved if self._aot is not None else 0

    def aot_save_all(self, boot_done: bool = False) -> int:
        """Called where a boot's own warm-up ends (the warm-up invoke, the
        ``bucket-warm`` daemon; build-time by the warm runner, which must
        not exit with a snapshot half written). Every program compiled so
        far was queued for the store at its first run, so this only waits
        for the saver thread. ``boot_done``: nothing more runs before the
        deploy is ready, so what is saved from now on is the traffic's and
        no boot preloads it (``AotStore.preload``). Returns the number of
        artifacts written since the last call."""
        if self._aot is None:
            return 0
        self._aot.drain()
        with self._fns_lock:
            if boot_done:
                self._aot_boot = False
            n = self._aot.saved - self._aot_saved_seen
            self._aot_saved_seen += n
        return n

    def _compiled(self, b: int, sb: int, steps: int):
        cache_len = min(sb + steps, self.model.cfg.max_len)

        def build():
            def generate(params, prompt, length, temperature, top_k, top_p,
                         rng, eos_id):
                return _serve_decode(
                    self.model, params, prompt, length, temperature, top_k,
                    top_p, rng, eos_id, decode_steps=steps,
                    cache_len=cache_len)

            return jax.jit(generate)

        return self._fn_cached((b, sb, steps), build)

    def _validate(self, s: int, max_new_tokens: int) -> None:
        cfg = self.model.cfg
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
        if max_new_tokens > self.decode_cap:
            raise ValueError(
                f"max_new_tokens {max_new_tokens} exceeds the server's "
                f"decode cap {self.decode_cap}")
        if s + max_new_tokens > cfg.max_len:
            raise ValueError(
                f"prompt {s} + max_new_tokens {max_new_tokens} exceeds "
                f"max_len {cfg.max_len}")

    @staticmethod
    def _pad_rows(rows, lengths, bb: int, sb: int):
        """(padded [bb, sb] int32 array, per-row length operand) — dummy
        length-1 rows fill the batch bucket; they are free under per-row
        lengths."""
        import numpy as np

        padded = np.zeros((bb, sb), np.int32)
        for r, row in enumerate(rows):
            padded[r, :lengths[r]] = row
        return (jnp.asarray(padded),
                jnp.asarray(lengths + [1] * (bb - len(rows)), jnp.int32))

    @staticmethod
    def _knob_operands(temperature, top_k, top_p, seed, eos_id, b: int = 1):
        """PER-ROW runtime sampling-knob operands shared by the fused and
        streaming programs: ``(temperature [b] f32, top_k [b] i32,
        top_p [b] f32, keys [b, 2] u32, eos [b] i32)``.

        Each knob may be a scalar (broadcast over the b rows; None = the
        knob's disabled sentinel) or a length-<=b list of per-row values
        (batcher-fused rows each carrying their own request's knobs;
        short lists pad with the disabled sentinel for the bucket's
        dummy rows). Row r's PRNG stream is ``fold_in(PRNGKey(seed_r),
        0)`` for listed seeds and ``fold_in(PRNGKey(seed), r)`` for one
        shared seed — a function of the row's own request alone, NEVER
        of batch composition, so a row samples identically solo or
        packed next to arbitrary traffic (VERDICT r5 #2)."""
        import numpy as np

        def vec(x, default, dtype):
            if isinstance(x, (list, tuple, np.ndarray)):
                vals = [default if e is None else e for e in x]
                vals += [default] * (b - len(vals))
                return jnp.asarray(vals[:b], dtype)
            return jnp.full((b,), default if x is None else x, dtype)

        if isinstance(seed, (list, tuple, np.ndarray)):
            seeds = ([int(s) if s is not None else 0 for s in seed]
                     + [0] * b)[:b]
            keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(s), 0)
                              for s in seeds])
        else:
            base = jax.random.PRNGKey(int(seed) if seed is not None else 0)
            keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(
                jnp.arange(b))
        return (vec(temperature, 0.0, jnp.float32),
                vec(top_k, 0, jnp.int32),
                vec(top_p, 1.0, jnp.float32),
                keys,
                vec(eos_id, -1, jnp.int32))

    def _mesh_ctx(self):
        if self.mesh is None:
            from contextlib import nullcontext

            return nullcontext()
        from lambdipy_tpu.parallel.mesh import use_mesh

        return use_mesh(self.mesh)

    def generate(self, prompt_tokens, *, max_new_tokens: int,
                 temperature: float = 0.0, top_k: int | None = None,
                 top_p: float | None = None, seed: int = 0,
                 eos_id: int | None = None, prefix=None,
                 return_logprobs: bool = False):
        """prompt_tokens: [s], [b, s], or a RAGGED list of rows with
        different lengths (each row decodes from its own prompt end) ->
        [b, max_new_tokens].

        Every sampling knob (``temperature``/``top_k``/``top_p``/
        ``seed``/``eos_id``) may be a scalar (applies to all rows) or a
        length-b list of per-row values — the form the batchers use to
        fuse requests with unrelated knobs into one device call. A
        row's sampled tokens depend only on its own seed, never on what
        shares the batch (:meth:`_knob_operands`).

        ``prefix``: optional shared-prefix tokens (single-row requests): a
        cached prefill KV for them is reused across requests
        (:meth:`cache_prefix`), and only ``prompt_tokens`` — the suffix
        after the prefix — is prefilled per request. With the float KV
        cache, output is exactly ``generate(prefix + prompt)``; under
        ``kv_quant`` the suffix attends the QUANTIZED prefix KV (the full
        prompt prefills against exact float K/V), so outputs agree only
        to quantization tolerance."""
        import numpy as np

        cfg = self.model.cfg
        rows, lengths = self._normalize_prompts(prompt_tokens)
        b, s = len(rows), max(lengths)
        if prefix is not None:
            return self._generate_with_prefix(
                prefix, rows, lengths, max_new_tokens, temperature, top_k,
                top_p, seed, eos_id, return_logprobs=return_logprobs)
        self._validate(s, max_new_tokens)
        # prefer power-of-two buckets for reuse, but shrink toward the
        # exact request near the max_len boundary instead of rejecting:
        # any request with s + max_new <= max_len must be servable
        steps = min(_next_bucket(max_new_tokens, self.min_bucket),
                    self.decode_cap, cfg.max_len - s)
        sb = min(cfg.prompt_bucket(s, self.min_bucket), cfg.max_len - steps)
        # batch is bucketed too (micro-batching produces nondeterministic
        # sizes; each distinct b would otherwise compile at request time)
        bb = _next_bucket(b, 1)
        fn = self._compiled(bb, sb, steps)
        prompt_op, length_op = self._pad_rows(rows, lengths, bb, sb)
        args = (self.params, prompt_op, length_op,
                *self._knob_operands(temperature, top_k, top_p, seed,
                                     eos_id, b=bb))
        with self._mesh_ctx():
            toks, lps = fn(*args)
        toks = np.asarray(jax.device_get(toks))[:b, :max_new_tokens]
        if return_logprobs:
            lps = np.asarray(jax.device_get(lps))[:b, :max_new_tokens]
            return toks, lps
        return toks

    # -- prefix caching ------------------------------------------------------

    @staticmethod
    def _prefix_key(tokens) -> str:
        import hashlib

        import numpy as np

        arr = np.asarray(tokens, np.int32).reshape(-1)
        return hashlib.sha1(arr.tobytes()).hexdigest()

    def cache_prefix(self, prefix_tokens) -> str:
        """Prefill ``prefix_tokens`` once and keep its KV cache for
        :meth:`generate`'s ``prefix=`` path (idempotent; LRU-bounded).
        Returns the cache key. The stored cache is sized to the full
        context window so any suffix + decode the window allows can
        continue from it."""
        require_row_a_token(
            self.model.cfg, "the server's prefix cache (cache_prefix)")
        cfg = self.model.cfg
        rows, lengths = self._normalize_prompts(prefix_tokens)
        if len(rows) != 1:
            raise ValueError("prefix caching is single-row")
        s = lengths[0]
        if s >= cfg.max_len:
            raise ValueError(f"prefix {s} fills the whole context window")
        key = self._prefix_key(rows[0])
        wait_s, timeouts, max_timeouts = 300.0, 0, 2
        while True:
            with self._prefix_lock:
                if key in self._prefixes:
                    self._prefixes.move_to_end(key)
                    return key
                waiter = self._prefix_inflight.get(key)
                if waiter is None:
                    # we own the prefill for this key
                    self._prefix_inflight[key] = threading.Event()
                    break
            # another thread is prefilling this exact prefix — wait for it
            # instead of duplicating the device work, then re-check (its
            # prefill may have failed or been evicted already). A wait
            # that TIMES OUT means the owner's device prefill is likely
            # hung: surface an error after a bounded number of timeouts
            # rather than looping forever with nothing reported to the
            # client.
            if not waiter.wait(timeout=wait_s):
                timeouts += 1
                if timeouts >= max_timeouts:
                    raise RuntimeError(
                        f"prefix prefill (key {key[:8]}...) owned by "
                        f"another thread did not complete within "
                        f"{timeouts * wait_s:.0f}s — device prefill "
                        "appears wedged; failing this request")
        try:
            return self._prefill_prefix(key, rows, lengths)
        finally:
            with self._prefix_lock:
                self._prefix_inflight.pop(key).set()

    def get_prefix(self, key: str):
        """LRU-refreshing peek: ``(cache, length)`` for an exact prefix
        key, or None — never prefills (the radix prefix store's fast
        path; :meth:`cache_prefix` is the prefill-on-miss sibling)."""
        with self._prefix_lock:
            entry = self._prefixes.get(key)
            if entry is not None:
                self._prefixes.move_to_end(key)
            return entry

    def register_prefix(self, key: str, cache, length: int) -> None:
        """Insert an externally built full-window prefix cache under
        ``key`` (same LRU bound as :meth:`cache_prefix`) — the radix
        prefix store's injection point: it assembles a cache from its
        block slices (or finishes an extension walk) and registers it
        here so every existing ``prefix=`` path — fused, streaming,
        continuous-engine join, speculative — serves from it
        unchanged."""
        require_row_a_token(
            self.model.cfg, "the server's prefix cache (register_prefix)")
        with self._prefix_lock:
            self._prefixes[key] = (cache, int(length))
            self._prefixes.move_to_end(key)
            while len(self._prefixes) > self._prefix_cache_max:
                self._prefixes.popitem(last=False)

    def _prefix_first_fn(self, sb: int, cache_len: int):
        """First-chunk prefix prefill: embed the (padded) chunk into a
        full-window cache, index = true length."""
        def build():
            def prefix_first(params, prompt, length):
                _, prefill_cache = self.model.apply(
                    params, prompt,
                    logit_positions=jnp.zeros((1,), jnp.int32))
                cache = prefill_into_cache(self.model.cfg, prefill_cache, 1,
                                           cache_len, 0)
                for entry in cache:
                    entry["index"] = length  # int32 scalar
                return cache

            return jax.jit(prefix_first)

        return self._fn_cached(("prefix", sb, cache_len), build)

    def _prefix_ext_fn(self, sbs: int):
        """Extend a full-window prefix cache by one PADDED chunk (no token
        selection; lm_head at one position so the vocab matmul is
        skipped). Every chunk except the last must be full-width: the
        scalar-index write covers the whole padded chunk, the NEXT
        chunk's write overwrites those padding cells, and the final
        ragged chunk's padding stays unreachable behind the cache
        index."""
        def build():
            def prefix_ext(params, cache, chunk, chunk_len):
                idx = cache[0]["index"].reshape(())
                cache = [{**c, "index": idx} for c in cache]
                positions = (idx + jnp.arange(sbs))[None, :]
                _, new_cache = self.model.apply(
                    params, chunk, positions=positions, cache=cache,
                    logit_positions=jnp.zeros((1,), jnp.int32))
                for entry in new_cache:
                    entry["index"] = idx + chunk_len
                return new_cache

            # donate the incoming cache: it is single-owner inside the
            # chunk loop, and without donation every ext call copies the
            # full-window KV (multi-GB at 8B) to write one chunk
            return jax.jit(prefix_ext, donate_argnums=(1,))

        return self._fn_cached(("prefix_ext", sbs), build)

    def _sp_first_fn(self, sb: int, cache_len: int, sp: int):
        """Whole-prompt sequence-parallel first chunk: ONE sharded
        program embeds the (padded) round into a full-window cache with
        the prompt's attention ring-sharded over the sp axis
        (``sp_prefill=sp`` routes the no-cache branch through
        :func:`~lambdipy_tpu.parallel.ring.ring_attention`). For a
        prompt that fits one round this IS the cold prefill — one
        program, critical path 1/sp of the chunk chain."""
        if sb % sp:
            raise ValueError(f"sp first-chunk width {sb} % sp={sp} != 0")

        def build():
            def sp_first(params, prompt, length):
                _, prefill_cache = self.model.apply(
                    params, prompt,
                    logit_positions=jnp.zeros((1,), jnp.int32),
                    sp_prefill=sp)
                cache = prefill_into_cache(self.model.cfg, prefill_cache, 1,
                                           cache_len, 0)
                for entry in cache:
                    entry["index"] = length  # int32 scalar
                return cache

            return jax.jit(sp_first)

        return self._fn_cached(("sp_prefill", 1, sb // sp, cache_len, sp),
                               build)

    def _sp_ext_fn(self, sbs: int, sp: int):
        """Sequence-parallel twin of :meth:`_prefix_ext_fn`: extend the
        cache by one ROUND of ``sp`` chunk-widths in a single program —
        the round's queries shard over the sp axis
        (:func:`~lambdipy_tpu.parallel.ring.sp_chunk_attention`), the
        cache write and index math are byte-identical to the serial
        ext's (same scalar-index branch, same padded-chunk contract:
        only the last round may be ragged)."""
        if sbs % sp:
            raise ValueError(f"sp round width {sbs} % sp={sp} != 0")

        def build():
            def sp_ext(params, cache, chunk, chunk_len):
                idx = cache[0]["index"].reshape(())
                cache = [{**c, "index": idx} for c in cache]
                positions = (idx + jnp.arange(sbs))[None, :]
                _, new_cache = self.model.apply(
                    params, chunk, positions=positions, cache=cache,
                    logit_positions=jnp.zeros((1,), jnp.int32),
                    sp_prefill=sp)
                for entry in new_cache:
                    entry["index"] = idx + chunk_len
                return new_cache

            return jax.jit(sp_ext, donate_argnums=(1,))

        return self._fn_cached(("sp_prefill_ext", 1, sbs // sp, sp), build)

    def _sp_prefill_cache(self, row, upto: int, cache_len: int, sp: int,
                          stats=None):
        """Whole-prompt sequence-parallel cold prefill: embed
        ``row[:upto]`` through rounds of ``sp * prefill_chunk`` tokens —
        each round ONE sharded program — instead of the serial chunk
        chain. ceil(upto / (sp*ck)) program dispatches on the TTFT
        critical path where the chunked walk pays ceil(upto / ck).
        Caller holds the mesh context (the programs shard over its sp
        axis) and has resolved ``sp`` via :func:`resolve_sp_prefill`."""
        ck = self.prefill_chunk
        rk = max(ck * sp, sp)
        layers = self.model.cfg.layers
        first = min(rk, upto)
        sb = min(_next_bucket(max(first, sp), self.min_bucket * sp),
                 cache_len)
        pf_fn = self._sp_first_fn(sb, cache_len, sp)
        prompt_op, _ = self._pad_rows([row[:first]], [first], 1, sb)
        cache = pf_fn(self.params, prompt_op, jnp.int32(first))
        if stats is not None:
            stats.record_round(-(-first // max(ck, 1)), sp,
                               ring_hops=layers * sp)
        pos = first
        if pos < upto:
            ext = self._sp_ext_fn(rk, sp)
            while pos < upto:
                n = min(rk, upto - pos)
                chunk_op, _ = self._pad_rows([row[pos:pos + n]], [n], 1, rk)
                cache = ext(self.params, cache, chunk_op, jnp.int32(n))
                if stats is not None:
                    stats.record_round(-(-n // max(ck, 1)), sp)
                pos += n
        return cache

    def _chunked_prefill_cache(self, row, upto: int, cache_len: int,
                               sp: int = 0, stats=None):
        """Embed ``row[:upto]`` into a fresh ``cache_len`` KV cache
        through the fixed-width chunk programs (first + ext): bounded
        attention memory (O(ck x s), not O(s^2)) and O(1) compiled
        programs in prompt length. Requires ``upto > prefill_chunk``;
        the final chunk may be ragged (its padding stays unreachable
        behind the cache index). The ONE chunk-walk shared by the
        prefix cache and the continuous engine's chunked joiner
        prefill — the donation-sensitive ext loop must not fork.
        Caller holds the mesh context.

        ``sp >= 2`` (resolved via :func:`resolve_sp_prefill`) takes the
        whole-prompt sequence-parallel walk instead: same cache result
        (token-for-token), 1/sp the serial program chain."""
        if sp >= 2:
            return self._sp_prefill_cache(row, upto, cache_len, sp,
                                          stats=stats)
        ck = self.prefill_chunk
        pf_fn = self._prefix_first_fn(ck, cache_len)
        prompt_op, _ = self._pad_rows([row[:ck]], [ck], 1, ck)
        cache = pf_fn(self.params, prompt_op, jnp.int32(ck))
        if stats is not None:
            stats.record_round(1, 1)
        ext = self._prefix_ext_fn(ck)
        pos = ck
        while pos < upto:
            n = min(ck, upto - pos)
            chunk_op, _ = self._pad_rows([row[pos:pos + n]], [n], 1, ck)
            cache = ext(self.params, cache, chunk_op, jnp.int32(n))
            if stats is not None:
                stats.record_round(1, 1)
            pos += n
        return cache

    def _prefill_prefix(self, key: str, rows, lengths) -> str:
        cfg = self.model.cfg
        s = lengths[0]
        cache_len = cfg.max_len
        ck = self.prefill_chunk
        with self._mesh_ctx():
            if ck and s > ck:
                cache = self._chunked_prefill_cache(rows[0], s, cache_len)
            else:
                sb = min(_next_bucket(s, self.min_bucket), cfg.max_len)
                pf_fn = self._prefix_first_fn(sb, cache_len)
                prompt_op, _ = self._pad_rows(rows, lengths, 1, sb)
                cache = pf_fn(self.params, prompt_op, jnp.int32(s))
        self.register_prefix(key, cache, s)
        return key

    def _prefix_entry(self, prefix_tokens):
        """(cache, prefix_len) for ``prefix_tokens``, prefilling if absent.
        (Re)ensure + fetch atomically: a concurrent burst of distinct
        prefixes may evict this one between ensure and lookup — retry,
        don't 500."""
        entry = None
        for _ in range(3):
            key = self.cache_prefix(prefix_tokens)  # idempotent fast path
            with self._prefix_lock:
                entry = self._prefixes.get(key)
                if entry is not None:
                    self._prefixes.move_to_end(key)
                    break
        if entry is None:
            raise RuntimeError(
                "prefix cache thrashing: entry evicted immediately after "
                "insert 3x; raise prefix_cache_max")
        return entry

    def _generate_with_prefix(self, prefix_tokens, rows, lengths,
                              max_new_tokens, temperature, top_k, top_p,
                              seed, eos_id, return_logprobs: bool = False):
        """Continue-prefill + decode from a cached prefix KV (batch 1).
        With the float cache, output is exactly `generate(prefix +
        suffix)` — the suffix chunk attends the cached prefix through the
        same masked-attention core, so masked-out padding contributes
        exact zeros either way. Under ``kv_quant`` the prefix KV is read
        back quantized, so parity is to quantization tolerance."""
        import numpy as np

        cfg = self.model.cfg
        if len(rows) != 1:
            raise ValueError("prefix= requires a single prompt row")
        cache, plen = self._prefix_entry(prefix_tokens)
        s = lengths[0]
        self._validate(plen + s, max_new_tokens)
        steps = min(_next_bucket(max_new_tokens, self.min_bucket),
                    self.decode_cap, cfg.max_len - plen - s)
        sbs = min(_next_bucket(s, self.min_bucket),
                  cfg.max_len - plen - steps)
        cache_len = cache_width(cache)

        def build():
            def generate_prefix(params, cache, suffix, suffix_len,
                                temperature, top_k, top_p, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                carry = _continue_prefill(self.model, params, cache, suffix,
                                          suffix_len, select, rng, eos_id,
                                          sbs)
                return _scan_decode(self.model, params, select, *carry,
                                    eos_id, steps)

            return jax.jit(generate_prefix)

        cont_fn = self._fn_cached(("continue", sbs, steps, cache_len), build)
        suffix_op, _ = self._pad_rows(rows, lengths, 1, sbs)
        args = (self.params, cache, suffix_op, jnp.int32(s),
                *self._knob_operands(temperature, top_k, top_p, seed, eos_id))
        with self._mesh_ctx():
            toks, lps = cont_fn(*args)
        toks = np.asarray(jax.device_get(toks))[:, :max_new_tokens]
        if return_logprobs:
            return toks, np.asarray(jax.device_get(lps))[:, :max_new_tokens]
        return toks

    def _stream_fns(self, b: int, sb: int, cache_len: int, segment: int,
                    sp_prefill: int = 0):
        """Compiled (prefill, segment) pair for streaming. The prefill
        program returns the decode carry; each segment program advances it
        ``segment`` tokens and returns (tokens, carry). Cached like the
        fused programs, so streaming adds at most two programs per
        bucket. ``sp_prefill >= 2`` keys a variant whose prefill member
        ring-shards the prompt's attention over the sp axis (the
        continuous engine's sharded GROUP prefill); the segment member
        is byte-identical to the serial pair's."""
        def build():
            def prefill(params, prompt, length, temperature, top_k, top_p,
                        rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                return _serve_prefill(self.model, params, prompt, length,
                                      select, rng, eos_id,
                                      cache_len=cache_len,
                                      sp_prefill=sp_prefill)

            def seg(params, temperature, top_k, top_p, first, lp, cache,
                    pos, done, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                return _segment_decode(self.model, params, select, first, lp,
                                       cache, pos, done, rng, eos_id,
                                       segment, cache_len)

            return (jax.jit(prefill), jax.jit(seg))

        key = (("stream", b, sb, cache_len, segment) if not sp_prefill
               else ("stream", b, sb, cache_len, segment, sp_prefill))
        return self._fn_cached(key, build)

    def _windowed_seg_fn(self, b: int, cache_len: int, window: int,
                         segment: int):
        """Window-bucketed segment decode for the continuous engine: the
        program slices the first ``window`` positions of the B-slot
        cache, runs the segment scan over that NARROW cache — decode
        attention reads ``window`` positions per step instead of
        ``cache_len`` — and writes the advanced window back into the
        full carry (:func:`_segment_decode`; where the segment keeps a
        tail the slice is read-only and the tail merges into the full
        carry). The decode-side twin of prefill's pow-2 bucketing:
        XLA KV reads scale with the live batch's actual context, no
        kernel required. Exactness: the engine only dispatches here when
        every active row's positions stay below ``window`` for the whole
        segment, and positions past a row's index are masked to exact
        zeros either way, so tokens are bitwise the full-window
        program's (asserted in tests). Keyed ("seg_w", ...) in the LRU
        program cache and NAMED for the bundle's AOT store: which window
        buckets run is the traffic's choice, so a bucket is compiled and
        snapshotted the first time a writable bundle meets it and loaded
        from there at its first use in every later boot (a cache HIT of
        one is 10-13 s at 7B widths, a load 2-7 s: PERF.md section 5;
        an artifact a bucket, a mix asks for a handful)."""
        def build():
            def seg(params, temperature, top_k, top_p, first, lp, cache,
                    pos, done, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                return _segment_decode(self.model, params, select, first, lp,
                                       cache, pos, done, rng, eos_id,
                                       segment, window)

            return jax.jit(seg)

        return self._fn_cached(("seg_w", b, cache_len, window, segment),
                               build)

    def _spec_seg_fn(self, b: int, cache_len: int, window: int, kb: int):
        """B-slot SPECULATIVE verify segment for the continuous engine:
        one multi-token forward scores each row's pending token plus its
        kb-1 host-drafted proposals through the existing window-bucketed
        segment math (slice the first ``window`` positions, run, merge
        back — :meth:`_windowed_seg_fn`'s shape), then
        :func:`_spec_chain_verify` accepts per row the longest draft
        prefix matching the row's deterministic chain and rolls the
        PRNG walk back past the rejected tail. The carry advances by a
        VARIABLE per-row ``count`` (1..kb): the cache index moves to
        ``pos + count``, so rejected-tail K/V writes sit beyond the
        index in already-garbage positions — unreachable behind the
        validity mask, overwritten by the next chunk before any query
        could expose them (the same rollback-by-index trick the solo
        verify fns use, batched). Same 6-leaf carry as the plain
        segment programs, so the pack/joiner machinery is untouched.
        Keyed ("spec_seg", ...) in the LRU cache and NOT named for the
        AOT store (``_aot_name``): no measured cell runs speculation, so
        the variants (window x kb) enter through jax at every boot until
        one does."""
        def build():
            def seg(params, temperature, top_k, top_p, draft, tok, lp,
                    cache, pos, done, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                win = cache
                if window < cache_len:
                    win = [{name: (val if name == "index"
                                   else jax.lax.slice_in_dim(
                                       val, 0, window, axis=1))
                            for name, val in entry.items()}
                           for entry in cache]
                # embed a CLAMPED copy of the drafts (an out-of-vocab
                # proposal would gather a NaN fill row, and 0 * NaN
                # through the masked attention poisons every row's
                # output) while verifying against the RAW values — a
                # clamped alias can therefore never be falsely accepted
                chunk = jnp.concatenate(
                    [tok[:, None],
                     jnp.clip(draft, 0, self.model.cfg.vocab_size - 1)],
                    axis=1)
                positions = pos[:, None] + jnp.arange(kb)[None, :]
                logits, new_cache = self.model.apply(
                    params, chunk, positions=positions, cache=win)
                lg = logits.astype(jnp.float32)        # [b, kb, v]
                lps_block, count, tok2, lp2, keys2 = _spec_chain_verify(
                    select, lg, draft, lp, rng)
                pos2 = pos + count
                for entry in new_cache:
                    entry["index"] = pos2
                merged = new_cache
                if window < cache_len:
                    merged = [
                        {name: (val if name == "index"
                                else jax.lax.dynamic_update_slice_in_dim(
                                    cache[i][name], val, 0, axis=1))
                         for name, val in entry.items()}
                        for i, entry in enumerate(new_cache)]
                return ((chunk, lps_block, count, tok2),
                        (tok2, lp2, merged, pos2, done, keys2))

            return jax.jit(seg)

        return self._fn_cached(("spec_seg", b, cache_len, window, kb),
                               build)

    def _mspec_seg_fn(self, b: int, cache_len: int, window: int, kb: int,
                      exit_layer: int):
        """MODEL-DRAFT twin of :meth:`_spec_seg_fn`: before the verify
        chunk, :func:`_shallow_draft` runs ``kb - 1`` shallow-exit
        (``exit_layer`` layers + tied lm_head) greedy steps in-program
        off the row's true carry token, then each row takes its model
        chain or the host-provided draft operand per the ``use_model``
        mask — the per-row provider seam's device half. Host-masked
        positions arrive as RAW ``-1`` in ``draft_host`` with
        ``use_model 0``, so a row drafting fewer than ``kb - 1`` tokens
        (per-row adaptive k) can never have its padding accepted: the
        chain compares raw values and every real chain token is in
        ``[0, vocab)``. Verification is untouched — the same
        :func:`_spec_chain_verify` walk decides acceptance, so emitted
        tokens stay bitwise the non-speculative engine's whatever the
        draft source proposes."""
        def build():
            def seg(params, temperature, top_k, top_p, draft_host,
                    use_model, tok, lp, cache, pos, done, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                win = cache
                if window < cache_len:
                    win = [{name: (val if name == "index"
                                   else jax.lax.slice_in_dim(
                                       val, 0, window, axis=1))
                            for name, val in entry.items()}
                           for entry in cache]
                d_model = _shallow_draft(self.model, params, tok, win,
                                         pos, kb, exit_layer)
                draft = jnp.where(use_model > 0, d_model, draft_host)
                # clamp-for-embedding / compare-raw, as in _spec_seg_fn
                chunk = jnp.concatenate(
                    [tok[:, None],
                     jnp.clip(draft, 0, self.model.cfg.vocab_size - 1)],
                    axis=1)
                positions = pos[:, None] + jnp.arange(kb)[None, :]
                logits, new_cache = self.model.apply(
                    params, chunk, positions=positions, cache=win)
                lg = logits.astype(jnp.float32)        # [b, kb, v]
                lps_block, count, tok2, lp2, keys2 = _spec_chain_verify(
                    select, lg, draft, lp, rng)
                pos2 = pos + count
                for entry in new_cache:
                    entry["index"] = pos2
                merged = new_cache
                if window < cache_len:
                    merged = [
                        {name: (val if name == "index"
                                else jax.lax.dynamic_update_slice_in_dim(
                                    cache[i][name], val, 0, axis=1))
                         for name, val in entry.items()}
                        for i, entry in enumerate(new_cache)]
                return ((chunk, lps_block, count, tok2),
                        (tok2, lp2, merged, pos2, done, keys2))

            return jax.jit(seg)

        return self._fn_cached(
            ("mspec_seg", b, cache_len, window, kb, exit_layer), build)

    # -- paged KV programs (runtime/pagepool.py arena) ------------------------
    #
    # The paged engine's device programs. Each one follows the same
    # shape: gather the rows' pages into the contiguous cache the
    # EXISTING decode/continuation math expects, run that math
    # unchanged, scatter the written pages back — so paged tokens are
    # bitwise the dense engine's by construction (the gathered values
    # ARE the page values, and masked positions contribute exact zeros
    # either way). Keyed in the LRU program cache; deliberately not
    # AOT-able (like the window-bucket variants, they are load-dependent
    # and compile in seconds at engine shapes).

    def _paged_seg_fn(self, b: int, n_pages: int, page: int, window: int,
                      segment: int):
        """Paged segment decode: gather each row's first ``window``
        positions from its block table, run the shared segment scan over
        that contiguous window (the same ``_scan_decode`` every other
        decode path uses), scatter the advanced window back into the
        arena. Composes with window bucketing exactly like
        :meth:`_windowed_seg_fn` — the gather width is the pow-2 window
        of the live batch's max context."""
        def build():
            def seg(params, temperature, top_k, top_p, first, lp, arena,
                    tables, pos, done, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                cache = _gather_page_cache(arena, tables, window, page, pos)
                (toks, lps), carry = _scan_decode(
                    self.model, params, select, first, lp, cache, pos,
                    done, rng, eos_id, segment, return_carry=True)
                f2, lp2, wcache, pos2, done2, rng2 = carry
                new_arena = _scatter_page_cache(arena, tables, wcache, page)
                return (toks, lps), (f2, lp2, new_arena, pos2, done2, rng2)

            return jax.jit(seg)

        return self._fn_cached(("pseg", b, n_pages, page, window, segment),
                               build)

    def _spec_pseg_fn(self, b: int, n_pages: int, page: int, window: int,
                      kb: int):
        """Paged twin of :meth:`_spec_seg_fn`: gather each row's first
        ``window`` positions through its block table, run the same
        verify-chunk math, scatter the written window back. The
        rollback story composes with paging for free: rejected-tail
        writes inside the window land in the row's OWN pages at
        positions beyond its index (overwritten by the next chunk), and
        writes past the row's allocated pages scatter through
        null-padded table entries into the reserved null page — page 0
        absorbs them exactly as it absorbs the dense engine's
        over-decode, so no transient page charge is needed for the
        worst-case k-token advance."""
        def build():
            def seg(params, temperature, top_k, top_p, draft, tok, lp,
                    arena, tables, pos, done, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                cache = _gather_page_cache(arena, tables, window, page,
                                           pos)
                # clamp-for-embedding / compare-raw, as in _spec_seg_fn
                chunk = jnp.concatenate(
                    [tok[:, None],
                     jnp.clip(draft, 0, self.model.cfg.vocab_size - 1)],
                    axis=1)
                positions = pos[:, None] + jnp.arange(kb)[None, :]
                logits, new_cache = self.model.apply(
                    params, chunk, positions=positions, cache=cache)
                lg = logits.astype(jnp.float32)        # [b, kb, v]
                lps_block, count, tok2, lp2, keys2 = _spec_chain_verify(
                    select, lg, draft, lp, rng)
                pos2 = pos + count
                new_arena = _scatter_page_cache(arena, tables, new_cache,
                                                page)
                return ((chunk, lps_block, count, tok2),
                        (tok2, lp2, new_arena, pos2, done, keys2))

            return jax.jit(seg)

        return self._fn_cached(
            ("spec_pseg", b, n_pages, page, window, kb), build)

    def _mspec_pseg_fn(self, b: int, n_pages: int, page: int, window: int,
                       kb: int, exit_layer: int):
        """Paged twin of :meth:`_mspec_seg_fn`: gather the rows' pages
        into the contiguous window, run the in-program shallow-exit
        draft chain over a SCRATCH alias of that gathered window's early
        layers (writes land in throwaway gathered arrays — the arena is
        only ever written by the verify chunk's scatter), then the same
        per-row provider select + chunk verify, and scatter back. The
        null-page-0 over-allocation story is unchanged: only the verify
        chunk's ``new_cache`` reaches :func:`_scatter_page_cache`."""
        def build():
            def seg(params, temperature, top_k, top_p, draft_host,
                    use_model, tok, lp, arena, tables, pos, done, rng,
                    eos_id):
                select = _serve_select(temperature, top_k, top_p)
                cache = _gather_page_cache(arena, tables, window, page,
                                           pos)
                d_model = _shallow_draft(self.model, params, tok, cache,
                                         pos, kb, exit_layer)
                draft = jnp.where(use_model > 0, d_model, draft_host)
                # clamp-for-embedding / compare-raw, as in _spec_seg_fn
                chunk = jnp.concatenate(
                    [tok[:, None],
                     jnp.clip(draft, 0, self.model.cfg.vocab_size - 1)],
                    axis=1)
                positions = pos[:, None] + jnp.arange(kb)[None, :]
                logits, new_cache = self.model.apply(
                    params, chunk, positions=positions, cache=cache)
                lg = logits.astype(jnp.float32)        # [b, kb, v]
                lps_block, count, tok2, lp2, keys2 = _spec_chain_verify(
                    select, lg, draft, lp, rng)
                pos2 = pos + count
                new_arena = _scatter_page_cache(arena, tables, new_cache,
                                                page)
                return ((chunk, lps_block, count, tok2),
                        (tok2, lp2, new_arena, pos2, done, keys2))

            return jax.jit(seg)

        return self._fn_cached(
            ("mspec_pseg", b, n_pages, page, window, kb, exit_layer),
            build)

    def _paged_pack_fn(self, gb: int, n_pages: int, page: int, width: int):
        """Pack row ``src`` of a ``gb``-row contiguous prefill carry into
        batch slot ``slot`` — the scalar leaves via the same
        dynamic-update-slice the dense pack uses, the cache row
        scattered into the slot's block-table pages. Table entries past
        the row's allocation are the null page (the prefill cache is
        zeros there, so the null page just absorbs zeros)."""
        def build():
            def pack(tok, lp, pos, done, keys, group_carry, src, slot,
                     arena, table):
                def upd(b_leaf, g_leaf):
                    row = jax.lax.dynamic_slice_in_dim(g_leaf, src, 1, 0)
                    return jax.lax.dynamic_update_slice_in_dim(
                        b_leaf, row.astype(b_leaf.dtype), slot, 0)

                gtok, glp, gcache, gpos, gdone, gkeys = group_carry
                new5 = (upd(tok, gtok), upd(lp, glp), upd(pos, gpos),
                        upd(done, gdone), upd(keys, gkeys))
                nb = width // page
                new_arena = []
                for aentry, centry in zip(arena, gcache):
                    e = {}
                    for name, val in aentry.items():
                        row = jax.lax.dynamic_slice_in_dim(
                            centry[name], src, 1, 0)[0]  # [width, ...]
                        pages = row.reshape(
                            nb, page, *row.shape[1:]).astype(val.dtype)
                        e[name] = val.at[table].set(pages)
                    new_arena.append(e)
                return new5, new_arena

            return jax.jit(pack)

        return self._fn_cached(("ppack", gb, n_pages, page, width), build)

    def _paged_continue_fn(self, sbs: int, n_pages: int, page: int,
                           window: int):
        """Continue-prefill from SHARED prefix pages: gather the row's
        table (matched prefix pages + freshly allocated suffix pages)
        into a contiguous window, run the one
        :func:`_continue_prefill` every prefix path shares, scatter the
        written suffix back. The prefix pages are read in place and
        written back bitwise-unchanged — this is the zero-copy hit: no
        ``concat_cache_blocks`` assembly, no registered full-window
        duplicate, no peak-HBM spike; the hit's cost is a refcount
        bump plus the suffix prefill the request owes anyway."""
        def build():
            def paged_continue(params, arena, table, plen, suffix, suffix_len,
                               temperature, top_k, top_p, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                cache = _gather_page_cache(arena, table, window, page, plen)
                first, lp0, new_cache, start, done0, keys = \
                    _continue_prefill(self.model, params, cache, suffix,
                                      suffix_len, select, rng, eos_id, sbs)
                new_arena = _scatter_page_cache(arena, table, new_cache,
                                                page)
                return first, lp0, new_arena, start, done0, keys

            return jax.jit(paged_continue)

        return self._fn_cached(("pcont", sbs, n_pages, page, window), build)

    def _lpaged_seg_fn(self, b: int, n_pages: int, page: int, window: int,
                       segment: int):
        """LOGICAL-window twin of :meth:`_paged_seg_fn` (the long-context
        tier, runtime/longctx.py): the block table maps a SLIDING view of
        a context far larger than the compiled ``window`` — slot 0 of the
        gathered cache is logical token ``base`` — so the carry's ``pos``
        is the LOCAL frame (cache writes, validity mask) while RoPE sees
        ``pos + base``, the token's logical position. With ``base = 0``
        this computes exactly what :meth:`_paged_seg_fn` computes (int32
        ``+ 0`` is exact); the host slides ``base`` by whole pages
        between segments, spilling evicted pages to the offload arena."""
        def build():
            def seg(params, temperature, top_k, top_p, first, lp, arena,
                    tables, local, base, done, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                cache = _gather_page_cache(arena, tables, window, page,
                                           local)
                (toks, lps), carry = _scan_decode(
                    self.model, params, select, first, lp, cache, local,
                    done, rng, eos_id, segment, return_carry=True,
                    pos_offset=base)
                f2, lp2, wcache, local2, done2, rng2 = carry
                new_arena = _scatter_page_cache(arena, tables, wcache,
                                                page)
                return (toks, lps), (f2, lp2, new_arena, local2, done2,
                                     rng2)

            return jax.jit(seg)

        return self._fn_cached(("lpseg", b, n_pages, page, window, segment),
                               build)

    def _lpaged_continue_fn(self, sbs: int, n_pages: int, page: int,
                            window: int):
        """LOGICAL-window twin of :meth:`_paged_continue_fn`: continue a
        windowed prefill from the view's filled head — the gathered
        window holds logical tokens ``[base, base + local)``, the suffix
        chunk lands at local positions ``[local, local + suffix_len)``
        with RoPE at their LOGICAL positions. Chained over chunks (the
        host sliding ``base`` between them) this is the long-context
        prefill schedule; with ``base = 0`` and one chunk it computes
        exactly the paged continuation."""
        def build():
            def lpaged_continue(params, arena, table, local, base, suffix,
                                suffix_len, temperature, top_k, top_p, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                cache = _gather_page_cache(arena, table, window, page,
                                           local)
                first, lp0, new_cache, start, done0, keys = \
                    _continue_prefill(self.model, params, cache, suffix,
                                      suffix_len, select, rng, eos_id,
                                      sbs, pos_offset=base)
                new_arena = _scatter_page_cache(arena, table, new_cache,
                                                page)
                return first, lp0, new_arena, start, done0, keys

            return jax.jit(lpaged_continue)

        return self._fn_cached(("lpcont", sbs, n_pages, page, window),
                               build)

    def _lsp_round_fn(self, n_chunks: int, n_pages: int, page: int,
                      window: int, sp: int):
        """Paged twin of the whole-prompt sp-prefill family for the
        long-context tier: ONE sharded program runs ``n_chunks`` of the
        serial window/2 slide schedule as a single ROUND. The gathered
        UNION view holds the prior half-window (``prior_len`` tokens —
        0 on round 0) followed by the round's ``n_chunks * window/2``
        tokens; ``band = window/2`` restricts every query to exactly the
        keys its serial chunk would have seen resident, RoPE sees
        logical positions via ``base``, and the written KV scatters
        straight back into the arena pages (prior pages come back
        bitwise-unchanged, the validated ``_page_write_fn``-shaped
        per-page layout). The S/(window/2) serial chain collapses to
        ceil(S / (sp * window/2)) rounds."""
        w2 = window // 2
        rbs = n_chunks * w2       # round token width
        uw = (n_chunks + 1) * w2  # union view: prior half-window + round

        def build():
            def lsp_round(params, arena, table, prior_len, base, chunk,
                          round_len, temperature, top_k, top_p, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                cache = _gather_page_cache(arena, table, uw, page,
                                           prior_len)
                first, lp0, new_cache, start, done0, keys = \
                    _continue_prefill(self.model, params, cache, chunk,
                                      round_len, select, rng, eos_id,
                                      rbs, pos_offset=base - prior_len,
                                      sp_prefill=sp, band=w2)
                new_arena = _scatter_page_cache(arena, table, new_cache,
                                                page)
                return first, lp0, new_arena, start, done0, keys

            return jax.jit(lsp_round)

        return self._fn_cached(
            ("sp_pprefill", n_chunks, n_pages, page, window, sp), build)

    def _paged_gather_fn(self, n_pages: int, page: int, window: int):
        """Read-only page gather -> contiguous single-row cache (index
        attached): the prefix store's extend path continues a cold walk
        from cached pages without any host-visible assembly."""
        def build():
            def page_gather(arena, table, index):
                return _gather_page_cache(arena, table, window, page,
                                          index)

            return jax.jit(page_gather)

        return self._fn_cached(("pgather", n_pages, page, window), build)

    def _page_write_fn(self, n_pages: int, page: int):
        """Write one block's per-layer KV slices (as
        :func:`slice_cache_blocks` returns) into arena page ``pid`` —
        the prefix store's insertion primitive (one program total; the
        page id is a traced operand)."""
        def build():
            def page_write(arena, pid, block_kv):
                new = []
                for aentry, bentry in zip(arena, block_kv):
                    e = {}
                    for name, val in aentry.items():
                        blk = bentry[name].reshape(
                            1, page, *val.shape[2:]).astype(val.dtype)
                        e[name] = jax.lax.dynamic_update_slice(
                            val, blk, (pid,) + (0,) * (val.ndim - 1))
                    new.append(e)
                return new

            return jax.jit(page_write)

        return self._fn_cached(("pwrite", n_pages, page), build)

    def _stream_prefix_fn(self, sbs: int, cache_len: int | None = None):
        """Continue-prefill program for streaming-from-a-cached-prefix:
        same continuation math as the fused prefix path, but returns the
        decode CARRY so segment programs take over (the combination the
        VERDICT r3 called out: TTFT and KV reuse were mutually
        exclusive). By default the carry's cache is the prefix cache's
        full-window size, pairing with segment programs keyed at
        cache_len=max_len; a non-None ``cache_len`` keys a separate
        program for continuation over a smaller cache (the continuous
        engine's chunked joiner prefill) — sharing the default key
        would collide with its shape-strict AOT executable."""
        def build():
            def stream_prefix(params, cache, suffix, suffix_len,
                              temperature, top_k, top_p, rng, eos_id):
                select = _serve_select(temperature, top_k, top_p)
                return _continue_prefill(self.model, params, cache, suffix,
                                         suffix_len, select, rng, eos_id,
                                         sbs)

            return jax.jit(stream_prefix)

        key = (("stream_prefix", sbs) if cache_len is None
               else ("stream_prefix", sbs, cache_len))
        return self._fn_cached(key, build)

    def _generate_stream_with_prefix(self, prefix_tokens, rows, lengths,
                                     max_new_tokens, temperature, top_k,
                                     top_p, seed, eos_id, segment,
                                     return_logprobs):
        """Streaming decode from a cached prefix KV (batch 1): one
        continue-prefill of the suffix, then the same segment walk as
        :meth:`generate_stream`. Token/RNG parity with the fused
        ``generate(prefix=...)`` path is exact — the continuation and the
        per-step RNG walk are identical, segments only change where the
        host observes them."""
        import numpy as np

        cfg = self.model.cfg
        if len(rows) != 1:
            raise ValueError("prefix= streaming requires a single row")
        cache, plen = self._prefix_entry(prefix_tokens)
        s = lengths[0]
        self._validate(plen + s, max_new_tokens)
        sbs = min(_next_bucket(s, self.min_bucket), cfg.max_len - plen)
        cache_len = cache_width(cache)
        cont = self._stream_prefix_fn(sbs)
        _, seg = self._stream_fns(1, sbs, cache_len, segment)
        suffix_op, _ = self._pad_rows(rows, lengths, 1, sbs)
        *knobs, key, eos = self._knob_operands(temperature, top_k, top_p,
                                               seed, eos_id)
        with self._mesh_ctx():
            carry = cont(self.params, cache, suffix_op, jnp.int32(s),
                         *knobs, key, eos)
            emitted = 0
            while emitted < max_new_tokens:
                (toks, lps, *_), carry = seg(self.params, *knobs, *carry,
                                             eos)
                chunk = np.asarray(jax.device_get(toks))
                take = min(chunk.shape[1], max_new_tokens - emitted)
                emitted += take
                if return_logprobs:
                    lp_chunk = np.asarray(jax.device_get(lps))
                    yield chunk[:, :take], lp_chunk[:, :take]
                else:
                    yield chunk[:, :take]
                if eos_id is not None:
                    done = np.asarray(jax.device_get(carry[4]))
                    if bool(done.all()):
                        return

    def generate_stream(self, prompt_tokens, *, max_new_tokens: int,
                        temperature: float = 0.0, top_k: int | None = None,
                        top_p: float | None = None, seed: int = 0,
                        eos_id: int | None = None, segment: int = 16,
                        prefix=None, return_logprobs: bool = False):
        """Streaming :meth:`generate`: yields ``[b, k]`` numpy chunks
        (k <= segment) as they decode — ``(tokens, logprobs)`` pairs when
        ``return_logprobs`` — stopping early once every row has latched
        eos. Concatenated chunks are EXACTLY the fused ``generate``
        output prefix — the segment boundaries don't change the RNG
        walk, so a seeded sampled stream matches its non-streamed twin
        token for token. Time-to-first-token is one prefill plus one
        segment instead of the whole decode. ``prefix=`` streams from a
        cached prefix KV (single row), combining TTFT with KV reuse."""
        import numpy as np

        cfg = self.model.cfg
        rows, lengths = self._normalize_prompts(prompt_tokens)
        b, s = len(rows), max(lengths)
        if max_new_tokens == 0:
            # nothing to emit: skip the device work (the prefix path's
            # continue-prefill would otherwise compile + run for nothing)
            self._validate(s, max_new_tokens)
            return
        if prefix is not None:
            segment = max(1, min(int(segment), max(1, max_new_tokens)))
            yield from self._generate_stream_with_prefix(
                prefix, rows, lengths, max_new_tokens, temperature, top_k,
                top_p, seed, eos_id, segment, return_logprobs)
            return
        self._validate(s, max_new_tokens)
        segment = max(1, min(int(segment), max(1, max_new_tokens)))
        # same bucketing discipline as generate(): pow-2 prompt bucket
        # (shrinking toward the exact prompt near max_len), batch
        # bucketed, and the SEGMENT COUNT pow-2 bucketed too — cache_len
        # is part of the compiled-program key, so without it every
        # distinct ceil(max_new/segment) would compile a fresh pair.
        # Only ceil(max_new/segment) segments ever run; the bucketed
        # extras just size the cache. The last segment may run past
        # max_new_tokens; those tail tokens are discarded, and any of
        # their cache writes that would land past max_len are
        # scatter-dropped — every KEPT token attends an in-bounds cache
        # (length_r + max_new <= max_len is validated above).
        n_needed = -(-max_new_tokens // segment)
        n_segs = _next_bucket(n_needed, 1)
        if s + n_segs * segment > cfg.max_len:
            n_segs = n_needed  # shrink toward exact near the boundary
        sb = max(s, min(cfg.prompt_bucket(s, self.min_bucket),
                        cfg.max_len - n_segs * segment))
        bb = _next_bucket(b, 1)
        cache_len = min(sb + n_segs * segment, cfg.max_len)
        prefill, seg = self._stream_fns(bb, sb, cache_len, segment)
        prompt_op, length_op = self._pad_rows(rows, lengths, bb, sb)
        *knobs, key, eos = self._knob_operands(temperature, top_k, top_p,
                                               seed, eos_id, b=bb)
        with self._mesh_ctx():
            carry = prefill(self.params, prompt_op, length_op,
                            *knobs, key, eos)
            emitted = 0
            while emitted < max_new_tokens:
                (toks, lps, *_), carry = seg(self.params, *knobs, *carry,
                                             eos)
                chunk = np.asarray(jax.device_get(toks))[:b]
                take = min(chunk.shape[1], max_new_tokens - emitted)
                emitted += take
                if return_logprobs:
                    lp_chunk = np.asarray(jax.device_get(lps))[:b]
                    yield chunk[:, :take], lp_chunk[:, :take]
                else:
                    yield chunk[:, :take]
                # all real rows latched eos -> nothing more can be
                # emitted. Fetch the done flags only when eos is active:
                # each fetch is a host round trip per segment, pure waste
                # without an eos to latch.
                if eos_id is not None:
                    done = np.asarray(jax.device_get(carry[4]))[:b]
                    if bool(done.all()):
                        return

    # -- speculative decoding ------------------------------------------------

    def _spec_verify_fn(self, kb: int, cache_len: int):
        """Compiled verify step for speculative decoding: run the pending
        token + kb-1 draft tokens as ONE multi-token chunk (the scalar-
        index continuation branch of the cache), greedily re-derive the
        true successor at every position, and accept the longest draft
        prefix that matches. Emits 1..kb tokens per WEIGHT READ — decode
        is weight-bytes-bound, so accepted drafts are nearly free, which
        is the only way past the 1-token-per-read decode roofline.
        Rollback after partial acceptance is just the cache index: the
        attention validity mask never exposes entries past it, so the
        stale K/V written for rejected drafts is unreachable."""
        def build():
            def spec_verify(params, draft, tok, cache):
                idx = cache[0]["index"].reshape(())  # scalar-index branch
                cache = [{**c, "index": idx} for c in cache]
                chunk = jnp.concatenate(
                    [tok.reshape(1, 1), draft[:, :kb - 1]], axis=1)
                positions = (idx + jnp.arange(kb))[None, :]
                logits, new_cache = self.model.apply(
                    params, chunk, positions=positions, cache=cache)
                lg = logits[0].astype(jnp.float32)          # [kb, v]
                g = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # true succ.
                ok = (g[:kb - 1] == draft[0, :kb - 1]).astype(jnp.int32)
                m = jnp.sum(jnp.cumprod(ok))                # 0..kb-1
                count = m + 1          # emitted: [tok, d_0..d_{m-1}]
                # logprob of the GREEDY token at each position: equals
                # the accepted draft's logprob where drafts match, and is
                # the right value for the new pending token where the
                # draft was rejected
                logz = jax.nn.logsumexp(lg, axis=-1)
                lp_g = jnp.take_along_axis(
                    lg, g[:, None], axis=1)[:, 0] - logz
                new_tok = jax.lax.dynamic_slice(g, (m,), (1,))
                new_idx = idx + count
                for entry in new_cache:
                    entry["index"] = new_idx
                return chunk[0], lp_g, count, new_tok, new_cache

            return jax.jit(spec_verify)

        return self._fn_cached(("spec", kb, cache_len), build)

    def _spec_steps(self, rows, max_new_tokens: int, kb: int, eos_id,
                    ngram_max: int, stats_out: dict, prefix=None,
                    prefix_entry=None, temperature: float = 0.0,
                    top_k=None, top_p=None, seed: int = 0):
        """The speculative verify loop as a per-step generator: yields
        ``(tokens, logprobs)`` LISTS per verify step (1..kb tokens each —
        the accepted draft prefix plus the corrected token), filling
        ``stats_out`` with the acceptance counters as it goes. Both the
        fused :meth:`generate_speculative` and the streaming
        :meth:`generate_speculative_stream` consume this one loop, so
        their emitted tokens agree by construction. With ``prefix`` the
        initial carry comes from the cached prefix KV's continuation
        program (only the suffix prefills; the prefix tokens still feed
        the lookup-draft context — a shared system prompt is prime
        n-gram material)."""
        require_row_a_token(
            self.model.cfg, "speculative decoding (_spec_steps)")
        cfg = self.model.cfg
        s = len(rows[0])
        cache_len = cfg.max_len
        sampled = (temperature or 0.0) > 0.0
        # the prefill/continuation selects the FIRST pending token under
        # the request's own knobs (greedy callers pass t=0 -> argmax)
        knobs = self._knob_operands(temperature, top_k, top_p, seed, None)
        with self._mesh_ctx():
            if prefix is not None:
                # the caller already fetched the entry for validation —
                # don't re-hash the (possibly long) prefix per request
                pcache, plen = (prefix_entry if prefix_entry is not None
                                else self._prefix_entry(prefix))
                sbs = min(_next_bucket(s, self.min_bucket),
                          cfg.max_len - plen)
                cont = self._stream_prefix_fn(sbs)
                suffix_op, _ = self._pad_rows(rows, [s], 1, sbs)
                tok, lp0, cache, _pos, _done, _rng = cont(
                    self.params, pcache, suffix_op, jnp.int32(s), *knobs)
                context0 = [int(t) for t in
                            jnp.asarray(prefix).reshape(-1).tolist()] \
                    + list(map(int, rows[0]))
            else:
                sb = min(_next_bucket(s, self.min_bucket), cache_len)
                # prefill keyed at the streaming default segment: the
                # prefill program does not depend on the segment size,
                # so every k (and the streaming path itself) shares ONE
                # compiled prefill per bucket instead of compiling a
                # byte-identical copy per k
                prefill, _ = self._stream_fns(1, sb, cache_len, 16)
                prompt_op, length_op = self._pad_rows(rows, [s], 1, sb)
                tok, lp0, cache, _pos, _done, _rng = prefill(
                    self.params, prompt_op, length_op, *knobs)
                context0 = list(map(int, rows[0]))
        if sampled:
            vf = self._spec_sampled_verify_fn(kb, cache_len)
            t_op = jnp.float32(temperature)  # the verify fn clamps
            k_op = jnp.int32(top_k if top_k is not None else 0)
            p_op = jnp.float32(top_p if top_p is not None else 1.0)
            # verify-step randomness: its own seed-derived stream (the
            # draw STRUCTURE differs from plain sampling, so bitwise
            # parity is impossible by construction; determinism per
            # seed is the contract)
            base_key = jax.random.fold_in(
                jax.random.PRNGKey(int(seed)), 1)
        else:
            vf = self._spec_verify_fn(kb, cache_len)
        # normalize the prefill cache's per-row (1,) index to the scalar
        # the verify fn itself writes: without this the first vf call
        # traces a second shape variant, doubling the (multi-second
        # remote) warm compile per ('spec', kb, cache_len) key (ADVICE r4)
        cache = [{**c, "index": c["index"].reshape(())} for c in cache]
        pending, pending_lp = (
            float(x) for x in jax.device_get((tok[0], lp0[0])))
        pending = int(pending)
        emitted = 0
        context = context0
        generated: list[int] = []
        steps = 0
        while emitted < max_new_tokens:
            draft, draft_hit = _lookup_draft_hit(context + [pending], kb,
                                                 ngram_max=ngram_max)
            draft_op = jnp.asarray([draft], jnp.int32)
            with self._mesh_ctx():
                if sampled:
                    step_keys = jax.random.split(
                        jax.random.fold_in(base_key, steps), kb)
                    chunk, lp_next, count, new_tok, cache = vf(
                        self.params, draft_op, tok, cache, t_op, k_op,
                        p_op, step_keys)
                else:
                    chunk, lp_next, count, new_tok, cache = vf(
                        self.params, draft_op, tok, cache)
            chunk_h, lp_h, cnt, new_h = jax.device_get(
                (chunk, lp_next, count, new_tok))
            cnt = int(cnt)
            steps += 1
            toks_step = [int(t) for t in chunk_h[:cnt]]
            lps_step = [pending_lp] + [float(x) for x in lp_h[:cnt - 1]]
            emitted += cnt
            generated.extend(toks_step)
            pending, pending_lp = int(new_h[0]), float(lp_h[cnt - 1])
            tok = new_tok
            context = context0 + generated
            stats_out.update(
                {"steps": steps, "emitted": emitted,
                 "tokens_per_step": round(emitted / max(1, steps), 2),
                 "k": kb})
            # the cumulative /metrics surface (shared with the engine's
            # spec mode): proposals = the kb-1 drafts, accepted = the
            # cnt-1 that matched, emitted = accepted + the corrected
            # token the step owes regardless
            self.spec_metrics.record_step(
                proposed=kb - 1, accepted=cnt - 1, emitted=cnt,
                hit=draft_hit)
            yield toks_step, lps_step
            if eos_id is not None and eos_id in toks_step:
                return

    def generate_speculative_stream(self, prompt_tokens, *,
                                    max_new_tokens: int, k: int = 8,
                                    eos_id: int | None = None,
                                    return_logprobs: bool = False,
                                    ngram_max: int = 3,
                                    prefix=None,
                                    temperature: float = 0.0,
                                    top_k: int | None = None,
                                    top_p: float | None = None,
                                    seed: int = 0,
                                    stats_out: dict | None = None):
        """Streaming speculative decode (VERDICT r5 weak #2 composition):
        each verify step's ACCEPTED chunk is a stream segment, so
        time-to-first-token is one prefill plus one verify step — the
        TTFT-sensitive streamed traffic is exactly where lookup
        speculation pays most. Yields ``[1, c]`` arrays (1 <= c <= k;
        ``(tokens, logprobs)`` pairs when asked). Concatenated chunks
        equal :meth:`generate_speculative`'s output up to and including
        the first eos (the fused path then pads with eos filler) and are
        truncated at ``max_new_tokens``. Pass ``stats_out={}`` to
        receive the acceptance counters (thread-safe, unlike
        ``spec_stats``)."""
        import numpy as np

        cfg = self.model.cfg
        rows, lengths = self._normalize_prompts(prompt_tokens)
        if len(rows) != 1:
            raise ValueError("speculative decoding is single-row")
        s = lengths[0]
        plen, pentry = 0, None
        if prefix is not None:
            pentry = self._prefix_entry(prefix)
            plen = pentry[1]
        self._validate(plen + s, max_new_tokens)
        kb = max(2, _next_bucket(max(2, int(k)), 2))
        stats = {} if stats_out is None else stats_out
        if max_new_tokens == 0 or \
                plen + s + max_new_tokens + kb > cfg.max_len:
            # no room for a full verify chunk near the context boundary:
            # stream plain decode instead (same fallback as the fused
            # path, segment-bounded TTFT)
            stats.update({"fallback": "plain", "steps": max_new_tokens,
                          "emitted": max_new_tokens,
                          "tokens_per_step": 1.0, "k": kb})
            self.spec_metrics.record_fallback("near_window")
            yield from self.generate_stream(
                rows[0], max_new_tokens=max_new_tokens, eos_id=eos_id,
                prefix=prefix, temperature=temperature, top_k=top_k,
                top_p=top_p, seed=seed, return_logprobs=return_logprobs)
            return
        emitted = 0
        for toks_step, lps_step in self._spec_steps(
                rows, max_new_tokens, kb, eos_id, ngram_max, stats,
                prefix=prefix, prefix_entry=pentry,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=seed):
            take = min(len(toks_step), max_new_tokens - emitted)
            if take <= 0:
                return
            chunk, lp_chunk = toks_step[:take], lps_step[:take]
            # stop at the row's eos: deliver through it, drop the rest
            if eos_id is not None and eos_id in chunk:
                cut = chunk.index(eos_id) + 1
                chunk, lp_chunk = chunk[:cut], lp_chunk[:cut]
            emitted += len(chunk)
            arr = np.asarray([chunk], np.int32)
            if return_logprobs:
                yield arr, np.asarray([lp_chunk], np.float32)
            else:
                yield arr
            if eos_id is not None and eos_id in chunk:
                return

    def _spec_sampled_verify_fn(self, kb: int, cache_len: int):
        """Compiled verify step for SAMPLED speculative decoding: one
        multi-token forward over the pending token + kb-1 drafts, then
        the delta-proposal rejection core (:func:`_spec_accept_resample`)
        under per-request runtime knobs. Same cache-rollback-by-index
        trick as the greedy verify; the emitted sequence is exactly
        target-chain distributed (not bitwise the non-speculative
        sampled stream — the draw structure differs — but
        seed-deterministic within the speculative path)."""
        def build():
            def spec_sampled_verify(params, draft, tok, cache, temperature,
                                    top_k, top_p, keys):
                idx = cache[0]["index"].reshape(())
                cache = [{**c, "index": idx} for c in cache]
                chunk = jnp.concatenate(
                    [tok.reshape(1, 1), draft[:, :kb - 1]], axis=1)
                positions = (idx + jnp.arange(kb))[None, :]
                logits, new_cache = self.model.apply(
                    params, chunk, positions=positions, cache=cache)
                lg = logits[0].astype(jnp.float32)          # [kb, v]
                t = jnp.maximum(temperature, jnp.float32(1e-6))
                filt = filter_logits_runtime(lg / t, top_k, top_p)
                probs = jax.nn.softmax(filt, axis=-1)
                m, new_tok = _spec_accept_resample(
                    probs, draft[0, :kb - 1], keys)
                count = m + 1  # emitted: [tok, d_0..d_{m-1}]
                # raw model logprobs of the EMITTED tokens: the accepted
                # drafts at their positions, the fresh draw at position
                # m (knob-independent log_softmax, like every other path)
                logz = jax.nn.logsumexp(lg, axis=-1)
                lp_draft = jnp.take_along_axis(
                    lg[: kb - 1], draft[0, : kb - 1, None],
                    axis=1)[:, 0] - logz[: kb - 1]
                lp_out = jnp.where(
                    jnp.arange(kb) < m,
                    jnp.concatenate([lp_draft, jnp.zeros((1,))]),
                    jnp.float32(0.0))
                lp_new = jnp.take(lg[m], new_tok) - logz[m]
                lp_out = lp_out.at[m].set(lp_new)
                new_idx = idx + count
                for entry in new_cache:
                    entry["index"] = new_idx
                return (chunk[0], lp_out, count, new_tok.reshape(1),
                        new_cache)

            return jax.jit(spec_sampled_verify)

        return self._fn_cached(("spec_s", kb, cache_len), build)

    def generate_speculative(self, prompt_tokens, *, max_new_tokens: int,
                             k: int = 8, eos_id: int | None = None,
                             return_logprobs: bool = False,
                             return_stats: bool = False,
                             ngram_max: int = 3, prefix=None,
                             temperature: float = 0.0,
                             top_k: int | None = None,
                             top_p: float | None = None, seed: int = 0):
        """Decode with prompt-lookup speculative verification (single
        row). Greedy by default: in exact arithmetic the output is
        BITWISE :meth:`generate`'s greedy output — speculation only
        changes how many tokens each weight read verifies, never the
        argmax — and the CPU f32 tests assert that equality. With
        ``temperature > 0`` the verify step runs delta-proposal
        REJECTION SAMPLING (:func:`_spec_accept_resample`): the emitted
        sequence is exactly target-chain distributed and deterministic
        per seed, but its draw structure necessarily differs from the
        non-speculative sampled stream, so the same seed yields a
        different (equally valid) sample than plain sampling. On bf16 hardware an
        argmax whose top-2 logit gap sits below bf16 resolution can
        break differently between the chunked verification forward and
        the one-token step (measured on v5e at 8B: first divergence at a
        0.006 logit gap); every emitted token is still the argmax of a
        forward over the correct emitted prefix, i.e. the result is a
        valid greedy decode under the chunked forward's numerics — the
        same caveat class as batch-shape-dependent reductions. Returns
        the same ``[1, max_new_tokens]`` array (plus logprobs when
        asked), with ``self.spec_stats`` recording the step/acceptance
        counters of the last call."""
        import numpy as np

        cfg = self.model.cfg
        rows, lengths = self._normalize_prompts(prompt_tokens)
        if len(rows) != 1:
            raise ValueError("speculative decoding is single-row")
        s = lengths[0]
        plen, pentry = 0, None
        if prefix is not None:
            pentry = self._prefix_entry(prefix)
            plen = pentry[1]
        self._validate(plen + s, max_new_tokens)
        kb = max(2, _next_bucket(max(2, int(k)), 2))
        if max_new_tokens == 0 or \
                plen + s + max_new_tokens + kb > cfg.max_len:
            # no room for a full verify chunk near the context boundary
            out = self.generate(rows[0], max_new_tokens=max_new_tokens,
                                eos_id=eos_id, prefix=prefix,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p, seed=seed,
                                return_logprobs=return_logprobs)
            stats = {"fallback": "plain", "steps": max_new_tokens,
                     "emitted": max_new_tokens, "tokens_per_step": 1.0,
                     "k": kb}
            self.spec_stats = stats
            self.spec_metrics.record_fallback("near_window")
            return (out, stats) if return_stats else out
        emitted: list[int] = []
        lps: list[float] = []
        stats: dict = {}
        for toks_step, lps_step in self._spec_steps(
                rows, max_new_tokens, kb, eos_id, ngram_max, stats,
                prefix=prefix, prefix_entry=pentry,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=seed):
            emitted.extend(toks_step)
            lps.extend(lps_step)
        # kept as a convenience for single-threaded callers/tests; the
        # thread-safe channel is return_stats (a threaded server must not
        # read another request's counters)
        self.spec_stats = stats
        toks = emitted[:max_new_tokens]
        lps = lps[:max_new_tokens]
        # eos latch parity with the fused path: truncate + fill
        if eos_id is not None and eos_id in toks:
            cut = toks.index(eos_id) + 1
            toks = toks[:cut] + [eos_id] * (max_new_tokens - cut)
            lps = lps[:cut] + [0.0] * (max_new_tokens - cut)
        # pad (loop may break early only on eos; otherwise it fills)
        toks += [eos_id if eos_id is not None else 0] * \
            (max_new_tokens - len(toks))
        lps += [0.0] * (max_new_tokens - len(lps))
        out = np.asarray([toks], np.int32)
        if return_logprobs:
            out = (out, np.asarray([lps], np.float32))
        return (out, stats) if return_stats else out

    @staticmethod
    def _normalize_prompts(prompt_tokens):
        """-> (list of 1-D int32 row arrays, list of true lengths)."""
        import numpy as np

        if isinstance(prompt_tokens, (list, tuple)) and prompt_tokens and \
                isinstance(prompt_tokens[0], (list, tuple, np.ndarray)):
            rows = [np.asarray(r, np.int32).reshape(-1) for r in prompt_tokens]
        else:
            ids = np.asarray(prompt_tokens, np.int32)
            rows = list(ids[None, :] if ids.ndim == 1 else ids)
        if not rows or any(len(r) < 1 for r in rows):
            raise ValueError("empty prompt")
        return rows, [len(r) for r in rows]


def _decode(model: LlamaModel, params, prompt_tokens, *, max_new_tokens: int,
            max_len: int | None, select_fn, rng, eos_id: int | None):
    """Shared decode loop: prefill once, then ``lax.scan`` one compiled
    step per token; ``select_fn(logits_f32, rng) -> (token ids, logprobs)``.
    Returns token ids only (the legacy generate API)."""
    cfg = model.cfg
    b, s = prompt_tokens.shape
    max_len = max_len or min(cfg.max_len, s + max_new_tokens)

    logits, prefill_cache = model.apply(
        params, prompt_tokens,
        logit_positions=jnp.full((b,), s - 1, jnp.int32))
    cache = prefill_into_cache(cfg, prefill_cache, b, max_len, s)
    # per-row PRNG chains (row r = fold_in of the caller's key), the same
    # scheme the serving path uses (_knob_operands)
    keys = jax.vmap(lambda r: jax.random.fold_in(rng, r))(jnp.arange(b))
    keys, subs = _split_rows(keys)
    first_token, lp0 = select_fn(logits[:, -1, :].astype(jnp.float32), subs)
    eos = jnp.int32(-1 if eos_id is None else eos_id)
    done0 = (eos >= 0) & (first_token == eos)
    toks, _ = _scan_decode(model, params, select_fn, first_token, lp0, cache,
                           jnp.int32(s), done0, keys, eos, max_new_tokens)
    return toks


def greedy_generate(model: LlamaModel, params, prompt_tokens, *, max_new_tokens: int,
                    max_len: int | None = None, eos_id: int | None = None):
    """Greedy decode. prompt_tokens: [b, s] int32 -> [b, max_new_tokens].
    After ``eos_id`` (when given) a sequence keeps emitting ``eos_id``."""

    def select(logits, _rng):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tok, _token_logprob(logits, tok)

    return _decode(model, params, prompt_tokens, max_new_tokens=max_new_tokens,
                   max_len=max_len, select_fn=select,
                   rng=jax.random.PRNGKey(0), eos_id=eos_id)


def sample_generate(model: LlamaModel, params, prompt_tokens, *, rng,
                    max_new_tokens: int, temperature: float = 1.0,
                    top_k: int | None = None, top_p: float | None = None,
                    max_len: int | None = None, eos_id: int | None = None):
    """Stochastic decode: temperature + top-k + nucleus filtering, one
    categorical draw per step from the shared ``lax.scan`` loop.
    temperature <= 0 degrades to greedy."""
    if temperature <= 0.0:
        return greedy_generate(model, params, prompt_tokens,
                               max_new_tokens=max_new_tokens, max_len=max_len,
                               eos_id=eos_id)

    def select(logits, keys):
        filt = filter_logits(logits / jnp.float32(temperature),
                             top_k=top_k, top_p=top_p)
        tok = jax.vmap(jax.random.categorical)(keys, filt).astype(jnp.int32)
        return tok, _token_logprob(logits, tok)

    return _decode(model, params, prompt_tokens, max_new_tokens=max_new_tokens,
                   max_len=max_len, select_fn=select, rng=rng, eos_id=eos_id)
