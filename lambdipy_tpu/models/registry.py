"""Model registry: payload model names -> builders, params IO, TP rules.

Recipes name their payload model (``[payload] model = "resnet50"``); the
registry maps that name to a family adapter: how to construct the module,
make an example batch (for warmup/AOT), initialize + save params into the
bundle (orbax for JAX families — SURVEY.md §6 checkpoint row), and which
tensor-parallel sharding rules apply on a multi-chip mesh.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

from lambdipy_tpu.utils.logs import get_logger

log = get_logger("lambdipy.models")


class ModelError(KeyError):
    pass


@dataclass(frozen=True)
class ModelSpec:
    name: str
    kind: str  # "jax" | "sklearn" | "torch"
    build: Callable[..., Any]  # kind-specific builder, see adapters below
    description: str = ""
    tags: tuple[str, ...] = ()


_MODELS: dict[str, ModelSpec] = {}


def register(name: str, kind: str, description: str = "", tags: tuple[str, ...] = ()):
    def deco(fn):
        _MODELS[name] = ModelSpec(name=name, kind=kind, build=fn,
                                  description=description, tags=tags)
        return fn
    return deco


def get(name: str) -> ModelSpec:
    try:
        return _MODELS[name]
    except KeyError:
        raise ModelError(
            f"unknown model {name!r}; registered: {sorted(_MODELS)}") from None


def names() -> list[str]:
    return sorted(_MODELS)


# --------------------------------------------------------------------------
# JAX family adapter


@dataclass
class JaxModel:
    """Uniform wrapper over the flax model families."""

    module: Any
    example_batch: Callable[[int], Any]  # batch_size -> input pytree (tuple of args)
    tp_rules: Any  # ShardingRules
    forward: Callable[..., Any]  # (params, *batch) -> output
    generate: Callable[..., Any] | None = None
    # (params, mesh=None, **caps) -> a compile-once serving decoder
    # (llama.LlamaServer): prompt-length bucketing + runtime sampling knobs
    make_server: Callable[..., Any] | None = None
    config: Any = None
    # (params, *batch) -> (output, aux_loss) for models with an auxiliary
    # training loss (MoE router balance); feed to sharded_train_step's
    # model_apply_aux so the router receives its balance gradient
    forward_with_aux: Callable[..., Any] | None = None

    def init_params(self, seed: int = 0, batch_size: int = 1):
        import jax

        return self.module.init(jax.random.PRNGKey(seed), *self.example_batch(batch_size))


def _dtype(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


@register("resnet50", "jax", "flax ResNet-50 image classifier (config 3)")
def _build_resnet50(dtype: str = "bfloat16", quant: str | None = None,
                    extra: dict | None = None) -> JaxModel:
    import jax.numpy as jnp

    from lambdipy_tpu.models.resnet import resnet50
    from lambdipy_tpu.parallel.sharding import ShardingRules
    from jax.sharding import PartitionSpec as P

    extra = extra or {}
    module = resnet50(num_classes=int(extra.get("num_classes", 1000)),
                      dtype=_dtype(dtype))
    size = int(extra.get("image_size", 224))

    def example_batch(batch_size: int):
        return (jnp.zeros((batch_size, size, size, 3), _dtype(dtype)),)

    return JaxModel(
        module=module,
        example_batch=example_batch,
        tp_rules=ShardingRules(rules=()),  # convnet serving: replicate, dp batch
        forward=lambda params, x: module.apply(params, x, train=False),
    )


@register("resnet50-tiny", "jax", "tiny ResNet for tests/dry-runs")
def _build_resnet_tiny(dtype: str = "float32", quant: str | None = None,
                       extra: dict | None = None) -> JaxModel:
    import jax.numpy as jnp

    from lambdipy_tpu.models.resnet import resnet_tiny
    from lambdipy_tpu.parallel.sharding import ShardingRules

    module = resnet_tiny(dtype=_dtype(dtype))

    def example_batch(batch_size: int):
        return (jnp.zeros((batch_size, 32, 32, 3), _dtype(dtype)),)

    return JaxModel(
        module=module,
        example_batch=example_batch,
        tp_rules=ShardingRules(rules=()),
        forward=lambda params, x: module.apply(params, x, train=False),
    )


def _bert_tp_rules():
    from jax.sharding import PartitionSpec as P

    from lambdipy_tpu.parallel.sharding import ShardingRules

    return ShardingRules(rules=(
        ("*attn/query/kernel", P(None, "tp", None)),
        ("*attn/key/kernel", P(None, "tp", None)),
        ("*attn/value/kernel", P(None, "tp", None)),
        ("*attn/out/kernel", P("tp", None, None)),
        ("*mlp_in/kernel", P(None, "tp")),
        ("*mlp_out/kernel", P("tp", None)),
    ))


def _build_bert(cfg, dtype: str) -> JaxModel:
    import jax.numpy as jnp

    from lambdipy_tpu.models.bert import BertClassifier

    module = BertClassifier(cfg)

    def example_batch(batch_size: int):
        ids = jnp.zeros((batch_size, cfg.max_len), jnp.int32)
        mask = jnp.ones((batch_size, cfg.max_len), jnp.int32)
        return (ids, mask)

    return JaxModel(
        module=module,
        example_batch=example_batch,
        tp_rules=_bert_tp_rules(),
        forward=lambda params, ids, mask: module.apply(params, ids, mask),
        config=cfg,
    )


@register("bert-base", "jax", "flax BERT-base text classifier (config 4 jax path)")
def _build_bert_base(dtype: str = "bfloat16", quant: str | None = None,
                     extra: dict | None = None) -> JaxModel:
    import dataclasses

    from lambdipy_tpu.models.bert import BERT_BASE

    extra = extra or {}
    cfg = dataclasses.replace(
        BERT_BASE, dtype=_dtype(dtype),
        max_len=int(extra.get("max_len", 128)),
        num_classes=int(extra.get("num_classes", 2)))
    return _build_bert(cfg, dtype)


@register("bert-tiny", "jax", "tiny BERT for tests/dry-runs")
def _build_bert_tiny(dtype: str = "float32", quant: str | None = None,
                     extra: dict | None = None) -> JaxModel:
    import dataclasses

    from lambdipy_tpu.models.bert import BERT_TINY

    cfg = dataclasses.replace(BERT_TINY, dtype=_dtype(dtype))
    return _build_bert(cfg, dtype)


def _llama_tp_rules():
    from jax.sharding import PartitionSpec as P

    from lambdipy_tpu.parallel.sharding import ShardingRules

    return ShardingRules(rules=(
        ("*embed/embedding", P("tp", None)),
        # latent attention's down-projection feeds ONE cache row shared by
        # all heads: replicated. (Its up-projection kv_b_proj splits by
        # head like q/k/v below. validate_serving_mesh refuses a mesh for
        # the latent and routed kinds until a PR shards them; the rules
        # are here so that the tree has no leaf without one.)
        ("*kv_a_proj/*", P()),
        # the compressed query feeds every head and the indexer; the
        # indexer (sparse attention) scores with all its heads at once
        ("*q_a_proj/*", P()),
        ("*index_*", P()),
        # eva attention's two learned pooling vectors a head: tiny, float32
        # (validate_serving_mesh refuses a mesh for that kind too)
        ("*adaptive_*", P()),
        ("*o_proj/kernel*", P("tp", None)),
        ("*down_proj/kernel*", P("tp", None)),
        ("*o_proj/scale", P()),
        ("*down_proj/scale", P()),
        ("*_proj/kernel*", P(None, "tp")),  # q/k/v/gate/up
        ("*_proj/scale", P(None, "tp")),
        ("*lm_head/kernel*", P(None, "tp")),
        ("*lm_head/scale", P(None, "tp")),
        # MoE experts: expert dim over ep, expert-hidden over tp; router
        # replicated (tiny, fp32, routing must agree across shards).
        # Trailing * covers the int8 layout (_int8 stacks and _scale
        # tensors shard like their float originals; scale dim 1 is size 1)
        ("*moe/experts_gate*", P("ep", None, "tp")),
        ("*moe/experts_up*", P("ep", None, "tp")),
        ("*moe/experts_down_int8", P("ep", "tp", None)),
        ("*moe/experts_down_scale", P("ep", None, None)),
        ("*moe/experts_down", P("ep", "tp", None)),
        ("*moe/router", P()),
        ("*moe/e_score_correction_bias", P()),
    ))


_ATTN_BACKENDS = ("dense", "flash", "ring", "blocked")


def _llama_overrides(extra: dict | None) -> dict:
    """Filter ``extra`` down to LlamaConfig fields and validate the backend
    knobs — a misspelled backend must raise, not silently fall back to the
    default path while the user benchmarks the wrong thing."""
    import dataclasses

    from lambdipy_tpu.models.llama import LlamaConfig

    extra = dict(extra or {})
    if "matmul_backend" in extra:
        # a bundle that still asks for the removed kernel must not be
        # served as if it had been honoured (no silent ignore: PR 21)
        raise ValueError(
            "bundle extra 'matmul_backend' is no longer accepted: the "
            "Pallas int8 matmul it selected was removed in PR 29 and every "
            "QDense runs XLA's dot; drop the key from [payload.extra]")
    # manifest JSON round-trips the rope_scaling tuple as a list; the
    # config field must be hashable (flax module attribute). A STRING here
    # means it came through the recipe schema's stringification — tuple()
    # of it would silently become a tuple of characters; reject instead
    # (rope scaling is set by the HF import manifest, not by recipes).
    if extra.get("rope_scaling"):
        if isinstance(extra["rope_scaling"], str):
            raise ValueError(
                "rope_scaling cannot be set via recipe [payload.extra] "
                "(TOML values are stringified); it is populated by the HF "
                "import path (models/convert.py)")
        extra["rope_scaling"] = tuple(extra["rope_scaling"])
    # recipe TOML [payload.extra] values arrive as STRINGS (the schema
    # stringifies them for a hashable spec); coerce by the declared field
    # annotation so `hidden = 768` in a recipe doesn't become shape '768'.
    # Manifest-borne extras (HF import) keep native JSON types and pass
    # through untouched.
    annotations = {f.name: f.type for f in dataclasses.fields(LlamaConfig)}

    def coerce(name: str, v):
        if isinstance(v, str):
            t = annotations.get(name)
            if t == "int":
                return int(v)
            if t == "float":
                return float(v)
            if t == "bool":
                return v.lower() in ("1", "true", "yes")
        return v

    fields = set(annotations)
    out = {k: coerce(k, v) for k, v in extra.items()
           if k in fields - {"dtype", "quant"}}
    # operator-level backend switch: LAMBDIPY_ATTN_BACKEND selects the
    # attention backend (e.g. "blocked" for length-aware decode reads)
    # without editing the bundle; an explicit [payload.extra] value wins
    import os

    env_backend = os.environ.get("LAMBDIPY_ATTN_BACKEND")
    if env_backend and "attn_backend" not in out:
        out["attn_backend"] = env_backend
    if out.get("attn_backend", "dense") not in _ATTN_BACKENDS:
        raise ValueError(f"unknown attn_backend {out['attn_backend']!r}; "
                         f"supported: {_ATTN_BACKENDS}")
    if out.get("kv_quant") not in (None, "int8"):
        raise ValueError(f"unknown kv_quant {out['kv_quant']!r}; "
                         "supported: int8 (or omit for the float cache)")
    return out


def _build_llama(cfg) -> JaxModel:
    import jax.numpy as jnp

    from lambdipy_tpu.models.llama import LlamaModel, greedy_generate, sample_generate

    module = LlamaModel(cfg)

    def example_batch(batch_size: int):
        return (jnp.zeros((batch_size, 16), jnp.int32),)

    def generate(params, prompt, max_new_tokens=16, max_len=None, *,
                 temperature=0.0, top_k=None, top_p=None, seed=0, eos_id=None):
        if temperature and temperature > 0.0:
            import jax

            return sample_generate(
                module, params, prompt, rng=jax.random.PRNGKey(seed),
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, max_len=max_len, eos_id=eos_id)
        return greedy_generate(module, params, prompt,
                               max_new_tokens=max_new_tokens, max_len=max_len,
                               eos_id=eos_id)

    forward_with_aux = None
    if cfg.moe_experts:
        from lambdipy_tpu.models.moe import moe_aux_loss

        def forward_with_aux(params, tokens):
            (logits, _), state = module.apply(params, tokens,
                                              mutable=["intermediates"])
            return logits, moe_aux_loss(state["intermediates"])

    def make_server(params, mesh=None, **caps):
        from lambdipy_tpu.models.llama import LlamaServer

        return LlamaServer(module, params, mesh=mesh, **caps)

    return JaxModel(
        module=module,
        example_batch=example_batch,
        tp_rules=_llama_tp_rules(),
        forward=lambda params, tokens: module.apply(params, tokens)[0],
        generate=generate,
        make_server=make_server,
        config=cfg,
        forward_with_aux=forward_with_aux,
    )


@register("llama3-8b", "jax", "Llama-3-8B int8 TP generate (config 5)")
def _build_llama3_8b(dtype: str = "bfloat16", quant: str | None = "int8",
                     extra: dict | None = None) -> JaxModel:
    import dataclasses

    from lambdipy_tpu.models.llama import LLAMA3_8B

    cfg = dataclasses.replace(LLAMA3_8B, dtype=_dtype(dtype), quant=quant,
                              **_llama_overrides(extra))
    return _build_llama(cfg)


@register("llama-hf", "jax", "Llama with architecture from an imported HF checkpoint")
def _build_llama_hf(dtype: str = "bfloat16", quant: str | None = None,
                    extra: dict | None = None) -> JaxModel:
    """Serve an HF-imported checkpoint: every architecture field comes from
    ``extra`` (recorded in the bundle manifest by models/convert.py), so
    the module exactly matches the converted weights."""
    from lambdipy_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(dtype=_dtype(dtype), quant=quant,
                      **_llama_overrides(extra))
    return _build_llama(cfg)


@register("deepseek-v3", "jax",
          "DeepSeek-V3-style block: latent attention, dropless routed experts")
def _build_deepseek_v3(dtype: str = "bfloat16", quant: str | None = None,
                       extra: dict | None = None) -> JaxModel:
    """The ``deepseek_v3`` architecture through the one block
    (models/llama.py ``LlamaConfig.layer_spec``): multi-head latent
    attention without query compression in every layer,
    ``first_dense_layers`` leading dense SwiGLUs of width ``mlp``, then
    dropless routed FFNs (models/moe.py ``RoutedMLP``). Every shape key
    comes from ``extra`` (docs/serving.md, "deepseek-v3 recipe keys");
    the defaults of the kinds are DeepSeek-V3's own (sigmoid scores,
    normalised top-k weights, interleaved rotary dims)."""
    from lambdipy_tpu.models.llama import LlamaConfig

    extra = {"rope_interleave": True, "scoring_func": "sigmoid",
             "norm_topk_prob": True, **(extra or {})}
    cfg = LlamaConfig(dtype=_dtype(dtype), quant=quant,
                      **{**_llama_overrides(extra), "attn_kind": "latent",
                         "ffn_kind": "routed"})
    return _build_llama(cfg)


@register("deepseek-v32", "jax",
          "DeepSeek-V3.2-style block: latent attention with query compression "
          "under sparse attention, group-routed experts of which a share")
def _build_deepseek_v32(dtype: str = "bfloat16", quant: str | None = None,
                        extra: dict | None = None) -> JaxModel:
    """The ``deepseek_v32`` architecture through the one block: the
    ``deepseek-v3`` kinds with the query compressed (``q_lora_rank``),
    DeepSeek Sparse Attention (``index_heads``, ``index_head_dim``,
    ``index_topk``: models/latent.py),
    group-limited routing (``moe_n_group``, ``moe_topk_group``), a chip's
    share of each layer's routed experts (``moe_experts_held``,
    ``moe_first_expert``) and YaRN. A recipe's TOML cannot carry the
    ``rope_scaling`` tuple, so YaRN comes as scalars, ``rope_factor``
    (absent or 1: none), ``rope_original_len``, ``rope_beta_fast``,
    ``rope_beta_slow``, ``rope_mscale`` (docs/serving.md, "deepseek-v32
    recipe keys")."""
    from lambdipy_tpu.models.llama import LlamaConfig

    extra = {"rope_interleave": True, "scoring_func": "sigmoid",
             "norm_topk_prob": True, **(extra or {})}
    yarn = {key: float(extra.pop(f"rope_{key}", default))
            for key, default in (("factor", 1.0), ("original_len", 4096),
                                 ("beta_fast", 32.0), ("beta_slow", 1.0),
                                 ("mscale", 1.0))}
    if yarn["factor"] != 1.0:
        extra["rope_scaling"] = ("yarn", *yarn.values())
    cfg = LlamaConfig(dtype=_dtype(dtype), quant=quant,
                      **{**_llama_overrides(extra), "attn_kind": "latent",
                         "ffn_kind": "routed"})
    return _build_llama(cfg)


def _layer_kinds(extra: dict, model: str, kinds_of: str) -> tuple:
    """``extra["layer_kinds"]`` as a tuple: a comma-separated string from a
    recipe's TOML or a sequence from a manifest; a model of kinds a layer
    cannot be built without it."""
    kinds = extra.get("layer_kinds") or ()
    if isinstance(kinds, str):
        kinds = [k.strip() for k in kinds.split(",") if k.strip()]
    if not kinds:
        raise ValueError(f"{model} needs layer_kinds: one of {kinds_of} for "
                         "each layer")
    return tuple(kinds)


@register("minicpm-sala", "jax",
          "MiniCPM-SALA block: block-sparse (InfLLM-V2) attention layers "
          "among Lightning linear-attention layers, muP scalars")
def _build_minicpm_sala(dtype: str = "bfloat16", quant: str | None = None,
                        extra: dict | None = None) -> JaxModel:
    """The ``minicpm_sala`` architecture through the one block, its attention
    kind chosen a LAYER (``layer_kinds``, one of ``sparse_kv`` and
    ``linear`` for each layer, as a comma-separated string from a recipe's
    TOML or a sequence from a manifest): models/sparse_kv.py (grouped-query
    K/V without rope whose attended blocks are chosen by content:
    ``sparse_*``) and models/linear_attn.py (a recurrent state a slot:
    ``lin_*``), both with ``qk_norm`` and an output gate, under MiniCPM's
    three scalars (``embed_scale``, ``residual_scale``, ``logit_divisor``).
    Every shape key comes from ``extra`` (docs/serving.md, "minicpm-sala
    recipe keys")."""
    from lambdipy_tpu.models.llama import LlamaConfig

    extra = {"qk_norm": True, "attn_output_gate": True, **(extra or {})}
    extra["layer_kinds"] = _layer_kinds(extra, "minicpm-sala",
                                        "sparse_kv and linear")
    cfg = LlamaConfig(dtype=_dtype(dtype), quant=quant,
                      **_llama_overrides(extra))
    return _build_llama(cfg)


@register("bailing-hybrid", "jax",
          "Ling-3.0 block: Kimi-Delta-Attention layers beside latent-"
          "attention layers, group-routed experts of which a share")
def _build_bailing_hybrid(dtype: str = "bfloat16", quant: str | None = None,
                          extra: dict | None = None) -> JaxModel:
    """The ``bailing_hybrid`` architecture through the one block, its
    attention kind chosen a LAYER (``layer_kinds``, one of ``kda`` and
    ``latent`` for each layer, as a comma-separated string from a recipe's
    TOML or a sequence from a manifest): models/kda.py (a gated delta-rule
    state behind a short convolution: ``kda_*``) and the block's latent
    attention without query compression, both under a head-wise output gate;
    ``first_dense_layers`` leading dense SwiGLUs, then the ``deepseek-v32``
    builder's group-limited routing over a chip's share of the experts. Two
    parts of the published model are refused by name, never guessed:
    ``swiglu_limit`` (the clamped SwiGLU of its last layers) and
    ``nextn_predict_layers`` (its multi-token-prediction layer) must be 0.
    Every shape key comes from ``extra`` (docs/serving.md, "bailing-hybrid
    recipe keys")."""
    from lambdipy_tpu.models.llama import LlamaConfig

    extra = {"rope_interleave": True, "scoring_func": "sigmoid",
             "norm_topk_prob": True, "attn_output_gate": True,
             "attn_gate_headwise": True, **(extra or {})}
    for key, what in (("swiglu_limit", "a clamped SwiGLU (the catalog does "
                       "not give the clamp's form)"),
                      ("nextn_predict_layers", "a multi-token-prediction "
                       "layer (it sits behind the last layer; ROADMAP R7)")):
        if float(extra.pop(key, 0) or 0):
            raise NotImplementedError(
                f"bailing-hybrid: {key} must be 0: {what} is not written "
                "(PERF.md section 7)")
    extra["layer_kinds"] = _layer_kinds(extra, "bailing-hybrid",
                                        "kda and latent")
    cfg = LlamaConfig(dtype=_dtype(dtype), quant=quant,
                      **{**_llama_overrides(extra), "ffn_kind": "routed"})
    return _build_llama(cfg)


@register("evabyte", "jax",
          "EvaByte block: EVA chunked linearized attention, multi-byte heads")
def _build_evabyte(dtype: str = "bfloat16", quant: str | None = None,
                   extra: dict | None = None) -> JaxModel:
    """The ``evabyte`` architecture through the one block (models/llama.py
    ``attn_kind`` "eva"): multi-head attention over an exact window of
    ``window_size`` positions beside one learned summary for every
    ``chunk_size`` earlier positions, a dense SwiGLU, RMSNorm gains stored
    as offsets from one, a head of ``pred_heads`` x ``vocab_size`` columns
    of which the first ``vocab_size`` are served. Every shape key comes
    from ``extra`` (docs/serving.md, "evabyte recipe keys"); the two
    learned vectors a head stay float32 under int8."""
    from lambdipy_tpu.models.llama import LlamaConfig

    extra = {"norm_unit_offset": True, **(extra or {})}
    cfg = LlamaConfig(dtype=_dtype(dtype), quant=quant,
                      **{**_llama_overrides(extra), "attn_kind": "eva"})
    return _build_llama(cfg)


@register("llama-moe-tiny", "jax", "tiny MoE Llama (expert-parallel tests/dry-runs)")
def _build_llama_moe_tiny(dtype: str = "float32", quant: str | None = None,
                          extra: dict | None = None) -> JaxModel:
    import dataclasses

    from lambdipy_tpu.models.llama import LLAMA_TINY

    # every extra key applies through the shared validator (the same
    # silently-dropped-extra bug class _build_llama_tiny had); only the
    # MoE-enabling default differs from LlamaConfig's
    extra = dict(extra or {})
    extra.setdefault("moe_experts", 4)
    cfg = dataclasses.replace(LLAMA_TINY, dtype=_dtype(dtype), quant=quant,
                              **_llama_overrides(extra))
    return _build_llama(cfg)


@register("llama-tiny", "jax", "tiny Llama for tests/dry-runs")
def _build_llama_tiny(dtype: str = "float32", quant: str | None = None,
                      extra: dict | None = None) -> JaxModel:
    import dataclasses

    from lambdipy_tpu.models.llama import LLAMA_TINY

    # extra MUST apply (code-review r5: it was silently dropped, so every
    # test building llama-tiny with attn_backend='ring' was vacuously
    # exercising the dense path while claiming sp coverage)
    cfg = dataclasses.replace(LLAMA_TINY, dtype=_dtype(dtype), quant=quant,
                              **_llama_overrides(extra))
    return _build_llama(cfg)


def draft_twin(adapter: JaxModel, *, layers: int = 2, hidden: int | None = None,
               seed: int = 0, params: Any = None, mesh=None, **caps):
    """Build a small same-family DRAFT server for the aux draft tier.

    Returns a compile-once server (``LlamaServer``) over a shrunken copy
    of ``adapter``'s config — same vocab (drafts are token ids in the
    target's vocabulary, so the vocab may never differ), fewer layers,
    optionally a narrower ``hidden`` (head count scales to preserve the
    target's head_dim). The twin is TP-REPLICATED: its params carry
    empty sharding rules, so on a mesh every shard drafts locally and no
    collective sits on the draft path — the whole point of a draft model
    is to be too small to be worth sharding.

    ``params=None`` random-inits the twin (tests/benches exercising the
    seam); a real deployment passes distilled weights. Wrap the returned
    server in :class:`lambdipy_tpu.runtime.continuous.AuxModelDraft` and
    hand it to the engine as ``draft_provider`` with
    ``draft_mode="aux"``. Extra ``caps`` go to the server constructor
    (e.g. ``prefix_cache_max``).
    """
    import dataclasses

    from lambdipy_tpu.parallel.sharding import ShardingRules

    cfg = adapter.config
    if cfg is None or not hasattr(cfg, "vocab_size"):
        raise ModelError("draft_twin needs a llama-family adapter "
                         "(adapter.config must be a LlamaConfig)")
    overrides: dict[str, Any] = {
        "layers": max(1, min(int(layers), cfg.layers)),
        # quant/kv_quant buy nothing at draft scale and int8 random-init
        # is a pointless extra code path — the twin serves float
        "quant": None, "kv_quant": None,
    }
    if hidden is not None:
        head_dim = max(1, cfg.hidden // cfg.heads)
        heads = max(1, int(hidden) // head_dim)
        overrides.update(
            hidden=heads * head_dim,
            heads=heads,
            kv_heads=max(1, min(cfg.kv_heads, heads)),
            mlp=2 * heads * head_dim,
        )
    twin = _build_llama(dataclasses.replace(cfg, **overrides))
    twin.tp_rules = ShardingRules(rules=())  # replicate on any mesh
    if params is None:
        params = twin.init_params(seed=seed)
    if mesh is not None:
        from lambdipy_tpu.parallel.sharding import shard_params

        params = shard_params(params, mesh, twin.tp_rules)
    return twin.make_server(params, mesh=mesh, **caps)


# --------------------------------------------------------------------------
# non-JAX families (configs 2 and 4 compatibility paths)


@register("tabular", "sklearn", "sklearn tabular classifier (config 2)")
def _build_tabular(dtype: str = "float32", quant: str | None = None,
                   extra: dict | None = None):
    extra = extra or {}
    n_features = int(extra.get("n_features", 16))

    def make_fitted(seed: int = 0):
        import numpy as np
        from sklearn.ensemble import GradientBoostingClassifier

        rng = np.random.default_rng(seed)
        X = rng.normal(size=(256, n_features))
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(int)
        clf = GradientBoostingClassifier(n_estimators=20, max_depth=3,
                                         random_state=seed)
        clf.fit(X, y)
        return clf

    return {"make_fitted": make_fitted, "n_features": n_features}


@register("bert-base-torch", "torch", "torch BERT-base (config 4, torch-xla or CPU smoke)")
def _build_bert_torch(dtype: str = "float32", quant: str | None = None,
                      extra: dict | None = None):
    extra = extra or {}

    def make_model():
        import torch

        from lambdipy_tpu.models.torch_bert import TorchBertClassifier

        model = TorchBertClassifier(
            vocab_size=int(extra.get("vocab_size", 30522)),
            hidden=int(extra.get("hidden", 768)),
            layers=int(extra.get("layers", 12)),
            heads=int(extra.get("heads", 12)),
            max_len=int(extra.get("max_len", 128)),
            num_classes=int(extra.get("num_classes", 2)),
        )
        model.eval()
        return model

    return {"make_model": make_model, "max_len": int(extra.get("max_len", 128))}


# --------------------------------------------------------------------------
# params IO (bundle build + serve sides)


def shrink_params_for_serving(adapter, params, dtype_name: str):
    """Cast float32 leaves of rank >= 2 (kernels, embeddings) to the
    serving dtype when doing so is PROVABLY inert, verified — not assumed.

    flax modules cast params to their compute ``dtype`` at every call
    (promote_dtype), so for bf16-serving models the cast weights are what
    the matmuls already see; pre-casting on disk halves the checkpoint
    read and the host->device transfer (440 MB -> 220 MB for BERT-base).
    Rank-1 leaves
    (LayerNorm/BatchNorm scales and biases, RMSNorm gains) stay float32 —
    those are computed in fp32 by the modules.

    The gate is exact: a forward on the example batch must be BITWISE
    equal with cast params. Models with genuine fp32 compute on rank-2
    params (e.g. a float-serving Llama's fp32 lm_head) fail the gate and
    keep their fp32 weights wholesale. Returns (params, info dict).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    target = _dtype(dtype_name)
    if target == jnp.float32:
        return params, {"applied": False, "reason": "serving dtype is f32"}

    leaves, treedef = jax.tree_util.tree_flatten(params)
    candidates = [i for i, x in enumerate(leaves)
                  if getattr(x, "ndim", 0) >= 2 and x.dtype == jnp.float32]
    if not candidates:
        return params, {"applied": False, "reason": "no f32 kernels"}

    batch = adapter.example_batch(1)
    ref = jax.device_get(adapter.forward(params, *batch))

    def passes(cast_set) -> bool:
        cast_leaves = [x.astype(target) if i in cast_set else x
                       for i, x in enumerate(leaves)]
        got = jax.device_get(adapter.forward(
            jax.tree_util.tree_unflatten(treedef, cast_leaves), *batch))
        return jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: a.dtype == b.dtype
            and np.array_equal(a, b, equal_nan=True), ref, got))

    # a model typically has a small number of genuine-f32-compute heads
    # (Llama's lm_head, BERT's classifier): delta-debug them out instead
    # of rejecting the whole cast. Each failing round bisects to ONE
    # offending leaf (log2(n) forwards) and excludes it; more than 4
    # offenders means fp32 compute is structural — keep f32 wholesale.
    active = list(candidates)
    excluded: list[int] = []
    while active and not passes(set(active)):
        if len(excluded) >= 4:
            return params, {"applied": False,
                            "reason": "forward parity failed; kept f32"}
        group = list(active)
        while len(group) > 1:
            half = group[: len(group) // 2]
            group = half if not passes(set(half)) else group[len(group) // 2:]
        excluded.append(group[0])
        active.remove(group[0])
    if not active:
        return params, {"applied": False,
                        "reason": "all f32 kernels are fp32-compute"}
    cast_leaves = [x.astype(target) if i in set(active) else x
                   for i, x in enumerate(leaves)]
    cast_params = jax.tree_util.tree_unflatten(treedef, cast_leaves)
    saved = sum(leaves[i].nbytes // 2 for i in active)
    return cast_params, {"applied": True, "n_cast": len(active),
                         "n_kept_f32": len(excluded),
                         "bytes_saved": int(saved)}


def save_init_params(model: str, params_dir: Path, *, dtype: str = "bfloat16",
                     quant: str | None = None, extra: dict | None = None,
                     seed: int = 0, params_format: str = "both") -> dict:
    """Initialize a model's params and persist them into a bundle params dir.
    Returns an info dict recorded in the bundle manifest.

    params_format (jax families): "both" writes the canonical orbax
    checkpoint plus the params.fpk boot accelerator; "fpk"/"orbax" write
    one — big payloads (8 GB for int8 Llama-8B) must not ship their
    dominant bytes twice."""
    spec = get(model)
    params_dir = Path(params_dir)
    params_dir.mkdir(parents=True, exist_ok=True)
    if spec.kind == "jax":
        from lambdipy_tpu.utils.platform import prefer_cpu_backend

        # init math doesn't need the device, and holding the TPU here
        # starves the builder's warm subprocess (the step that must own it)
        prefer_cpu_backend()
        import jax

        adapter = spec.build(dtype=dtype, quant=quant, extra=extra)
        params = adapter.init_params(seed=seed)
        params, shrink = shrink_params_for_serving(adapter, params, dtype)
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        # checkpoint host arrays, not device arrays: orbax records the
        # save-time device/shardings otherwise, and a bundle built on TPU
        # must still boot on CPU (and vice versa) — serve re-shards on load
        params = jax.device_get(params)
        # orbax stays canonical; params.fpk is the boot accelerator the
        # loader prefers (~0.1 s mmap read vs ~3.6 s orbax restore on this
        # 1-core host — a third of the cold-start budget)
        from lambdipy_tpu.bundle.flatpack import save_checkpoint_files

        fmt = save_checkpoint_files(params_dir, params, params_format)
        info = {"format": fmt, "n_params": int(n_params),
                "seed": seed, "serving_cast": shrink}
    elif spec.kind == "sklearn":
        import joblib

        built = spec.build(dtype=dtype, quant=quant, extra=extra)
        clf = built["make_fitted"](seed)
        joblib.dump(clf, params_dir / "model.joblib")
        info = {"format": "joblib", "n_features": built["n_features"]}
    elif spec.kind == "torch":
        import torch

        built = spec.build(dtype=dtype, quant=quant, extra=extra)
        model_obj = built["make_model"]()
        torch.save(model_obj.state_dict(), params_dir / "model.pt")
        info = {"format": "torch",
                "n_params": sum(p.numel() for p in model_obj.parameters())}
    else:
        raise ModelError(f"unknown model kind {spec.kind!r}")
    (params_dir / "info.json").write_text(json.dumps({"model": model, **info}))
    return info


def save_random_params(model: str, path: Path, *, dtype: str = "bfloat16",
                       quant: str | None = "int8", extra: dict | None = None,
                       seed: int = 0) -> dict:
    """Write seeded random params for a jax model straight to a flatpack
    file, at any width, without running the model's own float init (for
    the int8 8B model that init needs ~32 GB of host RAM and minutes; FLOPs
    and HBM bytes do not care what the weights are). The tree layout comes
    from ``jax.eval_shape`` of the same init the bundle path uses, so the
    file loads exactly like a real checkpoint — and shapes are all this
    touches of jax: no backend starts, so a parent that must stay off the
    chip may call it.

    Values: int8 kernels uniform over the full range with the per-channel
    scale a lecun-magnitude weight would have (bf16 activations stay
    finite through 32 layers), normal(0, 0.02) embeddings, unit norm
    gains. Returns ``{"path", "bytes", "n_params", "seed"}``."""
    import jax
    import ml_dtypes
    import numpy as np

    from lambdipy_tpu.bundle import flatpack

    adapter = get(model).build(dtype=dtype, quant=quant, extra=extra)
    shapes = jax.eval_shape(lambda: adapter.init_params(seed=0))
    rng = np.random.default_rng(seed)
    hidden = adapter.config.hidden

    def fill(leaf):
        n = int(np.prod(leaf.shape))
        if leaf.dtype == np.int8:
            # full-range 64-bit draws viewed as bytes: an order of
            # magnitude faster than bounded int8 draws at 8 GB
            raw = rng.integers(0, 1 << 64, -(-n // 8), dtype=np.uint64)
            return raw.view(np.int8)[:n].reshape(leaf.shape)
        if leaf.dtype == ml_dtypes.bfloat16:
            return (rng.standard_normal(leaf.shape, np.float32) * 0.02
                    ).astype(ml_dtypes.bfloat16)
        if np.issubdtype(leaf.dtype, np.floating):
            if leaf.ndim == 2 and quant == "int8":  # QDense scales [1, out]
                return np.full(leaf.shape, 1.0 / (127.0 * hidden ** 0.5),
                               leaf.dtype)
            if leaf.ndim >= 2:  # float kernels / embeddings
                return (rng.standard_normal(leaf.shape, np.float32) * 0.02
                        ).astype(leaf.dtype)
            return np.ones(leaf.shape, leaf.dtype)  # norm gains
        raise ModelError(f"save_random_params: unhandled dtype {leaf.dtype}")

    tree = jax.tree.map(fill, shapes)
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    flatpack.save(path, tree)
    return {"path": str(path), "bytes": path.stat().st_size,
            "n_params": sum(int(np.prod(l.shape))
                            for l in jax.tree.leaves(shapes)),
            "seed": seed}


def load_params(model: str, params_dir: Path, *, device: bool = False):
    """Load params previously saved by save_init_params.

    ``device=True`` (jax + flatpack only): load straight onto the single
    device via grouped bulk transfers (flatpack.device_load) — at 8B
    scale this removes the per-leaf transfer overhead that dominates the
    boot upload. Meshed payloads keep the host tree (the sharder places
    it)."""
    spec = get(model)
    params_dir = Path(params_dir)
    if spec.kind == "jax":
        fpk = params_dir / "params.fpk"
        if fpk.is_file():
            from lambdipy_tpu.bundle import flatpack

            if device:
                return flatpack.device_load(fpk)
            return flatpack.load(fpk)
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        return ckptr.restore((params_dir / "orbax").resolve())
    if spec.kind == "sklearn":
        import joblib

        return joblib.load(params_dir / "model.joblib")
    if spec.kind == "torch":
        import torch

        return torch.load(params_dir / "model.pt", weights_only=True)
    raise ModelError(f"unknown model kind {spec.kind!r}")
