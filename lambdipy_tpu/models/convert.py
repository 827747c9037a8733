"""HuggingFace weight import: transformers checkpoints -> framework params.

The migration path for users arriving with real weights: a local HF Llama
checkpoint (or in-memory ``LlamaForCausalLM``) converts into the exact
pytree models/llama.py expects, verified to logits parity in
tests/test_convert.py. Conversion happens on host numpy (no device memory
spike); quantization (llama.quantize_params) and sharding happen after, on
the target mesh.

Mapping notes (HF ``modeling_llama`` naming):
- torch ``nn.Linear`` stores ``weight`` as [out, in] -> transposed into
  our [in, out] kernels;
- HF rotary embeddings use the rotate-half convention, same as llama.rope
  (split halves, not interleaved pairs) — weights port without permutation;
- ``tie_word_embeddings``: the lm_head kernel falls back to the transposed
  embedding matrix.

Offline rule (SURVEY.md §8, no network): sources are local paths or
already-constructed models only; nothing here downloads.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from lambdipy_tpu.utils.logs import get_logger, log_event

log = get_logger("lambdipy.convert")


def _to_numpy(t) -> np.ndarray:
    """Torch/array -> numpy, preserving the checkpoint dtype: an 8B bf16
    checkpoint must not silently double into fp32 orbax params. The fp32
    hop is exact for bf16/f16 (strict supersets), so round-tripping back
    to the source dtype loses nothing."""
    if hasattr(t, "detach"):  # torch tensor
        orig = str(t.dtype).replace("torch.", "")
        arr = t.detach().to("cpu").float().numpy()
        if orig == "bfloat16":
            import ml_dtypes

            return arr.astype(ml_dtypes.bfloat16)
        if orig == "float16":
            return arr.astype(np.float16)
        return arr
    return np.asarray(t)


def _state_dict_of(source) -> tuple[dict, dict | None]:
    """(state_dict, hf_config_dict|None) from a model / path / mapping."""
    if hasattr(source, "state_dict") and hasattr(source, "config"):
        return dict(source.state_dict()), source.config.to_dict()
    if isinstance(source, (str, Path)):
        from transformers import AutoModelForCausalLM

        model = AutoModelForCausalLM.from_pretrained(
            str(source), local_files_only=True)
        return dict(model.state_dict()), model.config.to_dict()
    return dict(source), None


def _rope_scaling_from_hf(hf_cfg: dict) -> tuple | None:
    """HF ``rope_scaling`` dict -> our hashable tuple form; raises for
    schemes the model does not implement (yarn, dynamic, longrope)."""
    rs = hf_cfg.get("rope_scaling")
    if not rs:
        return None
    kind = rs.get("rope_type", rs.get("type", "default"))
    if kind in (None, "default"):
        return None
    if kind == "linear":
        return ("linear", float(rs["factor"]))
    if kind == "llama3":
        return ("llama3", float(rs["factor"]),
                float(rs["low_freq_factor"]), float(rs["high_freq_factor"]),
                float(rs["original_max_position_embeddings"]))
    if kind == "yarn" and hf_cfg.get("model_type") == "deepseek_v32":
        # the latent kind's YaRN: the frequency blend and the softmax
        # scale's mscale squared (llama.LlamaConfig.attn_scale_mult); cos
        # and sin stay unscaled, which is the published rule only where
        # mscale / mscale_all_dim is 1. A llama checkpoint's yarn scales
        # cos and sin instead and stays refused
        mscale = float(rs.get("mscale", 1))
        if float(rs.get("mscale_all_dim", 0)) != mscale:
            raise ValueError(
                "unsupported HF config field: rope_scaling mscale "
                f"{rs.get('mscale')!r} differs from mscale_all_dim "
                f"{rs.get('mscale_all_dim')!r} (cos and sin would be scaled "
                "by their ratio, which is not implemented)")
        return ("yarn", float(rs["factor"]),
                float(rs["original_max_position_embeddings"]),
                float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
                mscale)
    raise ValueError(
        f"unsupported HF config field: rope_scaling type {kind!r} "
        "(supported: default, linear, llama3; yarn for deepseek_v32)")


def _check_supported_hf_config(hf_cfg: dict) -> None:
    """Reject HF config fields that would silently change numerics if
    dropped (VERDICT r2 missing #6): wrong logits with no error is the
    worst failure mode on the advertised migration path."""
    if hf_cfg.get("attention_bias"):
        raise ValueError(
            "unsupported HF config field: attention_bias=True "
            "(q/k/v/o projection biases are not implemented)")
    if hf_cfg.get("mlp_bias"):
        raise ValueError(
            "unsupported HF config field: mlp_bias=True "
            "(gate/up/down projection biases are not implemented)")
    head_dim = hf_cfg.get("head_dim")
    derived = int(hf_cfg["hidden_size"]) // int(hf_cfg["num_attention_heads"])
    if head_dim is not None and int(head_dim) != derived:
        raise ValueError(
            f"unsupported HF config field: head_dim={head_dim} differs from "
            f"hidden_size/num_attention_heads={derived}")


def llama_config_from_hf(hf_cfg: dict, **overrides):
    """Map an HF LlamaConfig dict onto our LlamaConfig; raises a clear
    error for unsupported fields instead of silently dropping them."""
    from lambdipy_tpu.models.llama import LlamaConfig

    import jax.numpy as jnp

    _check_supported_hf_config(hf_cfg)
    cfg = LlamaConfig(
        vocab_size=int(hf_cfg["vocab_size"]),
        hidden=int(hf_cfg["hidden_size"]),
        layers=int(hf_cfg["num_hidden_layers"]),
        heads=int(hf_cfg["num_attention_heads"]),
        kv_heads=int(hf_cfg.get("num_key_value_heads",
                                hf_cfg["num_attention_heads"])),
        mlp=int(hf_cfg["intermediate_size"]),
        max_len=int(hf_cfg.get("max_position_embeddings", 8192)),
        rope_theta=float(hf_cfg.get("rope_theta", 10000.0)),
        rope_scaling=_rope_scaling_from_hf(hf_cfg),
        norm_eps=float(hf_cfg.get("rms_norm_eps", 1e-5)),
        dtype=jnp.bfloat16,
    )
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def import_hf_llama(source, *, config_overrides: dict | None = None):
    """Convert an HF Llama checkpoint into (LlamaConfig, params).

    ``source``: a ``transformers`` model instance, a local checkpoint path,
    or a raw ``state_dict`` mapping (then pass the architecture via
    ``config_overrides`` on a LlamaConfig-complete dict).
    """
    sd, hf_cfg = _state_dict_of(source)
    sd = {k: _to_numpy(v) for k, v in sd.items()}
    if hf_cfg is None:
        raise ValueError(
            "raw state_dict needs an HF config; pass a model or path instead")
    cfg = llama_config_from_hf(hf_cfg, **(config_overrides or {}))

    def lin(name):  # torch Linear [out, in] -> kernel [in, out]
        return {"kernel": np.ascontiguousarray(sd[f"{name}.weight"].T)}

    def norm(name):
        return {"scale": sd[f"{name}.weight"]}

    params: dict = {
        "embed": {"embedding": sd["model.embed_tokens.weight"]},
        "final_norm": norm("model.norm"),
    }
    for i in range(cfg.layers):
        hf = f"model.layers.{i}"
        params[f"layer_{i}"] = {
            "attn_norm": norm(f"{hf}.input_layernorm"),
            "q_proj": lin(f"{hf}.self_attn.q_proj"),
            "k_proj": lin(f"{hf}.self_attn.k_proj"),
            "v_proj": lin(f"{hf}.self_attn.v_proj"),
            "o_proj": lin(f"{hf}.self_attn.o_proj"),
            "mlp_norm": norm(f"{hf}.post_attention_layernorm"),
            "gate_proj": lin(f"{hf}.mlp.gate_proj"),
            "up_proj": lin(f"{hf}.mlp.up_proj"),
            "down_proj": lin(f"{hf}.mlp.down_proj"),
        }
    if "lm_head.weight" in sd:
        params["lm_head"] = {"kernel": np.ascontiguousarray(sd["lm_head.weight"].T)}
    else:  # tie_word_embeddings
        params["lm_head"] = {
            "kernel": np.ascontiguousarray(sd["model.embed_tokens.weight"].T)}
    n = sum(v.size for v in jax_tree_leaves(params))
    log_event(log, "hf llama imported", layers=cfg.layers, n_params=int(n))
    return cfg, {"params": params}


# -- MiniCPM-SALA: an attention kind a layer ---------------------------------

_SALA_KINDS = {"minicpm4": "sparse_kv", "lightning-attn": "linear"}
# MiniCPM4's published sparse_config (InfLLM-V2), for a config that carries
# none of its own
_SALA_SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                "topk": 64, "init_blocks": 1, "window_size": 2048,
                "dense_len": 8192}
# our module name -> the checkpoint's, under ``model.layers.<i>.``; the gate
# and the head norms' names are as this file's author knows the published
# ``modeling_minicpm_sala.py`` and could not be checked offline
SALA_NAMES = {
    "attn_norm": "input_layernorm", "mlp_norm": "post_attention_layernorm",
    "q_proj": "self_attn.q_proj", "k_proj": "self_attn.k_proj",
    "v_proj": "self_attn.v_proj", "o_proj": "self_attn.o_proj",
    "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
    "o_norm": "self_attn.o_norm", "out_gate_proj": "self_attn.o_gate",
    "gate_proj": "mlp.gate_proj", "up_proj": "mlp.up_proj",
    "down_proj": "mlp.down_proj"}


def minicpm_sala_config_from_hf(hf_cfg: dict, **overrides):
    """Map a ``minicpm_sala`` config dict onto our LlamaConfig: the layers'
    kinds from ``mixer_types`` (``minicpm4`` -> ``sparse_kv``,
    ``lightning-attn`` -> ``linear``), the muP scalars, the sparse settings
    (``sparse_config``, MiniCPM4's where absent). ``scale_depth`` is over
    ``num_hidden_layers``: a checkpoint cut in depth passes the published
    count as ``published_layers``. Raises for what would silently change
    numerics."""
    import jax.numpy as jnp

    from lambdipy_tpu.models.llama import LlamaConfig

    _check_supported_hf_config(hf_cfg)
    for key, want in (("attn_use_rope", False), ("tie_word_embeddings", False),
                      ("lightning_scale", "1/sqrt(d)")):
        if hf_cfg.get(key, want) != want:
            raise ValueError(f"unsupported HF config field: {key}="
                             f"{hf_cfg[key]!r} (served: {want!r})")
    if hf_cfg["lightning_nkv"] != hf_cfg["lightning_nh"]:
        raise ValueError("unsupported HF config field: lightning_nkv differs "
                         "from lightning_nh (grouped linear heads are not "
                         "implemented)")
    kinds = tuple(_SALA_KINDS.get(m, m) for m in hf_cfg["mixer_types"])
    sparse = {**_SALA_SPARSE, **(hf_cfg.get("sparse_config") or {})}
    depth = int(overrides.pop("published_layers",
                              hf_cfg["num_hidden_layers"]))
    cfg = LlamaConfig(
        vocab_size=int(hf_cfg["vocab_size"]),
        hidden=int(hf_cfg["hidden_size"]),
        layers=int(hf_cfg["num_hidden_layers"]),
        heads=int(hf_cfg["num_attention_heads"]),
        kv_heads=int(hf_cfg["num_key_value_heads"]),
        mlp=int(hf_cfg["intermediate_size"]),
        max_len=int(hf_cfg.get("max_position_embeddings", 8192)),
        rope_theta=float(hf_cfg.get("rope_theta", 10000.0)),
        norm_eps=float(hf_cfg.get("rms_norm_eps", 1e-5)),
        dtype=jnp.bfloat16, layer_kinds=kinds,
        qk_norm=bool(hf_cfg.get("qk_norm", True)),
        attn_output_gate=bool(hf_cfg.get("attn_use_output_gate", True)),
        sparse_kernel=int(sparse["kernel_size"]),
        sparse_stride=int(sparse["kernel_stride"]),
        sparse_block=int(sparse["block_size"]),
        sparse_topk=int(sparse["topk"]),
        sparse_init_blocks=int(sparse["init_blocks"]),
        sparse_window=int(sparse["window_size"]),
        sparse_dense_len=int(sparse["dense_len"]),
        lin_heads=int(hf_cfg["lightning_nh"]),
        lin_head_dim=int(hf_cfg["lightning_head_dim"]),
        lin_rope=bool(hf_cfg.get("lightning_use_rope", True)),
        lin_output_norm=bool(hf_cfg.get("use_output_norm", True)),
        embed_scale=float(hf_cfg.get("scale_emb", 1.0)),
        residual_scale=float(hf_cfg.get("scale_depth", 1.0)) / depth ** 0.5,
        logit_divisor=float(hf_cfg["hidden_size"])
        / float(hf_cfg.get("dim_model_base", hf_cfg["hidden_size"])))
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def import_minicpm_sala(state_dict, hf_cfg: dict, **config_overrides):
    """Convert a ``minicpm_sala`` checkpoint's state dict (its config beside
    it) into (LlamaConfig, params): float kernels, ``quantize_params``
    after. A layer's leaves are its kind's: a ``lightning-attn`` layer has an
    output norm, every layer the head norms and the gate the config
    switches on."""
    cfg = minicpm_sala_config_from_hf(hf_cfg, **config_overrides)
    sd = {k: _to_numpy(v) for k, v in dict(state_dict).items()}

    def leaf(ours, name):
        w = sd[f"{name}.weight"]
        return {"scale": w} if ours.endswith("norm") \
            else {"kernel": np.ascontiguousarray(w.T)}

    params: dict = {
        "embed": {"embedding": sd["model.embed_tokens.weight"]},
        "final_norm": {"scale": sd["model.norm.weight"]},
        "lm_head": {"kernel": np.ascontiguousarray(sd["lm_head.weight"].T)}}
    for i, kind in enumerate(cfg.layer_kinds):
        skip = set()
        if not cfg.qk_norm:
            skip |= {"q_norm", "k_norm"}
        if not cfg.attn_output_gate:
            skip.add("out_gate_proj")
        if kind != "linear" or not cfg.lin_output_norm:
            skip.add("o_norm")
        params[f"layer_{i}"] = {
            ours: leaf(ours, f"model.layers.{i}.{theirs}")
            for ours, theirs in SALA_NAMES.items() if ours not in skip}
    n = sum(v.size for v in jax_tree_leaves(params))
    log_event(log, "hf minicpm_sala imported", layers=cfg.layers,
              n_params=int(n))
    return cfg, {"params": params}


def jax_tree_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def bert_config_from_hf(hf_cfg: dict, num_classes: int, **overrides):
    from lambdipy_tpu.models.bert import BertConfig

    import jax.numpy as jnp

    cfg = BertConfig(
        vocab_size=int(hf_cfg["vocab_size"]),
        hidden=int(hf_cfg["hidden_size"]),
        layers=int(hf_cfg["num_hidden_layers"]),
        heads=int(hf_cfg["num_attention_heads"]),
        mlp=int(hf_cfg["intermediate_size"]),
        max_len=int(hf_cfg.get("max_position_embeddings", 512)),
        type_vocab=int(hf_cfg.get("type_vocab_size", 2)),
        num_classes=num_classes,
        dtype=jnp.bfloat16,
    )
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def import_hf_bert(source, *, config_overrides: dict | None = None):
    """Convert an HF ``BertForSequenceClassification`` checkpoint (or local
    path) into (BertConfig, params) for models/bert.py BertClassifier.

    Mapping notes: torch Linear [out, in] -> [in, out] kernels; the q/k/v
    projections reshape into DenseGeneral's [hidden, heads, head_dim], the
    output projection into [heads, head_dim, hidden]; LayerNorm
    weight/bias -> scale/bias. Parity verified in tests/test_convert.py.
    """
    if isinstance(source, (str, Path)):
        from transformers import AutoModelForSequenceClassification

        source = AutoModelForSequenceClassification.from_pretrained(
            str(source), local_files_only=True)
    sd = {k: _to_numpy(v) for k, v in source.state_dict().items()}
    hf_cfg = source.config.to_dict()
    num_classes = sd["classifier.weight"].shape[0]
    cfg = bert_config_from_hf(hf_cfg, num_classes, **(config_overrides or {}))
    h, heads, hd = cfg.hidden, cfg.heads, cfg.hidden // cfg.heads

    def lin(name):
        return {"kernel": np.ascontiguousarray(sd[f"{name}.weight"].T),
                "bias": sd[f"{name}.bias"]}

    def qkv(name):  # [h_out, h_in] -> kernel [h_in, heads, head_dim]
        return {"kernel": np.ascontiguousarray(
                    sd[f"{name}.weight"].T.reshape(h, heads, hd)),
                "bias": sd[f"{name}.bias"].reshape(heads, hd)}

    def ln(name):
        return {"scale": sd[f"{name}.weight"], "bias": sd[f"{name}.bias"]}

    enc: dict = {
        "tok_emb": {"embedding": sd["bert.embeddings.word_embeddings.weight"]},
        "pos_emb": {"embedding": sd["bert.embeddings.position_embeddings.weight"]},
        "type_emb": {"embedding": sd["bert.embeddings.token_type_embeddings.weight"]},
        "emb_ln": ln("bert.embeddings.LayerNorm"),
    }
    for i in range(cfg.layers):
        hf = f"bert.encoder.layer.{i}"
        enc[f"layer_{i}"] = {
            "attn": {
                "query": qkv(f"{hf}.attention.self.query"),
                "key": qkv(f"{hf}.attention.self.key"),
                "value": qkv(f"{hf}.attention.self.value"),
                # output projection: [h_out, h_in] -> [heads, head_dim, h]
                "out": {"kernel": np.ascontiguousarray(
                            sd[f"{hf}.attention.output.dense.weight"].T
                            .reshape(heads, hd, h)),
                        "bias": sd[f"{hf}.attention.output.dense.bias"]},
            },
            "ln_attn": ln(f"{hf}.attention.output.LayerNorm"),
            "mlp_in": lin(f"{hf}.intermediate.dense"),
            "mlp_out": lin(f"{hf}.output.dense"),
            "ln_mlp": ln(f"{hf}.output.LayerNorm"),
        }
    params = {
        "encoder": enc,
        "pooler": lin("bert.pooler.dense"),
        "classifier": lin("classifier"),
    }
    n = sum(v.size for v in jax_tree_leaves(params))
    log_event(log, "hf bert imported", layers=cfg.layers, n_params=int(n))
    return cfg, {"params": params}


def save_hf_params(hf_path: str | Path, params_dir: Path, *,
                   quant: str | None = None,
                   params_format: str = "both") -> dict:
    """Bundle-build hook: convert a local HF Llama checkpoint and persist
    it as the bundle's orbax params (bundle/package.py params="hf")."""
    from lambdipy_tpu.utils.platform import prefer_cpu_backend

    prefer_cpu_backend()  # host-side conversion; leave the TPU to the warmer
    import jax

    from lambdipy_tpu.models.llama import quantize_params

    cfg, params = import_hf_llama(hf_path)
    if quant == "int8":
        params = jax.device_get(quantize_params(params))
    params_dir = Path(params_dir)
    params_dir.mkdir(parents=True, exist_ok=True)
    from lambdipy_tpu.bundle.flatpack import save_checkpoint_files

    fmt = save_checkpoint_files(params_dir, params, params_format)
    n = sum(v.size for v in jax_tree_leaves(params))
    info = {"format": fmt, "n_params": int(n), "source": "hf",
            "hf_path": str(hf_path), "quant": quant,
            # the COMPLETE architecture: the serve side rebuilds the module
            # from exactly this dict, so every field that changes numerics
            # or limits (norm_eps! max_len!) must be here, not defaulted
            "config": {"vocab_size": cfg.vocab_size, "hidden": cfg.hidden,
                       "layers": cfg.layers, "heads": cfg.heads,
                       "kv_heads": cfg.kv_heads, "mlp": cfg.mlp,
                       "rope_theta": cfg.rope_theta,
                       "rope_scaling": (list(cfg.rope_scaling)
                                        if cfg.rope_scaling else None),
                       "norm_eps": cfg.norm_eps, "max_len": cfg.max_len}}
    return info
