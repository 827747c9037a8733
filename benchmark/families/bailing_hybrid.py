"""The ``bailing_hybrid`` block (Ling-3.0-flash) as its public ``config.json``
keys describe it: ``layer_group_size`` layers make a group, the last of them
latent attention (MLA, DeepSeek-V3's) and the others Kimi Delta Attention
(KDA, arXiv:2510.26692); group-routed experts beside one shared expert behind
``first_k_dense_replace`` dense layers; served by the program's
``bailing-hybrid`` builder with int8 kernels and one float32 scale per output
channel, and cut to ONE chip's share of a stated deployment: the chip holds
``num_experts`` of the ``routed_experts_published`` routed experts of a layer
(ids ``first_routed_expert`` ..), a slice of the vocabulary, and the layers
``layers_held`` (published numbering, which decides each one's kind).

**The layer equations the reference follows** (token t, ``a = rmsnorm(x)``,
eps ``rms_norm_eps``, no biases, untied head; what the catalog row cannot
confirm is listed under ``assumed`` in the configuration file). Published
layer ``i`` is MLA where ``(i + 1) % layer_group_size == 0`` and KDA
otherwise; the first ``first_k_dense_replace`` held layers have the dense
SwiGLU of width ``intermediate_size``, the others the routed FFN.

1. KDA, ``H`` heads of ``d = head_dim`` for q, k and v alike: ``q~, k~, v~ =
   W a``; ``u_t = SiLU(sum_j w_j u~_(t - K + 1 + j))``, ``j = 0 .. K - 1``, ``K
   = short_conv_kernel_size``, a weight a channel and tap, zeros before the
   prompt (``linear_silu``); ``q <- d^-1/2 q rsqrt(|q|^2 + 1e-6)``, ``k <- k
   rsqrt(|k|^2 + 1e-6)`` a head (``use_qk_norm``), no rope; ``log alpha_t =
   kda_lower_bound x sigmoid(exp(A_log[head]) x (W_f a + dt_bias))`` a head
   and channel (``kda_safe_gate``; ``W_f`` one full matrix: ``no_kda_lora``);
   ``beta_t = sigmoid(W_b a)`` a head; ``S_t = Diag(alpha_t) S_t-1 + k_t
   u_t^T`` with ``u_t = beta_t (v_t - S_t-1^T (alpha_t * k_t))``, ``S_-1 =
   0``, float32; ``o_t = S_t^T q_t``; ``o <- rmsnorm(o)`` over all ``H x d``
   values with a gain (``group_norm_size`` 1); ``o <- o * sigmoid(W_g a)``,
   one gate a head (``head_wise``); ``y = W_o o``. HERE: the plain
   recurrence, one position a step.
2. MLA (``q_lora_rank`` null): ``q = W_q a`` -> [H, nope + rope]; ``W_kva a``
   -> [rank + rope] = ``c | k_pe``; ``c <- rmsnorm(c)``; ``c W_kvb`` -> [H,
   nope + v] = ``k_nope | v``; rope on ``q_pe`` and ``k_pe`` (ONE key for all
   heads), rotary dims as INTERLEAVED pairs, ``rope_theta``, no scaling;
   causal softmax of ``(q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)``
   in float32; the same head-wise gate; ``y = W_o o``. Always EXPANDED here.
3. Routed FFN: ``m = rmsnorm(x)``; ``s = sigmoid(m W_r)`` over ALL published
   experts, float32; choice score ``s + b``; a group (``n_group`` groups of
   consecutive experts) scores the sum of its two largest choice scores; the
   ``topk_group`` best groups stay; the ``num_experts_per_tok`` best experts
   inside them by ``s + b``; weights ``s`` (no ``b``) of those, over their
   sum, x ``routed_scaling_factor``. ``y = sum_{picked e HELD HERE} g_e
   SwiGLU_e(m) + SwiGLU_shared(m)``: what the absent experts would add is
   left out, here as in the program (the guide's section 4: a chip's share).
4. Final norm; the head over this chip's ``vocab_size`` rows.

**Refused by name, never guessed**: a non-zero entry of
``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list`` (the
clamped SwiGLU of the published model's last layers: the catalog does not
give the clamp's form) and ``num_nextn_predict_layers`` > 0 (the
multi-token-prediction layer sits behind the last layer). ``max_window_layers``,
``mtp_*``, ``seq_aux``, ``use_nGPT``, ``value_norm``, ``up_proj_norm`` are
training-time or off and are not read.

**Weights.** The ``deepseek_v32`` family's rules (int8 kernels uniform over
the full range with one float32 scale of 1/(127 sqrt(fan_in)) per output
channel, so each projection maps unit variance to about 0.58; expert stacks
kin, drawn under their PUBLISHED ids; the embedding int8-uniform x 2^-12; unit
norm gains; a float32 router int8-uniform x 2/(127 sqrt(h));
``e_score_correction_bias`` int8-uniform x 0.05/127), and three rules of this
family's own, each drawn so that a fault of the new kind shows:

- ``conv_weight`` int8-uniform x ``CONV_STEP`` (1.5/127), every channel and
  tap its own draw of either sign: four taps of variance 0.75 over inputs of
  variance 0.34 give SiLU an input of unit scale. A FLAT draw (every tap
  alike, or the last tap alone) would make the convolution a moving average
  or nothing, and ``no_conv`` and ``stale_tail`` would read like a sound run.
- ``dt_bias`` uniform over (-8, 0) a channel and ``A_log`` uniform over
  (-ln 2, ln 2) a head: with ``W_f a`` of order 0.6, ``log alpha`` spreads
  from -0.002 (a channel that remembers 600 tokens) to -4.9 (one that forgets
  in one), most channels between: a state that neither dies in 10 tokens nor
  never decays. At a single decay ``no_decay`` or ``bf16_states`` would be a
  change of scale that the output norm removes.
- ``b_proj`` and ``out_gate_proj`` are int8 kernels like any other: ``beta``
  and the gates lie around 0.5 +- 0.14.

**Controls.** ``True``: every int8 kernel (the expert stacks too) rounded to
int4. ``FAULTS`` plant one fault each: ``no_delta`` (``u_t = beta_t v_t``:
plain gated linear attention), ``no_decay`` (``alpha`` = 1), ``no_conv``
(the last tap alone), ``stale_tail`` (the conv tail never written by decode:
from a row's first served position on, the three earlier taps read the
prompt's last three positions), ``bf16_states`` (the state rounded to
bfloat16 after every update), ``no_groups`` (plain top-k of all experts),
``int4_experts`` (the held stacks only), ``no_gate`` (no output gate, both
kinds). ``python3 -m benchmark.families.bailing_hybrid --config <file>
--seeds 1,2`` walks them on seeded rows; with ``--cell <cell>`` on the tokens
that cell's program served.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import weights

SCOPES = ("embed", "qkv_proj", "kv_write", "attend", "o_proj", "mlp",
          "lm_head", "sample", "kv_window", "mla_absorb", "router", "experts",
          "shared_expert", "kda_conv", "kda_gate", "kda_state", "kda_scan")
WITNESS = ("qkv_proj", "router", "sample", "kda_state")
FAULTS = ("no_delta", "no_decay", "no_conv", "stale_tail", "bf16_states",
          "no_groups", "int4_experts", "no_gate")

QUERY_BLOCK = 128     # queries one turn of the reference's attention scores
L2_EPS = 1e-6


# -- 1. the widths, under the program's names --------------------------------

def layers_held(config: dict) -> list:
    """The published numbers of the layers this configuration holds."""
    return list(config.get("layers_held")
                or range(config["num_hidden_layers"]))


def dims_of(config: dict) -> dict:
    """The configuration's keys under the names the program's
    ``bailing-hybrid`` builder takes (``LlamaConfig`` fields, and the two
    keys it refuses unless 0). Booleans go as the strings a recipe's TOML
    would hand the builder anyway."""
    heads = config["num_attention_heads"]
    if config.get("use_bias") or config.get("use_qkv_bias") \
            or config.get("tie_word_embeddings") \
            or config.get("q_lora_rank") is not None \
            or config.get("rope_scaling") or config.get("use_mla_nope") \
            or config.get("rotary_dim", config["qk_rope_head_dim"]) \
            != config["qk_rope_head_dim"] \
            or config.get("topk_method", "noaux_tc") != "noaux_tc" \
            or config.get("scale_router_input") \
            or not config.get("moe_router_enable_expert_bias", True):
        raise ValueError(
            "bailing-hybrid family: biases, a tied head, query compression, "
            "rope scaling, use_mla_nope, a rotary_dim other than "
            "qk_rope_head_dim, a gate other than noaux_tc with its bias, and "
            "scale_router_input are not written")
    if not config.get("no_kda_lora", True) or config.get("use_kda_lora") \
            or not config.get("kda_safe_gate", True) \
            or not config.get("linear_silu", True) \
            or not config.get("use_qk_norm", True) \
            or config.get("group_norm_size", 1) != 1 \
            or config.get("gated_attention_proj_granularity_type",
                          "head_wise") != "head_wise" \
            or config.get("num_kv_heads_for_linear_attn", 0) not in (0, heads) \
            or config["head_dim"] != config["v_head_dim"] \
            or config["head_dim"] != config["qk_nope_head_dim"]:
        raise ValueError(
            "bailing-hybrid family: a low-rank kda gate, a gate without "
            "kda_safe_gate, no SiLU behind the convolution, no q/k norm, "
            "more than one output-norm group, a gate that is not head_wise, "
            "grouped kda heads and kda heads of another width than the "
            "latent layers' are not written")
    held = layers_held(config)
    if len(held) != config["num_hidden_layers"]:
        raise ValueError("bailing-hybrid family: layers_held is not one "
                         "published number for each of num_hidden_layers")
    limits = [*config.get("expert_swiglu_limit_list", ()),
              *config.get("share_expert_swiglu_limit_list", ())]
    if any(limits):
        raise ValueError(
            "bailing-hybrid family: expert_swiglu_limit_list / "
            "share_expert_swiglu_limit_list hold a non-zero limit: the "
            "clamped SwiGLU is not written (the catalog does not give the "
            "clamp's form)")
    if config.get("num_nextn_predict_layers", 0):
        raise ValueError(
            "bailing-hybrid family: num_nextn_predict_layers > 0: the "
            "multi-token-prediction layer is not written")
    if config.get("moe_shared_expert_intermediate_size",
                  config["moe_intermediate_size"]) \
            != config["moe_intermediate_size"]:
        raise ValueError("bailing-hybrid family: a shared expert of another "
                         "width than the routed ones is not written")
    group = config["layer_group_size"]
    published = config.get("routed_experts_published", config["num_experts"])
    return {
        "vocab_size": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "mlp": config["intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "max_len": int(config["context_served"]),
        "layer_kinds": ",".join("latent" if (i + 1) % group == 0 else "kda"
                                for i in held),
        "kda_heads": heads,
        "kda_head_dim": config["head_dim"],
        "kda_conv": config["short_conv_kernel_size"],
        "kda_lower_bound": float(config["kda_lower_bound"]),
        "qk_nope": config["qk_nope_head_dim"],
        "qk_rope": config["qk_rope_head_dim"],
        "v_head": config["v_head_dim"],
        "kv_lora_rank": config["kv_lora_rank"],
        "rope_interleave": str(bool(config.get("rope_interleave", True))
                               ).lower(),
        "first_dense_layers": config["first_k_dense_replace"],
        "moe_experts": published,
        "moe_experts_held": config["num_experts"],
        "moe_first_expert": config.get("first_routed_expert", 0),
        "moe_n_group": config["n_group"],
        "moe_topk_group": config["topk_group"],
        "moe_top_k": config["num_experts_per_tok"],
        "moe_intermediate": config["moe_intermediate_size"],
        "n_shared_experts": config["num_shared_experts"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "norm_topk_prob": str(bool(config["norm_topk_prob"])).lower(),
        "scoring_func": config["scoring_func"],
        "swiglu_limit": max(limits, default=0),
        "nextn_predict_layers": config.get("num_nextn_predict_layers", 0),
    }


def _kernels(d: dict, kind: str, routed: bool) -> dict:
    """``{kernel: (fan_in, fan_out)}`` of a layer's int8 :class:`QDense`
    kernels, the shared expert's (under ``moe/``) among them."""
    h, heads = d["hidden"], d["heads"]
    if kind == "kda":
        wide = d["kda_heads"] * d["kda_head_dim"]
        attn = {"q_proj": (h, wide), "k_proj": (h, wide), "v_proj": (h, wide),
                "f_proj": (h, wide), "b_proj": (h, d["kda_heads"]),
                "out_gate_proj": (h, d["kda_heads"]), "o_proj": (wide, h)}
    else:
        rank, rope_d = d["kv_lora_rank"], d["qk_rope"]
        attn = {"q_proj": (h, heads * (d["qk_nope"] + rope_d)),
                "kv_a_proj": (h, rank + rope_d),
                "kv_b_proj": (rank, heads * (d["qk_nope"] + d["v_head"])),
                "out_gate_proj": (h, heads),
                "o_proj": (heads * d["v_head"], h)}
    if routed:
        width = d["n_shared_experts"] * d["moe_intermediate"]
        ffn = {"moe/shared_gate_proj": (h, width),
               "moe/shared_up_proj": (h, width),
               "moe/shared_down_proj": (width, h)}
    else:
        ffn = {"gate_proj": (h, d["mlp"]), "up_proj": (h, d["mlp"]),
               "down_proj": (d["mlp"], h)}
    return {**attn, **ffn}


def _layer_of(path: str, d: dict) -> tuple:
    """(kind, routed) of the layer a leaf at ``layer_<i>/...`` is in."""
    i = int(path.split("/")[0].split("_")[1])
    return d["layer_kinds"].split(",")[i], i >= d["first_dense_layers"]


# -- 2. the leaves -------------------------------------------------------------

ROUTER_STEP = 2.0 / 127.0     # x 1/sqrt(hidden): logits of unit order
BIAS_STEP = 0.05 / 127.0
KIN_EIGHTHS = 7               # of 8: the layer's common draw in an expert
CONV_STEP = 1.5 / 127.0       # four taps: SiLU's input of unit scale
DT_BIAS_SPAN = 8.0            # dt_bias uniform over (-8, 0)
_STACKS = ("/experts_gate_int8", "/experts_up_int8", "/experts_down_int8")


def leaf(seed: int, path: str, shape, dtype, config: dict):
    """One parameter leaf by its path in the program's tree, e.g.
    ``layer_3/moe/experts_up_int8`` or ``layer_0/conv_weight``. An expert
    stack holds the experts this chip holds, drawn under their PUBLISHED ids
    (the stack's row i is expert ``first_routed_expert`` + i), so another
    share of the same layer draws other experts."""
    name = np.dtype(dtype).name
    if name == "int8" and path.endswith("/kernel_int8"):
        return weights.int8_draw(seed, path, shape)
    if name == "int8" and path.endswith(_STACKS):
        first = int(config.get("first_routed_expert", 0))
        common = KIN_EIGHTHS * weights.int8_draw(
            seed, path + "/common", shape[1:]).astype(np.int16) + 4
        out = np.empty(shape, np.int8)
        for i in range(shape[0]):
            mix = weights.int8_draw(seed, f"{path}/{first + i}",
                                    shape[1:]).astype(np.int16)
            mix *= 8 - KIN_EIGHTHS
            mix += common
            mix >>= 3
            out[i] = mix
        return out
    if path.endswith("embedding"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * weights.EMBED_STEP).astype(dtype)
    if path.endswith("norm/scale"):
        return np.ones(shape, dtype)
    if path.endswith(("/scale", "_scale")):
        d = dims_of(config)
        parts = path.split("/")
        if parts[-2] == "lm_head":
            fan_in = d["hidden"]
        elif path.endswith("_scale"):    # an expert stack's
            fan_in = d["moe_intermediate"] if "down" in parts[-1] \
                else d["hidden"]
        else:
            fan_in = _kernels(d, *_layer_of(path, d)).get(
                "/".join(parts[1:-1]), (None,))[0]
        if fan_in is None:
            return None
        return np.full(shape, 1.0 / (127.0 * fan_in ** 0.5), dtype)
    draw = weights.int8_draw(seed, path, shape).astype(np.float32)
    if path.endswith("/conv_weight"):
        return (draw * CONV_STEP).astype(dtype)
    if path.endswith("/dt_bias"):
        return ((draw - 127.0) * (DT_BIAS_SPAN / 255.0)).astype(dtype)
    if path.endswith("/A_log"):
        return (draw * (math.log(2.0) / 127.0)).astype(dtype)
    if path.endswith("/moe/router"):
        return (draw * (ROUTER_STEP / config["hidden_size"] ** 0.5)
                ).astype(dtype)
    if path.endswith("/moe/e_score_correction_bias"):
        return (draw * BIAS_STEP).astype(dtype)
    return None


# -- 3. the reference's walk ---------------------------------------------------

def route(scores, bias, d: dict, groups: bool = True):
    """Equation 3's choice: ``scores`` [..., E] float32 -> (chosen [..., k],
    gates [..., k])."""
    import jax
    import jax.numpy as jnp

    choice = scores + bias
    n_group, e = d["moe_n_group"], scores.shape[-1]
    if groups and n_group > 1:
        by_group = choice.reshape(*choice.shape[:-1], n_group, e // n_group)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, d["moe_topk_group"])
        inside = jnp.sum(jax.nn.one_hot(kept, n_group), axis=-2) > 0
        choice = jnp.where(jnp.repeat(inside, e // n_group, axis=-1),
                           choice, -jnp.inf)
    _, chosen = jax.lax.top_k(choice, d["moe_top_k"])       # lowest index
    gates = jnp.take_along_axis(scores, chosen, axis=-1)    # wins a tie
    if d["norm_topk_prob"] == "true":
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return chosen, gates * d["routed_scaling_factor"]


def _layer_fns(d: dict, fault):
    """The jitted parts of a walk: ``fault`` False for the reference, True
    for the int4 control, or one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp

    heads, nope, rope_d, vd = d["heads"], d["qk_nope"], d["qk_rope"], d["v_head"]
    rank, eps = d["kv_lora_rank"], d["norm_eps"]
    kh, kd, taps = d["kda_heads"], d["kda_head_dim"], d["kda_conv"]
    first, held = d["moe_first_expert"], d["moe_experts_held"]
    gated = fault != "no_gate"

    def deq(w, scale, int4=fault is True):
        w = w.astype(jnp.float32)
        if int4:
            w = jnp.clip(jnp.round(w / 16.0), -8, 7) * 16.0
        return w * scale

    def norm(x, g):
        return x * (jnp.mean(x * x, -1, keepdims=True) + eps) ** -0.5 * g

    def l2(x):
        return x * (jnp.sum(x * x, -1, keepdims=True) + L2_EPS) ** -0.5

    def swiglu(m, gate, up, down, int4=fault is True):
        return (jax.nn.silu(m @ deq(*gate, int4))
                * (m @ deq(*up, int4))) @ deq(*down, int4)

    def kda(x, cos, sin, stale_from, p):
        """One row: x [s, h]."""
        s = x.shape[0]
        a = norm(x, p["attn_norm"])
        raw = jnp.stack([a @ deq(*p[name])
                         for name in ("q_proj", "k_proj", "v_proj")], axis=1)
        # tap j of position t reads position t - (taps - 1) + j; under
        # stale_tail a served position's earlier taps read the prompt's end
        t = jnp.arange(s)[:, None]
        j = jnp.arange(taps)[None, :]
        at = t - (taps - 1) + j
        if fault == "stale_tail":
            at = jnp.where((t >= stale_from) & (j < taps - 1),
                           stale_from - (taps - 1) + j, at)
        w = p["conv_weight"]                                 # [taps, 3, wide]
        if fault == "no_conv":
            w = w * (jnp.arange(taps) == taps - 1)[:, None, None]
        seen = jnp.where((at >= 0)[:, :, None, None],
                         raw[jnp.maximum(at, 0)], 0.0)   # [s, taps, 3, wide]
        q, k, v = (u.reshape(s, kh, kd) for u in jnp.moveaxis(
            jax.nn.silu(jnp.sum(seen * w[None], axis=1)), 1, 0))
        q, k = l2(q) * kd ** -0.5, l2(k)
        g = d["kda_lower_bound"] * jax.nn.sigmoid(
            jnp.exp(p["A_log"])[:, None]
            * (a @ deq(*p["f_proj"]) + p["dt_bias"]).reshape(s, kh, kd))
        if fault == "no_decay":
            g = jnp.zeros_like(g)
        beta = jax.nn.sigmoid(a @ deq(*p["b_proj"]))              # [s, kh]

        def step(state, args):
            q_t, k_t, v_t, g_t, b_t = args
            alpha = jnp.exp(g_t)
            seen_v = jnp.einsum("hkv,hk->hv", state, alpha * k_t)
            if fault == "no_delta":
                seen_v = jnp.zeros_like(seen_v)
            u = b_t[:, None] * (v_t - seen_v)
            state = alpha[:, :, None] * state + k_t[:, :, None] * u[:, None, :]
            if fault == "bf16_states":
                state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

        _, o = jax.lax.scan(step, jnp.zeros((kh, kd, kd), jnp.float32),
                            (q, k, v, g, beta))
        o = norm(o.reshape(s, kh * kd), p["o_norm"]).reshape(s, kh, kd)
        if gated:
            o = o * jax.nn.sigmoid(a @ deq(*p["out_gate_proj"]))[..., None]
        return x + o.reshape(s, kh * kd) @ deq(*p["o_proj"])

    def rope(x, cos, sin):    # interleaved pairs; cos, sin [.., rope_d / 2]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        if d["rope_interleave"] != "true":
            x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def latent(x, cos, sin, stale_from, p):
        """One row, a block of queries a turn."""
        s = x.shape[0]
        a = norm(x, p["attn_norm"])
        q = (a @ deq(*p["q_proj"])).reshape(s, heads, nope + rope_d)
        kva = a @ deq(*p["kv_a_proj"])
        c = norm(kva[..., :rank], p["kv_a_norm"])
        k_pe = rope(kva[..., rank:], cos, sin)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], cos[:, None], sin[:, None])],
            axis=-1)
        kv = (c @ deq(*p["kv_b_proj"])).reshape(s, heads, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        block = min(s, QUERY_BLOCK)
        turns = -(-s // block)

        def turn(args):
            i, q_b = args
            pos = i * block + jnp.arange(block)
            visible = jnp.arange(s)[None, :] <= pos[:, None]
            logits = (jnp.einsum("qhd,thd->hqt", q_b[..., :nope], k_nope)
                      + jnp.einsum("qhd,td->hqt", q_b[..., nope:], k_pe)) \
                * (nope + rope_d) ** -0.5
            probs = jax.nn.softmax(jnp.where(visible[None], logits, -jnp.inf),
                                   axis=-1)
            return jnp.einsum("hqt,thd->qhd", probs, v)

        o = jax.lax.map(turn, (jnp.arange(turns), jnp.pad(
            q, ((0, turns * block - s), (0, 0), (0, 0))).reshape(
                turns, block, heads, nope + rope_d)))
        o = o.reshape(turns * block, heads, vd)[:s]
        if gated:
            o = o * jax.nn.sigmoid(a @ deq(*p["out_gate_proj"]))[..., None]
        return x + o.reshape(s, heads * vd) @ deq(*p["o_proj"])

    def dense(x, p):
        return x + swiglu(norm(x, p["mlp_norm"]), p["gate_proj"],
                          p["up_proj"], p["down_proj"])

    def routed(x, p):
        m = norm(x, p["mlp_norm"])
        scores = jax.nn.sigmoid(m @ p["router"]) if d["scoring_func"] == \
            "sigmoid" else jax.nn.softmax(m @ p["router"], axis=-1)
        chosen, gates = route(scores, p["bias"], d,
                              groups=fault != "no_groups")
        gate_of = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1])
                          * gates[..., None], axis=-2)          # [s, E]

        def one(y, i):
            w = tuple((jax.lax.dynamic_index_in_dim(p[k][0], i, 0, False),
                       jax.lax.dynamic_index_in_dim(p[k][1], i, 0, False))
                      for k in ("gate", "up", "down"))
            out = swiglu(m, *w, int4=fault in (True, "int4_experts"))
            return y + out * jax.lax.dynamic_index_in_dim(
                gate_of, first + i, 1, True), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
        return x + y + swiglu(m, p["moe/shared_gate_proj"],
                              p["moe/shared_up_proj"],
                              p["moe/shared_down_proj"])

    def layer(attn, ffn):
        # a row at a time: one row's float32 scores and states at a time
        def run(x, cos, sin, stale_from, p):
            return jax.lax.map(
                lambda args: ffn(attn(args[0], cos, sin, args[1], p), p),
                (x, stale_from))
        return jax.jit(run)

    def head(x, rows, pos, g, w, scale):
        return norm(x[rows, pos], g) @ deq(w, scale)

    return {("kda", False): layer(kda, dense), ("kda", True): layer(kda, routed),
            ("latent", False): layer(latent, dense),
            ("latent", True): layer(latent, routed), "head": jax.jit(head)}


def walk(config: dict, ids: np.ndarray, rows_op, pos_op, flags: tuple, *,
         first_only: tuple = ()):
    """Logits at ``(rows_op, pos_op)`` of the batch ``ids`` [rows, length],
    one array per flag (False = the float32 reference, True = its int4
    control, or one of ``FAULTS``), walking the layers once with one layer's
    weights on the device at a time."""
    import jax
    import jax.numpy as jnp

    d = dims_of(config)
    h, e = d["hidden"], d["moe_experts"]
    held, m = d["moe_experts_held"], d["moe_intermediate"]
    kinds = d["layer_kinds"].split(",")
    wide = d["kda_heads"] * d["kda_head_dim"]

    def get(path, shp, dtype="float32"):
        return jnp.asarray(weights.leaf(config, path, shp, dtype))

    ang = np.arange(ids.shape[1], dtype=np.float32)[:, None] / (
        d["rope_theta"] ** (np.arange(0, d["qk_rope"], 2, dtype=np.float32)
                            / d["qk_rope"]))
    cos, sin = jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))
    rows_np, pos_np = np.asarray(rows_op), np.asarray(pos_op)
    # stale_tail: from a row's first served position on (the position after
    # the first one asked for) the conv tail was never written
    stale_from = jnp.asarray([
        int(pos_np[rows_np == r].min()) + 1 if (rows_np == r).any()
        else ids.shape[1] for r in range(ids.shape[0])], jnp.int32)
    embed = weights.leaf(config, "embed/embedding", (d["vocab_size"], h),
                         "float32")
    x0 = jnp.asarray(embed[ids])
    del embed
    fns = {flag: _layer_fns(d, flag) for flag in flags}
    xs = {flag: x0 for flag in flags}
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(kinds):
            at, routed = f"layer_{i}", i >= d["first_dense_layers"]
            p = {"attn_norm": get(f"{at}/attn_norm/scale", (h,)),
                 "mlp_norm": get(f"{at}/mlp_norm/scale", (h,))}
            if kind == "kda":
                p.update(
                    o_norm=get(f"{at}/o_norm/scale", (wide,)),
                    conv_weight=get(f"{at}/conv_weight",
                                    (d["kda_conv"], 3, wide)),
                    dt_bias=get(f"{at}/dt_bias", (wide,)),
                    A_log=get(f"{at}/A_log", (d["kda_heads"],)))
            else:
                p["kv_a_norm"] = get(f"{at}/kv_a_norm/scale",
                                     (d["kv_lora_rank"],))
            for name, shp in _kernels(d, kind, routed).items():
                p[name] = (get(f"{at}/{name}/kernel_int8", shp, "int8"),
                           get(f"{at}/{name}/scale", (1, shp[1])))
            if routed:
                moe = f"{at}/moe"
                p["router"] = get(f"{moe}/router", (h, e))
                p["bias"] = get(f"{moe}/e_score_correction_bias", (e,))
                for name, shp in (("gate", (held, h, m)), ("up", (held, h, m)),
                                  ("down", (held, m, h))):
                    p[name] = (get(f"{moe}/experts_{name}_int8", shp, "int8"),
                               get(f"{moe}/experts_{name}_scale",
                                   (held, 1, shp[2])))
            for flag in flags:    # one stream's old activations at a time
                xs[flag] = fns[flag][kind, routed](xs[flag], cos, sin,
                                                   stale_from, p)
            del p
        g = get("final_norm/scale", (h,))
        w = get("lm_head/kernel_int8", (h, d["vocab_size"]), "int8")
        sc = get("lm_head/scale", (1, d["vocab_size"]))
        out = {}
        for flag, x in xs.items():
            logits = fns[flag]["head"](x, jnp.asarray(rows_op),
                                       jnp.asarray(pos_op), g, w, sc)
            out[flag] = logits.argmax(axis=-1) if flag in first_only \
                else logits
        return out


# -- 4. what a step needs: int8 kernels at 1 byte, a float32 router, float32
# states, a bf16 conv tail and latent rows ------------------------------------

def _counts(d: dict) -> tuple:
    """(kda layers, latent layers, dense layers, routed layers)."""
    kinds = d["layer_kinds"].split(",")
    dense = d["first_dense_layers"]
    return kinds.count("kda"), kinds.count("latent"), dense, \
        d["layers"] - dense


def _attn_params(d: dict, kind: str) -> int:
    return sum(a * b for name, (a, b) in _kernels(d, kind, False).items()
               if name not in ("gate_proj", "up_proj", "down_proj"))


def _expert_params(d: dict) -> int:
    return 3 * d["hidden"] * d["moe_intermediate"]


def experts_touched(d: dict, rows: float) -> float:
    """Distinct HELD experts a step of ``rows`` tokens is expected to need
    in one layer under even routing: a pick lands on a given expert with
    probability k / E."""
    e, k = d["moe_experts"], d["moe_top_k"]
    return d["moe_experts_held"] * (1.0 - (1.0 - k / e) ** rows)


def kda_step_bytes(config: dict, *, rows: float) -> float:
    """Bytes the kda layers of ONE decode step NEED: each live row's float32
    state and bf16 conv tail, read once and written once, a layer."""
    d = dims_of(config)
    wide = d["kda_heads"] * d["kda_head_dim"]
    return _counts(d)[0] * rows * 2 * (
        4 * wide * d["kda_head_dim"] + 2 * 3 * (d["kda_conv"] - 1) * wide)


def decode_step_bytes(config: dict, *, rows: float, context: float) -> float:
    """HBM bytes ONE decode step needs: every kernel outside the routed
    experts once (the float32 conv weights, gates' biases among them), in
    each routed layer the float32 router, the shared expert and the held
    experts the rows are expected to touch, the head's slice, the kda
    layers' states and tails both ways (``kda_step_bytes``), and the latent
    rows at this context."""
    d = dims_of(config)
    n_kda, n_lat, dense, routed = _counts(d)
    wide = d["kda_heads"] * d["kda_head_dim"]
    kernels = n_kda * (_attn_params(d, "kda")
                       + 4 * (3 * d["kda_conv"] * wide + wide)) \
        + n_lat * _attn_params(d, "latent") \
        + dense * 3 * d["hidden"] * d["mlp"] + d["hidden"] * d["vocab_size"] \
        + routed * (4 * d["hidden"] * d["moe_experts"]
                    + d["n_shared_experts"] * _expert_params(d)
                    + experts_touched(d, rows) * _expert_params(d))
    rows_read = n_lat * rows * context * 2 * (d["kv_lora_rank"] + d["qk_rope"])
    return kernels + kda_step_bytes(config, rows=rows) + rows_read


def _token_params(d: dict) -> float:
    """Parameters one token's matmuls in the layers use here: of its top-k
    experts the share this chip holds under even routing."""
    n_kda, n_lat, dense, routed = _counts(d)
    local = d["moe_top_k"] * d["moe_experts_held"] / d["moe_experts"]
    return n_kda * _attn_params(d, "kda") + n_lat * _attn_params(d, "latent") \
        + dense * 3 * d["hidden"] * d["mlp"] \
        + routed * (d["hidden"] * d["moe_experts"]
                    + (d["n_shared_experts"] + local) * _expert_params(d))


def decode_step_flops(config: dict, *, rows: float, context: float) -> float:
    """The kda step's multiply-reduces over the state (two reads, one
    rank-one update) and absorbed attention over the latent rows."""
    d = dims_of(config)
    n_kda, n_lat, _, _ = _counts(d)
    state = 8 * d["kda_heads"] * d["kda_head_dim"] ** 2
    attend = 2 * d["heads"] * context * (2 * d["kv_lora_rank"] + d["qk_rope"])
    return rows * (2 * _token_params(d) + 2 * d["hidden"] * d["vocab_size"]
                   + n_kda * state + n_lat * attend)


def prefill_flops(config: dict, *, rows: int, seq_len: int,
                  chunk: int = 32) -> float:
    """A kda layer the chunked form (a chunk's two [c, c] matrices from
    channel-wise decays, its inverse, three products with the state); a
    latent layer expanded attention over the causal half; lm_head at one
    position."""
    d = dims_of(config)
    n_kda, n_lat, _, _ = _counts(d)
    kd = d["kda_head_dim"]
    scan = seq_len * d["kda_heads"] * (6 * chunk * kd + 8 * kd * kd
                                       + 2 * chunk * chunk)
    attend = 2 * d["heads"] * seq_len * seq_len / 2 \
        * (d["qk_nope"] + d["qk_rope"] + d["v_head"])
    return rows * (2 * seq_len * _token_params(d) + n_kda * scan
                   + n_lat * attend + 2 * d["hidden"] * d["vocab_size"])


# -- 5. the faults' readings ----------------------------------------------------

def _gap_line(ref, firsts: dict, keep) -> dict:
    """Each control's widest gap under the reference's logits ``ref`` at the
    places ``keep``, and the share of them where its first token is
    another."""
    best, n = ref.max(axis=-1), np.arange(len(ref))
    out = {}
    for flag, first in firsts.items():
        gap = (best - ref[n, np.asarray(first)])[keep]
        out["int4" if flag is True else flag] = {
            "widest_gap": float(gap.max()),
            "other_first_share": float(np.mean(gap > 0))}
    return out


def fault_gaps(config: dict, seeds: list, *, rows: int = 1,
               length: int = 4096, served: int = 2048,
               flags: tuple = (True,) + FAULTS) -> list:
    """What each control reads on ``rows`` seeded rows of ``length`` token
    ids at their last ``served`` positions, a sample per seed, all in one
    walk: the widest gap by which the token that stream puts first lies
    below the reference's best, and the share of positions where it is
    another."""
    ids = np.concatenate([np.random.default_rng(seed).integers(
        1, config["vocab_size"], (rows, length)) for seed in seeds]
    ).astype(np.int32)
    n = rows * served
    at = np.tile(np.arange(length - served, length), len(ids))
    out = walk(config, ids, np.repeat(np.arange(len(ids)), served), at,
               (False,) + tuple(flags), first_only=tuple(flags))
    ref = np.asarray(out[False])
    return [{"seed": seed, **_gap_line(
        ref, {flag: out[flag] for flag in flags},
        np.arange(len(at)) // n == i)} for i, seed in enumerate(seeds)]


def sample_gaps(config: dict, rows: list, shape: tuple, flags: tuple) -> dict:
    """``benchmark/reference.py served_gaps`` with any of this family's
    controls beside the reference, in one walk: ``rows`` the ``(tokens,
    n_prompt)`` pairs of a sample, ``shape`` the cell's. The program's own
    widest gap, then each flag's."""
    n_rows, length, n_new = shape
    ids = np.zeros((n_rows, length), np.int32)
    pos = np.zeros((n_rows, n_new), np.int32)
    tok = np.zeros((n_rows, n_new), np.int32)
    live = np.zeros((n_rows, n_new), bool)
    for r, (tokens, n_prompt) in enumerate(rows):
        k = len(tokens) - n_prompt
        ids[r, :len(tokens)] = tokens
        pos[r, :k] = np.arange(n_prompt - 1, len(tokens) - 1)
        tok[r, :k], live[r, :k] = tokens[n_prompt:], True
    out = walk(config, ids, np.repeat(np.arange(n_rows), n_new),
               pos.reshape(-1), (False,) + tuple(flags),
               first_only=tuple(flags))
    ref, keep = np.asarray(out[False]), live.reshape(-1)
    gaps = _gap_line(ref, {"program": tok.reshape(-1),
                           **{flag: out[flag] for flag in flags}}, keep)
    return {"rows": len(rows), "served_tokens": int(keep.sum()),
            "positions": int(sum(len(t) for t, _ in rows)), **gaps}


def served_fault_gaps(cell: str, seeds: list, seconds: float,
                      flags: tuple, manifest: str, work_dir=None):
    """What each control reads on the tokens the PROGRAM served: one boot of
    the cell, one window a seed, and after the server has stopped each
    window's sample (the finished requests ``correct`` would take) through
    :func:`sample_gaps`. Yields a line a window."""
    from pathlib import Path

    from benchmark import harness as H
    from benchmark import warmup
    from benchmark.bundle import DEFAULT_WORK
    from benchmark.serve import Served

    ctx = H.load_cell(Path(manifest), cell)
    work = Path(work_dir) if work_dir else DEFAULT_WORK
    work.mkdir(parents=True, exist_ok=True)
    bundle = H.prepare(ctx, work)
    shape = H.reference_shape(ctx)
    samples = []
    with Served(bundle, work, traced=False, env=H.server_env(ctx)) as served:
        H.check_device(ctx, served.device)
        warmup.send(served, ctx["traffic"], ctx["config"])
        for seed in seeds:
            win = H.run_window(ctx, served, seed, seconds)
            samples.append((seed, win["summary"],
                            H.sample_rows(win["records"], seed, shape)))
    H.enable_reference_cache(work)
    for seed, summary, rows in samples:
        yield {"seed": seed, "out_tok_s": summary["out_tok_s"],
               "tpot_p90_ms": summary.get("tpot_p90_ms"),
               "failed": summary["failed"],
               **sample_gaps(ctx["config"], rows, shape, flags)}


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=fault_gaps.__doc__)
    ap.add_argument("--config", default=None)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--length", type=int, default=4096)
    ap.add_argument("--served", type=int, default=2048)
    ap.add_argument("--faults", default=None,
                    help="comma-separated subset (int4 for the control)")
    ap.add_argument("--cell", default=None,
                    help="read the faults on what this cell's program "
                         "serves (served_fault_gaps) in place of seeded rows")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--work-dir", default=None)
    args = ap.parse_args(argv)
    flags = (True,) + FAULTS
    if args.faults:
        flags = tuple(True if f == "int4" else f
                      for f in args.faults.split(","))
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.cell:
        lines = served_fault_gaps(args.cell, seeds, args.seconds, flags,
                                  args.manifest, args.work_dir)
    else:
        with open(args.config) as f:
            config = json.load(f)
        lines = fault_gaps(config, seeds, rows=args.rows, length=args.length,
                           served=args.served, flags=flags)
    for line in lines:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
