"""The ``deepseek_v3`` block as its public ``config.json`` keys describe it
(``model_type`` ``deepseek_v3`` without query compression: ``q_lora_rank``
null), served by the program's ``deepseek-v3`` builder with int8 kernels and
one float32 scale per output channel.

**The layer equations the reference follows.** Hidden ``h``, ``H`` heads,
RMSNorm with ``rms_norm_eps``, SiLU, untied head, no rope scaling.

- Attention, every layer: ``a = rmsnorm(x)``; ``q = a W_q`` -> [H, nope +
  rope] = ``q_nope | q_pe``; ``a W_kva`` -> [rank + rope] = ``c | k_pe``;
  ``c = rmsnorm_kv_a(c)``; ``k_pe = rope(k_pe)`` (ONE key for all heads),
  ``q_pe = rope(q_pe)``, with ``rope_interleave``: the rope dims are read
  as (even, odd) pairs, pair i turning with frequency i, and come out as
  the two halves ``[even' | odd']``; ``c W_kvb`` -> [H, nope + v] =
  ``k_nope | v``; ``scores = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope +
  rope)``, causal softmax in float32, ``out = probs v`` -> [H x v] ``W_o``
  -> h, added to the residual. Always EXPANDED here (keys and values of
  every position through ``W_kvb``): the program's absorbed decode form is
  the same function, and this walk is what says so.
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``. Of the others: ``m = rmsnorm(x)``; ``s =
  sigmoid(m W_r)`` over the routed experts in float32; the
  ``num_experts_per_tok`` experts with the largest ``s + b`` (``b`` =
  ``e_score_correction_bias``; ties to the lowest index); weights ``s`` at
  those WITHOUT ``b``, divided by their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum_i w_i SwiGLU_i(m) +
  SwiGLU_shared(m)`` with the shared experts as one SwiGLU of width
  ``n_shared_experts x moe_intermediate_size``; added to the residual. No
  capacity, no dropped token. Every expert runs on every token in a loop
  and the gates of the unchosen are zero: plain, and exact.

**Departures from the published description**: ``n_group`` / ``topk_group``
above 1 (group-limited routing) and ``q_lora_rank`` are refused, not
approximated; ``rope_scaling`` must be null (so no ``mscale``).

**Weights** (recorded under ``assumed`` in the configuration file):
int8 kernels uniform over the full range with one float32 scale of
1/(127*sqrt(fan_in)) per output channel, fan-in by path (each projection
maps unit variance to about 0.58), the routed experts' stacks among them;
the llama family's embedding (int8 uniform x 2^-12, exact in bfloat16) and
unit norm gains, ``kv_a_norm`` among them; a float32 router of int8-uniform
values x 2/(127*sqrt(h)): logits of unit order, so that routing is neither
uniform nor one-hot; ``e_score_correction_bias`` int8-uniform x 0.05/127,
small and nonzero so that a program which ignored it, or weighted by it,
is seen.

**The routed experts of a layer are kin** (``KIN_EIGHTHS``): each int8
kernel of an expert is ``(7 x the layer's common draw + the expert's own
draw) / 8``, rounded, at the scale every other kernel has. Why. A top-6 of
128 has near-ties: the bfloat16 program's hidden state differs from the
float32 reference's by enough to flip the marginal expert in one
token-layer of ten (a CPU walk at the cell's widths with bfloat16 rounding
at the program's places), so half of all places hold a flip somewhere and
no comparison can step around them. Trained experts near a routing
boundary do alike there; experts seeded apart are unrelated vectors, a
flip swaps 0.4 x (one for another), and sound runs read 0.35-0.60 on the
chip where the int4 control reads 0.97-1.36 and, in that walk, int4 in the
expert stacks alone 0.50. The router's scale is no knob (flips per
token-layer are scale-free, measured) and neither is the size of the
routed sum (PR 27's first answer, the down-projections at a quarter: it
shrank every fault of the routed FFN with the flip, so that int4 experts,
half the routing scale or no routed sum at all read under its limit). Kin
experts shrink what telling two experts APART is worth and nothing else:
the routed sum, its scale, every byte of every stack and what rounding
them costs stay at full size. On the chip at the cell's size (PERF.md
section 2): sound 0.030-0.052, ``no_routed`` 2.1-2.7, ``half_scale``
1.2-1.4, ``int4`` 0.78-1.24, ``no_bias`` 0.12-0.14, ``int4_experts``
0.09-0.13, limit 0.09. The price, said plainly: ONE wrong pick reads like
a sound flip, by construction; wrong picks as a rule (the bias ignored)
read over the limit, int4 in the expert stacks ALONE reads at it, and the
CPU tests, exact to 2e-5, hold every single pick.

**Controls.** ``True``: every int8 kernel (the expert stacks too) rounded
to int4, the nearest precision below the one the configurations state (the
harness's control; the router and its bias are float32 in both). ``FAULTS``
plant one fault in the routed FFN alone, for the limit's sake:
``int4_experts`` (the three stacks only), ``no_routed`` (the routed sum
dropped), ``no_bias`` (the choice made without the bias), ``half_scale``
(``routed_scaling_factor`` halved). ``python3 -m
benchmark.families.deepseek_v3 --config <file> --seeds 1,2`` walks them all
on seeded rows and prints each one's widest gap.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights

SCOPES = ("embed", "qkv_proj", "kv_write", "attend", "o_proj", "mlp",
          "lm_head", "sample", "kv_window", "mla_absorb", "router", "experts",
          "shared_expert")
WITNESS = ("qkv_proj", "mla_absorb", "router", "sample")


# -- 1. the widths, under the program's names --------------------------------

def dims_of(config: dict) -> dict:
    """The configuration's keys under the names the program's
    ``deepseek-v3`` builder takes (``LlamaConfig`` fields). Booleans go as
    the strings a recipe's TOML would hand the builder anyway."""
    if config.get("q_lora_rank") is not None or config.get("rope_scaling") \
            or config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1 \
            or config.get("attention_bias") or config.get("moe_layer_freq", 1) != 1:
        raise ValueError("deepseek-v3 family: query compression, rope "
                         "scaling, attention bias, group-limited routing and "
                         "a layer frequency other than 1 are not written")
    return {
        "vocab_size": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "mlp": config["intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "max_len": int(config["context_served"]),
        "qk_nope": config["qk_nope_head_dim"],
        "qk_rope": config["qk_rope_head_dim"],
        "v_head": config["v_head_dim"],
        "kv_lora_rank": config["kv_lora_rank"],
        "rope_interleave": str(bool(config["rope_interleave"])).lower(),
        "first_dense_layers": config["first_k_dense_replace"],
        "moe_experts": config["n_routed_experts"],
        "moe_top_k": config["num_experts_per_tok"],
        "moe_intermediate": config["moe_intermediate_size"],
        "n_shared_experts": config["n_shared_experts"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "norm_topk_prob": str(bool(config["norm_topk_prob"])).lower(),
        "scoring_func": config["scoring_func"],
    }


def _fan_in(path: str, config: dict) -> int | None:
    """Rows of the kernel whose scale (or expert stack) lies at ``path``."""
    h, m = config["hidden_size"], config["moe_intermediate_size"]
    name = path.split("/")[-2] if path.endswith("/scale") else \
        path.split("/")[-1]
    return {
        "q_proj": h, "kv_a_proj": h, "kv_b_proj": config["kv_lora_rank"],
        "o_proj": config["num_attention_heads"] * config["v_head_dim"],
        "gate_proj": h, "up_proj": h, "down_proj": config["intermediate_size"],
        "shared_gate_proj": h, "shared_up_proj": h,
        "shared_down_proj": config["n_shared_experts"] * m,
        "experts_gate_scale": h, "experts_up_scale": h,
        "experts_down_scale": m, "lm_head": h,
    }.get(name)


# -- 2. the leaves -------------------------------------------------------------

ROUTER_STEP = 2.0 / 127.0     # x 1/sqrt(hidden): logits of unit order
BIAS_STEP = 0.05 / 127.0
KIN_EIGHTHS = 7               # of 8: the layer's common draw in an expert
FAULTS = ("int4_experts", "no_routed", "no_bias", "half_scale")
_STACKS = ("/experts_gate_int8", "/experts_up_int8", "/experts_down_int8")


def leaf(seed: int, path: str, shape, dtype, config: dict):
    """One parameter leaf by its path in the program's tree, e.g.
    ``layer_3/moe/experts_up_int8`` or ``layer_0/kv_b_proj/scale``."""
    name = np.dtype(dtype).name
    if name == "int8" and path.endswith("/kernel_int8"):
        return weights.int8_draw(seed, path, shape)
    if name == "int8" and path.endswith(_STACKS):
        # [E, in, out]: whole numbers all the way, so every machine rounds
        # alike; in place: 0.6 s for a stack of 0.2 G where a float mix is 5
        mix = weights.int8_draw(seed, path, shape).astype(np.int16)
        mix *= 8 - KIN_EIGHTHS
        mix += KIN_EIGHTHS * weights.int8_draw(
            seed, path + "/common", shape[1:]).astype(np.int16) + 4
        mix >>= 3
        return mix.astype(np.int8)
    if path.endswith("embedding"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * weights.EMBED_STEP).astype(dtype)
    if path.endswith("norm/scale"):     # attn, mlp, kv_a and final norm gains
        return np.ones(shape, dtype)
    if path.endswith(("/scale", "_scale")):
        fan_in = _fan_in(path, config)
        if fan_in is None:
            return None
        return np.full(shape, 1.0 / (127.0 * fan_in ** 0.5), dtype)
    if path.endswith("/moe/router"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * (ROUTER_STEP / config["hidden_size"] ** 0.5)).astype(dtype)
    if path.endswith("/moe/e_score_correction_bias"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * BIAS_STEP).astype(dtype)
    return None


# -- 3. the reference's walk ---------------------------------------------------

def _layer_fns(d: dict, fault):
    """The three jitted parts of a walk: ``fault`` False for the reference,
    True for the int4 control, or one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp

    heads, nope, rope_d, vd = d["heads"], d["qk_nope"], d["qk_rope"], d["v_head"]
    rank, top_k, eps = d["kv_lora_rank"], d["moe_top_k"], d["norm_eps"]
    scaling = d["routed_scaling_factor"] * (0.5 if fault == "half_scale"
                                            else 1.0)
    interleave = d["rope_interleave"] == "true"
    norm_topk = d["norm_topk_prob"] == "true"

    def deq(w, scale, int4=fault is True):
        w = w.astype(jnp.float32)
        if int4:
            w = jnp.clip(jnp.round(w / 16.0), -8, 7) * 16.0
        return w * scale

    def norm(x, g):
        return x * (jnp.mean(x * x, -1, keepdims=True) + eps) ** -0.5 * g

    def rope(x, cos, sin):  # [r, s, n, rope_d]; cos, sin [1, s, 1, rope_d/2]
        if interleave:
            x1, x2 = x[..., 0::2], x[..., 1::2]
        else:
            x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def swiglu(m, gate, up, down, int4=fault is True):
        return (jax.nn.silu(m @ deq(*gate, int4))
                * (m @ deq(*up, int4))) @ deq(*down, int4)

    def attention(x, cos, sin, p):
        r, s, _ = x.shape
        a = norm(x, p["attn_norm"])
        q = (a @ deq(*p["q_proj"])).reshape(r, s, heads, nope + rope_d)
        kva = a @ deq(*p["kv_a_proj"])
        c = norm(kva[..., :rank], p["kv_a_norm"])
        k_pe = rope(kva[..., rank:].reshape(r, s, 1, rope_d), cos, sin)
        q_pe = rope(q[..., nope:], cos, sin)
        kv = (c @ deq(*p["kv_b_proj"])).reshape(r, s, heads, nope + vd)
        scores = (jnp.einsum("rqhd,rkhd->rhqk", q[..., :nope], kv[..., :nope])
                  + jnp.einsum("rqhd,rkd->rhqk", q_pe, k_pe[:, :, 0])) \
            / np.sqrt(nope + rope_d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        att = jnp.einsum("rhqk,rkhd->rqhd", probs, kv[..., nope:])
        return x + att.reshape(r, s, heads * vd) @ deq(*p["o_proj"])

    def dense_layer(x, cos, sin, p):
        x = attention(x, cos, sin, p)
        return x + swiglu(norm(x, p["mlp_norm"]), p["gate_proj"],
                          p["up_proj"], p["down_proj"])

    def routed_layer(x, cos, sin, p):
        x = attention(x, cos, sin, p)
        m = norm(x, p["mlp_norm"])
        scores = jax.nn.sigmoid(m @ p["router"]) if d["scoring_func"] == \
            "sigmoid" else jax.nn.softmax(m @ p["router"], axis=-1)
        bias = 0.0 if fault == "no_bias" else p["bias"]
        _, chosen = jax.lax.top_k(scores + bias, top_k)         # lowest index
        gates = jnp.take_along_axis(scores, chosen, axis=-1)    # wins a tie
        if norm_topk:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        gate_of = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1])
                          * (gates * scaling)[..., None], axis=-2)  # [r, s, E]

        def one(y, e):
            w = tuple((jax.lax.dynamic_index_in_dim(p[k][0], e, 0, False),
                       jax.lax.dynamic_index_in_dim(p[k][1], e, 0, False))
                      for k in ("gate", "up", "down"))
            out = swiglu(m, *w, int4=fault in (True, "int4_experts"))
            return y + out * jax.lax.dynamic_index_in_dim(
                gate_of, e, 2, True), None

        y = jnp.zeros_like(x)
        if fault != "no_routed":
            y, _ = jax.lax.scan(one, y, jnp.arange(scores.shape[-1]))
        return x + y + swiglu(m, p["shared_gate_proj"], p["shared_up_proj"],
                              p["shared_down_proj"])

    def head(x, rows, pos, g, w, scale):
        return norm(x[rows, pos], g) @ deq(w, scale)

    return jax.jit(dense_layer), jax.jit(routed_layer), jax.jit(head)


def walk(config: dict, ids: np.ndarray, rows_op, pos_op, flags: tuple, *,
         first_only: tuple = ()):
    """Logits at ``(rows_op, pos_op)`` of the batch ``ids`` [rows, length],
    one array per flag (False = the float32 reference, True = its int4
    control, or one of ``FAULTS``), walking the layers once with one layer's
    weights on the device at a time."""
    import jax
    import jax.numpy as jnp

    d = dims_of(config)
    h, heads, e = d["hidden"], d["heads"], d["moe_experts"]
    m, rank, rope_d = d["moe_intermediate"], d["kv_lora_rank"], d["qk_rope"]

    def get(path, shp, dtype):
        return jnp.asarray(weights.leaf(config, path, shp, dtype))

    def kernel(at, name, shp):
        return (get(f"{at}/{name}/kernel_int8", shp, "int8"),
                get(f"{at}/{name}/scale", (1, shp[1]), "float32"))

    freqs = 1.0 / (d["rope_theta"]
                   ** (np.arange(0, rope_d, 2, dtype=np.float32) / rope_d))
    ang = np.arange(ids.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    embed = weights.leaf(config, "embed/embedding", (d["vocab_size"], h),
                         "float32")
    x0 = jnp.asarray(embed[ids])
    del embed
    fns = {flag: _layer_fns(d, flag) for flag in flags}
    xs = {flag: x0 for flag in flags}
    attn_shapes = (("q_proj", (h, heads * (d["qk_nope"] + rope_d))),
                   ("kv_a_proj", (h, rank + rope_d)),
                   ("kv_b_proj", (rank, heads * (d["qk_nope"] + d["v_head"]))),
                   ("o_proj", (heads * d["v_head"], h)))
    with jax.default_matmul_precision("highest"):
        for i in range(d["layers"]):
            at = f"layer_{i}"
            p = {"attn_norm": get(f"{at}/attn_norm/scale", (h,), "float32"),
                 "kv_a_norm": get(f"{at}/kv_a_norm/scale", (rank,), "float32"),
                 "mlp_norm": get(f"{at}/mlp_norm/scale", (h,), "float32")}
            for name, shp in attn_shapes:
                p[name] = kernel(at, name, shp)
            if i < d["first_dense_layers"]:
                for name, shp in (("gate_proj", (h, d["mlp"])),
                                  ("up_proj", (h, d["mlp"])),
                                  ("down_proj", (d["mlp"], h))):
                    p[name] = kernel(at, name, shp)
                kind = 0
            else:
                moe = f"{at}/moe"
                p["router"] = get(f"{moe}/router", (h, e), "float32")
                p["bias"] = get(f"{moe}/e_score_correction_bias", (e,),
                                "float32")
                for name, shp in (("gate", (e, h, m)), ("up", (e, h, m)),
                                  ("down", (e, m, h))):
                    p[name] = (get(f"{moe}/experts_{name}_int8", shp, "int8"),
                               get(f"{moe}/experts_{name}_scale",
                                   (e, 1, shp[2]), "float32"))
                width = d["n_shared_experts"] * m
                for name, shp in (("shared_gate_proj", (h, width)),
                                  ("shared_up_proj", (h, width)),
                                  ("shared_down_proj", (width, h))):
                    p[name] = kernel(moe, name, shp)
                kind = 1
            xs = {flag: fns[flag][kind](x, cos, sin, p)
                  for flag, x in xs.items()}
            del p
        g = get("final_norm/scale", (h,), "float32")
        w = get("lm_head/kernel_int8", (h, d["vocab_size"]), "int8")
        sc = get("lm_head/scale", (1, d["vocab_size"]), "float32")
        out = {}
        for flag, x in xs.items():
            logits = fns[flag][2](x, jnp.asarray(rows_op), jnp.asarray(pos_op),
                                  g, w, sc)
            out[flag] = logits.argmax(axis=-1) if flag in first_only else logits
        return out


# -- 4. what a step needs: int8 kernels at 1 byte, a float32 router, a bf16
# latent cache ----------------------------------------------------------------

def _attention_params(d: dict) -> int:
    h, heads = d["hidden"], d["heads"]
    return h * heads * (d["qk_nope"] + d["qk_rope"]) \
        + h * (d["kv_lora_rank"] + d["qk_rope"]) \
        + d["kv_lora_rank"] * heads * (d["qk_nope"] + d["v_head"]) \
        + heads * d["v_head"] * h


def _expert_params(d: dict) -> int:
    return 3 * d["hidden"] * d["moe_intermediate"]


def _shared_params(d: dict) -> int:
    return d["n_shared_experts"] * _expert_params(d)


def experts_touched(d: dict, rows: float) -> float:
    """Distinct routed experts a step of ``rows`` tokens is expected to
    need in one layer under even routing: E (1 - (1 - k/E)^rows)."""
    e, k = d["moe_experts"], d["moe_top_k"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def moe_step_bytes(config: dict, *, rows: float) -> float:
    """Bytes the routed FFNs of ONE decode step need: in each routed
    layer the float32 router, the shared experts' kernels and the routed
    experts the step's rows are expected to touch (not all of them: a
    program that streams every expert reads its true share of this)."""
    d = dims_of(config)
    routed = d["layers"] - d["first_dense_layers"]
    return routed * (4 * d["hidden"] * d["moe_experts"] + _shared_params(d)
                     + experts_touched(d, rows) * _expert_params(d))


def decode_step_bytes(config: dict, *, rows: float, context: float) -> float:
    """HBM bytes ONE decode step needs: every kernel outside the routed
    experts once, the routed FFNs' share (``moe_step_bytes``), the head,
    and each live row's latent cache rows: (kv_lora_rank + qk_rope) bf16
    values a token a layer."""
    d = dims_of(config)
    dense = d["first_dense_layers"]
    kernels = d["layers"] * _attention_params(d) \
        + dense * 3 * d["hidden"] * d["mlp"] + d["hidden"] * d["vocab_size"]
    cache = d["layers"] * 2 * (d["kv_lora_rank"] + d["qk_rope"])
    return kernels + moe_step_bytes(config, rows=rows) + rows * context * cache


def _token_params(d: dict) -> float:
    """Parameters one token's matmuls in the layers use: its own top-k
    experts, not all of them."""
    routed = d["layers"] - d["first_dense_layers"]
    return d["layers"] * _attention_params(d) \
        + d["first_dense_layers"] * 3 * d["hidden"] * d["mlp"] \
        + routed * (d["hidden"] * d["moe_experts"] + _shared_params(d)
                    + d["moe_top_k"] * _expert_params(d))


def decode_step_flops(config: dict, *, rows: float, context: float) -> float:
    """Absorbed attention: scores over rank + rope and the weighted sum
    over rank, per head and cached position."""
    d = dims_of(config)
    attend = 2 * d["heads"] * context * (2 * d["kv_lora_rank"] + d["qk_rope"])
    return rows * (2 * _token_params(d) + 2 * d["hidden"] * d["vocab_size"]
                   + d["layers"] * attend)


def prefill_flops(config: dict, *, rows: int, seq_len: int) -> float:
    """Expanded attention over ``seq_len`` tokens a row, lm_head at one
    position."""
    d = dims_of(config)
    attend = d["layers"] * 2 * d["heads"] * seq_len * seq_len \
        * (d["qk_nope"] + d["qk_rope"] + d["v_head"]) / 2
    return rows * (2 * seq_len * _token_params(d) + attend
                   + 2 * d["hidden"] * d["vocab_size"])


# -- 5. the routed FFN's own controls ------------------------------------------

def fault_gaps(config: dict, seeds: list, *, rows: int = 4, length: int = 448,
               served: int = 320) -> list:
    """What each control reads on ``rows`` seeded rows of ``length`` token
    ids at their last ``served`` positions (the shape of a cell's sample),
    a sample per seed, all in one walk: the widest gap by which the token
    that stream puts first lies below the reference's best, and the share
    of positions where it is another."""
    ids = np.concatenate([np.random.default_rng(seed).integers(
        0, config["vocab_size"], (rows, length)) for seed in seeds]
    ).astype(np.int32)
    n = rows * served                                   # places a sample
    at = np.tile(np.arange(length - served, length), len(ids))
    controls = (True,) + FAULTS
    out = walk(config, ids, np.repeat(np.arange(len(ids)), served), at,
               (False,) + controls, first_only=controls)
    ref = np.asarray(out[False])
    best = ref.max(axis=-1)
    gaps = {flag: best - ref[np.arange(len(at)), np.asarray(out[flag])]
            for flag in controls}
    return [{"seed": seed, **{
        "int4" if flag is True else flag: {
            "widest_gap": float(gap[i * n:(i + 1) * n].max()),
            "other_first_share": float(np.mean(gap[i * n:(i + 1) * n] > 0))}
        for flag, gap in gaps.items()}} for i, seed in enumerate(seeds)]


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=fault_gaps.__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    for line in fault_gaps(config, [int(s) for s in args.seeds.split(",")]):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
