"""The ``deepseek_v32`` block (DeepSeek-V3.2-Exp) as its public
``config.json`` keys and the model's published inference code
(``inference/model.py``: classes ``MLA``, ``Indexer``, ``Gate``) describe
it, served by the program's ``deepseek-v32`` builder with int8 kernels and
one float32 scale per output channel, and cut to ONE chip's share of a
stated deployment: the chip holds ``n_routed_experts`` of the
``routed_experts_published`` routed experts of a layer (ids
``first_routed_expert`` ..) and a slice of the vocabulary.

**The layer equations the reference follows.** Hidden ``h``, ``H`` heads,
RMSNorm with ``rms_norm_eps``, SiLU, untied head; token t, ``a =
rmsnorm(x)``.

1. Query: ``c_q = rmsnorm_q_a(a W_qa)`` (``q_lora_rank``); ``q = c_q W_qb``
   -> [H, nope + rope] = ``q_nope | q_pe``; ``q_pe`` roped, rotary dims as
   INTERLEAVED (even, odd) pairs.
2. Key/value: ``a W_kva`` -> [rank + rope] = ``c | k_pe``; ``c =
   rmsnorm_kv_a(c)``; ``k_pe`` roped (interleaved), ONE key for all heads;
   ``c W_kvb`` -> [H, nope + v] = ``k_nope | v``.
3. Indexer: ``qI = c_q W_Iq`` -> [J, D] (``index_n_heads`` x
   ``index_head_dim``); ``kI = LayerNorm(a W_Ik)`` (gain and bias, eps
   1e-6), ONE key a token; the FIRST ``rope`` dims of ``qI_j`` and ``kI``
   roped with the same frequencies, rotary dims as HALVES (rotate-half,
   never interleaved); ``w = (a W_w) x J^-1/2 x D^-1/2`` in float32;
   ``I(t, s) = sum_j w_j ReLU(qI_j . kI_s)`` for ``s <= t``.
4. Selection: ``S_t`` = the ``min(index_topk, t + 1)`` positions ``s <= t``
   of largest ``I(t, s)``, ties to the lowest position, exact.
5. Attention: ``softmax_{s in S_t}(scale x (q_nope . k_nope + q_pe .
   k_pe))`` in float32, ``scale = (nope + rope)^-1/2 x m^2``, ``m = 0.1 x
   mscale x ln(factor) + 1``; ``out = probs v`` -> [H x v] ``W_o`` -> h,
   added to the residual. Always EXPANDED here, a block of queries a turn.
6. YaRN, both ropes: ``theta_d = rope_theta^(-2d/rope)``; ``ramp_d =
   clip((d - lo) / (hi - lo), 0, 1)`` with ``lo`` / ``hi`` the floor / the
   ceiling of the correction dims of ``beta_fast`` / ``beta_slow`` rotations
   over ``original_max_position_embeddings``; ``theta_d <- theta_d / factor
   x ramp_d + theta_d x (1 - ramp_d)``; cos and sin unscaled.
7. FFN of the first ``first_k_dense_replace`` layers: SwiGLU of width
   ``intermediate_size``. Of the others: ``m = rmsnorm(x)``; ``s =
   sigmoid(m W_r)`` over ALL published experts, float32; choice score ``s +
   b``; a group (``n_group`` groups of consecutive experts) scores the sum
   of its two largest choice scores; the ``topk_group`` best groups stay;
   the ``num_experts_per_tok`` best experts inside them by ``s + b``;
   weights ``s`` (no ``b``) of those, over their sum, x
   ``routed_scaling_factor``. ``y = sum_{picked e HELD HERE} g_e SwiGLU_e(m)
   + SwiGLU_shared(m)``: the weights are normalised over all picks whoever
   holds them, and what the absent experts would add is left out, here as
   in the program (the guide's section 4: a chip's share). Every held
   expert runs on every token in a loop and the gates of the unchosen are
   zero: plain, and exact.
8. Final norm; the head over this chip's ``vocab_size`` rows.

**Departures from the published description** (``assumed`` in the
configuration file): the indexer's products in bfloat16 and its keys cached
in bfloat16 where the model publishes FP8 (a v5e has no FP8 unit: more
precise, never less); the Hadamard rotation the published indexer applies
to ``qI`` and ``kI`` before quantising is left out (orthogonal, applied to
both sides: every ``qI . kI`` is unchanged; it exists for FP8's sake); the
multi-token-prediction module is not on this chip (it sits behind the last
layer); ``rope_interleave`` true as the published code reads its rotary
dims.

**Weights.** As ``deepseek_v3.py``: int8 kernels uniform over the full
range with one float32 scale of 1/(127 sqrt(fan_in)) per output channel
(each projection maps unit variance to about 0.58), the embedding
int8-uniform x 2^-12, unit norm gains (LayerNorm: unit gain, zero bias), a
float32 router int8-uniform x 2/(127 sqrt(h)), ``e_score_correction_bias``
int8-uniform x 0.05/127, the routed experts of a layer kin
(``KIN_EIGHTHS``, for the reason given there: a top-8 of 256 has near-ties).
EXCEPT what makes the selection matter: at the fan-in scale every attention
logit is of order 0.1, the softmax over 2048 selected rows is nearly
uniform, and the mean over ANY 2048 rows reads the same, so a wrong
selection would pass ``correct``. So

``q_b_proj``'s scale is x ``Q_GAIN`` (1.5): attention logits with a standard
deviation of about 1 (0.63 without it, at these widths and under
``mscale``), so a range of several units over the 2048 selected rows, a
softmax that a few hundred of them carry, and an attention output that
counts beside the FFN's. The index scores need no such rule: ``sum_j w_j ReLU(q_j . k)``
is positively homogeneous, so no scale moves the choice, and a float's
rounding is relative; ``index_weights_proj`` is float32, int8-uniform x
``INDEX_W_STEP`` / sqrt(h), head weights of either sign. What the limit
then has to tolerate: bfloat16 moves a score by about half a percent of
the scores' spread, and near the 2048th of 10000 a dozen keys lie that
close, so program and reference differ in about ten of a query's 2048 rows
in every layer, a tenth of that layer's attention output in norm whatever
the softmax's temperature (sqrt(2 x 10 / 2048)): to selection what a
near-tied expert is to routing. A fault of the selection moves half or
more of the set.
The mathematics and every byte moved are unchanged. ``python3 -m
benchmark.families.deepseek_v32 --config <file> --seeds 1,2`` walks the
faults below on seeded rows and prints each one's widest gap; with ``--cell
<cell>`` in place of ``--config`` it boots the cell's program and reads them
on the tokens that program served, the sample ``correct`` takes; PERF.md
section 2 has the chip's readings and the limit they set.

**Controls.** ``True``: every int8 kernel (the expert stacks too) rounded
to int4. ``FAULTS`` plant one fault each: ``no_indexer`` (every visible row
attended), ``topk_half`` (half of ``index_topk``), ``stale_index`` (the
indexer keys of the served positions never written: zeros, what an empty
cache holds), ``index_interleaved`` (the indexer's rope read as interleaved
pairs), ``no_mscale`` (``m`` = 1), ``no_yarn`` (plain frequencies),
``no_groups`` (plain top-k of all experts), ``no_q_norm`` (``c_q`` without
its norm), ``int4_experts`` (the held stacks only).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import weights

SCOPES = ("embed", "qkv_proj", "kv_write", "attend", "o_proj", "mlp",
          "lm_head", "sample", "kv_window", "mla_absorb", "router", "experts",
          "shared_expert", "dsa_index", "dsa_select")
WITNESS = ("qkv_proj", "mla_absorb", "router", "sample", "dsa_index")

QUERY_BLOCK = 128   # queries one turn of the reference's attention scores


# -- 1. the widths, under the program's names --------------------------------

def dims_of(config: dict) -> dict:
    """The configuration's keys under the names the program's
    ``deepseek-v32`` builder takes (``LlamaConfig`` fields, and the
    builder's YaRN scalars). Booleans go as the strings a recipe's TOML
    would hand the builder anyway."""
    if config.get("attention_bias") or config.get("moe_layer_freq", 1) != 1 \
            or config.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("deepseek-v32 family: attention bias, a layer "
                         "frequency other than 1 and a gate other than "
                         "noaux_tc are not written")
    rs = config.get("rope_scaling") or {}
    if rs and rs.get("type") != "yarn":
        raise ValueError("deepseek-v32 family: rope scaling is yarn or none")
    if rs and rs.get("mscale", 1) != rs.get("mscale_all_dim", 0):
        raise ValueError("deepseek-v32 family: mscale differs from "
                         "mscale_all_dim (cos and sin scaled by their ratio "
                         "is not written, here or in the program)")
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
        "q_lora_rank": config["q_lora_rank"],
        "index_heads": config["index_n_heads"],
        "index_head_dim": config["index_head_dim"],
        "index_topk": config["index_topk"],
        "rope_interleave": str(bool(config.get("rope_interleave", True))
                               ).lower(),
        "first_dense_layers": config["first_k_dense_replace"],
        "moe_experts": config["routed_experts_published"],
        "moe_experts_held": config["n_routed_experts"],
        "moe_first_expert": config.get("first_routed_expert", 0),
        "moe_n_group": config["n_group"],
        "moe_topk_group": config["topk_group"],
        "moe_top_k": config["num_experts_per_tok"],
        "moe_intermediate": config["moe_intermediate_size"],
        "n_shared_experts": config["n_shared_experts"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "norm_topk_prob": str(bool(config["norm_topk_prob"])).lower(),
        "scoring_func": config["scoring_func"],
        "rope_factor": float(rs.get("factor", 1.0)),
        "rope_original_len": int(rs.get("original_max_position_embeddings",
                                        4096)),
        "rope_beta_fast": float(rs.get("beta_fast", 32)),
        "rope_beta_slow": float(rs.get("beta_slow", 1)),
        "rope_mscale": float(rs.get("mscale", 1)),
    }


def _fan_in(path: str, config: dict) -> int | None:
    """Rows of the kernel whose scale (or expert stack) lies at ``path``."""
    h, m = config["hidden_size"], config["moe_intermediate_size"]
    name = path.split("/")[-2] if path.endswith("/scale") else \
        path.split("/")[-1]
    return {
        "q_a_proj": h, "q_b_proj": config["q_lora_rank"],
        "kv_a_proj": h, "kv_b_proj": config["kv_lora_rank"],
        "o_proj": config["num_attention_heads"] * config["v_head_dim"],
        "index_wq_b": config["q_lora_rank"], "index_wk": h,
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
Q_GAIN = 1.5                  # on q_b_proj's scale: attention logits of units
INDEX_W_STEP = 2.0 / 127.0    # x 1/sqrt(hidden): head weights of unit order
FAULTS = ("no_indexer", "topk_half", "stale_index", "index_interleaved",
          "no_mscale", "no_yarn", "no_groups", "no_q_norm", "int4_experts")
_STACKS = ("/experts_gate_int8", "/experts_up_int8", "/experts_down_int8")
_GAINS = {"q_b_proj": Q_GAIN}


def leaf(seed: int, path: str, shape, dtype, config: dict):
    """One parameter leaf by its path in the program's tree, e.g.
    ``layer_3/moe/experts_up_int8`` or ``layer_0/index_wq_b/scale``. An
    expert stack holds the experts this chip holds, drawn under their
    PUBLISHED ids (the stack's row i is expert ``first_routed_expert`` +
    i), so another share of the same layer draws other experts."""
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
    if path.endswith(("norm/scale", "index_k_norm_scale")):
        return np.ones(shape, dtype)
    if path.endswith("index_k_norm_bias"):
        return np.zeros(shape, dtype)
    if path.endswith("index_weights_proj"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * (INDEX_W_STEP / config["hidden_size"] ** 0.5)).astype(dtype)
    if path.endswith(("/scale", "_scale")):
        fan_in = _fan_in(path, config)
        if fan_in is None:
            return None
        gain = _GAINS.get(path.split("/")[-2], 1.0)
        return np.full(shape, gain / (127.0 * fan_in ** 0.5), dtype)
    if path.endswith("/moe/router"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * (ROUTER_STEP / config["hidden_size"] ** 0.5)).astype(dtype)
    if path.endswith("/moe/e_score_correction_bias"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * BIAS_STEP).astype(dtype)
    return None


# -- 3. the reference's walk ---------------------------------------------------

def rope_freqs(d: dict, yarn: bool = True) -> np.ndarray:
    """The rotary pairs' frequencies, YaRN's blend applied (equation 6)."""
    dim, base = d["qk_rope"], d["rope_theta"]
    freqs = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    factor = d["rope_factor"]
    if not yarn or factor == 1.0:
        return freqs

    def correction_dim(rotations):
        return dim * math.log(d["rope_original_len"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(correction_dim(d["rope_beta_fast"])), 0)
    hi = min(math.ceil(correction_dim(d["rope_beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - lo)
                   / (hi - lo if hi != lo else 0.001), 0.0, 1.0)
    return (freqs / factor * ramp + freqs * (1.0 - ramp)).astype(np.float32)


def softmax_scale(d: dict, mscale: bool = True) -> float:
    scale = (d["qk_nope"] + d["qk_rope"]) ** -0.5
    if mscale and d["rope_factor"] != 1.0:
        scale *= (0.1 * d["rope_mscale"] * math.log(d["rope_factor"])
                  + 1.0) ** 2
    return scale


def route(scores, bias, d: dict, groups: bool = True):
    """Equation 7's choice: ``scores`` [..., E] float32 -> (chosen [..., k],
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


def select(score, visible, k: int):
    """Equation 4: ``score`` [q, t] float32, ``visible`` [q, t] bool ->
    [q, t] bool, the k visible positions of largest score, ties to the
    lowest position (``jax.lax.top_k``'s order), all where fewer."""
    import jax
    import jax.numpy as jnp

    if score.shape[-1] <= k:
        return visible
    _, at = jax.lax.top_k(jnp.where(visible, score, -jnp.inf), k)
    picked = jnp.zeros(score.shape, bool).at[
        jnp.arange(score.shape[0])[:, None], at].set(True)
    return picked & visible


def _layer_fns(d: dict, fault):
    """The three jitted parts of a walk: ``fault`` False for the reference,
    True for the int4 control, or one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp

    heads, nope, rope_d, vd = d["heads"], d["qk_nope"], d["qk_rope"], d["v_head"]
    rank, eps = d["kv_lora_rank"], d["norm_eps"]
    n_idx, d_idx = d["index_heads"], d["index_head_dim"]
    topk = d["index_topk"] // (2 if fault == "topk_half" else 1)
    scale = softmax_scale(d, mscale=fault != "no_mscale")
    first, held = d["moe_first_expert"], d["moe_experts_held"]

    def deq(w, scale, int4=fault is True):
        w = w.astype(jnp.float32)
        if int4:
            w = jnp.clip(jnp.round(w / 16.0), -8, 7) * 16.0
        return w * scale

    def norm(x, g):
        return x * (jnp.mean(x * x, -1, keepdims=True) + eps) ** -0.5 * g

    def rope(x, cos, sin, interleave):  # [.., rope_d]; cos, sin [.., rope_d/2]
        if interleave:
            x1, x2 = x[..., 0::2], x[..., 1::2]
        else:
            x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def swiglu(m, gate, up, down, int4=fault is True):
        return (jax.nn.silu(m @ deq(*gate, int4))
                * (m @ deq(*up, int4))) @ deq(*down, int4)

    def attend_row(q, k_nope, k_pe, v, q_idx, w_idx, k_idx):
        """One row, a block of queries a turn: q [s, H, nope + rope],
        k_nope [s, H, nope], k_pe [s, rope], v [s, H, vd], q_idx [s, J, D],
        w_idx [s, J], k_idx [s, D] -> [s, H, vd]."""
        s = q.shape[0]
        block = min(s, QUERY_BLOCK)
        turns = -(-s // block)
        pad = turns * block - s

        def cut(x):
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            return x.reshape(turns, block, *x.shape[1:])

        def turn(args):
            i, q_b, qi_b, wi_b = args
            pos = i * block + jnp.arange(block)
            visible = jnp.arange(s)[None, :] <= pos[:, None]
            picked = visible
            if fault != "no_indexer":
                dots = jnp.einsum("qjd,td->qjt", qi_b, k_idx)
                score = jnp.sum(jax.nn.relu(dots) * wi_b[..., None], axis=1)
                picked = select(score, visible, topk)
            logits = (jnp.einsum("qhd,thd->hqt", q_b[..., :nope], k_nope)
                      + jnp.einsum("qhd,td->hqt", q_b[..., nope:], k_pe)) \
                * scale
            probs = jax.nn.softmax(jnp.where(picked[None], logits, -jnp.inf),
                                   axis=-1)
            return jnp.einsum("hqt,thd->qhd", probs, v)

        out = jax.lax.map(turn, (jnp.arange(turns), cut(q), cut(q_idx),
                                 cut(w_idx)))
        return out.reshape(turns * block, heads, vd)[:s]

    def attention(x, cos, sin, stale_from, p):
        """One row: x [s, h]; cos, sin [s, rope / 2]."""
        s = x.shape[0]
        a = norm(x, p["attn_norm"])
        c_q = a @ deq(*p["q_a_proj"])
        if fault != "no_q_norm":
            c_q = norm(c_q, p["q_a_norm"])
        q = (c_q @ deq(*p["q_b_proj"])).reshape(s, heads, nope + rope_d)
        kva = a @ deq(*p["kv_a_proj"])
        c = norm(kva[..., :rank], p["kv_a_norm"])
        inter = d["rope_interleave"] == "true"
        k_pe = rope(kva[..., rank:], cos, sin, inter)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], cos[:, None], sin[:, None],
                                 inter)], axis=-1)
        kv = (c @ deq(*p["kv_b_proj"])).reshape(s, heads, nope + vd)
        # the indexer
        q_idx = (c_q @ deq(*p["index_wq_b"])).reshape(s, n_idx, d_idx)
        k_idx = a @ deq(*p["index_wk"])
        k_idx = k_idx - jnp.mean(k_idx, -1, keepdims=True)
        k_idx = k_idx * (jnp.mean(k_idx * k_idx, -1, keepdims=True)
                         + 1e-6) ** -0.5 * p["index_k_norm_scale"] \
            + p["index_k_norm_bias"]
        pairs = fault == "index_interleaved"
        q_idx = jnp.concatenate(
            [rope(q_idx[..., :rope_d], cos[:, None], sin[:, None], pairs),
             q_idx[..., rope_d:]], axis=-1)
        k_idx = jnp.concatenate(
            [rope(k_idx[..., :rope_d], cos, sin, pairs),
             k_idx[..., rope_d:]], axis=-1)
        if fault == "stale_index":
            k_idx = jnp.where(jnp.arange(s)[:, None] >= stale_from, 0.0,
                              k_idx)
        w_idx = (a @ p["index_weights_proj"]) * (n_idx * d_idx) ** -0.5
        att = attend_row(q, kv[..., :nope], k_pe, kv[..., nope:], q_idx,
                         w_idx, k_idx)
        return x + att.reshape(s, heads * vd) @ deq(*p["o_proj"])

    def dense_row(x, cos, sin, stale_from, p):
        x = attention(x, cos, sin, stale_from, p)
        return x + swiglu(norm(x, p["mlp_norm"]), p["gate_proj"],
                          p["up_proj"], p["down_proj"])

    def routed_row(x, cos, sin, stale_from, p):
        x = attention(x, cos, sin, stale_from, p)
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
        return x + y + swiglu(m, p["shared_gate_proj"], p["shared_up_proj"],
                              p["shared_down_proj"])

    def by_row(row_fn):
        # a row at a time: at the cell's size one row's float32 queries,
        # keys and values are 3.5 GB, four rows' do not fit the chip
        def layer(x, cos, sin, stale_from, p):
            return jax.lax.map(
                lambda args: row_fn(args[0], cos, sin, args[1], p),
                (x, stale_from))
        return layer

    dense_layer, routed_layer = by_row(dense_row), by_row(routed_row)

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
    held, m = d["moe_experts_held"], d["moe_intermediate"]
    rank, rope_d, q_rank = d["kv_lora_rank"], d["qk_rope"], d["q_lora_rank"]

    def get(path, shp, dtype):
        return jnp.asarray(weights.leaf(config, path, shp, dtype))

    def kernel(at, name, shp):
        return (get(f"{at}/{name}/kernel_int8", shp, "int8"),
                get(f"{at}/{name}/scale", (1, shp[1]), "float32"))

    def trig(yarn):
        ang = np.arange(ids.shape[1], dtype=np.float32)[:, None] \
            * rope_freqs(d, yarn)
        return jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))

    rows_np, pos_np = np.asarray(rows_op), np.asarray(pos_op)
    # stale_index: each row's indexer keys from its first served position
    # on (the position after the first one asked for) were never written
    stale_from = jnp.asarray([
        int(pos_np[rows_np == r].min()) + 1 if (rows_np == r).any()
        else ids.shape[1] for r in range(ids.shape[0])], jnp.int32)
    embed = weights.leaf(config, "embed/embedding", (d["vocab_size"], h),
                         "float32")
    x0 = jnp.asarray(embed[ids])
    del embed
    fns = {flag: _layer_fns(d, flag) for flag in flags}
    trigs = {flag: trig(flag != "no_yarn") for flag in flags}
    xs = {flag: x0 for flag in flags}
    attn_shapes = (("q_a_proj", (h, q_rank)),
                   ("q_b_proj", (q_rank, heads * (d["qk_nope"] + rope_d))),
                   ("kv_a_proj", (h, rank + rope_d)),
                   ("kv_b_proj", (rank, heads * (d["qk_nope"] + d["v_head"]))),
                   ("o_proj", (heads * d["v_head"], h)),
                   ("index_wq_b", (q_rank, d["index_heads"]
                                   * d["index_head_dim"])),
                   ("index_wk", (h, d["index_head_dim"])))
    with jax.default_matmul_precision("highest"):
        for i in range(d["layers"]):
            at = f"layer_{i}"
            p = {"attn_norm": get(f"{at}/attn_norm/scale", (h,), "float32"),
                 "q_a_norm": get(f"{at}/q_a_norm/scale", (q_rank,),
                                 "float32"),
                 "kv_a_norm": get(f"{at}/kv_a_norm/scale", (rank,), "float32"),
                 "mlp_norm": get(f"{at}/mlp_norm/scale", (h,), "float32"),
                 "index_k_norm_scale": get(f"{at}/index_k_norm_scale",
                                           (d["index_head_dim"],), "float32"),
                 "index_k_norm_bias": get(f"{at}/index_k_norm_bias",
                                          (d["index_head_dim"],), "float32"),
                 "index_weights_proj": get(f"{at}/index_weights_proj",
                                           (h, d["index_heads"]), "float32")}
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
                for name, shp in (("gate", (held, h, m)), ("up", (held, h, m)),
                                  ("down", (held, m, h))):
                    p[name] = (get(f"{moe}/experts_{name}_int8", shp, "int8"),
                               get(f"{moe}/experts_{name}_scale",
                                   (held, 1, shp[2]), "float32"))
                width = d["n_shared_experts"] * m
                for name, shp in (("shared_gate_proj", (h, width)),
                                  ("shared_up_proj", (h, width)),
                                  ("shared_down_proj", (width, h))):
                    p[name] = kernel(moe, name, shp)
                kind = 1
            for flag in flags:    # one stream's old activations at a time
                xs[flag] = fns[flag][kind](xs[flag], *trigs[flag],
                                           stale_from, p)
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


# -- 4. what a step needs: int8 kernels at 1 byte, a float32 router and
# indexer weight, a bf16 cache of three leaves --------------------------------

def _attention_params(d: dict) -> int:
    h, heads, q_rank = d["hidden"], d["heads"], d["q_lora_rank"]
    return h * q_rank + q_rank * heads * (d["qk_nope"] + d["qk_rope"]) \
        + h * (d["kv_lora_rank"] + d["qk_rope"]) \
        + d["kv_lora_rank"] * heads * (d["qk_nope"] + d["v_head"]) \
        + heads * d["v_head"] * h


def _indexer_params(d: dict) -> int:
    return d["q_lora_rank"] * d["index_heads"] * d["index_head_dim"] \
        + d["hidden"] * d["index_head_dim"] + d["hidden"] * d["index_heads"]


def _expert_params(d: dict) -> int:
    return 3 * d["hidden"] * d["moe_intermediate"]


def experts_touched(d: dict, rows: float) -> float:
    """Distinct HELD experts a step of ``rows`` tokens is expected to need
    in one layer under even routing: a pick lands on a given expert with
    probability k / E."""
    e, k = d["moe_experts"], d["moe_top_k"]
    return d["moe_experts_held"] * (1.0 - (1.0 - k / e) ** rows)


def dsa_step_bytes(config: dict, *, rows: float, visible: float,
                   selected: float) -> float:
    """Cache bytes the sparse attention of ONE decode step NEEDS: each live
    row scores its ``visible`` indexer keys (``index_head_dim`` bf16 values)
    and attends its ``selected`` latent rows (``kv_lora_rank + qk_rope``
    bf16 values), in every layer. A program that reads whole leaves under a
    mask reads a low share of its roofline."""
    d = dims_of(config)
    return d["layers"] * rows * 2 * (
        visible * d["index_head_dim"]
        + selected * (d["kv_lora_rank"] + d["qk_rope"]))


def decode_step_bytes(config: dict, *, rows: float, context: float) -> float:
    """HBM bytes ONE decode step needs: every kernel outside the routed
    experts once (the indexer's among them), in each routed layer the
    float32 router, the shared expert and the held experts the rows are
    expected to touch, the head's slice, and the cache the sparse attention
    needs at this context (``dsa_step_bytes``)."""
    d = dims_of(config)
    dense = d["first_dense_layers"]
    routed = d["layers"] - dense
    # (the indexer's head weights are float32: three bytes a value more)
    kernels = d["layers"] * (_attention_params(d) + _indexer_params(d)
                             + 3 * d["hidden"] * d["index_heads"]) \
        + dense * 3 * d["hidden"] * d["mlp"] + d["hidden"] * d["vocab_size"] \
        + routed * (4 * d["hidden"] * d["moe_experts"]
                    + d["n_shared_experts"] * _expert_params(d)
                    + experts_touched(d, rows) * _expert_params(d))
    return kernels + dsa_step_bytes(
        config, rows=rows, visible=context,
        selected=min(context, d["index_topk"]))


def _token_params(d: dict) -> float:
    """Parameters one token's matmuls in the layers use here: of its top-k
    experts the share this chip holds under even routing."""
    routed = d["layers"] - d["first_dense_layers"]
    local = d["moe_top_k"] * d["moe_experts_held"] / d["moe_experts"]
    return d["layers"] * (_attention_params(d) + _indexer_params(d)) \
        + d["first_dense_layers"] * 3 * d["hidden"] * d["mlp"] \
        + routed * (d["hidden"] * d["moe_experts"]
                    + (d["n_shared_experts"] + local) * _expert_params(d))


def decode_step_flops(config: dict, *, rows: float, context: float) -> float:
    """The indexer's scores over the whole context, absorbed attention over
    the selected rows."""
    d = dims_of(config)
    index = 2 * d["index_heads"] * d["index_head_dim"] * context
    attend = 2 * d["heads"] * min(context, d["index_topk"]) \
        * (2 * d["kv_lora_rank"] + d["qk_rope"])
    return rows * (2 * _token_params(d) + 2 * d["hidden"] * d["vocab_size"]
                   + d["layers"] * (index + attend))


def prefill_flops(config: dict, *, rows: int, seq_len: int) -> float:
    """Expanded attention under the selection's mask and the indexer's
    scores, both over the causal half; lm_head at one position."""
    d = dims_of(config)
    pairs = seq_len * seq_len / 2
    attend = 2 * d["heads"] * pairs \
        * (d["qk_nope"] + d["qk_rope"] + d["v_head"])
    index = 2 * d["index_heads"] * d["index_head_dim"] * pairs
    return rows * (2 * seq_len * _token_params(d)
                   + d["layers"] * (attend + index)
                   + 2 * d["hidden"] * d["vocab_size"])


# -- 5. the faults' readings ----------------------------------------------------

def fault_gaps(config: dict, seeds: list, *, rows: int = 1,
               length: int = 6144, served: int = 1024,
               flags: tuple = (True,) + FAULTS) -> list:
    """What each control reads on ``rows`` seeded rows of ``length`` token
    ids at their last ``served`` positions, a sample per seed, all in one
    walk: the widest gap by which the token that stream puts first lies
    below the reference's best, and the share of positions where it is
    another. (At the cell's widths a row of 6144 is three times
    ``index_topk``: the selection drops two thirds of what is visible.)"""
    ids = np.concatenate([np.random.default_rng(seed).integers(
        1, config["vocab_size"], (rows, length)) for seed in seeds]
    ).astype(np.int32)
    n = rows * served                                   # places a sample
    at = np.tile(np.arange(length - served, length), len(ids))
    out = walk(config, ids, np.repeat(np.arange(len(ids)), served), at,
               (False,) + tuple(flags), first_only=tuple(flags))
    ref = np.asarray(out[False])
    best = ref.max(axis=-1)
    gaps = {flag: best - ref[np.arange(len(at)), np.asarray(out[flag])]
            for flag in flags}
    return [{"seed": seed, **{
        "int4" if flag is True else flag: {
            "widest_gap": float(gap[i * n:(i + 1) * n].max()),
            "other_first_share": float(np.mean(gap[i * n:(i + 1) * n] > 0))}
        for flag, gap in gaps.items()}} for i, seed in enumerate(seeds)]


def sample_gaps(config: dict, rows: list, shape: tuple, flags: tuple) -> dict:
    """``benchmark/reference.py served_gaps`` with any of this family's
    controls beside the reference, in one walk: ``rows`` the
    ``(tokens, n_prompt)`` pairs of a sample, ``shape`` the cell's. The
    program's own widest gap, then for each flag the widest gap of the token
    that stream puts first and the share of served places where that is
    another token than the reference's."""
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
    best, n = ref.max(axis=-1), np.arange(len(keep))
    line = {"rows": len(rows), "served_tokens": int(keep.sum()),
            "positions": int(sum(len(t) for t, _ in rows)),
            "program": {"widest_gap": float(
                (best - ref[n, tok.reshape(-1)])[keep].max())}}
    for flag in flags:
        gap = (best - ref[n, np.asarray(out[flag])])[keep]
        line["int4" if flag is True else flag] = {
            "widest_gap": float(gap.max()),
            "other_first_share": float(np.mean(gap > 0))}
    return line


def served_fault_gaps(cell: str, seeds: list, seconds: float,
                      flags: tuple, manifest: str, work_dir=None):
    """What each control reads on the tokens the PROGRAM served, the way
    ``benchmark.study --control 1`` reads the int4 control: one boot of the
    cell, one window a seed, and after the server has stopped each window's
    sample (the four finished requests ``correct`` would take, prompt plus
    served tokens) through :func:`sample_gaps`. Yields a line a window."""
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
               "failed": summary["failed"],
               **sample_gaps(ctx["config"], rows, shape, flags)}


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=fault_gaps.__doc__)
    ap.add_argument("--config", default=None)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--length", type=int, default=6144)
    ap.add_argument("--served", type=int, default=1024)
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
