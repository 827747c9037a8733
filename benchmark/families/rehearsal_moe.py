"""Rehearsal only: the repo's own sparse FFN (``lambdipy_tpu/models/moe.py``,
served by the ``llama-hf`` builder when ``moe_experts`` is set) at toy
widths on the CPU. It stands for no model and is never a cell of
``BENCHMARK.json``: it is here to show that an architecture is only files.
What it has that the llama block has not: a 2-D float32 ``router``, 3-D
``experts_*_int8`` stacks with ``[E, 1, out]`` scales, and widths
(experts, experts per token) that shape the parameter tree.

The layer as ``models/moe.py`` documents it: the llama block's attention,
then softmax over all experts of ``x @ router`` in float32, the top-k of
it with their gates renormalised to sum 1, SwiGLU experts, the gated sum
added to the residual stream. The program seats tokens by capacity and
DROPS what overflows, which makes a token's result depend on the tokens
batched with it; ``dims_of`` therefore sets the capacity factor to
``experts / top_k``, at which every token of a routing group is seated
whatever the others choose, and only there is this reference the
program's function. (A served model may not drop tokens at all: ROADMAP
queue 2 A, R1.)

Weights: the llama block's rules (its family's ``leaf`` and ``dims_of``,
imported: a family may use another's parts, never edit them) for the
leaves it shares; a router of
int8-uniform values times 2/(127*sqrt(hidden)) (logits of unit order, so
that routing is neither uniform nor one-hot); expert kernels int8-uniform
with a scale of 2^-10 for every output channel: a power of two near
1/(127*sqrt(fan_in)) at these widths, because the program multiplies
kernel by scale in bfloat16 and a power of two is exact there.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights
from benchmark.families import llama_hf

# ``moe`` is the flax module inside the program's ``mlp`` scope: the
# innermost name wins, so the experts are split from the norm before them
SCOPES = ("embed", "qkv_proj", "kv_write", "attend", "o_proj", "mlp", "moe",
          "lm_head", "sample", "kv_window")
WITNESS = ("qkv_proj", "sample")
EXPERT_SCALE = 2.0 ** -10


def dims_of(config: dict) -> dict:
    experts = int(config["num_local_experts"])
    top_k = int(config["num_experts_per_tok"])
    return {
        **llama_hf.dims_of(config),     # ``mlp`` is one expert's width here
        "moe_experts": experts,
        "moe_top_k": top_k,
        # every token seated: capacity = tokens of the routing group
        "moe_capacity_factor": experts / top_k,
    }


def leaf(seed: int, path: str, shape, dtype, config: dict):
    if "/moe/" not in path:     # the leaves it shares with the llama block
        return llama_hf.leaf(seed, path, shape, dtype, config)
    if path.endswith(("/experts_gate_int8", "/experts_up_int8",
                      "/experts_down_int8")):
        return weights.int8_draw(seed, path, shape)
    if path.endswith(("/experts_gate_scale", "/experts_up_scale",
                      "/experts_down_scale")):
        return np.full(shape, EXPERT_SCALE, dtype)
    if path.endswith("/router"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * (2.0 / (127.0 * config["hidden_size"] ** 0.5))).astype(dtype)
    return None


def _layer_fn(d: dict, int4: bool):
    import jax
    import jax.numpy as jnp

    heads, kvh, top_k = d["heads"], d["kv_heads"], d["moe_top_k"]
    hd = d["hidden"] // heads
    eps = d["norm_eps"]

    def deq(w, scale):
        w = w.astype(jnp.float32)
        if int4:
            w = jnp.clip(jnp.round(w / 16.0), -8, 7) * 16.0
        return w * scale

    def norm(x, g):
        return x * (jnp.mean(x * x, -1, keepdims=True) + eps) ** -0.5 * g

    def rope(x, cos, sin):  # [r, s, heads, hd]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def layer(x, cos, sin, p):
        r, s, _ = x.shape
        a = norm(x, p["attn_norm"])
        q = rope((a @ deq(*p["q_proj"])).reshape(r, s, heads, hd), cos, sin)
        k = rope((a @ deq(*p["k_proj"])).reshape(r, s, kvh, hd), cos, sin)
        v = (a @ deq(*p["v_proj"])).reshape(r, s, kvh, hd)
        k, v = (jnp.repeat(t, heads // kvh, axis=2) for t in (k, v))
        scores = jnp.einsum("rqhd,rkhd->rhqk", q, k) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        att = jnp.einsum("rhqk,rkhd->rqhd", probs, v).reshape(r, s, heads * hd)
        x = x + att @ deq(*p["o_proj"])
        m = norm(x, p["mlp_norm"])
        route = jax.nn.softmax(m @ p["router"], axis=-1)        # [r, s, E]
        gates, chosen = jax.lax.top_k(route, top_k)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        gate_of = jnp.sum(jax.nn.one_hot(chosen, route.shape[-1])
                          * gates[..., None], axis=-2)           # [r, s, E]
        # every expert on every token, then the gated sum: plain, and small
        # enough at the widths this family is for
        up = jax.nn.silu(jnp.einsum("rsh,ehm->rsem", m, deq(*p["gate"]))) \
            * jnp.einsum("rsh,ehm->rsem", m, deq(*p["up"]))
        out = jnp.einsum("rsem,emh->rseh", up, deq(*p["down"]))
        return x + jnp.einsum("rse,rseh->rsh", gate_of, out)

    def head(x, rows, pos, g, w, scale):
        return norm(x[rows, pos], g) @ deq(w, scale)

    return jax.jit(layer), jax.jit(head)


def walk(config: dict, ids: np.ndarray, rows_op, pos_op, flags: tuple):
    """As the seam asks (``benchmark/families``): False = the float32
    reference, True = its control, every int8 kernel rounded to int4."""
    import jax
    import jax.numpy as jnp

    d = dims_of(config)
    h, e, m = d["hidden"], d["moe_experts"], d["mlp"]
    hd = h // d["heads"]
    kv = d["kv_heads"] * hd

    def get(path, shp, dtype):
        return jnp.asarray(weights.leaf(config, path, shp, dtype))

    freqs = 1.0 / (d["rope_theta"] ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(ids.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x0 = jnp.asarray(weights.leaf(config, "embed/embedding",
                                  (d["vocab_size"], h), "float32")[ids])
    fns = {flag: _layer_fn(d, flag) for flag in flags}
    xs = {flag: x0 for flag in flags}
    with jax.default_matmul_precision("highest"):
        for i in range(d["layers"]):
            at = f"layer_{i}"
            p = {"attn_norm": get(f"{at}/attn_norm/scale", (h,), "float32"),
                 "mlp_norm": get(f"{at}/mlp_norm/scale", (h,), "float32"),
                 "router": get(f"{at}/moe/router", (h, e), "float32")}
            for name, shp in (("q_proj", (h, h)), ("k_proj", (h, kv)),
                              ("v_proj", (h, kv)), ("o_proj", (h, h))):
                p[name] = (get(f"{at}/{name}/kernel_int8", shp, "int8"),
                           get(f"{at}/{name}/scale", (1, shp[1]), "float32"))
            for name, shp in (("gate", (e, h, m)), ("up", (e, h, m)),
                              ("down", (e, m, h))):
                p[name] = (get(f"{at}/moe/experts_{name}_int8", shp, "int8"),
                           get(f"{at}/moe/experts_{name}_scale",
                               (e, 1, shp[2]), "float32"))
            xs = {flag: fns[flag][0](x, cos, sin, p) for flag, x in xs.items()}
        g = get("final_norm/scale", (h,), "float32")
        w = get("lm_head/kernel_int8", (h, d["vocab_size"]), "int8")
        sc = get("lm_head/scale", (1, d["vocab_size"]), "float32")
        return {flag: fns[flag][1](x, jnp.asarray(rows_op),
                                   jnp.asarray(pos_op), g, w, sc)
                for flag, x in xs.items()}


# -- what a step needs: int8 kernels at 1 byte, a float32 router, bf16 K/V ----

def _dense_params(d: dict) -> int:
    """Per layer, outside the experts: the four attention projections."""
    kv = d["kv_heads"] * (d["hidden"] // d["heads"])
    return 2 * d["hidden"] * d["hidden"] + 2 * d["hidden"] * kv


def _expert_params(d: dict) -> int:
    return 3 * d["hidden"] * d["mlp"]


def decode_step_bytes(config: dict, *, rows: float, context: float) -> float:
    """The dense weights once; of the experts, those a step of ``rows``
    tokens is expected to touch under uniform routing (the program's dense
    dispatch reads all of them: this is what the algorithm needs); each
    live row's cached context."""
    d = dims_of(config)
    e, k = d["moe_experts"], d["moe_top_k"]
    touched = e * (1.0 - (1.0 - k / e) ** rows)
    per_layer = _dense_params(d) + 4 * d["hidden"] * e \
        + touched * _expert_params(d)
    kv = 2 * d["kv_heads"] * (d["hidden"] // d["heads"]) * 2
    return d["layers"] * (per_layer + rows * context * kv) \
        + d["hidden"] * d["vocab_size"]


def _token_flops(d: dict) -> float:
    """Matmul operations one token needs in the layers: its own top-k
    experts, not all of them."""
    return 2.0 * d["layers"] * (_dense_params(d) + d["hidden"] * d["moe_experts"]
                                + d["moe_top_k"] * _expert_params(d))


def decode_step_flops(config: dict, *, rows: float, context: float) -> float:
    d = dims_of(config)
    return rows * (_token_flops(d) + 2 * d["hidden"] * d["vocab_size"]
                   + d["layers"] * 4 * d["hidden"] * context)


def prefill_flops(config: dict, *, rows: int, seq_len: int) -> float:
    d = dims_of(config)
    attn = d["layers"] * 2 * d["hidden"] * seq_len * seq_len
    return rows * (seq_len * _token_flops(d) + attn
                   + 2 * d["hidden"] * d["vocab_size"])
