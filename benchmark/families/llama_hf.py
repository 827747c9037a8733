"""The llama block: RMSNorm, rotate-half RoPE, causal grouped-query
attention, SwiGLU, residuals, an untied int8 head; served by the program's
``llama-hf`` builder with int8 kernels and one float32 scale per output
channel.

Weights (recorded under ``assumed`` in each configuration file): int8
kernels uniform over the full range with one float32 scale of
1/(127*sqrt(hidden)) per output channel — the magnitude
``registry.save_random_params`` of the program uses, under which bf16
activations stay finite through 32 layers (chip run, PR 21); an embedding
of int8-uniform values times 2^-12, which bfloat16 holds exactly; unit norm
gains.

The reference dequantizes int8 kernels to float32 (``int8 * scale``) and is
teacher-forced over whole rows in one batch. Its control rounds every int8
kernel to int4, the nearest precision below the one the configurations
state.

Bytes and operations are copied from ``lambdipy_tpu/utils/roofline.py`` at
commit fb0103a (``llama_matmul_params``, ``llama_weight_bytes``,
``llama_kv_bytes_per_pos``, ``llama_decode_step_cost``,
``llama_prefill_cost``), re-keyed on the configuration file's published
names. The original stays for the program's own records; a later PR may
delete it there (PERF.md, Open questions), never change this copy.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark import weights

# the names ``jax.named_scope`` gives the operations of a decode step in
# ``lambdipy_tpu/models/llama.py``
SCOPES = ("embed", "qkv_proj", "kv_write", "attend", "o_proj", "mlp",
          "lm_head", "sample", "kv_window")
# scopes only a program with named scopes has: flax names a module's
# operations after the module (``o_proj``, ``lm_head``, ``embed``) anyway
WITNESS = ("qkv_proj", "mlp", "sample")


# -- 1. the widths, under the program's names --------------------------------

def dims_of(config: dict) -> dict:
    """The configuration's published keys under the names the program's
    ``LlamaConfig`` gives them (the recipe's width and depth keys)."""
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
    }


# -- 2. the leaves -------------------------------------------------------------

def leaf(seed: int, path: str, shape, dtype, config: dict):
    """One parameter leaf. ``path`` is '/'-joined tree keys, e.g.
    ``layer_3/q_proj/kernel_int8``; ``dtype`` a numpy dtype or its name."""
    name = np.dtype(dtype).name
    hidden = config["hidden_size"]
    if name == "int8" and path.endswith("/kernel_int8"):
        return weights.int8_draw(seed, path, shape)
    if path.endswith("embedding"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * weights.EMBED_STEP).astype(dtype)
    if path.endswith("_proj/scale") or path.endswith("lm_head/scale"):
        return np.full(shape, 1.0 / (127.0 * hidden ** 0.5), dtype)
    if path.endswith("norm/scale"):  # norm gains
        return np.ones(shape, dtype)
    return None


# -- 3. the reference's walk ---------------------------------------------------

def _shapes(d: dict) -> dict:
    hd = d["hidden"] // d["heads"]
    h, kv, m = d["hidden"], d["kv_heads"] * hd, d["mlp"]
    return {"q_proj": (h, h), "k_proj": (h, kv), "v_proj": (h, kv),
            "o_proj": (h, h), "gate_proj": (h, m), "up_proj": (h, m),
            "down_proj": (m, h)}


def _layer_fn(d: dict, int4: bool):
    import jax
    import jax.numpy as jnp

    heads, kvh = d["heads"], d["kv_heads"]
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
        return x + (jax.nn.silu(m @ deq(*p["gate_proj"]))
                    * (m @ deq(*p["up_proj"]))) @ deq(*p["down_proj"])

    def head(x, rows, pos, g, w, scale):
        return norm(x[rows, pos], g) @ deq(w, scale)

    return jax.jit(layer), jax.jit(head)


def walk(config: dict, ids: np.ndarray, rows_op, pos_op, flags: tuple):
    """Logits at ``(rows_op, pos_op)`` of the batch ``ids`` [rows, length],
    one array per precision flag (False = the reference, True = its int4
    control), walking the layers once with one layer's weights on the
    device at a time."""
    import jax
    import jax.numpy as jnp

    d = dims_of(config)
    hidden = d["hidden"]

    def get(path, shp, dtype):
        return jnp.asarray(weights.leaf(config, path, shp, dtype))

    hd = hidden // d["heads"]
    freqs = 1.0 / (d["rope_theta"] ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(ids.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    embed = weights.leaf(config, "embed/embedding",
                         (d["vocab_size"], hidden), "float32")
    x0 = jnp.asarray(embed[ids])
    del embed
    fns = {flag: _layer_fn(d, flag) for flag in flags}
    xs = {flag: x0 for flag in flags}
    with jax.default_matmul_precision("highest"):
        for i in range(d["layers"]):
            p = {"attn_norm": get(f"layer_{i}/attn_norm/scale", (hidden,), "float32"),
                 "mlp_norm": get(f"layer_{i}/mlp_norm/scale", (hidden,), "float32")}
            for name, shp in _shapes(d).items():
                p[name] = (get(f"layer_{i}/{name}/kernel_int8", shp, "int8"),
                           get(f"layer_{i}/{name}/scale", (1, shp[1]), "float32"))
            xs = {flag: fns[flag][0](x, cos, sin, p) for flag, x in xs.items()}
        g = get("final_norm/scale", (hidden,), "float32")
        w = get("lm_head/kernel_int8", (hidden, d["vocab_size"]), "int8")
        sc = get("lm_head/scale", (1, d["vocab_size"]), "float32")
        return {flag: fns[flag][1](x, jnp.asarray(rows_op),
                                   jnp.asarray(pos_op), g, w, sc)
                for flag, x in xs.items()}


# -- 4. what a step needs ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shape:
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    mlp: int
    vocab: int
    weight_bytes_per_param: int
    kv_bytes_per_value: int


def shape_of(config: dict) -> Shape:
    return Shape(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        mlp=config["intermediate_size"], vocab=config["vocab_size"],
        weight_bytes_per_param=1 if config["precision"]["weights"] == "int8"
        else 2,
        kv_bytes_per_value=2)


def matmul_params(s: Shape) -> int:
    """Parameters that take part in a matmul (the embedding is a gather;
    the untied lm_head counts)."""
    kvd = s.kv_heads * s.head_dim
    per_layer = 2 * s.hidden * s.hidden + 2 * s.hidden * kvd \
        + 3 * s.hidden * s.mlp
    return s.layers * per_layer + s.hidden * s.vocab


def weight_bytes(s: Shape) -> int:
    return matmul_params(s) * s.weight_bytes_per_param


def kv_bytes_per_pos(s: Shape) -> int:
    """K and V of one cached position of one sequence, all layers."""
    return 2 * s.layers * s.kv_heads * s.head_dim * s.kv_bytes_per_value


def decode_step_bytes(config: dict, *, rows: float, context: float) -> float:
    """HBM bytes ONE decode step needs: the weights once, whatever the
    batch, plus each live row's own cached context."""
    s = shape_of(config)
    return weight_bytes(s) + rows * context * kv_bytes_per_pos(s)


def decode_step_flops(config: dict, *, rows: float, context: float) -> float:
    s = shape_of(config)
    return rows * (2 * matmul_params(s)
                   + s.layers * 4 * s.hidden * context)


def prefill_flops(config: dict, *, rows: int, seq_len: int) -> float:
    """Prefill of ``seq_len`` tokens a row, lm_head at one position."""
    s = shape_of(config)
    in_layers = matmul_params(s) - s.hidden * s.vocab
    attn = s.layers * 2 * s.hidden * seq_len * seq_len
    return rows * (2 * seq_len * in_layers + attn + 2 * s.hidden * s.vocab)
