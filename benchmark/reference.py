"""The plain reference: a llama-family forward pass in float32 ``jax.numpy``.

Independent of the program: it imports nothing of ``lambdipy_tpu``, and its
weights come from ``benchmark/weights.py`` leaf by leaf, never from the
bundle. Per layer: RMSNorm, rotate-half RoPE, causal grouped-query
attention, SwiGLU, residuals; int8 kernels are dequantized to float32
(``int8 * scale``); matmuls run at ``highest`` precision, which on a TPU is
what keeps float32 float32. It is teacher-forced over whole rows (prompt
plus the tokens that were served) in one batch of a fixed shape, one
layer's weights on the device at a time, so it fits beside nothing and is
run after the served program's state is freed.

What it returns, for every served token, is the gap by which that token's
reference logit lies below the reference's best logit at that position
(0 where the served token IS the reference's choice). With ``control`` it
also walks a second stream whose int8 kernels are rounded to int4 — the
nearest precision below the one the configurations state — and returns the
same gap for the token that stream puts first.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import weights

_PROJ = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
         "down_proj")


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


def _walk(config: dict, ids: np.ndarray, rows_op, pos_op, flags: tuple):
    """Logits at ``(rows_op, pos_op)`` of the batch ``ids`` [rows, length],
    one array per precision flag (False = the reference, True = its int4
    control), walking the layers once with one layer's weights on the
    device at a time."""
    import jax
    import jax.numpy as jnp

    d = weights.dims_of(config)
    seed, hidden = int(config["weights_seed"]), d["hidden"]

    def get(path, shp, dtype):
        return jnp.asarray(weights.leaf(seed, path, shp, dtype, hidden))

    hd = hidden // d["heads"]
    freqs = 1.0 / (d["rope_theta"] ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(ids.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    embed = weights.leaf(seed, "embed/embedding",
                         (d["vocab_size"], hidden), "float32", hidden)
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


def next_token_logits(config: dict, tokens: list):
    """The reference's logits for the token after ``tokens`` (one row)."""
    ids = np.asarray(tokens, np.int32)[None]
    return _walk(config, ids, [0], [len(tokens) - 1], (False,))[False][0]


def served_gaps(config: dict, rows: list, *, shape: tuple,
                control: bool = False) -> dict:
    """``rows``: ``(tokens, n_prompt)`` pairs, tokens = prompt + served.
    ``shape`` = (rows, length, served tokens per row) the batch is padded
    to — fixed per cell so the reference compiles once. Returns
    ``{"gap": [...], "control_gap": [...] | None, "seconds", "positions"}``
    with one gap per served token, row after row."""
    import jax
    import jax.numpy as jnp

    n_rows, length, n_new = shape
    if len(rows) > n_rows or any(len(t) > length or len(t) - n > n_new
                                 for t, n in rows):
        raise ValueError(f"reference: rows do not fit the shape {shape}")
    t0 = time.monotonic()
    ids = np.zeros((n_rows, length), np.int32)
    pos = np.zeros((n_rows, n_new), np.int32)
    tok = np.zeros((n_rows, n_new), np.int32)
    live = np.zeros((n_rows, n_new), bool)
    for r, (tokens, n_prompt) in enumerate(rows):
        ids[r, : len(tokens)] = tokens
        k = len(tokens) - n_prompt
        pos[r, :k] = np.arange(n_prompt - 1, len(tokens) - 1)  # t predicts t+1
        tok[r, :k] = tokens[n_prompt:]
        live[r, :k] = True
    logits = _walk(config, ids, np.repeat(np.arange(n_rows), n_new),
                   pos.reshape(-1), (False, True) if control else (False,))
    ref = logits[False]
    best = ref.max(axis=-1)
    n = jnp.arange(ref.shape[0])
    gap = np.asarray(best - ref[n, jnp.asarray(tok.reshape(-1))])
    ctl = None
    if control:
        ctl = np.asarray(best - ref[n, logits[True].argmax(axis=-1)])
    keep = live.reshape(-1)
    return {"gap": gap[keep].tolist(),
            "control_gap": None if ctl is None else ctl[keep].tolist(),
            "positions": int(sum(len(t) for t, _ in rows)),
            "served_tokens": int(keep.sum()),
            "seconds": time.monotonic() - t0,
            "platform": jax.devices()[0].platform}
