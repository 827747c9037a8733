"""The ``evabyte`` block as its public ``config.json`` keys describe it
(``model_type`` ``evabyte``, ``attention_class`` ``eva``), served by the
program's ``evabyte`` builder with int8 kernels and one float32 scale per
output channel.

**The layer equations the reference follows.** Hidden ``h``, ``H`` heads of
``d = h / H``, no biases, pre-norm residual block. ``rmsnorm(x) = x /
sqrt(mean(x^2) + rms_norm_eps) * (1 + g)`` (``norm_add_unit_offset``).

- ``m = rmsnorm_1(x)``; ``q, k, v = m W_q, m W_k, m W_v`` -> [H, d] each;
  rotate-half rope over all ``d`` dims at the absolute position ``t``
  (``rope_theta``), on ``q`` and ``k``; ``s = 1 / sqrt(d)``. With ``W`` =
  ``window_size``, ``C`` = ``chunk_size`` and, a head, two learned vectors
  ``mu_h``, ``phi_h`` (``adaptive_mu_k``, ``adaptive_phi``):
- *Chunk summaries.* Chunk ``c`` holds positions ``j`` in ``[cC, (c+1)C)``:
  ``b_j = softmax_j(k_j . mu_h)``, ``k~_c = sum_j b_j k_j``; ``a_j =
  softmax_j(s k_j . phi_h)``, ``v~_c = sum_j a_j v_j``; keys after rope.
- *Attention at position t.* Exact set ``E(t) = {j : j // W = t // W, j <=
  t}``: the window ``t`` lies in, causal. Summary set ``S(t) = {c : c < (t
  // W) W / C}``: every chunk of every EARLIER window, none of the current.
  One softmax over both: ``o_t = [sum_E e^{s q_t.k_j} v_j + sum_S e^{s
  q_t.k~_c} v~_c] / [sum_E e^{s q_t.k_j} + sum_S e^{s q_t.k~_c}]``; ``x + o
  W_o``. Computed here a window at a time, each window against its own keys
  and ALL summaries under a mask: no cache, no ring.
- FFN: ``y = x + W_down(silu(W_gate n) * W_up n)``, ``n = rmsnorm_2(x)``.
- Head: ``rmsnorm_f``, then ``W_head`` [h, ``num_pred_heads`` x vocab];
  columns ``0 .. vocab - 1`` are the next byte's and are what ``walk``
  returns.

**Departures from the published description.** The multi-byte
self-speculative decoding that heads 1 .. ``num_pred_heads`` - 1 exist for
is not served: the program computes the whole head and samples from the
first ``vocab_size`` columns. Residual adds run in bfloat16 in the program
as in every cell (``fp32_skip_add`` is the model card's); this reference is
float32 throughout. One detail the public config cannot confirm is written
as the model's published code has it to the author's knowledge (ISSUE 33)
and recorded under ``assumed`` (``chunk_pooling``): ``mu`` pools the keys,
without the softmax scale; ``phi`` pools the values, with it.

**Weights** (recorded under ``assumed`` in the configuration file): the
llama family's rules for the shapes it shares (int8 kernels uniform over the
full range, one float32 scale 1/(127*sqrt(hidden)) per output channel,
embedding int8-uniform x 2^-12, exact in bfloat16); norm gains at their
unit-offset zero, so every norm multiplies by one; ``mu_h``, ``phi_h``
float32, int8-uniform draws scaled so that the POOLING logits over a chunk
are of unit order (``POOL_LOGIT_STD``: a key after its projection has
entries of standard deviation ``KEY_STD``), NOT at the card's ``init_std``
0.01275, where pooling is uniform to three digits and a program that
mean-pooled would pass.

**Controls.** ``True``: every int8 kernel rounded to int4, the nearest
precision below the one the configurations state (the harness's control).
``FAULTS`` plant one fault in the attention alone, for the limit's sake:
``no_summaries`` (``S`` emptied: a window-only model), ``mean_pool`` (both
poolings uniform), ``swapped_pool`` (``mu`` and ``phi`` exchanged),
``stale_window`` (the exact set not reset at a window's edge: what a ring
attended whole would show, the ``W`` positions up to ``t``). ``python3 -m
benchmark.families.evabyte --config <file> --seeds 1,2`` walks them all on
seeded rows and prints each one's widest gap.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights

# the llama block's scopes, and the pooling and the summary write
SCOPES = ("embed", "qkv_proj", "kv_write", "attend", "o_proj", "mlp",
          "lm_head", "sample", "kv_window", "eva_summarize")
WITNESS = ("qkv_proj", "mlp", "sample", "eva_summarize")
FAULTS = ("no_summaries", "mean_pool", "swapped_pool", "stale_window")

# a projected key's entries: int8-uniform kernel (std 127 / sqrt 3) at scale
# 1 / (127 sqrt h) on a unit-RMS input
KEY_STD = 3.0 ** -0.5
POOL_LOGIT_STD = 1.0
INT8_STD = (2.0 ** 16 - 1.0) ** 0.5 / 12.0 ** 0.5   # uniform on -128 .. 127


# -- 1. the widths, under the program's names --------------------------------

def dims_of(config: dict) -> dict:
    """The configuration's keys under the names the program's ``evabyte``
    builder takes (``LlamaConfig`` fields). Booleans go as the strings a
    recipe's TOML would hand the builder anyway."""
    if config.get("rope_scaling") or config.get("attention_bias") \
            or config["num_key_value_heads"] != config["num_attention_heads"] \
            or config.get("attention_class", "eva") != "eva":
        raise ValueError("evabyte family: rope scaling, attention bias, "
                         "grouped K/V heads and another attention class "
                         "than eva are not written")
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
        "window_size": config["window_size"],
        "chunk_size": config["chunk_size"],
        "pred_heads": config["num_pred_heads"],
        "norm_unit_offset": str(bool(config["norm_add_unit_offset"])).lower(),
    }


# -- 2. the leaves -------------------------------------------------------------

def leaf(seed: int, path: str, shape, dtype, config: dict):
    """One parameter leaf. ``path`` is '/'-joined tree keys, e.g.
    ``layer_3/adaptive_phi``; ``dtype`` a numpy dtype or its name."""
    name = np.dtype(dtype).name
    hidden = config["hidden_size"]
    d = hidden // config["num_attention_heads"]
    if name == "int8" and path.endswith("/kernel_int8"):
        return weights.int8_draw(seed, path, shape)
    if path.endswith("embedding"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * weights.EMBED_STEP).astype(dtype)
    if path.endswith("_proj/scale") or path.endswith("lm_head/scale"):
        return np.full(shape, 1.0 / (127.0 * hidden ** 0.5), dtype)
    if path.endswith("norm/scale"):  # gains as offsets from one
        return np.zeros(shape, dtype) if config["norm_add_unit_offset"] \
            else np.ones(shape, dtype)
    if path.endswith(("/adaptive_mu_k", "/adaptive_phi")):
        # k . mu of unit order; phi's product is divided by sqrt(d) first
        std = POOL_LOGIT_STD / (KEY_STD * d ** 0.5)
        if path.endswith("phi"):
            std *= d ** 0.5
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * (std / INT8_STD)).astype(dtype)
    return None


# -- 3. the reference's walk ---------------------------------------------------

def _shapes(d: dict) -> dict:
    h, m = d["hidden"], d["mlp"]
    return {"q_proj": (h, h), "k_proj": (h, h), "v_proj": (h, h),
            "o_proj": (h, h), "gate_proj": (h, m), "up_proj": (h, m),
            "down_proj": (m, h)}


def _layer_fns(d: dict, fault):
    """The jitted parts of a walk: ``fault`` False for the reference, True
    for the int4 control, or one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp

    heads = d["heads"]
    hd = d["hidden"] // heads
    win, chunk, eps = d["window_size"], d["chunk_size"], d["norm_eps"]
    offset = 1.0 if d["norm_unit_offset"] == "true" else 0.0
    scale = 1.0 / np.sqrt(hd)

    def deq(w, s):
        w = w.astype(jnp.float32)
        if fault is True:
            w = jnp.clip(jnp.round(w / 16.0), -8, 7) * 16.0
        return w * s

    def norm(x, g):
        return x * (jnp.mean(x * x, -1, keepdims=True) + eps) ** -0.5 \
            * (offset + g)

    def rope(x, cos, sin):  # [r, s, heads, hd]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def project(x, cos, sin, p):
        """q, k, v in windows [r, n_win, win, H, d] and every chunk's
        summary [r, chunks, H, d]; the sequence padded to whole windows."""
        r, s, _ = x.shape
        a = norm(x, p["attn_norm"])
        q = rope((a @ deq(*p["q_proj"])).reshape(r, s, heads, hd), cos, sin)
        k = rope((a @ deq(*p["k_proj"])).reshape(r, s, heads, hd), cos, sin)
        v = (a @ deq(*p["v_proj"])).reshape(r, s, heads, hd)
        n_win = -(-s // win)
        pad = ((0, 0), (0, n_win * win - s), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
        mu, phi = p["mu"], p["phi"]
        if fault == "swapped_pool":
            mu, phi = phi, mu
        kc = k.reshape(r, -1, chunk, heads, hd)
        vc = v.reshape(r, -1, chunk, heads, hd)
        b_logit = jnp.einsum("rnchd,hd->rnch", kc, mu)
        a_logit = jnp.einsum("rnchd,hd->rnch", kc, phi) * scale
        if fault == "mean_pool":
            b_logit, a_logit = 0.0 * b_logit, 0.0 * a_logit
        sk = jnp.einsum("rnch,rnchd->rnhd", jax.nn.softmax(b_logit, 2), kc)
        sv = jnp.einsum("rnch,rnchd->rnhd", jax.nn.softmax(a_logit, 2), vc)
        return tuple(t.reshape(r, n_win, win, heads, hd)
                     for t in (q, k, v)) + (sk, sv)

    def one_window(w, q, k, v, k_prev, v_prev, sk, sv):
        """Window ``w`` of ONE row: q, k, v [win, H, d]; the window before
        it (read by ``stale_window`` alone); all summaries [chunks, H,
        d]."""
        at = jnp.arange(win)
        own = at[None, :] <= at[:, None]                     # causal [q, k]
        earlier = jnp.broadcast_to(
            jnp.arange(sk.shape[0])[None, :] < w * (win // chunk),
            (win, sk.shape[0]))
        if fault == "no_summaries":
            earlier = jnp.zeros_like(earlier)
        keys, vals, mask = [k, sk], [v, sv], [own, earlier]
        if fault == "stale_window":
            # the ring attended whole: slots after t's own still hold the
            # window before
            keys.append(k_prev)
            vals.append(v_prev)
            mask.append((at[None, :] > at[:, None]) & (w > 0))
        logits = jnp.concatenate(
            [jnp.einsum("qhd,khd->hqk", q, kk) for kk in keys], -1) * scale
        probs = jax.nn.softmax(
            jnp.where(jnp.concatenate(mask, -1)[None], logits, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, jnp.concatenate(vals, 0))

    def attend(w, q, k, v, k_prev, v_prev, sk, sv):
        # a row at a time: the scores are [H, win, win + chunks] float32
        return jax.lax.map(lambda t: one_window(w, *t),
                           (q, k, v, k_prev, v_prev, sk, sv))

    def finish(x, att, p):
        r, s, _ = x.shape
        x = x + att[:, :s].reshape(r, s, -1) @ deq(*p["o_proj"])
        m = norm(x, p["mlp_norm"])
        return x + (jax.nn.silu(m @ deq(*p["gate_proj"]))
                    * (m @ deq(*p["up_proj"]))) @ deq(*p["down_proj"])

    def head(x, rows, pos, g, w, s, vocab):
        return (norm(x[rows, pos], g) @ deq(w, s))[:, :vocab]

    project, attend, finish = map(jax.jit, (project, attend, finish))
    head = jax.jit(head, static_argnums=6)

    def layer(x, cos, sin, p):
        q, k, v, sk, sv = project(x, cos, sin, p)
        outs = []
        for w in range(q.shape[1]):      # a window a call: memory bounded
            prev = max(w - 1, 0)
            outs.append(attend(jnp.int32(w), q[:, w], k[:, w], v[:, w],
                               k[:, prev], v[:, prev], sk, sv))
        return finish(x, jnp.concatenate(outs, axis=1), p)

    return layer, head


def walk(config: dict, ids: np.ndarray, rows_op, pos_op, flags: tuple, *,
         first_only: tuple = ()):
    """Logits (the next byte's ``vocab_size`` columns) at ``(rows_op,
    pos_op)`` of the batch ``ids`` [rows, length], one array per flag (False
    = the float32 reference, True = its int4 control, or one of ``FAULTS``;
    a flag in ``first_only`` returns its first choice alone), walking the
    layers once with one layer's weights on the device at a time."""
    import jax
    import jax.numpy as jnp

    d = dims_of(config)
    h, heads = d["hidden"], d["heads"]
    hd = h // heads

    def get(path, shp, dtype):
        return jnp.asarray(weights.leaf(config, path, shp, dtype))

    freqs = 1.0 / (d["rope_theta"] ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(ids.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    embed = weights.leaf(config, "embed/embedding", (d["vocab_size"], h),
                         "float32")
    x0 = jnp.asarray(embed[ids])
    del embed
    fns = {flag: _layer_fns(d, flag) for flag in flags}
    xs = {flag: x0 for flag in flags}
    with jax.default_matmul_precision("highest"):
        for i in range(d["layers"]):
            at = f"layer_{i}"
            p = {"attn_norm": get(f"{at}/attn_norm/scale", (h,), "float32"),
                 "mlp_norm": get(f"{at}/mlp_norm/scale", (h,), "float32"),
                 "mu": get(f"{at}/adaptive_mu_k", (heads, hd), "float32"),
                 "phi": get(f"{at}/adaptive_phi", (heads, hd), "float32")}
            for name, shp in _shapes(d).items():
                p[name] = (get(f"{at}/{name}/kernel_int8", shp, "int8"),
                           get(f"{at}/{name}/scale", (1, shp[1]), "float32"))
            xs = {flag: fns[flag][0](x, cos, sin, p) for flag, x in xs.items()}
            del p
        cols = d["vocab_size"] * d["pred_heads"]
        g = get("final_norm/scale", (h,), "float32")
        w = get("lm_head/kernel_int8", (h, cols), "int8")
        sc = get("lm_head/scale", (1, cols), "float32")
        out = {}
        for flag, x in xs.items():
            logits = fns[flag][1](x, jnp.asarray(rows_op), jnp.asarray(pos_op),
                                  g, w, sc, d["vocab_size"])
            out[flag] = logits.argmax(axis=-1) if flag in first_only else logits
        return out


# -- 4. what a step needs: int8 kernels at 1 byte, a bf16 ring and summaries ---

def _matmul_params(d: dict) -> int:
    """Parameters that take part in a matmul: the layers' seven kernels and
    the whole head, every prediction head's columns (the program computes
    them all); the embedding is a gather, the pooling vectors are noise."""
    per_layer = 4 * d["hidden"] ** 2 + 3 * d["hidden"] * d["mlp"]
    return d["layers"] * per_layer \
        + d["hidden"] * d["vocab_size"] * d["pred_heads"]


def _row_bytes(d: dict) -> int:
    """K and V of one cached row (a ring row or a summary), all layers."""
    return 2 * d["layers"] * d["hidden"] * 2


def keys_visible(config: dict, context: float) -> float:
    """Cached rows one step attends at ``context`` cached positions: the
    ring rows of the current window and one summary for every chunk of the
    earlier ones. ``context`` is a MEAN over live rows: under one window it
    is exact (``context`` ring rows, no summary); from one window on the
    rows' places inside their windows are taken as spread evenly, so half a
    window of ring rows and a summary for every chunk before that."""
    win, chunk = config["window_size"], config["chunk_size"]
    if context < win:
        return float(context)
    return win / 2 + (context - win / 2) / chunk


def eva_step_bytes(config: dict, *, rows: float, context: float | None = None,
                   keys: float | None = None) -> float:
    """The cache side of ONE decode step: each live row reads the ring rows
    and summaries it has visible (``keys`` a row where the program counted
    them, else :func:`keys_visible` at the mean ``context``), K and V,
    bfloat16, every layer. Not the ring as allocated: a program that reads
    whole leaves reads a low share of this."""
    d = dims_of(config)
    if keys is None:
        keys = keys_visible(config, context)
    return rows * keys * _row_bytes(d)


def decode_step_bytes(config: dict, *, rows: float, context: float) -> float:
    """HBM bytes ONE decode step needs: every int8 kernel and the head
    once, whatever the batch, plus each live row's visible cache rows."""
    return _matmul_params(dims_of(config)) \
        + eva_step_bytes(config, rows=rows, context=context)


def decode_step_flops(config: dict, *, rows: float, context: float) -> float:
    d = dims_of(config)
    return rows * (2 * _matmul_params(d) + d["layers"] * 4 * d["hidden"]
                   * keys_visible(config, context))


def prefill_flops(config: dict, *, rows: int, seq_len: int) -> float:
    """Prefill of ``seq_len`` positions a row, the head at one position.
    Scores are window-local, not ``seq_len`` squared: a window's positions
    attend its own keys causally and the summaries of the windows before."""
    d = dims_of(config)
    win, chunk = d["window_size"], d["chunk_size"]
    head = d["hidden"] * d["vocab_size"] * d["pred_heads"]
    full, rest = divmod(seq_len, win)
    own = full * win * win + rest * rest                  # causal: half of 4
    summaries = sum(n * w * (win // chunk)
                    for w, n in enumerate([win] * full + [rest]))
    attn = d["layers"] * d["hidden"] * (2 * own + 4 * summaries)
    return rows * (2 * seq_len * (_matmul_params(d) - head) + attn + 2 * head)


# -- 5. the attention's own controls -------------------------------------------

def fault_gaps(config: dict, seeds: list, *, rows: int = 4,
               length: int | None = None, served: int | None = None) -> list:
    """What each control reads on ``rows`` seeded rows of ``length`` ids at
    their last ``served`` positions (by default the shape of a cell's
    sample: 27/32 of the engine window, its last ninth served), a sample
    per seed, all in one walk: the widest gap by which the token that
    stream puts first lies below the reference's best, and the share of
    positions where it is another."""
    length = length or int(config["engine_window"]) * 27 // 32
    served = served or max(1, length // 9)
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
    ap.add_argument("--length", type=int, default=None)
    ap.add_argument("--served", type=int, default=None)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    for seed in args.seeds.split(","):   # a walk a seed: memory bounded
        for line in fault_gaps(config, [int(seed)], length=args.length,
                               served=args.served):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
