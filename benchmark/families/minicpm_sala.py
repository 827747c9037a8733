"""The ``minicpm_sala`` block (MiniCPM-SALA 9B) as its public ``config.json``
keys describe it: ``mixer_types`` names each layer ``minicpm4`` (grouped-query
attention without rope under InfLLM-V2's block-sparse selection) or
``lightning-attn`` (Lightning linear attention: a recurrent state a head),
under MiniCPM's muP scalars; served by the program's ``minicpm-sala`` builder
with int8 kernels and one float32 scale per output channel.

**The layer equations the reference follows** (token t, ``a = rmsnorm(x)``,
eps ``rms_norm_eps``; what the catalog row cannot confirm is listed under
``assumed`` in the configuration file).

1. Model: ``x_0 = scale_emb E[id]``; every sublayer ``x <- x + (scale_depth /
   sqrt(L)) f(rmsnorm(x))`` with L the PUBLISHED layer count
   (``published.num_hidden_layers``), not the layers held; ``logits = W_head
   (rmsnorm(x_L) / (hidden_size / dim_model_base))``; FFN ``W_d (silu(W_g a)
   * W_u a)``.
2. ``lightning-attn``: ``q_i, k_i, v_i = W a`` (``lightning_nh`` heads of
   ``lightning_head_dim``); ``q_i, k_i <- rmsnorm`` over the head with a
   learned gain (``qk_norm``); rope on all dims, rotate-half, ``rope_theta``;
   ``S_i,t = lambda_i S_i,t-1 + k_i,t^T v_i,t`` (float32, ``S_i,-1 = 0``),
   ``lambda_i = exp(-2^(-8 i / heads))``, i = 1 .. heads; ``o_i,t = d^-1/2
   q_i,t S_i,t``; ``o_i <- rmsnorm(o_i)`` with a gain (``use_output_norm``);
   ``o <- o * sigmoid(W_gate a)`` (``use_output_gate``); ``y = W_o o``. HERE:
   the plain recurrence, one position a step.
3. ``minicpm4``: ``q_i = W_q,i a`` (heads x d), ``k_g, v_g = W a`` (KV heads x
   d; consecutive heads share a KV head); ``qk_norm``; no rope. For ``t + 1
   <= dense_len``: causal softmax attention, scale ``d^-1/2``. Past it
   (``sparse_config``): compressed keys ``kc_g,j = mean(k_g,s : stride j <=
   s < stride j + kernel_size)`` for ``stride j + kernel_size <= t + 1``;
   ``p_i(t, j) = softmax_j(d^-1/2 q_i,t . kc_g,j)``; ``P_g = sum_{i in g}
   p_i``; block b = tokens ``[block_size b, block_size (b + 1))`` scores the
   max of ``P_g(t, j)`` over the windows j that touch it; block 0
   (``init_blocks``) and the ``window_size / block_size`` most recent blocks
   are forced; the ``topk`` blocks of largest score (forced ones among them,
   ties to the lowest block, exact) are attended by all heads of the group
   under one softmax; ``o <- o * sigmoid(W_gate a)``
   (``attn_use_output_gate``); ``y = W_o o``.

**Departures**: int8 weight-only kernels; the exact softmax over the
compressed keys in (3) (the published kernels may normalise over coarser
keys: the paper's definition is what program and reference compute);
``mup_denominator`` is not read at inference.

**Weights** as ``llama_hf.py``: int8 kernels uniform over the full range with
one float32 scale of 1/(127 sqrt(fan_in)) per output channel, an embedding of
int8-uniform values x 2^-12, unit norm gains (the head norms' too). EXCEPT
what makes the selection matter: ``qk_norm`` gives every query and key unit
RMS, so an attention logit has a standard deviation of 1 and the softmax over
4096 selected keys is carried by some 1500 of them: the output is the mean of
so many values that it is a fortieth of one value's norm, beside the linear
layers' and the FFN's it moves no logit, and ANY 4096 keys read alike: at
unit gains ``dense_past``, ``no_forced`` and ``sparse_rope`` read UNDER the
sound program's own gap (my chip run, PR 39, call 2). So a configuration may
state ``sparse_q_gain`` (3.0 in the cell's): the gain of ``q_norm`` in the
``minicpm4`` layers, a learned weight of the model like any other: logits of
standard deviation 3, a softmax that a dozen keys carry, an output ten times
larger. The mathematics and every byte moved are unchanged.

**Controls.** ``True``: every int8 kernel rounded to int4. ``FAULTS`` plant
one fault each: ``dense_past`` (the selection dropped: every visible key
attended past ``dense_len`` too), ``no_forced`` (block 0 and the local window
compete like any block), ``stale_kc`` (the compressed keys whose window ends
at a served position never written: zeros), ``sparse_rope`` (rope applied in
the ``minicpm4`` layers), ``no_decay`` (``lambda`` = 1), ``bf16_states`` (the
state rounded to bfloat16 after every update). ``python3 -m
benchmark.families.minicpm_sala --config <file> --seeds 1,2`` walks them on
seeded rows; with ``--cell <cell>`` on the tokens that cell's program
served.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights

SCOPES = ("embed", "qkv_proj", "kv_write", "attend", "o_proj", "mlp",
          "lm_head", "sample", "kv_window", "sala_compress", "sala_select",
          "lin_state", "lin_scan")
WITNESS = ("qkv_proj", "mlp", "sample", "sala_select", "lin_state")
FAULTS = ("dense_past", "no_forced", "stale_kc", "sparse_rope", "no_decay",
          "bf16_states")

QUERY_BLOCK = 128     # queries one turn of the reference's attention scores
FFN_BLOCK = 2048      # positions one turn of the reference's FFN takes
KINDS = {"minicpm4": "sparse_kv", "lightning-attn": "linear"}


# -- 1. the widths, under the program's names --------------------------------

def dims_of(config: dict) -> dict:
    """The configuration's keys under the names the program's
    ``minicpm-sala`` builder takes (``LlamaConfig`` fields). Booleans go as
    the strings a recipe's TOML would hand the builder anyway."""
    if config.get("attention_bias") or config.get("attn_use_rope") \
            or config.get("tie_word_embeddings") \
            or config["lightning_nkv"] != config["lightning_nh"] \
            or config.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)" \
            or config["head_dim"] * config["num_attention_heads"] \
            != config["hidden_size"]:
        raise ValueError(
            "minicpm-sala family: attention bias, rope in the minicpm4 "
            "layers, a tied head, grouped lightning heads, a lightning "
            "scale other than 1/sqrt(d) and a head_dim other than hidden / "
            "heads are not written")
    unknown = set(config["mixer_types"]) - set(KINDS)
    if unknown or len(config["mixer_types"]) != config["num_hidden_layers"]:
        raise ValueError("minicpm-sala family: mixer_types names "
                         f"{sorted(unknown)} / is not one a layer")
    sparse = config["sparse_config"]
    published = (config.get("published") or {}).get(
        "num_hidden_layers", config["num_hidden_layers"])

    def flag(key):
        return str(bool(config.get(key, True))).lower()

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
        "layer_kinds": ",".join(KINDS[m] for m in config["mixer_types"]),
        "qk_norm": flag("qk_norm"),
        "attn_output_gate": flag("attn_use_output_gate"),
        "sparse_kernel": sparse["kernel_size"],
        "sparse_stride": sparse["kernel_stride"],
        "sparse_block": sparse["block_size"],
        "sparse_topk": sparse["topk"],
        "sparse_init_blocks": sparse["init_blocks"],
        "sparse_window": sparse["window_size"],
        "sparse_dense_len": sparse["dense_len"],
        "lin_heads": config["lightning_nh"],
        "lin_head_dim": config["lightning_head_dim"],
        "lin_rope": flag("lightning_use_rope"),
        "lin_output_norm": flag("use_output_norm"),
        "embed_scale": float(config["scale_emb"]),
        "residual_scale": float(config["scale_depth"]) / published ** 0.5,
        "logit_divisor": config["hidden_size"] / config["dim_model_base"],
    }


def _shapes(d: dict, kind: str) -> dict:
    """``{kernel: (fan_in, fan_out)}`` of a layer of kind ``kind``."""
    h, m, hd = d["hidden"], d["mlp"], d["hidden"] // d["heads"]
    if kind == "linear":
        wide = d["lin_heads"] * d["lin_head_dim"]
        attn = {"q_proj": (h, wide), "k_proj": (h, wide), "v_proj": (h, wide)}
    else:
        wide = d["heads"] * hd
        attn = {"q_proj": (h, wide), "k_proj": (h, d["kv_heads"] * hd),
                "v_proj": (h, d["kv_heads"] * hd)}
    if d["attn_output_gate"] == "true":
        attn["out_gate_proj"] = (h, wide)
    return {**attn, "o_proj": (wide, h), "gate_proj": (h, m),
            "up_proj": (h, m), "down_proj": (m, h)}


# -- 2. the leaves -------------------------------------------------------------

def _kind_at(path: str, config: dict) -> str:
    """The program's kind of the layer a leaf at ``layer_<i>/...`` is in."""
    return KINDS[config["mixer_types"][int(path.split("/")[0].split("_")[1])]]


def leaf(seed: int, path: str, shape, dtype, config: dict):
    """One parameter leaf by its path in the program's tree, e.g.
    ``layer_3/out_gate_proj/kernel_int8`` or ``layer_0/q_norm/scale``."""
    name = np.dtype(dtype).name
    if name == "int8" and path.endswith("/kernel_int8"):
        return weights.int8_draw(seed, path, shape)
    if path.endswith("embedding"):
        return (weights.int8_draw(seed, path, shape).astype(np.float32)
                * weights.EMBED_STEP).astype(dtype)
    if path.endswith("/q_norm/scale") and _kind_at(path, config) \
            == "sparse_kv":
        return np.full(shape, config.get("sparse_q_gain", 1.0), dtype)
    if path.endswith("norm/scale"):
        return np.ones(shape, dtype)
    if path.endswith("/scale"):
        d = dims_of(config)
        kernel = path.split("/")[-2]
        if kernel == "lm_head":
            fan_in = d["hidden"]
        else:
            fan_in = _shapes(d, _kind_at(path, config)).get(kernel,
                                                            (None,))[0]
        if fan_in is None:
            return None
        return np.full(shape, 1.0 / (127.0 * fan_in ** 0.5), dtype)
    return None


# -- 3. the reference's walk ---------------------------------------------------

def _layer_fns(d: dict, fault):
    """The jitted parts of a walk: ``fault`` False for the reference, True
    for the int4 control, or one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp

    heads, kvh = d["heads"], d["kv_heads"]
    hd = d["hidden"] // heads
    lh, ld = d["lin_heads"], d["lin_head_dim"]
    eps, rs = d["norm_eps"], d["residual_scale"]
    stride, kernel, block = (d["sparse_stride"], d["sparse_kernel"],
                             d["sparse_block"])
    per, topk = block // stride, d["sparse_topk"]
    local = d["sparse_window"] // block
    gated = d["attn_output_gate"] == "true"

    def deq(w, scale):
        w = w.astype(jnp.float32)
        if fault is True:
            w = jnp.clip(jnp.round(w / 16.0), -8, 7) * 16.0
        return w * scale

    def norm(x, g):
        return x * (jnp.mean(x * x, -1, keepdims=True) + eps) ** -0.5 * g

    def rope(x, cos, sin):      # [s, heads, d]; cos, sin [s, d / 2]
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        cos, sin = cos[:, None], sin[:, None]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def ffn(x, p):
        s = x.shape[0]
        pad = -s % FFN_BLOCK if s > FFN_BLOCK else 0

        def turn(m):
            m = norm(m, p["mlp_norm"])
            return (jax.nn.silu(m @ deq(*p["gate_proj"]))
                    * (m @ deq(*p["up_proj"]))) @ deq(*p["down_proj"])

        if s <= FFN_BLOCK:
            return x + rs * turn(x)
        y = jax.lax.map(turn, jnp.pad(x, ((0, pad), (0, 0))).reshape(
            -1, FFN_BLOCK, x.shape[-1]))
        return x + rs * y.reshape(-1, x.shape[-1])[:s]

    def gate(o, a, p):
        return o * jax.nn.sigmoid(a @ deq(*p["out_gate_proj"])) if gated \
            else o

    def linear_row(x, cos, sin, stale_from, p):
        s = x.shape[0]
        a = norm(x, p["attn_norm"])
        q, k, v = ((a @ deq(*p[name])).reshape(s, lh, ld)
                   for name in ("q_proj", "k_proj", "v_proj"))
        if d["qk_norm"] == "true":
            q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
        if d["lin_rope"] == "true":
            q, k = rope(q, cos, sin), rope(k, cos, sin)
        lam = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, lh + 1) / lh))
        if fault == "no_decay":
            lam = jnp.ones_like(lam)

        def step(state, qkv):
            q_t, k_t, v_t = qkv
            state = lam[:, None, None] * state \
                + k_t[:, :, None] * v_t[:, None, :]
            if fault == "bf16_states":
                state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return state, jnp.sum(q_t[:, :, None] * state, axis=1)

        _, o = jax.lax.scan(step, jnp.zeros((lh, ld, ld), jnp.float32),
                            (q, k, v))
        o = o * ld ** -0.5
        if d["lin_output_norm"] == "true":
            o = norm(o, p["o_norm"])
        o = gate(o.reshape(s, lh * ld), a, p)
        return ffn(x + rs * (o @ deq(*p["o_proj"])), p)

    def sparse_row(x, cos, sin, stale_from, p):
        s = x.shape[0]
        a = norm(x, p["attn_norm"])
        q = (a @ deq(*p["q_proj"])).reshape(s, heads, hd)
        k = (a @ deq(*p["k_proj"])).reshape(s, kvh, hd)
        v = (a @ deq(*p["v_proj"])).reshape(s, kvh, hd)
        if d["qk_norm"] == "true":
            q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
        if fault == "sparse_rope":
            q, k = rope(q, cos, sin), rope(k, cos, sin)
        # every compressed key the row can complete, gathered plainly
        nj = max(0, (s - kernel) // stride + 1)
        nb = -(-s // block)
        first = stride * jnp.arange(max(nj, 1))
        kc = jnp.mean(k[first[:, None] + jnp.arange(kernel)[None, :]], axis=1)
        if nj == 0:
            kc = jnp.zeros_like(kc)
        ends = first + kernel            # tokens 0 .. ends - 1 make it
        if fault == "stale_kc":
            # written only by the prefill: the windows that end at or before
            # the first served position
            kc = jnp.where((ends <= stale_from)[:, None, None], kc, 0.0)
        # the windows that touch block b: per b - 1 .. per b + per - 1
        touch = per * jnp.arange(nb)[:, None] + jnp.arange(-1, per)[None, :]
        real = (touch >= 0) & (touch < nj)
        touch = jnp.clip(touch, 0, max(nj, 1) - 1)
        q = q.reshape(s, kvh, heads // kvh, hd)
        scale = hd ** -0.5
        q_block = min(s, QUERY_BLOCK)
        turns = -(-s // q_block)

        def turn(args):
            i, q_b = args
            pos = i * q_block + jnp.arange(q_block)
            causal = jnp.arange(s)[None, :] <= pos[:, None]      # [Q, s]
            seen = jnp.broadcast_to(causal[None], (kvh, q_block, s))
            if fault != "dense_past" and nj > 0 and nb > topk:
                vis = (ends[None, :] <= pos[:, None] + 1) & (nj > 0)
                lg = jnp.einsum("qgid,jgd->giqj", q_b, kc) * scale
                pr = jax.nn.softmax(jnp.where(vis[None, None], lg, -jnp.inf),
                                    axis=-1)
                pr = jnp.where(vis[None, None], pr, 0.0)   # none visible: 0
                group = jnp.sum(pr, axis=1)                       # [g, Q, nj]
                score = jnp.max(jnp.where(real[None, None],
                                          group[..., touch], 0.0), axis=-1)
                blk = jnp.arange(nb)[None, :]
                cur = (pos // block)[:, None]
                if fault != "no_forced":
                    forced = (blk < d["sparse_init_blocks"]) \
                        | (blk > cur - local)
                    score = jnp.where(forced[None], jnp.inf, score)
                score = jnp.where((blk <= cur)[None], score, -jnp.inf)
                _, at = jax.lax.top_k(score, topk)         # lowest index
                picked = jnp.zeros(score.shape, bool).at[     # wins a tie
                    jnp.arange(kvh)[:, None, None],
                    jnp.arange(q_block)[None, :, None], at].set(True)
                tokens = jnp.repeat(picked, block, axis=-1)[..., :s]
                dense = (pos + 1 <= d["sparse_dense_len"])[None, :, None]
                seen = causal[None] & (dense | tokens)
            lg = jnp.einsum("qgid,tgd->giqt", q_b, k) * scale
            pr = jax.nn.softmax(jnp.where(seen[:, None], lg, -jnp.inf),
                                axis=-1)
            return jnp.einsum("giqt,tgd->qgid", pr, v)

        pad = turns * q_block - s
        o = jax.lax.map(turn, (jnp.arange(turns), jnp.pad(
            q, ((0, pad),) + ((0, 0),) * 3).reshape(
                turns, q_block, kvh, heads // kvh, hd)))
        o = gate(o.reshape(turns * q_block, heads * hd)[:s], a, p)
        return ffn(x + rs * (o @ deq(*p["o_proj"])), p)

    def by_row(row_fn):
        def layer(x, cos, sin, stale_from, p):
            return jax.lax.map(
                lambda args: row_fn(args[0], cos, sin, args[1], p),
                (x, stale_from))
        return jax.jit(layer)

    def head(x, rows, pos, g, w, scale):
        return (norm(x[rows, pos], g) / d["logit_divisor"]) @ deq(w, scale)

    return {"linear": by_row(linear_row), "sparse_kv": by_row(sparse_row),
            "head": jax.jit(head)}


def walk(config: dict, ids: np.ndarray, rows_op, pos_op, flags: tuple, *,
         first_only: tuple = ()):
    """Logits at ``(rows_op, pos_op)`` of the batch ``ids`` [rows, length],
    one array per flag (False = the float32 reference, True = its int4
    control, or one of ``FAULTS``), walking the layers once with one layer's
    weights on the device at a time."""
    import jax
    import jax.numpy as jnp

    d = dims_of(config)
    h = d["hidden"]
    kinds = d["layer_kinds"].split(",")

    def get(path, shp, dtype):
        return jnp.asarray(weights.leaf(config, path, shp, dtype))

    ang = np.arange(ids.shape[1], dtype=np.float32)[:, None] / (
        d["rope_theta"] ** (np.arange(0, d["lin_head_dim"], 2,
                                      dtype=np.float32) / d["lin_head_dim"]))
    cos, sin = jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))
    rows_np, pos_np = np.asarray(rows_op), np.asarray(pos_op)
    # stale_kc: a row's compressed keys from its first served position on
    # (the position after the first one asked for) were never written
    stale_from = jnp.asarray([
        int(pos_np[rows_np == r].min()) + 1 if (rows_np == r).any()
        else ids.shape[1] for r in range(ids.shape[0])], jnp.int32)
    embed = weights.leaf(config, "embed/embedding", (d["vocab_size"], h),
                         "float32")
    x0 = jnp.asarray(embed[ids]) * d["embed_scale"]
    del embed
    fns = {flag: _layer_fns(d, flag) for flag in flags}
    xs = {flag: x0 for flag in flags}
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(kinds):
            at = f"layer_{i}"
            p = {name: get(f"{at}/{name}/scale", (h,), "float32")
                 for name in ("attn_norm", "mlp_norm")}
            width = d["lin_head_dim"] if kind == "linear" \
                else h // d["heads"]
            if d["qk_norm"] == "true":
                for name in ("q_norm", "k_norm"):
                    p[name] = get(f"{at}/{name}/scale", (width,), "float32")
            if kind == "linear" and d["lin_output_norm"] == "true":
                p["o_norm"] = get(f"{at}/o_norm/scale", (width,), "float32")
            for name, shp in _shapes(d, kind).items():
                p[name] = (get(f"{at}/{name}/kernel_int8", shp, "int8"),
                           get(f"{at}/{name}/scale", (1, shp[1]), "float32"))
            for flag in flags:    # one stream's old activations at a time
                xs[flag] = fns[flag][kind](xs[flag], cos, sin, stale_from, p)
            del p
        g = get("final_norm/scale", (h,), "float32")
        w = get("lm_head/kernel_int8", (h, d["vocab_size"]), "int8")
        sc = get("lm_head/scale", (1, d["vocab_size"]), "float32")
        out = {}
        for flag, x in xs.items():
            logits = fns[flag]["head"](x, jnp.asarray(rows_op),
                                       jnp.asarray(pos_op), g, w, sc)
            out[flag] = logits.argmax(axis=-1) if flag in first_only \
                else logits
        return out


# -- 4. what a step needs: int8 kernels at 1 byte, bf16 rows and compressed
# keys, float32 states -----------------------------------------------------------

def _layer_params(d: dict, kind: str) -> int:
    return sum(a * b for a, b in _shapes(d, kind).values())


def _counts(d: dict) -> tuple:
    kinds = d["layer_kinds"].split(",")
    return kinds.count("sparse_kv"), kinds.count("linear")


def visible_kc(d: dict, context: float) -> float:
    """Compressed keys whose window lies inside ``context`` positions."""
    return max(0.0, (context - d["sparse_kernel"]) // d["sparse_stride"] + 1)


def attended_keys(d: dict, context: float) -> float:
    """Keys one query with ``context`` positions visible attends."""
    if context <= d["sparse_dense_len"]:
        return context
    return min(context, d["sparse_topk"] * d["sparse_block"])


def sala_step_bytes(config: dict, *, rows: float, visible: float,
                    attended: float) -> float:
    """Cache bytes the two kinds of ONE decode step NEED: in each
    block-sparse layer a live row scores the compressed keys its ``visible``
    positions hold and attends ``attended`` rows of ``k`` and of ``v`` (KV
    heads x head_dim bf16 values each); in each linear layer it reads and
    writes its float32 state. A program that reads whole leaves under a mask
    reads a low share of its roofline."""
    d = dims_of(config)
    sparse, linear = _counts(d)
    row = 2 * d["kv_heads"] * (d["hidden"] // d["heads"])
    state = 4 * d["lin_heads"] * d["lin_head_dim"] ** 2
    return rows * (sparse * row * (visible_kc(d, visible) + 2 * attended)
                   + linear * 2 * state)


def decode_step_bytes(config: dict, *, rows: float, context: float) -> float:
    """HBM bytes ONE decode step needs: every kernel once (the head's
    among them) and what the two kinds' caches need at this context
    (``sala_step_bytes``): not what a masked read of whole leaves
    fetches."""
    d = dims_of(config)
    sparse, linear = _counts(d)
    kernels = sparse * _layer_params(d, "sparse_kv") \
        + linear * _layer_params(d, "linear") + d["hidden"] * d["vocab_size"]
    return kernels + sala_step_bytes(
        config, rows=rows, visible=context,
        attended=attended_keys(d, context))


def decode_step_flops(config: dict, *, rows: float, context: float) -> float:
    d = dims_of(config)
    sparse, linear = _counts(d)
    hd = d["hidden"] // d["heads"]
    select = 2 * d["heads"] * hd * visible_kc(d, context)
    attend = 4 * d["heads"] * hd * attended_keys(d, context)
    state = 6 * d["lin_heads"] * d["lin_head_dim"] ** 2
    return rows * (2 * (sparse * _layer_params(d, "sparse_kv")
                        + linear * _layer_params(d, "linear"))
                   + 2 * d["hidden"] * d["vocab_size"]
                   + sparse * (select + attend) + linear * state)


def prefill_flops(config: dict, *, rows: int, seq_len: int,
                  chunk: int = 256) -> float:
    """A block-sparse layer attends under a MASK: scores and sums over the
    causal half, and the compressed keys' scores past ``dense_len``; a linear
    layer the chunked form; lm_head at one position."""
    d = dims_of(config)
    sparse, linear = _counts(d)
    hd = d["hidden"] // d["heads"]
    pairs = seq_len * seq_len / 2
    attend = 4 * d["heads"] * hd * pairs
    past = max(0, seq_len - d["sparse_dense_len"])
    select = 2 * d["heads"] * hd * past * (seq_len + d["sparse_dense_len"]) \
        / (2 * d["sparse_stride"])
    scan = seq_len * d["lin_heads"] * (4 * chunk * d["lin_head_dim"]
                                       + 4 * d["lin_head_dim"] ** 2)
    return rows * (2 * seq_len * (sparse * _layer_params(d, "sparse_kv")
                                  + linear * _layer_params(d, "linear"))
                   + sparse * (attend + select) + linear * scan
                   + 2 * d["hidden"] * d["vocab_size"])


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
               length: int = 12288, served: int = 1024,
               flags: tuple = (True,) + FAULTS) -> list:
    """What each control reads on ``rows`` seeded rows of ``length`` token
    ids at their last ``served`` positions, a sample per seed, all in one
    walk: the widest gap by which the token that stream puts first lies
    below the reference's best, and the share of positions where it is
    another. (At the cell's widths a row of 12288 is three times the 4096
    keys a query may attend and half again ``dense_len``.)"""
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
               "failed": summary["failed"],
               **sample_gaps(ctx["config"], rows, shape, flags)}


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=fault_gaps.__doc__)
    ap.add_argument("--config", default=None)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--length", type=int, default=12288)
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
