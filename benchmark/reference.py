"""The plain reference's shared half: one definition of ``correct`` for
every family.

The forward pass itself is the family's (``benchmark/families``: its
``walk``, float32 ``jax.numpy`` at ``highest`` precision, which on a TPU is
what keeps float32 float32; independent of the program, weights from
``benchmark/weights.py`` leaf by leaf, never from the bundle). Here: the
rows are teacher-forced whole (prompt plus the tokens that were served) in
one batch padded to a shape fixed per cell, one layer's weights on the
device at a time, so it fits beside nothing and is run after the served
program's state is freed.

What it returns, for every served token, is the gap by which that token's
reference logit lies below the reference's best logit at that position
(0 where the served token IS the reference's choice). With ``control`` the
family also walks its control stream — the nearest precision below the one
the configurations state — and the same gap is returned for the token that
stream puts first.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import families


def next_token_logits(config: dict, tokens: list):
    """The reference's logits for the token after ``tokens`` (one row)."""
    ids = np.asarray(tokens, np.int32)[None]
    return families.of(config).walk(
        config, ids, [0], [len(tokens) - 1], (False,))[False][0]


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
    logits = families.of(config).walk(
        config, ids, np.repeat(np.arange(n_rows), n_new), pos.reshape(-1),
        (False, True) if control else (False,))
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
