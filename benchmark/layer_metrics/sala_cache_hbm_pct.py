"""Kernels layer: the cache side of a decode step of block-sparse and
linear-attention layers as a share of its HBM roofline, over the traced
slice.

Bytes the live rows NEED, as the cell's family counts them
(``sala_step_bytes``: in each block-sparse layer a live row's visible
compressed keys and the rows of ``k`` and of ``v`` it attends, bfloat16; in
each linear layer its float32 state read and written; both counts a row from
the window's own counter, ``handler.sala``), over the device time the step
spent under ``sala_compress``, ``sala_select``, ``attend``, ``kv_write``,
``kv_window`` and ``lin_state`` (waits for the cache charged to the operation
behind them) and the peak of the copied table. The bytes are what is needed:
a program that reads whole leaves under a mask reads a low share, and none
reads over 100 because the time holds the softmaxes, the pooling, the
threshold and the norms too. Live rows are the harness's own count at the
slice's two ends, as ``decode_hbm_pct`` takes them. None where the family
counts no such bytes or the program has no such scopes or counter."""

from benchmark import roofline, scopes
from benchmark.layer_metrics.sala_keys_per_query import means


def read(ctx):
    count = getattr(ctx["family"], "sala_step_bytes", None)
    live = (ctx.get("slice") or {}).get("live") or []
    keys = means(ctx)
    if count is None or not live or keys is None:
        return None
    ms = scopes.step_ms(ctx, ("sala_compress", "sala_select", "attend",
                              "kv_write", "kv_window", "lin_state"))
    if not ms:
        return None
    rows = sum(n for n, _ in live) / len(live)
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    return 100.0 * count(ctx["config"], rows=rows, visible=keys[1],
                         attended=keys[0]) / (ms / 1e3) / peaks.hbm_bytes_s
