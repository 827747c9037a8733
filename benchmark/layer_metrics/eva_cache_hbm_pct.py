"""Kernels layer: the cache side of an EVA decode step as a share of its HBM
roofline, over the traced slice.

Bytes the live rows NEED, as the cell's family counts them
(``eva_step_bytes``: each live row's visible ring rows and summaries, K and
V, bfloat16, every layer; the keys a row from the window's own counter,
``handler.eva``, where the program has it, else from the rows' mean
context), over the device time the step spent under ``attend``,
``kv_write``, ``kv_window`` and ``eva_summarize`` (waits for the cache
charged to the operation behind them) and the peak of the copied table. The
bytes are what is needed, not what is allocated: a program that reads whole
leaves, or writes a ring back whole, reads a low share, and none reads over
100 because the time holds the softmax, the pooling and the scatters too.
Live rows are the harness's own count at the slice's two ends, as
``decode_hbm_pct`` takes them. None where the family counts no such bytes
or the program names no such scopes."""

from benchmark import roofline, scopes
from benchmark.layer_metrics.eva_keys_per_query import read as keys_per_query


def read(ctx):
    count = getattr(ctx["family"], "eva_step_bytes", None)
    live = (ctx.get("slice") or {}).get("live") or []
    if count is None or not live:
        return None
    ms = scopes.step_ms(ctx, ("attend", "kv_write", "kv_window",
                              "eva_summarize"))
    if not ms:
        return None
    rows = sum(n for n, _ in live) / len(live)
    context = sum(c for _, c in live) / len(live)
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    return 100.0 * count(ctx["config"], rows=rows, context=context,
                         keys=keys_per_query(ctx)) / (ms / 1e3) \
        / peaks.hbm_bytes_s
