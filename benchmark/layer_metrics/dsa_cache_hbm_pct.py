"""Kernels layer: the cache side of a sparse-attention decode step as a
share of its HBM roofline, over the traced slice.

Bytes the live rows NEED, as the cell's family counts them
(``dsa_step_bytes``: each live row's visible indexer keys and its selected
latent rows, bfloat16, every layer; both counts a row from the window's own
counter, ``handler.dsa``), over the device time the step spent under
``dsa_index``, ``dsa_select``, ``attend``, ``kv_write`` and ``kv_window``
(waits for the cache charged to the operation behind them) and the peak of
the copied table. The bytes are what is needed: a program that reads whole
leaves under a mask, or sorts where it could select, reads a low share, and
none reads over 100 because the time holds the indexer's projections, the
top-k and the softmax too. Live rows are the harness's own count at the
slice's two ends, as ``decode_hbm_pct`` takes them. None where the family
counts no such bytes or the program has no such scopes or counter."""

from benchmark import roofline, scopes
from benchmark.layer_metrics.dsa_keys_per_query import means


def read(ctx):
    count = getattr(ctx["family"], "dsa_step_bytes", None)
    live = (ctx.get("slice") or {}).get("live") or []
    keys = means(ctx)
    if count is None or not live or keys is None:
        return None
    ms = scopes.step_ms(ctx, ("dsa_index", "dsa_select", "attend",
                              "kv_write", "kv_window"))
    if not ms:
        return None
    rows = sum(n for n, _ in live) / len(live)
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    return 100.0 * count(ctx["config"], rows=rows, visible=keys[1],
                         selected=keys[0]) / (ms / 1e3) / peaks.hbm_bytes_s
