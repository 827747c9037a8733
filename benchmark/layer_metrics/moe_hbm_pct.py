"""Kernels layer: the routed FFNs' share of their HBM roofline in a decode
step of the traced slice.

Bytes the routed FFNs of one step need, as the cell's family counts them
(``moe_step_bytes``: router, shared experts and the routed experts the live
rows are EXPECTED to touch under even routing, not all of them), over the
device time the step spent under their scopes (``decode_moe_ms``: waits for
the experts' weights charged to the product behind them) and the peak of the
copied table. A program that streams every expert of every layer reads its
true share of what was needed, and none reads over 100: the time holds the
router's arithmetic, the sort and the gathers too. Live rows are the
harness's own count at the slice's two ends, as ``decode_hbm_pct`` takes
them. None where the family counts no such bytes or the program names no
such scopes."""

from benchmark import roofline, scopes


def read(ctx):
    count = getattr(ctx["family"], "moe_step_bytes", None)
    live = (ctx.get("slice") or {}).get("live") or []
    if count is None or not live:
        return None
    # the scopes of ``decode_moe_ms``
    ms = scopes.step_ms(ctx, ("router", "experts", "shared_expert"))
    if not ms:
        return None
    rows = sum(n for n, _ in live) / len(live)
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    return 100.0 * count(ctx["config"], rows=rows) / (ms / 1e3) \
        / peaks.hbm_bytes_s
