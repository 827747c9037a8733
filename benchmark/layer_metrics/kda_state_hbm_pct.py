"""Kernels layer: the Kimi-Delta-Attention side of a decode step as a share
of its HBM roofline, over the traced slice.

Bytes the live rows NEED, as the cell's family counts them
(``kda_step_bytes``: in each kda layer a live row's float32 state and its
bfloat16 conv tail, each read once and written once), over the device time
the step spent under ``kda_conv``, ``kda_gate`` and ``kda_state``
(``kda_state_ms``; waits for the state charged to the operation behind them)
and the peak of the copied table. A step that reads the state twice (once
for what it predicts, once to update it) cannot read over two thirds, and
none reads over 100: the time holds the convolution, the gates and the norm
too. Live rows are the harness's own count at the slice's two ends, as
``decode_hbm_pct`` takes them. None where the family counts no such bytes or
the program has no such scopes."""

from benchmark import roofline
from benchmark.layer_metrics.kda_state_ms import read as state_ms


def read(ctx):
    count = getattr(ctx["family"], "kda_step_bytes", None)
    live = (ctx.get("slice") or {}).get("live") or []
    if count is None or not live:
        return None
    ms = state_ms(ctx)
    if not ms:
        return None
    rows = sum(n for n, _ in live) / len(live)
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    return 100.0 * count(ctx["config"], rows=rows) / (ms / 1e3) \
        / peaks.hbm_bytes_s
