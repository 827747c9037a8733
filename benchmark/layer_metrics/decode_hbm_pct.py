"""Kernels layer (XLA today): the decode step's share of the HBM roofline
over the traced slice.

Bytes the algorithm needs = decode steps in the slice x (weight bytes + K/V
bytes of the live rows at their actual mean context), as the cell's family
counts them (``decode_step_bytes``, ``benchmark/families``). Steps = segments run
across the slice x segment length (/metrics deltas); live rows and their
contexts are the harness's own count of the requests open at the slice's
two ends (prompt length + tokens received). Divided by the device-busy
seconds between the two counter scrapes (the trace's busy share x the
seconds between them: both scrapes lie inside the traced interval) and by
the peak of the copied table. Prefill and the
window bucket's over-read are in the time and not in the bytes, so it
under-reads and cannot pass 100. It is a whole step's share, not one
kernel's."""

from benchmark import roofline


def read(ctx):
    tr, sl = ctx.get("trace"), ctx.get("slice")
    if not tr or not sl or tr["busy_s"] <= 0:
        return None
    try:
        a, b = (sl[k]["handler"]["batching"] for k in ("m0", "m1"))
        segments = b["segments_run"] - a["segments_run"]
        segment = int(b.get("segment", 16))
    except (KeyError, TypeError):
        return None
    live = sl.get("live") or []
    if segments <= 0 or not live:
        return None
    rows = sum(n for n, _ in live) / len(live)
    context = sum(c for _, c in live) / len(live)
    step = ctx["family"].decode_step_bytes(ctx["config"], rows=rows,
                                           context=context)
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    busy_s = tr["busy_s"] / tr["window_s"] * (sl["t1"] - sl["t0"])
    return 100.0 * segments * segment * step / busy_s / peaks.hbm_bytes_s
