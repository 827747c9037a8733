"""Device layer: share of the traced slice in which no operation ran on
the device (profiler trace: 1 - union of device-op intervals / slice)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
