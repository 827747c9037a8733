"""Device layer: peak bytes in use on the fullest chip after the window
(/metrics `device.memory` peak_bytes_in_use), in GB (1e9 bytes)."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
