"""Engine layer, open-loop cells: the median wait for the first token, from
due time. Not an end-to-end metric: a first token arrives with the end of a
16-step engine segment, so the median sits between two modes a segment
apart and runs of one schedule spread by 7 % (PERF.md section 2)."""


def read(ctx):
    return ctx["summary"].get("ttft_p50_ms")
