"""Boot layer: compile requests that the persistent cache did not answer,
process start -> end of warm-up (/metrics `compile`)."""


def read(ctx):
    c = ctx.get("compile_warm")
    if not c:
        return None
    return c["requests"] - c["cache_hits"]
