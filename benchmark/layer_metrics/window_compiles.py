"""Model-programs layer: compile requests (jax) plus new compiled programs
(LlamaServer) inside the window. Should read 0."""


def read(ctx):
    a, b = ctx.get("compile_open"), ctx.get("compile_close")
    if not a or not b:
        return None
    return max(b["requests"] - a["requests"], b["programs"] - a["programs"])
