"""Boot layer: harness clock, deploy call -> /healthz ready."""


def read(ctx):
    return ctx.get("boot_ready_s")
