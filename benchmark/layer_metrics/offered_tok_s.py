"""Engine layer, open-loop cells: output tokens completed per second of
window as the harness counted them. Below the knee it reads the offered
load; a fall means requests failed or the generator ran late."""


def read(ctx):
    return ctx["summary"].get("out_tok_s")
