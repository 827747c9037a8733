"""Chip micro-benchmark: the three forms of ``RoutedMLP``'s routed sum at the
``kanana2-30b`` cell's widths (128 experts of 3 x 2048 x 768 int8, top-6,
bfloat16), ms a layer at 8-256 tokens a call:

- ``streamed``: every expert on every token (``ops.grouped_experts
  .streamed_experts``: three batched products over the whole stacks);
- ``grouped``: XLA's data-dependent loop over the blocks of sorted
  assignments that exist (``models.moe.grouped_experts``);
- ``picked``: the Pallas kernel that fetches the distinct experts the rows
  picked (``ops.grouped_experts.picked_experts``).

The table in the comment over ``models/moe.py`` ``STREAM_ROWS`` is this
script's output on one v5e.

    python3 scripts/moe_forms.py [tokens ...]      # on the chip
    TOY=1 python3 scripts/moe_forms.py 8 16        # on the CPU: the paths, no number

Method: four layers with stacks of their own (2.4 GB: nothing is read
twice in a row), each a float32 router, the routed sum and a norm, so the
picks are the data's (about 41 distinct experts a layer at 8 tokens) and
each layer waits for the last; ``STEPS`` passes over the layers inside ONE
jitted scan, so a call's dispatch is spread over ``STEPS`` x 4 layers; wall
time ends with the result fetched; best and median of six calls. The router
and the norm are in the time of every form alike (about 0.01 ms a layer).
``TOY=1`` runs the kernel in the Pallas interpreter at toy widths. Prints a
JSON line a measurement and one of all, stamped with the device.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lambdipy_tpu.models import moe  # noqa: E402
from lambdipy_tpu.ops.grouped_experts import (distinct_experts,  # noqa: E402
                                              picked_experts,
                                              streamed_experts)

TOY = bool(os.environ.get("TOY"))
E, H, M, K, LAYERS, STEPS = (16, 128, 128, 3, 2, 2) if TOY \
    else (128, 2048, 768, 6, 4, 8)
DTYPE = jnp.float32 if TOY else jnp.bfloat16
SIZES = (8, 16, 32, 64, 128, 256)


def make_layers(key):
    def stack(k, shape):
        return (jax.random.randint(k, shape, -127, 128, jnp.int32).astype(
                    jnp.int8),
                jnp.full((shape[0], 1, shape[2]),
                         1.0 / (127 * shape[1] ** 0.5), jnp.float32))

    def layer(i):
        ks = jax.random.split(jax.random.fold_in(key, i), 4)
        return {"router": jax.random.normal(ks[3], (H, E), jnp.float32)
                * (1.2 / H ** 0.5),
                "stacks": [stack(ks[0], (E, H, M)), stack(ks[1], (E, H, M)),
                           stack(ks[2], (E, M, H))]}

    return [layer(i) for i in range(LAYERS)]


def streamed(tokens, chosen, w, stacks):
    return streamed_experts(tokens, chosen, w, None, stacks, DTYPE)


def grouped(tokens, chosen, w, stacks):
    return moe.grouped_experts(tokens, chosen, w, None,
                               moe.expert_by_index(stacks, DTYPE), E)


def picked(tokens, chosen, w, stacks):
    return picked_experts(tokens, chosen, w, None, stacks, DTYPE,
                          interpret=TOY)[0]


FORMS = {"streamed": streamed, "grouped": grouped, "picked": picked}


def program(form):
    """``STEPS`` passes over the layers; returns the last hidden state and
    the mean number of distinct experts a layer's call picked."""
    def layer(h, p):
        logits = h.astype(jnp.float32) @ p["router"]
        chosen, w = moe.route_dropless(logits, jnp.zeros((E,)), K,
                                       scoring="sigmoid", norm=True,
                                       scaling=2.448)
        h = h + form(h, chosen, w, p["stacks"]).astype(DTYPE)
        h32 = h.astype(jnp.float32)
        h = (h32 * jax.lax.rsqrt(jnp.mean(jnp.square(h32), -1, keepdims=True)
                                 + 1e-6)).astype(DTYPE)
        return h, distinct_experts(chosen, None, E, 1)[1]

    def run(layers, x):
        def step(carry, _):
            h, seen = carry
            for p in layers:
                h, distinct = layer(h, p)
                seen = seen + distinct
            return (h, seen), None

        (h, seen), _ = jax.lax.scan(step, (x, jnp.int32(0)), None,
                                    length=STEPS)
        return h, seen / (STEPS * LAYERS)

    return jax.jit(run)


def measure(forms=FORMS, sizes=SIZES, calls=6):
    key = jax.random.PRNGKey(0)
    layers = make_layers(key)
    out = {"device": jax.devices()[0].device_kind, "layers": LAYERS,
           "steps": STEPS, "experts": E, "widths": [H, M], "top_k": K}
    for t in sizes:
        x = jax.random.normal(jax.random.fold_in(key, 100 + t),
                              (t, H)).astype(DTYPE)
        for name, form in forms.items():
            t0 = time.time()
            fn = program(form).lower(layers, x).compile()
            compile_s = time.time() - t0
            jax.block_until_ready(fn(layers, x))
            times = []
            for i in range(calls):
                xi = x + jnp.asarray(0.01 * (i + 1), DTYPE)
                t0 = time.time()
                h, distinct = jax.block_until_ready(fn(layers, xi))
                times.append(time.time() - t0)
            per = 1e3 / (STEPS * LAYERS)
            out[f"{name}_t{t}"] = {
                "ms_per_layer": round(per * min(times), 4),
                "median": round(per * sorted(times)[len(times) // 2], 4),
                "distinct": round(float(distinct), 1),
                "compile_s": round(compile_s, 1)}
            print(json.dumps({f"{name}_t{t}": out[f"{name}_t{t}"]}),
                  flush=True)
    return out


if __name__ == "__main__":
    print(json.dumps(measure(
        sizes=tuple(int(a) for a in sys.argv[1:]) or SIZES)))
