"""Chip micro-benchmark: the two forms of a recurrent state's one-token step
(``ops/state_step.py``) at the two cells' shapes, ms a layer:

- ``reference``: XLA's two reads and a write of every state
  (``stepped_reference``: what ``models/kda.py`` and
  ``models/linear_attn.py`` serve where Mosaic does not compile);
- ``kernel``: the Pallas kernel that steps the state leaf in place
  (``stepped_in_place``).

Shapes: ``kda`` is ``ling3-flash.reasoning-decode``'s step (16 rows, 6 layers
of 32 heads of 128 x 128, the delta rule, a channel's decay), ``lightning``
``minicpm-sala.long-document``'s (8 rows, 12 layers, no delta rule, a head's
decay). A layer's bytes are its states once each way: 67.1 MB and 33.6 MB,
82 and 41 us at 819 GB/s.

    python3 scripts/state_forms.py [kda|lightning ...]     # on the chip
    TOY=1 python3 scripts/state_forms.py                   # on the CPU: the paths, no number

Method: every layer has a state leaf of its own, donated to the call;
``STEPS`` passes over the layers inside ONE jitted scan whose carry holds
the leaves, as the engine's segment holds them, so a call's dispatch is
spread over ``STEPS`` x layers; a layer's vectors are made from the last
layer's output (normed, so that nothing grows), so each layer waits for
the last; wall time ends with the result fetched; best and median of six
calls. ``TOY=1`` runs the kernel in the Pallas interpreter at toy widths.
Prints a JSON line a measurement and one of all, stamped with the device.
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

from lambdipy_tpu.ops.state_step import (stepped_in_place,  # noqa: E402
                                         stepped_reference)

TOY = bool(os.environ.get("TOY"))
HEADS, D, STEPS = (4, 128, 2) if TOY else (32, 128, 16)
# name -> (rows, layers, the delta rule, a channel's decay)
SHAPES = {"kda": (3 if TOY else 16, 2 if TOY else 6, True, True),
          "lightning": (3 if TOY else 8, 2 if TOY else 12, False, False)}


def reference(leaf, q, k, v, decay, beta):
    rows = leaf.shape[0]
    out, state = stepped_reference(leaf.reshape(rows, HEADS, D, D), q, k, v,
                                   decay, beta)
    return out, state.reshape(leaf.shape)


def kernel(leaf, q, k, v, decay, beta):
    return stepped_in_place(leaf, q, k, v, decay, beta, interpret=TOY)


FORMS = {"reference": reference, "kernel": kernel}


def program(form, delta: bool, channel: bool):
    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def layer(x, leaf, i):
        q, k, v = l2(x) * D ** -0.5, l2(jnp.roll(x, i + 1, -1)), x
        decay = jnp.exp(-5.0 * jax.nn.sigmoid(jnp.roll(x, i + 2, -1))) \
            if channel else jnp.exp(-jnp.exp2(
                -8.0 * jnp.arange(1, HEADS + 1, dtype=jnp.float32) / HEADS))
        beta = jax.nn.sigmoid(x[..., 0]) if delta else None
        out, leaf = form(leaf, q, k, v, decay, beta)
        return l2(x + out) * D ** 0.5, leaf

    def run(leaves, x):
        def step(carry, _):
            x, leaves = carry
            new = []
            for i, leaf in enumerate(leaves):
                x, leaf = layer(x, leaf, i)
                new.append(leaf)
            return (x, new), None

        (x, leaves), _ = jax.lax.scan(step, (x, leaves), None, length=STEPS)
        return x, leaves

    return jax.jit(run, donate_argnums=0)


def measure(names, forms=FORMS, calls=6):
    key = jax.random.PRNGKey(0)
    out = {"device": jax.devices()[0].device_kind, "steps": STEPS,
           "heads": HEADS, "d": D}
    for name in names:
        rows, layers, delta, channel = SHAPES[name]
        x = jax.random.normal(key, (rows, HEADS, D), jnp.float32)
        for form_name, form in forms.items():
            leaves = [jnp.zeros((rows, 1, HEADS * D, D), jnp.float32)
                      for _ in range(layers)]
            t0 = time.time()
            fn = program(form, delta, channel).lower(leaves, x).compile()
            compile_s = time.time() - t0
            _, leaves = jax.block_until_ready(fn(leaves, x))
            times = []
            for i in range(calls):
                t0 = time.time()
                y, leaves = jax.block_until_ready(fn(leaves, x + 0.01 * i))
                times.append(time.time() - t0)
            per = 1e3 / (STEPS * layers)
            tag = f"{form_name}_{name}"
            out[tag] = {"ms_per_layer": round(per * min(times), 4),
                        "median": round(per * sorted(times)[len(times) // 2],
                                        4),
                        "rows": rows, "layers": layers,
                        "checksum": round(float(jnp.sum(jnp.abs(y))), 3),
                        "compile_s": round(compile_s, 1)}
            print(json.dumps({tag: out[tag]}), flush=True)
    return out


if __name__ == "__main__":
    print(json.dumps(measure(sys.argv[1:] or list(SHAPES))))
