"""On-chip head-to-head: Pallas kernels vs XLA at REAL model dims
(VERDICT r3 weak #3 — the kernels were numerics-checked but never earned
their keep with a measured number; defaults follow whichever wins).

Measures, at Llama-3-8B shapes on the v5e chip:

- prefill attention: dense (XLA-fused reference) vs the Pallas flash
  kernel, causal, [1, S, 32 heads, 128 dim] bf16 with GQA kv=8, at
  S = 1024 and 4096;
- int8 weight-only matmul: XLA dequant-into-bf16-matmul vs the blocked
  Pallas kernel, at the 8B layer shapes (4096x4096 qo, 4096x14336 /
  14336x4096 mlp) for decode rows (m=1, 8) and a prefill chunk (m=512).

Method: the kernels are sub-millisecond, so a single call timed on the
host clock measures dispatch, not the kernel. Each candidate op runs K
times inside ONE jitted ``lax.scan`` whose carry folds a nonlinear
function of each output back into the next input — the iterations
serialize, nothing can be dead-code-eliminated, and (because the fold is
|out|-based, not linear) XLA's algebraic simplifier cannot rewrite the reduction into a
cheaper expression (observed without the guard: ``sum(x @ W)`` became
``dot(rowsum x, colsum W)`` and reported an impossible 5.8 TB/s). The
per-op time is wall / K, the wall ending with the scalar fetched. The
kernel's own device time comes from a profiler trace, not from here.
Results print as JSON lines, each run stamped with its device.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from bench import _timed  # noqa: E402


def _amortized_ms(fn, iters, n=5):
    """Median ms per op: fn() runs the op `iters` times device-side and
    returns a scalar, fetched inside the timed region."""
    float(fn())  # compile + warm
    float(fn())
    wall = statistics.median([_timed(lambda: float(fn()))
                              for _ in range(n)])
    return max(1e-4, wall / iters)


def _scan_many(op, iters):
    """op(carry) -> output; returns a jitted fn running op `iters` times
    with a serializing nonlinear carry fold."""
    import jax
    import jax.numpy as jnp

    def many(carry0):
        def step(c, _):
            o = op(c)
            bump = (jnp.abs(o).astype(jnp.float32).sum() * 1e-20
                    ).astype(c.dtype)
            return c + bump, ()

        c, _ = jax.lax.scan(step, carry0, None, length=iters)
        return jnp.abs(c).astype(jnp.float32).sum()

    return jax.jit(many)


def bench_attention():
    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.ops.attention import flash_attention, mha_reference
    from lambdipy_tpu.utils import roofline

    peak = roofline.peaks_for(jax.devices()[0].device_kind).bf16_flops
    h, kvh, d = 32, 8, 128
    for s, iters in ((1024, 50), (4096, 10)):
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, s, h, d), jnp.bfloat16)
        k = jax.random.normal(kk, (1, s, kvh, d), jnp.bfloat16)
        v = jax.random.normal(kv, (1, s, kvh, d), jnp.bfloat16)
        flops = 2 * 2 * h * s * s * d / 2  # qk + av, causal-halved

        kd = jnp.repeat(k, h // kvh, axis=2)
        vd = jnp.repeat(v, h // kvh, axis=2)
        dense = _scan_many(
            lambda c: mha_reference(c, kd, vd, causal=True), iters)
        flash = _scan_many(
            lambda c: flash_attention(c, k, v, causal=True,
                                      interpret=False), iters)
        out = {"op": "prefill_attention", "seq": s, "heads": h, "dim": d,
               "iters": iters}
        for name, fn in (("dense_ms", dense), ("flash_ms", flash)):
            ms = _amortized_ms(lambda: fn(q), iters)
            out[name] = round(ms, 3)
            out[name.replace("_ms", "_mfu")] = round(
                flops / (ms / 1e3) / peak, 3)
        out["winner"] = ("flash" if out["flash_ms"] < out["dense_ms"]
                         else "dense")
        print(json.dumps(out))


def bench_decode_attention():
    """Length-aware blocked decode attention vs the full-window dense
    reference at 8B decode shapes: [8, 1, 32 h, 128 d] queries against
    an 8192-position KV window (GQA kv=8, bf16), at active lengths
    512 / 2048 / 8192. The claim under test: blocked KV bytes scale
    with ``active_len`` (early-exit blocks skip compute AND their DMA
    via the clamped index map), so short rows stop paying full-window
    reads. ``kv_gb_s`` is bytes-the-path-must-read / time — for dense
    that is always the full window, for blocked the active prefix."""
    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.ops.decode_attention import (
        blocked_decode_attention, decode_attention_reference)

    b, h, kvh, d, t = 8, 32, 8, 128, 8192
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, 1, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, t, kvh, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, t, kvh, d), jnp.bfloat16)
    iters = 50
    for alen in (512, 2048, 8192):
        lens = jnp.full((b,), alen, jnp.int32)
        dense = _scan_many(
            lambda c: decode_attention_reference(c, k, v, lens), iters)
        blocked = _scan_many(
            lambda c: blocked_decode_attention(c, k, v, lens,
                                               interpret=False), iters)
        out = {"op": "decode_attention", "active_len": alen, "window": t,
               "batch": b, "heads": h, "kv_heads": kvh, "dim": d,
               "iters": iters}
        full_bytes = b * t * 2 * kvh * d * 2      # k+v, bf16, full window
        act_bytes = b * alen * 2 * kvh * d * 2    # what blocked must read
        for name, fn, nbytes in (("dense_ms", dense, full_bytes),
                                 ("blocked_ms", blocked, act_bytes)):
            ms = _amortized_ms(lambda: fn(q), iters)
            out[name] = round(ms, 3)
            out[name.replace("_ms", "_kv_gb_s")] = round(
                nbytes / (ms / 1e3) / 1e9, 1)
        out["winner"] = ("blocked" if out["blocked_ms"] < out["dense_ms"]
                         else "dense")
        print(json.dumps(out))


def bench_paged_decode_attention():
    """The paged-indirection cost question: the block-table decode
    kernel (scalar-prefetch table lookup per KV page) vs the contiguous
    clamped-index blocked kernel at the same 8B decode shapes and
    active lengths. Tables here are the identity layout (page j of row
    r at arena slot r*nb + j) so both kernels read the same bytes —
    any delta is pure indirection overhead, the number that decides
    whether paged mode costs decode latency on chip."""
    import jax
    import jax.numpy as jnp

    from lambdipy_tpu.ops.decode_attention import (
        blocked_decode_attention, paged_blocked_decode_attention)

    b, h, kvh, d, t, page = 8, 32, 8, 128, 8192, 128
    nb = t // page
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, 1, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, t, kvh, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, t, kvh, d), jnp.bfloat16)
    # the same KV re-laid out page-major, plus the identity block table
    k_pages = k.reshape(b * nb, page, kvh, d)
    v_pages = v.reshape(b * nb, page, kvh, d)
    tables = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    iters = 50
    for alen in (512, 2048, 8192):
        lens = jnp.full((b,), alen, jnp.int32)
        contiguous = _scan_many(
            lambda c: blocked_decode_attention(c, k, v, lens,
                                               block_k=page,
                                               interpret=False), iters)
        paged = _scan_many(
            lambda c: paged_blocked_decode_attention(
                c, k_pages, v_pages, tables, lens, interpret=False),
            iters)
        out = {"op": "paged_decode_attention", "active_len": alen,
               "window": t, "page": page, "batch": b, "iters": iters}
        for name, fn in (("contiguous_ms", contiguous),
                         ("paged_ms", paged)):
            out[name] = round(_amortized_ms(lambda: fn(q), iters), 3)
        out["indirection_overhead"] = round(
            out["paged_ms"] / max(out["contiguous_ms"], 1e-4) - 1.0, 4)
        print(json.dumps(out))


def bench_spec_verify():
    """The speculative-decoding amortization question, measured at the
    op level: ONE k-token verify chunk vs k sequential one-token decode
    steps, at 8B decode shapes. Decode is weight-bytes-bound, so the
    chunk should cost barely more than a single step (same weight
    read, k x the MXU work which is nowhere near the roofline at small
    batch) — ``speedup`` is the per-token gain an accept-all verify
    step realizes over plain decode, the on-chip ceiling for the
    engine's ``spec_k`` mode (bench.py --spec measures the CPU-scale
    end-to-end twin). Two ops cover the two traffic classes:

    - weight matmul (the dominant decode cost): bf16 [m, k] @ [k, n]
      at the 8B qo/mlp shapes, m = 1 (one step) vs m = k_spec (one
      chunk); ``seq_ms`` runs k_spec m=1 matmuls serialized in one
      program, ``chunk_ms`` the single wide one.
    - decode attention: k_spec sequential 1-token reads of an 8192-
      position KV window vs one k_spec-query chunk over the same
      window (the chunk re-reads the window once instead of k times).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lambdipy_tpu.ops.decode_attention import \
        decode_attention_reference

    rng = np.random.default_rng(0)
    for k_spec in (4, 8, 16):
        out = {"op": "spec_verify", "k": k_spec}
        # weight-read amortization at the big mlp shape
        kk, n = 4096, 14336
        w = jnp.asarray(rng.standard_normal((kk, n), np.float32),
                        jnp.bfloat16)
        x1 = jnp.asarray(rng.standard_normal((1, kk), np.float32),
                         jnp.bfloat16)
        xk = jnp.asarray(rng.standard_normal((k_spec, kk), np.float32),
                         jnp.bfloat16)
        iters = 50

        def seq_op(c):
            def step(x, _):
                y = x @ w
                bump = (jnp.abs(y).astype(jnp.float32).sum() * 1e-20
                        ).astype(x.dtype)
                return x + bump, ()

            x, _ = jax.lax.scan(step, c, None, length=k_spec)
            return x

        seq = _scan_many(seq_op, iters)
        chunk = _scan_many(lambda c: c @ w, iters)
        out["matmul_seq_ms"] = round(_amortized_ms(
            lambda: seq(x1), iters), 4)
        out["matmul_chunk_ms"] = round(_amortized_ms(
            lambda: chunk(xk), iters), 4)
        out["matmul_speedup"] = round(
            out["matmul_seq_ms"] / max(out["matmul_chunk_ms"], 1e-4), 2)

        # KV-window amortization: chunk attends once, steps k times
        b, h, kvh, d, t = 1, 32, 8, 128, 8192
        key = jax.random.PRNGKey(0)
        kq, kkey, kv = jax.random.split(key, 3)
        kc = jax.random.normal(kkey, (b, t, kvh, d), jnp.bfloat16)
        vc = jax.random.normal(kv, (b, t, kvh, d), jnp.bfloat16)
        lens = jnp.full((b,), t, jnp.int32)
        q1 = jax.random.normal(kq, (b, 1, h, d), jnp.bfloat16)
        qk_ = jax.random.normal(kq, (b, k_spec, h, d), jnp.bfloat16)

        def attn_seq(c):
            def step(x, _):
                y = decode_attention_reference(x, kc, vc, lens)
                bump = (jnp.abs(y).astype(jnp.float32).sum() * 1e-20
                        ).astype(x.dtype)
                return x + bump, ()

            x, _ = jax.lax.scan(step, c, None, length=k_spec)
            return x

        def attn_chunk(c):
            # the verify chunk's attention: every query reads the same
            # window once (causal masking differences are noise at
            # t = 8192)
            return decode_attention_reference(
                c.reshape(b * k_spec, 1, h, d),
                jnp.broadcast_to(kc, (b * k_spec, t, kvh, d)),
                jnp.broadcast_to(vc, (b * k_spec, t, kvh, d)),
                jnp.full((b * k_spec,), t, jnp.int32))

        a_iters = 20
        aseq = _scan_many(attn_seq, a_iters)
        achunk = _scan_many(attn_chunk, a_iters)
        out["attn_seq_ms"] = round(_amortized_ms(
            lambda: aseq(q1), a_iters), 4)
        out["attn_chunk_ms"] = round(_amortized_ms(
            lambda: achunk(qk_), a_iters), 4)
        out["attn_speedup"] = round(
            out["attn_seq_ms"] / max(out["attn_chunk_ms"], 1e-4), 2)
        print(json.dumps(out))


def bench_int8_matmul():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lambdipy_tpu.ops.quant import int8_matmul

    rng = np.random.default_rng(0)
    for m, k, n in ((1, 4096, 4096), (8, 4096, 4096),
                    (1, 4096, 14336), (8, 4096, 14336),
                    (1, 14336, 4096), (512, 4096, 4096)):
        x = jnp.asarray(rng.standard_normal((m, k), np.float32),
                        jnp.bfloat16)
        w = jnp.asarray(rng.integers(-127, 128, (k, n), np.int8))
        scale = jnp.asarray(
            np.full((1, n), 1.0 / (127 * k ** 0.5), np.float32))
        iters = 100 if m <= 8 else 20

        xla = _scan_many(
            lambda c: c @ (w.astype(jnp.bfloat16)
                           * scale.astype(jnp.bfloat16)), iters)
        pallas = _scan_many(
            lambda c: int8_matmul(c, w, scale, interpret=False), iters)
        out = {"op": "int8_matmul", "m": m, "k": k, "n": n,
               "weight_mb": round(k * n / 1e6, 1), "iters": iters}
        for name, fn in (("xla_ms", xla), ("pallas_ms", pallas)):
            ms = _amortized_ms(lambda: fn(x), iters)
            out[name] = round(ms, 4)
            # the serving-relevant figure: effective weight-read bandwidth
            out[name.replace("_ms", "_gb_s")] = round(
                k * n / (ms / 1e3) / 1e9, 1)
        out["winner"] = ("pallas" if out["pallas_ms"] < out["xla_ms"]
                         else "xla")
        print(json.dumps(out))


def main() -> int:
    import jax

    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "device_kind": devices[0].device_kind, "n_devices": len(devices)}
    if devices[0].platform != "tpu":
        print(json.dumps({"error": "needs the TPU: the kernels compile only "
                          "for it", **stamp}))
        return 1
    print(json.dumps(stamp))
    bench_attention()
    bench_decode_attention()
    bench_paged_decode_attention()
    bench_spec_verify()
    bench_int8_matmul()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
