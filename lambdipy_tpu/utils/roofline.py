"""Roofline / MFU accounting for every published number.

The reference (SURVEY.md §7) publishes no perf numbers, so the rebuild's
bar is hardware utilization: any measured latency/throughput we publish
must be relatable to what the chip could do at peak. This module computes
analytic FLOP and HBM-byte costs for the served models and turns a
measured wall-clock into

- ``mfu``       — model FLOPs / (time x peak FLOP/s), and
- ``hbm_util``  — model HBM bytes moved / (time x peak HBM GB/s),

against the peaks of the device the number was measured on, looked up by
``device_kind`` in :data:`PEAKS` — a device that is not in the table is an
error, never a default. Decode of a large LM is
weight-bytes-bound (every step re-reads all weights plus the KV cache),
so for serving the honest headline is ``hbm_util``; MFU is the training /
prefill headline. ``bench.py`` and ``scripts/measure_baseline.py`` attach
these fields to each record they publish (VERDICT r3 missing #2).

Cost models are analytic lower bounds: matmul FLOPs only (elementwise /
norm traffic is noise next to weights at these shapes), bytes = weights
read once per step + per-sequence KV read. Real programs move more, so
utilizations reported here are slightly optimistic about the program and
therefore conservative about the gap to peak.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published peaks of one chip, with where they were published."""

    bf16_flops: float   # FLOP/s
    int8_ops: float     # OP/s
    hbm_bytes_s: float  # bytes/s
    hbm_bytes: float    # bytes
    source: str


# keyed by ``jax.devices()[0].device_kind``
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, '
               "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip)"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks table entry for a device kind; raises ``KeyError`` for a
    kind the table does not hold — a utilization against an assumed peak
    is not a measurement."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add it to utils/roofline.py PEAKS with its "
            "source before publishing a utilization.") from None


def peaks_of(device) -> Peaks | None:
    """Peaks of a jax device: its table entry on a TPU (raising for a kind
    the table lacks), None on any other platform — a CPU rehearsal of a
    measurement publishes no utilization."""
    if device.platform != "tpu":
        return None
    return peaks_for(device.device_kind)


@dataclasses.dataclass(frozen=True)
class Cost:
    """Analytic cost of one invocation: FLOPs and HBM bytes moved."""

    flops: float
    hbm_bytes: float

    def time_lower_bound_ms(self, peaks: Peaks) -> float:
        """Roofline time bound: max of compute-bound and memory-bound."""
        return max(self.flops / peaks.bf16_flops,
                   self.hbm_bytes / peaks.hbm_bytes_s) * 1e3

    def mfu(self, measured_s: float, peaks: Peaks) -> float:
        return (self.flops / (measured_s * peaks.bf16_flops)
                if measured_s > 0 else 0.0)

    def hbm_util(self, measured_s: float, peaks: Peaks) -> float:
        return (self.hbm_bytes / (measured_s * peaks.hbm_bytes_s)
                if measured_s > 0 else 0.0)

    def utilization(self, measured_s: float, peaks: Peaks) -> dict:
        """The fields published next to a measured number."""
        return {
            "mfu": round(self.mfu(measured_s, peaks), 4),
            "hbm_util": round(self.hbm_util(measured_s, peaks), 4),
            "roofline_ms": round(self.time_lower_bound_ms(peaks), 4),
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
        }


def param_bytes(params) -> int:
    """Total bytes of a params pytree as stored (int8 counts 1B/param)."""
    import jax

    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(params)
               if hasattr(x, "dtype"))


def llama_matmul_params(cfg) -> int:
    """Matmul-participating param count (embed excluded: decode's embed is
    a [b] gather, not a matmul; lm_head included — it is untied)."""
    h, kvd = cfg.hidden, cfg.kv_heads * cfg.head_dim
    per_layer = (h * h              # q proj
                 + 2 * h * kvd      # k, v proj
                 + h * h            # o proj
                 + 3 * h * cfg.mlp)  # gate, up, down
    return cfg.layers * per_layer + h * cfg.vocab_size


def llama_weight_bytes(cfg) -> int:
    """Bytes of weights read per forward step as stored on HBM."""
    wbytes = 1 if cfg.quant == "int8" else (2 if cfg.dtype.__name__ in
                                            ("bfloat16", "float16") else 4)
    return llama_matmul_params(cfg) * wbytes


def llama_kv_bytes_per_pos(cfg) -> int:
    """KV-cache bytes per cached position per sequence (all layers)."""
    per_pos = 2 * cfg.layers * cfg.kv_heads * cfg.head_dim  # k and v
    return per_pos * (1 if cfg.kv_quant == "int8" else 2)


def llama_decode_step_cost(cfg, *, batch: int, cache_len: int,
                           weight_bytes: int | None = None) -> Cost:
    """Cost of ONE decode step producing one token per batch row.

    FLOPs: 2 x matmul-params per row plus attention (4 x hidden x
    cache_len per row per layer, q.k and attn.v). Bytes: weights are read
    once per step regardless of batch (the batch>1 amortization that makes
    batched decode fast); each row additionally reads its own KV prefix.
    """
    h = cfg.hidden
    flops = batch * (2 * llama_matmul_params(cfg)
                     + cfg.layers * 4 * h * cache_len)
    wb = llama_weight_bytes(cfg) if weight_bytes is None else weight_bytes
    hbm = wb + batch * cache_len * llama_kv_bytes_per_pos(cfg)
    return Cost(float(flops), float(hbm))


def llama_decode_window_cost(cfg, *, batch: int, window_len: int,
                             active_len: int | None = None,
                             weight_bytes: int | None = None) -> Cost:
    """Cost of ONE decode step under length-aware blocked/bucketed
    attention: the program READS ``window_len`` KV positions per row
    (the pow-2 window bucket, or the blocked kernel's fetched blocks)
    while attention FLOPs cover ``active_len`` positions actually
    attended (defaults to the window). The decode-window savings story
    is this against :func:`llama_decode_step_cost` at the full static
    ``cache_len`` — short rows stop paying full-window KV reads."""
    # one formula: delegate to the dense step cost at the READ window,
    # then deduct the attention FLOPs of the positions never attended
    base = llama_decode_step_cost(cfg, batch=batch, cache_len=window_len,
                                  weight_bytes=weight_bytes)
    active = window_len if active_len is None else active_len
    flops = base.flops - batch * cfg.layers * 4 * cfg.hidden * (
        window_len - active)
    return Cost(float(flops), base.hbm_bytes)


def llama_decode_tok_s_bound(cfg, *, batch: int, cache_len: int,
                             peaks: Peaks) -> float:
    """Roofline upper bound on decode tokens/second at this batch."""
    c = llama_decode_step_cost(cfg, batch=batch, cache_len=cache_len)
    return batch / (c.time_lower_bound_ms(peaks) / 1e3)


def llama_prefill_cost(cfg, *, batch: int, seq_len: int) -> Cost:
    """Cost of prefilling seq_len tokens per row (lm_head at 1 position,
    matching LlamaModel's logit_positions serving prefill)."""
    h = cfg.hidden
    per_layer_matmul = (h * h + 2 * h * cfg.kv_heads * cfg.head_dim
                        + h * h + 3 * h * cfg.mlp)
    # attention: q.k^T and attn.v are 2 x (2 x h x s^2) bidirectional;
    # the causal mask halves the useful work
    attn = cfg.layers * 2 * h * seq_len * seq_len
    flops = batch * (2 * seq_len * cfg.layers * per_layer_matmul
                     + attn + 2 * h * cfg.vocab_size)
    hbm = (llama_weight_bytes(cfg)
           + batch * seq_len * llama_kv_bytes_per_pos(cfg))  # cache write
    return Cost(float(flops), float(hbm))


def llama_prefix_continue_cost(cfg, *, suffix_len: int,
                               prefix_len: int) -> Cost:
    """Cost of a suffix-only continuation prefill from a cached prefix
    KV: ``suffix_len`` new tokens run the matmul stack once and attend
    ``prefix_len`` cached positions plus their own causal window
    (lm_head at one position, matching ``_continue_prefill``). The
    shared-prefix serving win is this against
    :func:`llama_prefill_cost` of the full ``prefix_len + suffix_len``
    prompt. Bytes: weights once, the cached prefix KV read, the
    suffix's KV written."""
    h = cfg.hidden
    per_layer_matmul = (h * h + 2 * h * cfg.kv_heads * cfg.head_dim
                        + h * h + 3 * h * cfg.mlp)
    # q.k^T + attn.v over the cached prefix (full rectangle) plus the
    # suffix's own causal triangle (same halved convention as
    # llama_prefill_cost)
    attn = cfg.layers * (4 * h * suffix_len * prefix_len
                         + 2 * h * suffix_len * suffix_len)
    flops = (2 * suffix_len * cfg.layers * per_layer_matmul + attn
             + 2 * h * cfg.vocab_size)
    hbm = (llama_weight_bytes(cfg)
           + (prefix_len + suffix_len) * llama_kv_bytes_per_pos(cfg))
    return Cost(float(flops), float(hbm))


# ResNet-50 v1.5 forward at 224x224: ~4.09 GFLOPs/image (standard count,
# MAC=2 FLOPs), 25.6M params.
RESNET50_FLOPS_PER_IMAGE = 4.09e9
RESNET50_PARAMS = 25.6e6


def resnet50_cost(*, batch: int, dtype_bytes: int = 2) -> Cost:
    """ResNet-50 forward; bytes = weights once + input activations (the
    batch=1 serving case is weight-read-bound)."""
    act = batch * 224 * 224 * 3 * dtype_bytes
    return Cost(batch * RESNET50_FLOPS_PER_IMAGE,
                RESNET50_PARAMS * dtype_bytes + act)
