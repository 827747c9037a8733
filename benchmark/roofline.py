"""Peaks of the chips and the bytes and operations a llama-family step
needs, computed from shapes.

Copied from ``lambdipy_tpu/utils/roofline.py`` at commit fb0103a (``PEAKS``,
``peaks_for``, ``llama_matmul_params``, ``llama_weight_bytes``,
``llama_kv_bytes_per_pos``, ``llama_decode_step_cost``,
``llama_prefill_cost``), reduced to what the per-layer metrics read and
re-keyed on the configuration file's published names. The original stays
for the program's own records; a later PR may delete it there (PERF.md,
Open questions), never change this copy.
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
PEAKS: dict = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, '
               "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip)"),
}


def peaks_for(device_kind: str) -> Peaks:
    """Raises for a kind the table does not hold: a utilization against an
    assumed peak is not a measurement."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class Shape:
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    mlp: int
    vocab: int
    weight_bytes_per_param: int
    kv_bytes_per_value: int


def shape_of(config: dict) -> Shape:
    return Shape(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        mlp=config["intermediate_size"], vocab=config["vocab_size"],
        weight_bytes_per_param=1 if config["precision"]["weights"] == "int8"
        else 2,
        kv_bytes_per_value=2)


def matmul_params(s: Shape) -> int:
    """Parameters that take part in a matmul (the embedding is a gather;
    the untied lm_head counts)."""
    kvd = s.kv_heads * s.head_dim
    per_layer = 2 * s.hidden * s.hidden + 2 * s.hidden * kvd \
        + 3 * s.hidden * s.mlp
    return s.layers * per_layer + s.hidden * s.vocab


def weight_bytes(s: Shape) -> int:
    return matmul_params(s) * s.weight_bytes_per_param


def kv_bytes_per_pos(s: Shape) -> int:
    """K and V of one cached position of one sequence, all layers."""
    return 2 * s.layers * s.kv_heads * s.head_dim * s.kv_bytes_per_value


def decode_step_bytes(s: Shape, *, rows: float, context: float) -> float:
    """HBM bytes ONE decode step needs: the weights once, whatever the
    batch, plus each live row's own cached context."""
    return weight_bytes(s) + rows * context * kv_bytes_per_pos(s)


def decode_step_flops(s: Shape, *, rows: float, context: float) -> float:
    return rows * (2 * matmul_params(s)
                   + s.layers * 4 * s.hidden * context)


def prefill_flops(s: Shape, *, rows: int, seq_len: int) -> float:
    """Prefill of ``seq_len`` tokens a row, lm_head at one position."""
    in_layers = matmul_params(s) - s.hidden * s.vocab
    attn = s.layers * 2 * s.hidden * seq_len * seq_len
    return rows * (2 * seq_len * in_layers + attn + 2 * s.hidden * s.vocab)
