"""Published peaks of the chips. The bytes and operations a step needs are
its family's to count (``benchmark/families``).

Copied from ``lambdipy_tpu/utils/roofline.py`` at commit fb0103a (``PEAKS``,
``peaks_for``). The original stays for the program's own records; a later
PR may delete it there (PERF.md, Open questions), never change this copy.
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
