"""Structural regression tests for the 8B measurement modes
(``scripts/measure_8b.py``) at tiny dims on CPU.

Why these exist: the modes only produce value on the chip, and chip
time is scarce — round 5 lost its first on-chip speculative run (~17
min of chip time) to a NameError sitting AFTER the measurements in a
code path no test had ever imported. Each mode here runs end-to-end at
toy dims and asserts its record's required keys, so a broken postamble
is caught on CPU before it can burn a measurement window.

Slow tier: each mode compiles several toy programs on one core.
"""

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "scripts"))

TINY = dict(vocab_size=256, hidden=64, layers=2, heads=4, kv_heads=2,
            mlp=128, max_len=512)


@pytest.fixture()
def tiny_dims(tmp_path, monkeypatch):
    """Point the module at toy dims and an isolated params cache."""
    import measure_8b as m

    monkeypatch.setenv("LAMBDIPY_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(m, "DIMS", dict(m.DIMS, **TINY))
    return m


@pytest.mark.slow
def test_measure_decode_and_prefill_record(tiny_dims):
    r = tiny_dims.measure(batches=(1, 2), n_new=8, prefill_len=64)
    for key in ("b1_decode_tok_s", "b2_decode_tok_s", "weight_upload_s",
                "prefill_512_net_ms", "platform", "device_kind"):
        assert key in r, (key, r)
    assert r["prefill_step_corrected"] is True
    # a CPU run publishes no utilization: there is no peaks entry for it
    assert r["platform"] == "cpu"
    assert not any(k.endswith(("_mfu", "_hbm_util", "_roofline_tok_s"))
                   for k in r), r


@pytest.mark.slow
def test_measure_speculative_record(tiny_dims):
    r = tiny_dims.measure_speculative(n_new=16, k=4)
    for key in ("plain_tok_s", "spec_tok_s", "speedup_vs_plain",
                "greedy_agreement", "platform"):
        assert key in r, (key, r)
    assert "roofline_plain_b1_tok_s" not in r  # CPU: no peaks entry
    assert "tokens_per_step" in r["spec_stats"]


@pytest.mark.slow
def test_measure_concurrent_record(tiny_dims):
    r = tiny_dims.measure_concurrent(n_requests=3, n_new=8)
    for key in ("serial_wall_s", "concurrent_wall_s", "speedup_vs_serial",
                "concurrent_tok_s", "rows_bitwise_equal",
                "solo_agreement_min", "solo_agreement_mean", "engine"):
        assert key in r, (key, r)
    # the adapter runs bfloat16 even on CPU, so a staggered join that
    # lands in a different-width group-prefill CAN legally flip a
    # near-tied argmax here too — hold the mode's own agreement floor
    # rather than demanding bitwise equality of every row
    assert r["solo_agreement_mean"] >= 0.9, r


@pytest.mark.slow
def test_measure_kv_quant_record(tiny_dims):
    r = tiny_dims.measure_kv_quant(n_new=32, context=128)
    for key in ("bf16_kv_b1_tok_s", "int8_kv_b1_tok_s",
                "bf16_kv_b1_pair_spread_ms",
                "greedy_agreement", "agreeing_prefix"):
        assert key in r, (key, r)


@pytest.mark.slow
def test_measure_prefill_table_record(tiny_dims):
    r = tiny_dims.measure_prefill(lens=(32, 64, 96, 128), flash_len=256,
                                  batch_len=32, batch=2)
    backends = {row["backend"] for row in r["rows"]}
    assert {"dense", "flash", "chunked512"} <= backends, backends
    assert "decode_step_ms" in r
    assert "scaling_fit" in r
    dense = [row for row in r["rows"] if row["backend"] == "dense"]
    assert all("raw_ms" in row for row in dense)
