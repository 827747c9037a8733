"""Roofline/MFU accounting (utils/roofline.py): the cost models every
published bench/baseline number is related to v5e peak through."""

import dataclasses

import jax.numpy as jnp
import pytest

from lambdipy_tpu.models.llama import LLAMA3_8B, LLAMA_TINY
from lambdipy_tpu.utils import roofline as R

V5E = R.peaks_for("TPU v5 lite")


def test_llama_8b_matmul_param_count():
    # Llama-3-8B has ~8.0B params incl. the 0.5B embedding; matmul
    # (embed-excluded) is ~7.5B
    n = R.llama_matmul_params(LLAMA3_8B)
    assert 7.4e9 < n < 7.6e9


def test_matmul_params_match_real_module():
    """The analytic count must equal the actual QDense kernel sizes of an
    initialized model (embed + norm scales are the only non-matmul
    params)."""
    from lambdipy_tpu.models import registry

    adapter = registry.get("llama-tiny").build()
    params = adapter.init_params(seed=0)
    import jax

    total = sum(x.size for x in jax.tree.leaves(params))
    cfg = LLAMA_TINY
    embed = cfg.vocab_size * cfg.hidden
    norms = cfg.layers * 2 * cfg.hidden + cfg.hidden
    assert total == R.llama_matmul_params(cfg) + embed + norms


def test_int8_weight_bytes_half_of_bf16():
    bf16 = R.llama_weight_bytes(LLAMA3_8B)
    int8 = R.llama_weight_bytes(dataclasses.replace(LLAMA3_8B, quant="int8"))
    # int8 stores 1 byte/param vs 2 (scales are per-channel noise)
    assert int8 * 2 == bf16


def test_8b_decode_is_weight_bytes_bound():
    """b1 decode of 8B int8 is HBM-bound: the roofline time equals the
    weight-read time, ~9 ms -> ~108 tok/s upper bound (the number the
    VERDICT's honest-accounting critique predicts)."""
    cfg = dataclasses.replace(LLAMA3_8B, quant="int8")
    c = R.llama_decode_step_cost(cfg, batch=1, cache_len=512)
    t_weights_ms = R.llama_weight_bytes(cfg) / V5E.hbm_bytes_s * 1e3
    assert c.time_lower_bound_ms(V5E) == pytest.approx(t_weights_ms, rel=0.05)
    bound = R.llama_decode_tok_s_bound(cfg, batch=1, cache_len=512,
                                       peaks=V5E)
    assert 95 < bound < 115


def test_batching_amortizes_weight_reads():
    cfg = dataclasses.replace(LLAMA3_8B, quant="int8")
    b1 = R.llama_decode_tok_s_bound(cfg, batch=1, cache_len=512, peaks=V5E)
    b8 = R.llama_decode_tok_s_bound(cfg, batch=8, cache_len=512, peaks=V5E)
    assert b8 > 6 * b1  # near-linear until KV reads start to matter


def test_kv_quant_halves_cache_traffic():
    cfg = LLAMA3_8B
    q = dataclasses.replace(cfg, kv_quant="int8")
    assert R.llama_kv_bytes_per_pos(q) * 2 == R.llama_kv_bytes_per_pos(cfg)


def test_decode_window_cost_scales_with_active_length():
    """The length-aware decode cost model: a short active window reads
    (and attends) less than the full static window, converging to the
    dense step cost when window == cache_len."""
    cfg = dataclasses.replace(LLAMA3_8B, quant="int8")
    full = R.llama_decode_step_cost(cfg, batch=1, cache_len=8192)
    short = R.llama_decode_window_cost(cfg, batch=1, window_len=512,
                                       active_len=300)
    assert short.hbm_bytes < full.hbm_bytes
    assert short.flops < full.flops
    # KV bytes scale with the window actually read
    kv_full = full.hbm_bytes - R.llama_weight_bytes(cfg)
    kv_short = short.hbm_bytes - R.llama_weight_bytes(cfg)
    assert kv_short == pytest.approx(kv_full * 512 / 8192)
    # window == cache_len degenerates to the dense step cost exactly
    same = R.llama_decode_window_cost(cfg, batch=1, window_len=8192)
    assert (same.flops, same.hbm_bytes) == (full.flops, full.hbm_bytes)


def test_prefill_is_compute_bound_at_1k():
    cfg = dataclasses.replace(LLAMA3_8B, quant="int8")
    c = R.llama_prefill_cost(cfg, batch=1, seq_len=1024)
    assert c.flops / V5E.bf16_flops > c.hbm_bytes / V5E.hbm_bytes_s


def test_param_bytes_counts_storage():
    params = {"a": jnp.zeros((4, 4), jnp.int8),
              "b": jnp.zeros((2, 2), jnp.float32)}
    assert R.param_bytes(params) == 16 + 16


def test_utilization_fields():
    c = R.Cost(flops=1e12, hbm_bytes=1e9)
    u = c.utilization(0.01, V5E)
    # 1e12 FLOP in 10 ms on a 197 TFLOP/s part
    assert u["mfu"] == pytest.approx(1e12 / (0.01 * V5E.bf16_flops),
                                     abs=1e-4)
    assert 0 < u["hbm_util"] < 1
    assert u["roofline_ms"] == pytest.approx(
        max(1e12 / V5E.bf16_flops, 1e9 / V5E.hbm_bytes_s) * 1e3,
        rel=1e-3)


def test_unknown_device_kind_is_an_error():
    """Peaks come from the table keyed by device_kind, each with its
    source; a kind the table does not hold raises — no utilization is ever
    computed against an assumed peak (this suite's own CPU included)."""
    import jax

    assert V5E.source and V5E.bf16_flops == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        R.peaks_for(jax.devices()[0].device_kind)
