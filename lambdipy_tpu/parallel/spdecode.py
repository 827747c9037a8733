"""Sequence-parallel DECODE: one-token attention over a KV cache whose
sequence dimension is sharded across the mesh's ``sp`` axis.

Ring attention (parallel/ring.py) makes long-context PREFILL scale over
sp; this module completes the long-context serving story for the decode
phase. Decode reads the entire cache every step — at 8B and 128k
context that is ~16 GB of KV per batch row, past a single chip's HBM —
so the cache must live sharded, and each step must combine per-shard
attention partials instead of gathering keys.

The TPU-native formulation (flash-decoding expressed as SPMD, not a
hand-rolled transport):

- the cache stays ``[b, T/sp, kvh, d]`` per device for the whole scan
  (it is the dominant HBM object; it must NEVER be gathered);
- this step's k/v (one token, replicated) is written by the OWNING
  shard only — a masked local ``at[].set`` replaces a cross-shard
  dynamic-update-slice the partitioner would otherwise have to gather
  for;
- each shard computes an online-softmax partial (local max, exp-sum,
  weighted accumulator) over its cache block, then one
  ``pmax`` + two ``psum`` collectives (tiny: [b, h] and [b, h, d])
  recover exact attention. Communication per step is O(b * h * d),
  independent of context length — the whole point.

GQA grouping matches models/llama.py `_attend` (kv heads can be
tp-sharded at the same time: the head dimension stays local to the
shard_map body, so sp x tp compose). int8 KV (kv_quant) is dequantized
by the caller per shard-local block before entry.
"""

from __future__ import annotations

import threading
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from lambdipy_tpu.utils.logs import get_logger

log = get_logger("lambdipy.spdecode")

NEG_INF = -1e30

# -- stand-down observability (ROADMAP direction-2 note) ---------------------
#
# sp decode only engages for one-token steps under attn_backend="ring".
# Configurations that LOOK like the long-context shape (an ambient mesh
# with sp > 1) but route a decode step elsewhere — blocked/dense
# attention backends, or a multi-token speculative verify chunk — used
# to stand down SILENTLY: the operator saw a working server whose
# decode quietly replicated the KV cache it paid an sp mesh to shard.
# Every stand-down now bumps the ``spec_standdown`` counter (mirrored
# into SpecDecodeStats.report / ``/metrics``) and the FIRST occurrence
# per distinct reason emits one structured log line. Counts accumulate
# at trace time (one per compiled layer, not per step) — the point is
# "this condition exists and here is why", not a step-rate gauge.

_standdown_lock = threading.Lock()
_standdown: dict[str, int] = {}
_standdown_logged: set = set()


def note_standdown(reason: str) -> None:
    """Record one sp-decode stand-down (mesh had an sp axis, the decode
    step did not take the sequence-parallel path)."""
    with _standdown_lock:
        _standdown[reason] = _standdown.get(reason, 0) + 1
        first = reason not in _standdown_logged
        _standdown_logged.add(reason)
        total = sum(_standdown.values())
    if first:
        log.warning(
            "sp_decode_standdown reason=%s spec_standdown=%d "
            "(sequence-parallel decode stood down; the KV cache decodes "
            "replicated despite the mesh's sp axis)", reason, total)


def standdown_count() -> int:
    """Total sp-decode stand-downs recorded this process."""
    with _standdown_lock:
        return sum(_standdown.values())


def standdown_stats() -> dict:
    """``spec_standdown`` counter + per-reason breakdown."""
    with _standdown_lock:
        return {"spec_standdown": sum(_standdown.values()),
                "reasons": dict(_standdown)}


def _reset_standdowns_for_tests() -> None:
    with _standdown_lock:
        _standdown.clear()
        _standdown_logged.clear()


def _owner_write(leaf, new_row, my, t_loc, index):
    """Write ``new_row`` [b, kvh, ...] at each row's position on the
    owning shard only. The non-owner "write" re-stores the OLD value at
    the clipped slot — selected in the small per-row gather, never on
    the cache — so the multi-GB cache block stays single-consumer and
    XLA can alias the scatter in place (a where() over the block would
    force a full copy per layer per step)."""
    b = leaf.shape[0]
    rows = jnp.arange(b)
    local_idx = index - my * t_loc  # [b]
    owner = (local_idx >= 0) & (local_idx < t_loc)
    clipped = jnp.clip(local_idx, 0, t_loc - 1)
    sel = owner.reshape((b,) + (1,) * (new_row.ndim - 1))
    val = jnp.where(sel, new_row, leaf[rows, clipped])
    return leaf.at[rows, clipped].set(val)


def _sp_decode_local(q, store_new, cache, index, *, axis_name: str,
                     scale: float, quant: bool):
    """Per-shard body. q: [b, 1, h, d] replicated over ``axis_name``;
    ``store_new``: this step's projections ([b, 1, kvh, ...] leaves —
    k/v, or int8 values + scales under ``quant``) replicated;
    ``cache``: the matching [b, T_local, kvh, ...] local cache blocks;
    index: [b] replicated write/validity position. Returns
    (out [b, 1, h, d] replicated, updated cache dict)."""
    my = jax.lax.axis_index(axis_name)
    first = next(iter(cache.values()))
    b, t_loc = first.shape[0], first.shape[1]
    kvh = first.shape[2]
    h, d = q.shape[2], q.shape[3]
    group = h // kvh

    cache = {name: _owner_write(cache[name], store_new[name][:, 0], my,
                                t_loc, index)
             for name in cache}
    if quant:
        ck = (cache["k_int8"].astype(q.dtype)
              * cache["k_scale"].astype(q.dtype))
        cv = (cache["v_int8"].astype(q.dtype)
              * cache["v_scale"].astype(q.dtype))
    else:
        ck, cv = cache["k"], cache["v"]

    # local online-softmax partial over this shard's block
    qg = q.reshape(b, 1, kvh, group, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, ck).astype(jnp.float32)
    logits = logits * jnp.float32(scale)  # [b, kvh, g, 1, t_loc]
    global_pos = my * t_loc + jnp.arange(t_loc)
    valid = global_pos[None, :] <= index[:, None]  # [b, t_loc]
    logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)  # [b, kvh, g, 1]
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)  # [b, kvh, g, 1]
    acc = jnp.einsum("bkgst,btkd->bskgd", p.astype(cv.dtype),
                     cv).astype(jnp.float32)  # [b, 1, kvh, g, d]

    # exact global combine: O(b*h*d) collectives, context-length-free.
    # pmax over the RAW max (-inf sentinel on empty shards): pmax'ing
    # m_safe would clamp the global max to >= 0 whenever ANY shard has
    # no valid positions yet, underflowing rows whose true max logit is
    # strongly negative. Empty shards then take a = 0 explicitly — their
    # (zero) partials must not turn an exp overflow into NaN * 0.
    m_g = jax.lax.pmax(m, axis_name)
    m_g_safe = jnp.where(m_g <= NEG_INF / 2, 0.0, m_g)
    a = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m_safe - m_g_safe))
    l_g = jax.lax.psum(l * a, axis_name)
    # broadcast [b, kvh, g, 1] coefficients onto [b, 1, kvh, g, d]
    a_acc = jnp.transpose(a, (0, 3, 1, 2))[..., None]
    acc_g = jax.lax.psum(acc * a_acc, axis_name)
    l_g = jnp.maximum(l_g, 1e-30)
    out = acc_g / jnp.transpose(l_g, (0, 3, 1, 2))[..., None]
    return out.reshape(b, 1, h, d).astype(q.dtype), cache


def sp_decode_step(q, store_new: dict, cache: dict, index, mesh: Mesh,
                   *, axis: str = "sp", scale: float | None = None):
    """One decode step over a sequence-sharded cache.

    q: [b, 1, h, d]; ``store_new``: this step's projections as a dict
    of [b, 1, kvh, ...] leaves — ``{"k", "v"}`` for a float cache, or
    ``{"k_int8", "k_scale", "v_int8", "v_scale"}`` for an int8-KV
    cache (quantized by the caller per vector; the per-shard dequant
    fuses into the local attention einsum, so int8 halves the SHARDED
    cache's HBM and read traffic exactly like the replicated path);
    ``cache``: the matching [b, T, kvh, ...] leaves with T sharded over
    ``axis``; index: [b] int32 — row r's write position (its keys
    <= index are valid). Returns (attn_out [b, 1, h, d], new cache
    dict) with the cache still sequence-sharded. The kv-head dim
    additionally shards over ``tp`` when the mesh has it; batch over
    ``dp``."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    names = mesh.axis_names
    bax = tuple(a for a in ("dp", "fsdp") if a in names)
    batch = bax if bax else None
    heads = "tp" if "tp" in names else None
    rep = P(batch, None, heads, None)           # q and store_new leaves
    cspec = P(batch, axis, heads, None)         # sharded cache leaves
    ispec = P(batch)                            # per-row index
    quant = "k_int8" in cache
    local = partial(_sp_decode_local, axis_name=axis, scale=scale,
                    quant=quant)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep, {name: rep for name in store_new},
                  {name: cspec for name in cache}, ispec),
        out_specs=(rep, {name: cspec for name in cache}))
    return fn(q, store_new, cache, index)
