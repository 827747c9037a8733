"""Flatpack: a single-file raw-tensor params format for fast cold starts.

Orbax stays the canonical, interoperable checkpoint (SURVEY.md §6); this
is the boot-path accelerator next to it. Measured on this image (ResNet-50
bundle, 91 MB orbax ocdbt): ``StandardCheckpointer.restore`` costs ~3.6 s
of tensorstore machinery on the 1-core host, while reading the same
tensors from one flat file is ~0.1 s — a third of the <10 s cold-start
budget (BASELINE.json) recovered for free. The builder writes both
formats; :func:`lambdipy_tpu.models.registry.load_params` prefers this one
and falls back to orbax, so bundles stay restorable without it.

Layout (all little-endian):

    b"LFPK1\n" | uint64 header_len | header JSON (utf-8) | pad to 64
    | tensor 0 bytes | pad to 64 | tensor 1 bytes | ...

Header: ``{"entries": [{"path": [..keys..], "dtype": "bfloat16",
"shape": [..], "offset": N, "nbytes": M}, ...]}`` — offsets are absolute.
Dtypes cover everything jax emits (bf16/fp8 via ml_dtypes names); the
tree is the nested-dict pytree flax uses. Loading memory-maps the file
and returns zero-copy numpy views, so params bytes are paged in lazily by
the consumer (typically ``jax.device_put``).
"""

from __future__ import annotations

import json
import mmap
import struct
from pathlib import Path

import numpy as np

MAGIC = b"LFPK1\n"
_ALIGN = 64


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # registers bfloat16/f8 etc; a jax dependency

        return np.dtype(getattr(ml_dtypes, name))


def _flatten(tree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flatten(tree[k], prefix + (str(k),))
    else:
        yield prefix, tree


def _unflatten(entries):
    root: dict = {}
    for path, value in entries:
        node = root
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = value
    return root


def save(path: Path, tree) -> dict:
    """Write a nested-dict tree of arrays; returns summary stats."""
    path = Path(path)
    leaves = [(list(p), np.asarray(v)) for p, v in _flatten(tree)]
    entries = []
    offset = None  # filled after the header size is known

    def aligned(n: int) -> int:
        return (n + _ALIGN - 1) // _ALIGN * _ALIGN

    # two passes: sizes first (offsets depend on header length, which
    # depends on the offsets' digits — stabilize by computing with final
    # padded header length)
    for p, a in leaves:
        entries.append({"path": p, "dtype": a.dtype.name,
                        "shape": list(a.shape), "nbytes": int(a.nbytes)})
    for attempt in range(6):
        header = json.dumps({"entries": entries},
                            separators=(",", ":")).encode()
        base = aligned(len(MAGIC) + 8 + len(header))
        offset = base
        changed = False
        for e in entries:
            if e.get("offset") != offset:
                e["offset"] = offset
                changed = True
            offset += aligned(e["nbytes"])
        if not changed:
            break
    else:
        # offset digits only grow, so the fixed point comes in a few
        # passes: two as a rule, a third and fourth where a grown header
        # crosses an alignment boundary (the bailing-hybrid twin's tree, PR
        # 41). Exiting with stale offsets would be silent weight corruption
        # at load time — refuse instead
        raise RuntimeError("flatpack header offsets failed to converge")

    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<Q", len(header)))
        f.write(header)
        f.write(b"\0" * (base - len(MAGIC) - 8 - len(header)))
        for e, (_, a) in zip(entries, leaves):
            assert f.tell() == e["offset"], (f.tell(), e)
            f.write(np.ascontiguousarray(a).tobytes())
            f.write(b"\0" * (aligned(a.nbytes) - a.nbytes))
    tmp.replace(path)
    return {"n_tensors": len(entries), "bytes": offset}


def save_checkpoint_files(params_dir: Path, params,
                          params_format: str = "both") -> str:
    """Shared bundle-params writer (registry.save_init_params and
    convert.save_hf_params): write the canonical orbax checkpoint and/or
    the flat boot file per ``params_format`` and return the format string
    recorded in the manifest. Rejects unknown formats up front — silently
    writing nothing would surface only at serve boot."""
    if params_format not in ("both", "fpk", "orbax"):
        raise ValueError(f"params_format must be 'both', 'fpk' or 'orbax', "
                         f"got {params_format!r}")
    params_dir = Path(params_dir)
    params_dir.mkdir(parents=True, exist_ok=True)
    fmt = []
    if params_format in ("both", "orbax"):
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        ckptr.save((params_dir / "orbax").resolve(), params)
        ckptr.wait_until_finished()
        fmt.append("orbax")
    if params_format in ("both", "fpk"):
        save(params_dir / "params.fpk", params)
        fmt.append("fpk")
    else:
        # rebuilding a params dir in place as orbax-only must not leave a
        # stale params.fpk behind: the loader prefers the flat file, so a
        # leftover one would silently serve the OLD weights
        (params_dir / "params.fpk").unlink(missing_ok=True)
    if params_format == "fpk" and (params_dir / "orbax").exists():
        # mirror image: an fpk-only rebuild must not ship (or fall back
        # to) a stale orbax checkpoint with the old weights
        import shutil

        shutil.rmtree(params_dir / "orbax")
    return "+".join(fmt)


def _read_header(path: Path):
    """(header dict, mmap over the whole file)."""
    with open(path, "rb") as f:
        head = f.read(len(MAGIC) + 8)
        if head[: len(MAGIC)] != MAGIC:
            raise ValueError(f"{path}: not a flatpack file")
        (header_len,) = struct.unpack("<Q", head[len(MAGIC):])
        header = json.loads(f.read(header_len))
        buf = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    return header, buf


# compiled unpack programs keyed by the group's relative layout — groups
# with identical structure (e.g. every transformer layer) share one
# compiled program
_unpack_cache: dict = {}


_STAGE_DTYPE = {1: np.uint8, 2: np.uint16, 4: np.uint32}


def device_load(path: Path, *, chunk_bytes: int = 512 << 20,
                small_leaf_bytes: int = 1 << 20):
    """Load a flatpack straight onto the (single) device with FEW LARGE
    transfers: leaves are packed into per-itemsize staging buffers that
    upload as one array each, then a jitted device-side unpack slices and
    SAME-WIDTH bitcasts every tensor out.

    Why: ``jax.device_put`` of a big pytree is one transfer per leaf; the
    8B int8 tree has ~420, most of them small. This turns hundreds of
    small host-to-device DMAs into dozens of large ones (8.6 GB in ~18 s
    of handler init on the attached v5e, chip_smoke.py, PR 21; the
    per-leaf alternative was not measured there).

    Two load-bearing shape rules:
    - staging buffers are 1-D arrays of the UNSIGNED dtype with the
      leaf's own itemsize, and the unpack only ever bitcasts same-width
      (u16->bf16, u32->f32, u8->i8). A mixed-width bitcast needs an
      [n, itemsize] uint8 intermediate whose minor dim the TPU tiles to
      128 — measured: a 1 GB bf16 embedding exploded into a 134 GB
      allocation request.
    - big leaves (> ``small_leaf_bytes``) chunk at ``chunk_bytes`` within
      their top-level subtree, so identical transformer layers share one
      compiled unpack program and peak extra HBM stays ~one chunk; ALL
      small leaves (scales, norms) of one width ride a single global
      buffer — one transfer instead of hundreds.

    Single-device only (callers with a mesh use the host-tree path and
    let the sharder place leaves)."""
    import jax
    import jax.numpy as jnp

    header, buf = _read_header(Path(path))
    entries = header["entries"]

    # 64-bit leaves cannot ride this path: under the default
    # jax_enable_x64=False, device_put canonicalizes a uint64 staging
    # buffer to uint32 and the bitcast would silently corrupt values.
    # Fall back to the host-tree load — the caller's device_put applies
    # jax's documented canonicalization to the VALUES (not raw bits),
    # which is the behavior such a model had before this fast path.
    if any(_np_dtype(e["dtype"]).itemsize > 4 for e in entries):
        return load(path)

    # partition into chunks: (stage_itemsize, [entry...]) — big leaves
    # grouped by (subtree, itemsize) capped at chunk_bytes; small leaves
    # into one global per-itemsize bucket
    chunks: list[tuple[int, list[dict]]] = []
    small: dict[int, list[dict]] = {}
    cur_key, cur = None, None
    for e in entries:
        isize = _np_dtype(e["dtype"]).itemsize
        if e["nbytes"] <= small_leaf_bytes:
            small.setdefault(isize, []).append(e)
            continue
        key = (tuple(e["path"][:2]), isize)
        if key != cur_key or sum(x["nbytes"] for x in cur) + e["nbytes"] \
                > chunk_bytes:
            cur = []
            chunks.append((isize, cur))
            cur_key = key
        cur.append(e)
    for isize, es in sorted(small.items()):
        chunks.append((isize, es))

    out = []
    for isize, group in chunks:
        stage_dt = _STAGE_DTYPE[isize]
        parts = [np.frombuffer(buf, stage_dt, count=e["nbytes"] // isize,
                               offset=e["offset"]) for e in group]
        staged = parts[0] if len(parts) == 1 else np.concatenate(parts)
        rel, sig = 0, []
        for e in group:
            sig.append((rel, e["dtype"], tuple(e["shape"])))
            rel += e["nbytes"] // isize
        sig = (isize, tuple(sig))
        fn = _unpack_cache.get(sig)
        if fn is None:
            def build(sig):
                _, leaf_sig = sig

                def unpack(raw):
                    leaves = []
                    for off, dtype_name, shape in leaf_sig:
                        dt = jnp.dtype(_np_dtype(dtype_name))
                        n = 1
                        for d in shape:
                            n *= d
                        b = jax.lax.slice(raw, (off,), (off + n,))
                        leaves.append(
                            jax.lax.bitcast_convert_type(b, dt).reshape(shape))
                    return leaves

                return jax.jit(unpack)

            fn = _unpack_cache[sig] = build(sig)
        staged_dev = jax.device_put(staged)
        leaves = fn(staged_dev)
        del staged_dev  # free the staging buffer before the next chunk
        for e, leaf in zip(group, leaves):
            out.append((tuple(e["path"]), leaf))
    return _unflatten(out)


def load(path: Path):
    """Memory-map ``path`` and return the nested-dict tree of numpy views."""
    path = Path(path)
    with open(path, "rb") as f:
        head = f.read(len(MAGIC) + 8)
        if head[: len(MAGIC)] != MAGIC:
            raise ValueError(f"{path}: not a flatpack file")
        (header_len,) = struct.unpack("<Q", head[len(MAGIC):])
        header = json.loads(f.read(header_len))
        buf = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    out = []
    for e in header["entries"]:
        a = np.frombuffer(buf, dtype=_np_dtype(e["dtype"]),
                          count=int(np.prod(e["shape"], dtype=np.int64)),
                          offset=e["offset"]).reshape(e["shape"])
        out.append((tuple(e["path"]), a))
    return _unflatten(out)
