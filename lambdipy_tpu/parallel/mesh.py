"""Device-mesh construction.

Canonical axis names, in nesting order (outermost first — DCN-adjacent axes
outermost, ICI-heavy axes innermost so bandwidth-hungry collectives ride
ICI, per the scaling-book recipe):

- ``dp``   data parallel (pure replication of params, sharded batch)
- ``fsdp`` fully-sharded data parallel (params sharded over batch axis)
- ``pp``   pipeline parallel (stage dimension; lax.ppermute microbatching)
- ``tp``   tensor parallel (heads/mlp/vocab sharded; all-reduce per block)
- ``sp``   sequence/context parallel (ring attention over seq axis)
- ``ep``   expert parallel (MoE expert dimension)
"""

from __future__ import annotations

import contextlib
import contextvars
import math

import jax
import numpy as np
from jax.sharding import Mesh

MESH_AXES: tuple[str, ...] = ("dp", "fsdp", "pp", "tp", "sp", "ep")

_ACTIVE_MESH: contextvars.ContextVar[Mesh | None] = contextvars.ContextVar(
    "lambdipy_active_mesh", default=None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Enter a mesh for both jax (``with mesh``) and framework consumers
    (:func:`current_mesh` — e.g. models picking a ring-attention backend)."""
    token = _ACTIVE_MESH.set(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _ACTIVE_MESH.reset(token)


def pcast_varying(x, axes):
    """Mark ``x`` device-varying over ``axes`` (shard_map's vma tracker
    needs scan carries typed the way the loop body leaves them). Axes the
    value already varies over are filtered out — pcast rejects re-marking
    them; the ONE home of that rule for ring, spdecode and pipeline."""
    need = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, need, to="varying") if need else x


def current_mesh() -> Mesh | None:
    """The ambient mesh, as entered through :func:`use_mesh`."""
    return _ACTIVE_MESH.get()


def make_mesh(shape: dict[str, int], devices=None) -> Mesh:
    """Build a Mesh from {axis: size}; axes absent from ``shape`` get size 1.

    Sizes must multiply to the device count used. ``shape`` values of -1 are
    filled with the remaining device factor (at most one -1).
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = dict(shape)
    unknown = set(sizes) - set(MESH_AXES)
    if unknown:
        raise ValueError(f"unknown mesh axes {sorted(unknown)}; known: {MESH_AXES}")
    n = len(devices)
    fills = [a for a, s in sizes.items() if s == -1]
    if len(fills) > 1:
        raise ValueError("at most one axis may be -1")
    fixed = math.prod(s for s in sizes.values() if s != -1)
    if fills:
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by fixed axes product {fixed}")
        sizes[fills[0]] = n // fixed
    total = math.prod(sizes.values()) if sizes else 1
    if total != n:
        raise ValueError(
            f"mesh shape {sizes} needs {total} devices, have {n}")
    axis_names = [a for a in MESH_AXES if sizes.get(a, 1) > 1] or ["dp"]
    dims = [sizes.get(a, 1) for a in axis_names]
    arr = np.asarray(devices).reshape(dims)
    return Mesh(arr, axis_names=tuple(axis_names))


def flat_mesh(axis: str = "dp", devices=None) -> Mesh:
    """All devices on a single named axis."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices), axis_names=(axis,))


def mesh_shape_for(n_devices: int, *, tp: int | None = None,
                   sp: int = 1, pp: int = 1) -> dict[str, int]:
    """Default mesh shape for n devices: fill tp up to 4 (one v5e host's
    worth of ICI-adjacent chips), rest dp. Serving configs override."""
    if tp is None:
        tp = math.gcd(n_devices, 4)
    denom = tp * sp * pp
    if n_devices % denom:
        raise ValueError(f"{n_devices} devices not divisible by tp*sp*pp={denom}")
    return {"dp": n_devices // denom, "pp": pp, "tp": tp, "sp": sp}


def parse_mesh_spec(spec: str) -> dict[str, int]:
    """Parse a serving mesh declaration into ``{axis: size}``.

    The one grammar shared by the ``mesh`` bundle extra, the
    ``LAMBDIPY_MESH`` env var, and ``lambdipy serve --mesh``:

    - ``"tp=2"`` / ``"tp=2,sp=1"`` / ``"dp=2 tp=4"``  explicit axes
      (comma or whitespace separated; axis names from :data:`MESH_AXES`)
    - ``"2"``                                          bare tensor-parallel
      width (the dominant serving shape)
    - ``"2x2"``                                        ``dp x tp`` grid
      (the ROADMAP's bundle shorthand)
    - ``""`` / ``"0"`` / ``"1"`` / ``"off"`` / ``"none"``  no mesh

    Size-1 axes are dropped (they would be omitted from the Mesh anyway);
    an all-size-1 spec means single-device serving and returns ``{}``.
    Unknown axes and non-positive sizes raise ``ValueError`` — a typo'd
    mesh must never silently serve replicated.
    """
    s = (spec or "").strip().lower()
    if s in ("", "0", "1", "off", "none"):
        return {}
    if "x" in s and "=" not in s:
        try:
            dims = [int(tok) for tok in s.split("x")]
        except ValueError:
            raise ValueError(f"unparseable mesh spec {spec!r}") from None
        if len(dims) != 2:
            raise ValueError(
                f"grid mesh spec must be AxB (dp x tp), got {spec!r}")
        shape = {"dp": dims[0], "tp": dims[1]}
    elif "=" not in s:
        try:
            shape = {"tp": int(s)}
        except ValueError:
            raise ValueError(f"unparseable mesh spec {spec!r}") from None
    else:
        shape = {}
        for tok in s.replace(",", " ").split():
            axis, _, val = tok.partition("=")
            if not _ or axis not in MESH_AXES:
                raise ValueError(
                    f"unknown mesh axis {axis!r} in {spec!r}; "
                    f"known: {MESH_AXES}")
            try:
                shape[axis] = int(val)
            except ValueError:
                raise ValueError(
                    f"mesh axis {axis} has non-integer size {val!r}"
                ) from None
    for axis, size in shape.items():
        if size < 1:
            raise ValueError(
                f"mesh axis {axis} must be >= 1, got {size}")
    return {a: n for a, n in shape.items() if n > 1}
