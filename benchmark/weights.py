"""Seeded weights of a configuration, made by the benchmark.

The served bundle and the float32 reference both get their weights from
here, each on its own: the bundle through a parameter file written once per
configuration (``write_params``), the reference leaf by leaf as it walks the
layers (``leaf``). Neither reads what the other made. A leaf is a function
of ``(weights_seed, its path)`` alone, drawn with numpy on the host, so the
same bits come out on any machine and in any order. Which leaves a tree has
and what each holds is the configuration's family's to say
(``benchmark/families``); the seeded draw and the walk over the program's
tree are shared.
"""

from __future__ import annotations

import zlib
from pathlib import Path

import numpy as np

from benchmark import families

EMBED_STEP = 2.0 ** -12


def int8_draw(seed: int, path: str, shape) -> np.ndarray:
    n = int(np.prod(shape))
    rng = np.random.default_rng([int(seed), zlib.crc32(path.encode())])
    # full-range 64-bit draws viewed as bytes: an order of magnitude faster
    # than bounded int8 draws at 7 GB
    raw = rng.integers(0, 1 << 64, -(-n // 8), dtype=np.uint64,
                       endpoint=False)
    return raw.view(np.int8)[:n].reshape(shape)


def leaf(config: dict, path: str, shape, dtype) -> np.ndarray:
    """One parameter leaf by the family's rules. ``path`` is '/'-joined
    tree keys; ``dtype`` a numpy dtype or its name. A leaf the family has
    no rule for raises: a guessed value would be served and never seen."""
    out = families.of(config).leaf(int(config["weights_seed"]), path,
                                   tuple(shape), dtype, config)
    if out is None:
        raise ValueError(
            f"family {families.name_of(config)!r}: no rule for leaf {path} "
            f"{tuple(shape)} {np.dtype(dtype).name}")
    return out


def write_params(config: dict, path: Path) -> dict:
    """The configuration's parameter file, in the program's own format and
    tree layout (``jax.eval_shape`` of the init it serves from: shapes are
    all this touches of jax, no backend starts)."""
    import jax

    from lambdipy_tpu.bundle import flatpack
    from lambdipy_tpu.models import registry

    adapter = registry.get(config["model"]).build(
        dtype="bfloat16", quant=config["precision"]["weights"],
        extra=families.of(config).dims_of(config))
    shapes = jax.eval_shape(lambda: adapter.init_params(seed=0))

    def fill(keypath, spec):
        keys = [getattr(k, "key", getattr(k, "name", None)) for k in keypath]
        name = "/".join(str(k) for k in keys if k != "params")
        return leaf(config, name, spec.shape, spec.dtype)

    tree = jax.tree_util.tree_map_with_path(fill, shapes)
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    flatpack.save(path, tree)
    return {"bytes": path.stat().st_size,
            "n_params": sum(int(np.prod(s.shape))
                            for s in jax.tree.leaves(shapes))}
