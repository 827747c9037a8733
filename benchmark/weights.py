"""Seeded weights of a llama-family configuration, made by the benchmark.

The served bundle and the float32 reference both get their weights from
here, each on its own: the bundle through a parameter file written once per
configuration (``write_params``), the reference leaf by leaf as it walks the
layers (``leaf``). Neither reads what the other made. A leaf is a function
of ``(weights_seed, its path)`` alone, drawn with numpy on the host, so the
same bits come out on any machine and in any order.

Values (recorded under ``assumed`` in each configuration file): int8 kernels
uniform over the full range with one float32 scale of 1/(127*sqrt(hidden))
per output channel — the magnitude ``registry.save_random_params`` of the
program uses, under which bf16 activations stay finite through 32 layers
(chip run, PR 21); an embedding of int8-uniform values times 2^-12, which
bfloat16 holds exactly; unit norm gains.
"""

from __future__ import annotations

import zlib
from pathlib import Path

import numpy as np

EMBED_STEP = 2.0 ** -12


def dims_of(config: dict) -> dict:
    """The configuration's published keys under the names the program's
    ``LlamaConfig`` gives them (the recipe's width and depth keys)."""
    return {
        "vocab_size": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "mlp": config["intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "max_len": int(config["context_served"]),
    }


def _int8(seed: int, path: str, shape) -> np.ndarray:
    n = int(np.prod(shape))
    rng = np.random.default_rng([int(seed), zlib.crc32(path.encode())])
    # full-range 64-bit draws viewed as bytes: an order of magnitude faster
    # than bounded int8 draws at 7 GB
    raw = rng.integers(0, 1 << 64, -(-n // 8), dtype=np.uint64,
                       endpoint=False)
    return raw.view(np.int8)[:n].reshape(shape)


def leaf(seed: int, path: str, shape, dtype, hidden: int) -> np.ndarray:
    """One parameter leaf. ``path`` is '/'-joined tree keys, e.g.
    ``layer_3/q_proj/kernel_int8``; ``dtype`` a numpy dtype or its name."""
    name = np.dtype(dtype).name
    if name == "int8":
        return _int8(seed, path, shape)
    if path.endswith("embedding"):
        return (_int8(seed, path, shape).astype(np.float32)
                * EMBED_STEP).astype(dtype)
    if path.endswith("_proj/scale") or path.endswith("lm_head/scale"):
        return np.full(shape, 1.0 / (127.0 * hidden ** 0.5), dtype)
    if len(shape) == 1:  # norm gains
        return np.ones(shape, dtype)
    raise ValueError(f"weights: no rule for leaf {path} {shape} {name}")


def write_params(config: dict, path: Path) -> dict:
    """The configuration's parameter file, in the program's own format and
    tree layout (``jax.eval_shape`` of the init it serves from: shapes are
    all this touches of jax, no backend starts)."""
    import jax

    from lambdipy_tpu.bundle import flatpack
    from lambdipy_tpu.models import registry

    dims = dims_of(config)
    adapter = registry.get(config["model"]).build(
        dtype="bfloat16", quant=config["precision"]["weights"], extra=dims)
    shapes = jax.eval_shape(lambda: adapter.init_params(seed=0))
    seed = int(config["weights_seed"])

    def fill(keypath, spec):
        keys = [getattr(k, "key", getattr(k, "name", None)) for k in keypath]
        name = "/".join(str(k) for k in keys if k != "params")
        return leaf(seed, name, spec.shape, spec.dtype, dims["hidden"])

    tree = jax.tree_util.tree_map_with_path(fill, shapes)
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    flatpack.save(path, tree)
    return {"bytes": path.stat().st_size,
            "n_params": sum(int(np.prod(s.shape))
                            for s in jax.tree.leaves(shapes))}
