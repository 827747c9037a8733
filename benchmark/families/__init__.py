"""The seam through which everything architecture-specific reaches the
harness: a configuration file names its family (its ``family`` key; a file
without one, its ``model`` key) and the harness loads
``benchmark/families/<family>.py`` by that name, ``-`` written ``_``. No
python file directly under ``benchmark/`` names a family, so a new
architecture's cell is new files and entries only (PERF.md section 4, "To
add an architecture: the files").

A family module has five parts, ``PARTS`` below:

1. ``dims_of(config)``: the configuration's published keys under the names
   the program's registry builder takes: ALL keys that shape the parameter
   tree. The recipe that is built and the tree that ``weights.write_params``
   fills both come from it.
2. ``leaf(seed, path, shape, dtype, config)``: one seeded parameter leaf of
   this family's tree as a numpy array, or None where the family has no
   rule for the path (``weights.leaf`` then raises, naming family and path).
3. ``walk(config, ids, rows_op, pos_op, flags)``: the plain reference.
   Float32 logits at ``highest`` precision at ``(rows_op, pos_op)`` of the
   batch ``ids`` [rows, length], one array per flag of ``flags``: False is
   the reference, True the family's control, the same walk in the nearest
   precision below the one its configurations state. It imports nothing of
   ``lambdipy_tpu``, takes its weights leaf by leaf from ``weights.leaf``
   and holds one layer's weights on the device at a time.
4. ``decode_step_bytes(config, *, rows, context)``,
   ``decode_step_flops(config, *, rows, context)``,
   ``prefill_flops(config, *, rows, seq_len)``: what a step needs, from
   shapes, for the roofline shares.
5. ``SCOPES``, ``WITNESS``: the scope names the program gives this family's
   device operations, and those of them that only a program with named
   scopes has (``benchmark/scopes.py``).
"""

from __future__ import annotations

import functools
import importlib
from pathlib import Path

HERE = Path(__file__).resolve().parent
PARTS = ("dims_of", "leaf", "walk", "decode_step_bytes", "decode_step_flops",
         "prefill_flops", "SCOPES", "WITNESS")


def name_of(config: dict) -> str:
    return str(config.get("family") or config["model"])


@functools.cache
def load(name: str):
    """The family's module. A name without a file, or a file without all
    five parts, ends the run before anything is built: no result line."""
    from benchmark.bundle import BenchFailure  # bundle -> weights -> here

    stem = name.replace("-", "_")
    path = HERE / f"{stem}.py"
    if not stem.isidentifier() or not path.is_file():
        known = sorted(p.stem for p in HERE.glob("*.py")
                       if p.stem != "__init__")
        raise BenchFailure(f"no family {name!r} under benchmark/families "
                           f"(known: {known})")
    mod = importlib.import_module(f"benchmark.families.{stem}")
    missing = [part for part in PARTS if not hasattr(mod, part)]
    if missing:
        raise BenchFailure(f"family {name!r} lacks {missing}")
    return mod


def of(config: dict):
    return load(name_of(config))
