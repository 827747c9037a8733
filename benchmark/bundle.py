"""Build once per configuration, deploy every run.

Copied from ``chip_smoke.py`` at commit fb0103a (``write_recipe``,
``build_bundle``, ``Served``), which stays a liveness check and may change:
the recipe is DERIVED from the builtin serving recipe (same handler, dtype,
quantization, base layer, engine settings), with the widths the
configuration's family reads from its file (``benchmark/families``), its
mesh and overrides on top, and built with ``lambdipy build`` as a user would. What differs from the smoke: the bundle is keyed by the
configuration file's content and kept in the work directory, so only a
cell's first run in a checkout builds; the parameter file comes from
``benchmark/weights.py``.
"""

from __future__ import annotations

import hashlib
import json
import shutil
import subprocess
import sys
import time
import tomllib
from pathlib import Path

from benchmark import families, weights

REPO = Path(__file__).resolve().parents[1]
BASE_RECIPE = REPO / "lambdipy_tpu" / "recipes" / "builtin" / "jax-llama3-8b.toml"
DEFAULT_WORK = Path(__file__).resolve().parent / ".work"


class BenchFailure(Exception):
    """The run cannot produce a result; exit non-zero, print no metrics."""


def note(**fields) -> None:
    """An earlier line of the output: free-form, one JSON object."""
    print(json.dumps(fields, default=str), flush=True)


def bundle_key(config_path: Path) -> str:
    return hashlib.sha256(Path(config_path).read_bytes()).hexdigest()[:16]


def _toml_value(v) -> str:
    return json.dumps(v) if isinstance(v, (str, list)) else str(v)


def write_recipe(name: str, params: Path, config: dict, out_dir: Path) -> Path:
    base = tomllib.loads(BASE_RECIPE.read_text())
    payload = {k: v for k, v in base["payload"].items()
               if k not in ("mesh", "extra")}
    payload["model"] = config["model"]
    payload["params"] = str(params)
    payload["quant"] = config["precision"]["weights"]
    payload["dtype"] = config["precision"]["activations"]
    mesh = config.get("mesh")
    top = {"schema": base["schema"], "name": name, "version": base["version"],
           "description": f"benchmark: {config['name']}",
           "python": base["python"],
           "device": f"tpu-v5e-{config['chips']}",
           "base_layer": base["base_layer"], "requires": base["requires"]}
    tables = [("", top), ("prune", base.get("prune", {})), ("payload", payload)]
    if mesh:
        tables.append(("payload.mesh", mesh))
    tables.append(("payload.extra", {**base["payload"]["extra"],
                                     **families.of(config).dims_of(config),
                                     **config.get("recipe_extra", {})}))
    lines = []
    for title, table in tables:
        if title:
            lines.append(f"\n[{title}]")
        lines += [f"{k} = {_toml_value(v)}" for k, v in table.items()]
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{name}.toml"
    path.write_text("\n".join(lines) + "\n")
    return path


def ensure_bundle(config_path: Path, config: dict, work: Path) -> tuple:
    """(bundle directory, whether this call built it)."""
    from lambdipy_tpu.utils.platform import child_env

    key = bundle_key(config_path)
    home = work / key
    bundle = home / "bundle"
    manifest = bundle / "manifest.json"
    if manifest.exists() and \
            (json.loads(manifest.read_text()).get("warm") or {}).get("ok"):
        note(stage="bundle", found=str(bundle), key=key, built=False)
        return bundle, False
    shutil.rmtree(home, ignore_errors=True)
    home.mkdir(parents=True)
    name = f"bench-{config['name']}"
    t0 = time.monotonic()
    params = home / "params.fpk"
    info = weights.write_params(config, params)
    note(stage="params", seconds=round(time.monotonic() - t0, 1), **info)
    write_recipe(name, params, config, home / "recipes")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "lambdipy_tpu", "build", name,
         "--recipe-dir", str(home / "recipes"), "--out", str(bundle)],
        cwd=str(REPO), capture_output=True, text=True, timeout=1500,
        env=child_env({"LAMBDIPY_WARM_TIMEOUT": "1200"}))
    if proc.returncode != 0:
        raise BenchFailure(f"lambdipy build rc={proc.returncode}: "
                           f"{proc.stderr.strip()[-800:]}")
    warm = json.loads(manifest.read_text()).get("warm") or {}
    note(stage="build", seconds=round(time.monotonic() - t0, 1), key=key,
         built=True, warm_ok=warm.get("ok"), warm_s=warm.get("wall_s"),
         warm_compile=warm.get("compile"), warm_device=warm.get("device"))
    if not warm.get("ok"):
        raise BenchFailure(f"warm record not ok: {warm}")
    params.unlink(missing_ok=True)  # the bundle holds its own copy
    return bundle, True
