"""AOT executable store: ship compiled programs inside the bundle.

Cold start on TPU is interpreter + PJRT init + trace/lower/compile
(BASELINE.md: ~10 s floor; SURVEY.md §9.6 names AOT as the make-or-break
weapon). The persistent compile cache (utils/compile_cache.py) already
turns XLA *compilation* into a disk hit, but tracing + lowering a real
model is still seconds of Python. This module removes that too, with two
tiers stored under ``<bundle>/aot/``:

- **tier 2 — serialized executable** (``*.exec``): the PJRT-compiled
  program via ``jax.experimental.serialize_executable``. Zero trace, zero
  lower, zero compile at boot. Only valid for the exact (platform, jax,
  jaxlib) that produced it — the key encodes all three, and loading is
  best-effort (some PJRT plugins don't support executable serialization).
- **tier 1 — jax.export StableHLO** (``*.hlo``): portable serialized
  module. Boot skips tracing/lowering; the compile that remains is a
  persistent-cache hit because the builder warmed it.

Misses fall through to plain ``jax.jit`` and (best-effort) write both
artifacts so the *next* boot — or the built bundle, when the builder's
warm subprocess does this — is fast. The reference has no analog: its
"AOT" is shipping pre-built wheels (SURVEY.md §1); this is the same idea
one level down, at the XLA-program level.
"""

from __future__ import annotations

import contextlib
import json
import pickle
from pathlib import Path
from typing import Any, Callable, Sequence

from lambdipy_tpu.runtime import spans
from lambdipy_tpu.utils.fsutil import atomic_write_bytes, atomic_write_text
from lambdipy_tpu.utils.logs import get_logger

log = get_logger("lambdipy.aot")

_SCHEMA = 1

def _mesh_sig(mesh) -> str | None:
    if mesh is None:
        return None
    return "x".join(f"{a}{mesh.shape[a]}" for a in mesh.axis_names)


def _env_key(mesh=None) -> dict:
    import jax
    import jaxlib

    return {
        "schema": _SCHEMA,
        "platform": jax.default_backend(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "n_devices": len(jax.devices()),
        "mesh": _mesh_sig(mesh),
    }


class AotStore:
    """Directory of AOT artifacts for one bundle, keyed by entry name and
    the producing environment — including the payload's mesh shape, so a
    multi-device program warmed on one topology is never replayed on
    another (VERDICT r2 missing #4: meshed payloads re-traced every boot)."""

    def __init__(self, bundle_dir: Path, mesh=None):
        self.dir = Path(bundle_dir) / "aot"
        self.mesh = mesh
        # set when a matching meta existed but produced no usable tier —
        # the signal that re-saving would just reproduce the same artifacts
        self.exhausted = False
        # artifacts deserialized ahead of time by preload(): name ->
        # (callable, tier). load() consumes these instead of re-reading
        # the tier file, and still probes them.
        self._preloaded: dict[str, tuple] = {}

    def _mesh_ctx(self):
        """Trace/compile/probe under the payload mesh (models read it for
        sharding hints and backend selection)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from lambdipy_tpu.parallel.mesh import use_mesh

        return use_mesh(self.mesh)

    def _paths(self, name: str) -> dict[str, Path]:
        import jax

        stem = f"{name}.{jax.default_backend()}"
        sig = _mesh_sig(self.mesh)
        if sig:
            stem += f".{sig}"
        return {
            "meta": self.dir / f"{stem}.json",
            "hlo": self.dir / f"{stem}.hlo",
            "exec": self.dir / f"{stem}.exec",
        }

    # -- save ---------------------------------------------------------------

    def save(self, name: str, fn: Callable,
             example_args: Sequence[Any]) -> tuple[dict, Callable]:
        """Export ``fn`` at ``example_args``'s shapes; write tier 1 always,
        tier 2 when the backend supports executable serialization.

        Returns ``(meta, jitted)`` — the same ``jax.jit`` object the export
        used, so a miss path can serve from it instead of re-tracing.
        Artifact writes are atomic and the meta (which declares the tiers)
        lands last: a crash mid-save leaves no meta, never a meta pointing
        at a torn tier file.
        """
        import jax
        import jax.export

        self.dir.mkdir(parents=True, exist_ok=True)
        paths = self._paths(name)
        meta = _env_key(self.mesh)
        meta["tiers"] = []

        with self._mesh_ctx():
            jitted = jax.jit(fn)
            # plain call FIRST: this is the compile that flows through the
            # persistent-cache writer. A manual lower().compile()
            # pre-populates the jit dispatch cache WITHOUT writing the
            # persistent cache (observed: bundles warmed compile-last
            # shipped caches missing their own forward program), so order
            # matters here.
            jax.block_until_ready(jitted(*example_args))

            try:
                exported = jax.export.export(jitted)(*example_args)
                atomic_write_bytes(paths["hlo"], bytes(exported.serialize()))
                meta["tiers"].append("hlo")
                # warm the hlo-tier boot path too: the round-tripped module
                # hashes differently from the original jit, so compile it
                # once here to put ITS cache entry in the bundle
                jax.block_until_ready(jax.jit(exported.call)(*example_args))
            except Exception as e:
                log.warning("aot %s: jax.export failed: %s", name, e)

            # exec tier is single-chip only: a serialized multi-device
            # executable binds to concrete device ids; the hlo tier + warm
            # cache is the meshed cold-start path
            if self.mesh is None:
                try:
                    from jax.experimental import serialize_executable

                    compiled = jitted.lower(*example_args).compile()
                    payload = serialize_executable.serialize(compiled)
                    atomic_write_bytes(paths["exec"], pickle.dumps(payload))
                    meta["tiers"].append("exec")
                except Exception as e:
                    log.info("aot %s: executable serialization unavailable: %s",
                             name, e)

        if meta["tiers"]:
            atomic_write_text(paths["meta"], json.dumps(meta, indent=1))
        return meta, jitted

    def save_from_jitted(self, name: str, jitted: Callable,
                         example_args: Sequence[Any],
                         exec_only: bool = False) -> dict:
        """Export an ALREADY-warmED ``jax.jit`` object's program (the
        caller has invoked it at ``example_args``' shapes, so its compile
        is done and cached in-session). Used by the serving path to
        snapshot its compiled programs after warmup without paying the
        extra trace+compile that :meth:`save`'s fresh ``jax.jit`` would.

        ``exec_only`` skips the hlo tier (and its round-trip cache warm)
        when the caller knows only the executable tier can win.
        """
        import jax
        import jax.export

        self.dir.mkdir(parents=True, exist_ok=True)
        paths = self._paths(name)
        meta = _env_key(self.mesh)
        meta["tiers"] = []
        with self._mesh_ctx():
            if self.mesh is None:
                try:
                    from jax.experimental import serialize_executable

                    # in-session this re-lower/compile is a compilation-
                    # cache hit, not a fresh compile — the caller already
                    # ran the program at these shapes
                    compiled = jitted.lower(*example_args).compile()
                    payload = serialize_executable.serialize(compiled)
                    atomic_write_bytes(paths["exec"], pickle.dumps(payload))
                    # self-test NOW (a deserialize + one call, seconds):
                    # on some platforms (observed: multi-device CPU) a
                    # serialized single-device executable cannot load
                    # back; shipping it would make every boot pay the
                    # failed attempt, and the skipped hlo warm below
                    # would leave the real fallback cold
                    fn = self._load_tier("exec", paths)
                    jax.block_until_ready(fn(*example_args))
                    meta["tiers"].append("exec")
                except Exception as e:
                    paths["exec"].unlink(missing_ok=True)
                    log.info("aot %s: executable tier unavailable: %s",
                             name, e)
            if not exec_only:
                try:
                    exported = jax.export.export(jitted)(*example_args)
                    atomic_write_bytes(paths["hlo"],
                                       bytes(exported.serialize()))
                    # exec is probed first at load, so "hlo" goes last
                    meta["tiers"].append("hlo")
                    if "exec" not in meta["tiers"]:
                        # platforms that will actually BOOT from the hlo
                        # tier need its round-tripped module warmed into
                        # the persistent cache (same reasoning as
                        # save()); exec-capable platforms never probe it,
                        # so skip the extra compile there
                        jax.block_until_ready(
                            jax.jit(exported.call)(*example_args))
                except Exception as e:
                    log.warning("aot %s: jax.export failed: %s", name, e)
        if meta["tiers"]:
            atomic_write_text(paths["meta"], json.dumps(meta, indent=1))
        return meta

    def prune_broken_tiers(self, name: str,
                           example_args: Sequence[Any]) -> list[str]:
        """Build-time self-test: load each just-saved tier on THIS platform
        and delete any that fails to load or run (observed: a serialized
        single-device executable that cannot load back on a multi-device
        CPU), so the serve boot never pays a failed attempt for it.
        Returns the pruned tier names."""
        import jax

        paths = self._paths(name)
        if not paths["meta"].is_file():
            return []
        try:
            meta = json.loads(paths["meta"].read_text())
        except Exception:
            return []
        pruned = []
        for tier in list(meta.get("tiers", ())):
            try:
                with self._mesh_ctx():
                    fn = self._load_tier(tier, paths)
                    if fn is None:
                        continue
                    jax.block_until_ready(fn(*example_args))
            except Exception as e:
                log.warning("aot %s: pruning %s tier (failed self-test: %s)",
                            name, tier, e)
                meta["tiers"].remove(tier)
                paths[tier].unlink(missing_ok=True)
                pruned.append(tier)
        if pruned:
            # keep the meta even when no tiers survive: it records "tried
            # and pruned on this platform", which stops every subsequent
            # boot from re-exporting/re-probing the same losing artifacts
            atomic_write_text(paths["meta"], json.dumps(meta, indent=1))
        return pruned

    def has(self, name: str) -> bool:
        """Cheap existence check (one stat) so callers can skip building
        probe operands for artifacts that were never saved."""
        return self._paths(name)["meta"].is_file()

    def preload(self, prefix: str = "srv-") -> dict:
        """Deserialize (and device-load) every matching artifact's best
        tier WITHOUT probing. Deserializing and loading an executable
        needs NO operands — the model weights don't have to be resident —
        so a boot overlaps this with the weight upload instead of paying
        programs-after-weights serially (VERDICT r5 #5). ``load()`` later
        consumes the preloaded callable and runs its usual probe at first
        invoke, when params exist.

        Returns ``{"names": [...], "seconds": s}`` for the boot
        decomposition. Failures are per-artifact and silent — a broken
        artifact just falls back to load()'s normal path."""
        import jax

        out: list[str] = []
        if not self.dir.is_dir():
            return {"names": out, "seconds": 0.0}
        with spans.span("boot.aot_preload") as whole:
            sig = _mesh_sig(self.mesh)
            suffix = f".{jax.default_backend()}" + (f".{sig}" if sig else "")
            env = _env_key(self.mesh)
            for meta_path in sorted(self.dir.glob(f"{prefix}*{suffix}.json")):
                name = meta_path.name[: -len(suffix + ".json")]
                try:
                    meta = json.loads(meta_path.read_text())
                except Exception:
                    continue
                if any(meta.get(k) != env[k]
                       for k in ("schema", "platform", "jax", "jaxlib",
                                 "n_devices", "mesh")):
                    continue
                paths = self._paths(name)
                for tier in ("exec", "hlo"):
                    if tier not in meta.get("tiers", ()):
                        continue
                    try:
                        with spans.span("boot.aot_load", program=name,
                                        tier=tier) as sp, self._mesh_ctx():
                            fn = self._load_tier(tier, paths)
                    except Exception:
                        continue
                    if fn is not None:
                        spans.program(name, tier, aot_load=sp.seconds)
                        self._preloaded[name] = (fn, tier)
                        out.append(name)
                        break
        return {"names": out, "seconds": round(whole.seconds, 3)}

    def _load_tier(self, tier: str, paths: dict):
        """Deserialize one tier into a callable (no probing/gating)."""
        import jax
        import jax.export

        if tier == "exec" and paths["exec"].is_file():
            from jax.experimental import serialize_executable

            payload = pickle.loads(paths["exec"].read_bytes())
            return serialize_executable.deserialize_and_load(*payload)
        if tier == "hlo" and paths["hlo"].is_file():
            exported = jax.export.deserialize(bytearray(paths["hlo"].read_bytes()))
            return jax.jit(exported.call)
        return None

    # -- load ---------------------------------------------------------------

    def load(self, name: str, example_args: Sequence[Any] | None = None,
             key=None) -> tuple[Callable, str] | None:
        """Return ``(callable, tier)`` for the best available artifact
        matching the current environment, or None. ``key`` (the caller's
        own name for the program) only labels the program record's entry.

        When ``example_args`` is given each candidate tier is probe-invoked
        before being returned — an AOT executable can deserialize fine yet
        fail at call time (observed: XLA:CPU AOT rejects a host whose CPU
        features differ from the compile machine). The probe call doubles
        as the warmup invoke.
        """
        paths = self._paths(name)
        if not paths["meta"].is_file():
            return None
        try:
            meta = json.loads(paths["meta"].read_text())
        except Exception:
            return None
        env = _env_key(self.mesh)
        if any(meta.get(k) != env[k]
               for k in ("schema", "platform", "jax", "jaxlib", "n_devices",
                         "mesh")):
            log.info("aot %s: environment mismatch (%s vs %s), ignoring",
                     name, meta, env)
            return None

        def _probe(fn: Callable) -> None:
            """Raises when the loaded program cannot run."""
            if example_args is not None:
                import jax

                jax.block_until_ready(fn(*example_args))

        pre = self._preloaded.pop(name, None)
        tried = None
        if pre is not None and pre[1] in meta.get("tiers", ()):
            # deserialized ahead of time (preload(), overlapped with the
            # weight upload); only the probe remains
            fn, tried = pre
            try:
                with spans.span("boot.warm", program=name,
                                tier=tried) as warm, self._mesh_ctx():
                    _probe(fn)
                spans.program(name, tried, key=key, warm=warm.seconds)
                return fn, tried
            except Exception as e:
                log.warning("aot %s: preloaded %s tier failed probe: %s",
                            name, tried, e)
        for tier in ("exec", "hlo"):
            if tier == tried or tier not in meta.get("tiers", ()):
                continue
            try:
                with self._mesh_ctx():
                    with spans.span("boot.aot_load", program=name,
                                    tier=tier) as sp:
                        fn = self._load_tier(tier, paths)
                    if fn is not None:
                        with spans.span("boot.warm", program=name,
                                        tier=tier) as warm:
                            _probe(fn)
                        spans.program(name, tier, key=key,
                                      aot_load=sp.seconds, warm=warm.seconds)
                        return fn, tier
            except Exception as e:
                log.warning("aot %s: %s tier failed to load: %s", name, tier, e)
        self.exhausted = True  # meta matched this env; nothing usable in it
        return None


def cached_jit(ctx, name: str, fn: Callable, example_args: Sequence[Any],
               mesh=None) -> tuple[Callable, str]:
    """The handler-facing entry: AOT artifact if present, else ``jax.jit``
    plus a best-effort save so the next boot skips trace/lower/compile.

    ``ctx`` is a HandlerContext (anything with ``bundle_dir``). Artifacts
    are keyed by device count AND mesh shape — a meshed payload (``mesh``
    given) saves/loads the StableHLO tier under its (topology, mesh)
    signature, so a multi-device boot skips tracing once any boot on the
    same topology has run; the device-bound exec tier stays single-chip
    only. The returned callable is shape-specialized to ``example_args``
    on a hit; handlers keep a plain-jit fallback for other shapes. Returns
    ``(callable, source)``, source in {"exec", "hlo", "jit"}.
    """
    import jax

    store = AotStore(ctx.bundle_dir, mesh=mesh)
    hit = store.load(name, example_args)
    if hit is not None:
        return hit
    spans.program(name, "jit")
    if store.exhausted:
        # a matching meta already records that this platform's artifacts
        # don't work — re-saving would just reproduce them; serve from
        # jit, whose compile is a hit in the bundle's warm persistent cache
        return jax.jit(fn), "jit"
    try:
        _, jitted = store.save(name, fn, example_args)
        store.prune_broken_tiers(name, example_args)
        return jitted, "jit"
    except Exception as e:  # bundle dir read-only, export unsupported, ...
        log.info("aot %s: save skipped: %s", name, e)
    return jax.jit(fn), "jit"
