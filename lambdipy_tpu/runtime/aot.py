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

A single-chip ``LlamaServer`` uses the exec tier alone and needs no
example operands (``models/llama.py`` ``_ServedProgram``): a program it had
to compile is handed over as the ``Compiled`` it runs
(:meth:`AotStore.save_later`, written on the store's own thread), a later
boot takes it back with :meth:`AotStore.load_exec` at the program's first
use, and that first real call is the probe. A meta's ``boot`` says whether
a boot of the bundle runs the program before it is ready:
:meth:`AotStore.preload` leaves the others to their first use.
"""

from __future__ import annotations

import contextlib
import json
import pickle
import queue
import threading
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
        # save_later()'s queue and thread (started with the first save),
        # the artifacts it has written, and whether the directory took
        # them: one refused write (a read-only bundle, as under Lambda's
        # /var/task) ends the saving for this boot, silently
        self._pending: queue.Queue | None = None
        self._saver_lock = threading.Lock()
        self.saved = 0
        self.writable = True

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
                self.drop_tier(name, tier)
                pruned.append(tier)
        return pruned

    def pruned(self, name: str) -> bool:
        """THIS environment tried the artifact's exec tier and dropped it
        (:meth:`drop_tier` keeps the meta): saving it again would write the
        same losing artifact. Another environment's meta says nothing of
        the kind, and is overwritten."""
        meta = self._meta(self._paths(name)["meta"])
        return meta is not None and "exec" not in meta.get("tiers", ())

    def _meta(self, meta_path: Path) -> dict | None:
        """The artifact's meta when it is THIS environment's (platform,
        jax, jaxlib, device count, mesh); None for another's, or none."""
        try:
            meta = json.loads(meta_path.read_text())
        except Exception:
            return None
        env = _env_key(self.mesh)
        if any(meta.get(k) != v for k, v in env.items()):
            log.info("aot %s: environment mismatch (%s vs %s), ignoring",
                     meta_path.name, meta, env)
            return None
        return meta

    # -- the exec tier without operands (a single-chip LlamaServer) ---------

    def save_later(self, name: str, compiled, boot: bool) -> None:
        """Queue the ``Compiled`` a server has just obtained and run for
        the exec tier. Serialising and writing happen on the store's one
        saver thread, so the caller's first run waits for none of it;
        :meth:`drain` waits for the queue. ``boot``: see :meth:`preload`."""
        if self.mesh is not None or not self.writable:
            return
        with self._saver_lock:
            if self._pending is None:
                self._pending = queue.Queue()
                threading.Thread(target=self._save_loop, daemon=True,
                                 name="aot-save").start()
        self._pending.put((name, compiled, boot))

    def _save_loop(self) -> None:
        while True:
            name, compiled, boot = self._pending.get()
            try:
                if self.writable:
                    self._save_compiled(name, compiled, boot)
            finally:
                self._pending.task_done()

    def _save_compiled(self, name: str, compiled, boot: bool) -> None:
        from jax.experimental import serialize_executable

        paths = self._paths(name)
        try:
            with spans.span("boot.aot_save", program=name):
                payload = pickle.dumps(
                    serialize_executable.serialize(compiled))
                self.dir.mkdir(parents=True, exist_ok=True)
                atomic_write_bytes(paths["exec"], payload)
                atomic_write_text(paths["meta"], json.dumps(
                    {**_env_key(), "tiers": ["exec"], "boot": boot},
                    indent=1))
            self.saved += 1
        except Exception as e:  # a read-only bundle (OSError), or a
            # backend that cannot serialise: met once, not once a program
            self.writable = False
            log.info("aot %s: not saved, and nothing after it (%s)", name, e)

    def drain(self) -> None:
        """Wait until everything queued by :meth:`save_later` is written."""
        if self._pending is not None:
            self._pending.join()

    def load_exec(self, name: str):
        """``(executable, aot_load seconds or None)`` of the exec tier, NOT
        probed: deserialising needs no operands, and the caller's first real
        call is the probe (:meth:`drop_tier` if it fails). The seconds are
        None where :meth:`preload` had loaded it. None: no artifact, another
        environment's, or a tier that does not load (dropped here)."""
        paths = self._paths(name)
        meta = self._meta(paths["meta"])
        if meta is None or "exec" not in meta.get("tiers", ()):
            self._preloaded.pop(name, None)
            return None
        pre = self._preloaded.pop(name, None)
        if pre is not None and pre[1] == "exec":
            return pre[0], None
        try:
            with spans.span("boot.aot_load", program=name,
                            tier="exec") as sp:
                fn = self._load_tier("exec", paths)
        except Exception as e:
            log.warning("aot %s: exec tier failed to load: %s", name, e)
            self.drop_tier(name, "exec")
            return None
        return fn, sp.seconds

    def drop_tier(self, name: str, tier: str) -> None:
        """Prune a tier that does not load or run here. The meta stays,
        without the tier, even when none survives: it records "tried on
        this platform", so no later boot writes or probes the same losing
        artifact again."""
        paths = self._paths(name)
        try:
            meta = json.loads(paths["meta"].read_text())
            meta["tiers"] = [t for t in meta.get("tiers", ()) if t != tier]
            paths[tier].unlink(missing_ok=True)
            atomic_write_text(paths["meta"], json.dumps(meta, indent=1))
        except Exception as e:  # read-only bundle: the next boot tries again
            log.info("aot %s: %s tier not pruned: %s", name, tier, e)

    def preload(self, prefix: str = "srv-") -> dict:
        """Deserialize (and device-load) every matching artifact's best
        tier WITHOUT probing. Deserializing and loading an executable
        needs NO operands — the model weights don't have to be resident —
        so a boot overlaps this with the weight upload instead of paying
        programs-after-weights serially (VERDICT r5 #5). ``load()`` /
        ``load_exec()`` later consume the preloaded callable; the probe is
        its first invoke, when params exist.

        Only the BOOT SET is loaded: an artifact whose meta says ``"boot":
        false`` was saved by a server after its boot's own warm-up had
        ended, for a program the traffic asked for. A deploy does not wait
        for those (2-7 s each at 7B widths, one after the other): they are
        loaded at their first use.

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
            for meta_path in sorted(self.dir.glob(f"{prefix}*{suffix}.json")):
                name = meta_path.name[: -len(suffix + ".json")]
                meta = self._meta(meta_path)
                if meta is None or meta.get("boot") is False:
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
            # the exec tier is single-chip (mesh None): on a host with
            # more devices it runs where unplaced operands live, on the
            # first; without this the load asks for a shard a device
            return serialize_executable.deserialize_and_load(
                *payload, execution_devices=jax.devices()[:1])
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
        meta = self._meta(paths["meta"])
        if meta is None:
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
