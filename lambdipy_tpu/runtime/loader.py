"""Bundle boot: manifest -> importable, warmed handler.

The cold-start path (SURVEY.md §4 D/E): every stage is timed because the
<10 s budget is consumed by interpreter + PJRT init + first compile
(BASELINE.md): each is a span ``boot.<stage>`` (``runtime/spans.py``), one
after the other on the boot thread, and ``cold_start`` (the ready line,
``/healthz``) is those spans' seconds. The loader:

1. reads + verifies the manifest, checks base-layer version skew,
2. layers sys.path: bundle ``site/`` first, base layer (host site) after,
3. turns on JAX's persistent compilation cache where
   ``utils/compile_cache.py`` places it (by default the bundle's
   ``compile_cache/``, shipped warm by the builder -> first compile
   becomes a cache hit, SURVEY.md §9.6),
4. imports ``handler.py``, calls ``init(ctx)``, runs a warmup invoke.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

from lambdipy_tpu.bundle.baselayer import check_skew, runtime_sys_path
from lambdipy_tpu.bundle.format import load_manifest
from lambdipy_tpu.runtime import spans
from lambdipy_tpu.utils.logs import get_logger, log_event

log = get_logger("lambdipy.runtime")


@dataclass
class HandlerContext:
    """What a bundle handler gets at init time."""

    bundle_dir: Path
    manifest: dict
    params_dir: Path | None
    spec: dict  # payload spec from the manifest

    def degraded(self) -> list[str]:
        return list(self.manifest.get("provenance", {}).get("skipped_optional", []))


@dataclass
class BootReport:
    bundle_dir: Path
    handler: Any
    state: Any
    stages: dict[str, float] = field(default_factory=dict)
    # the stage spans as (name, begin, end) on time.monotonic(), in order;
    # ``stages`` is their seconds under the stage's name, and the ``total``
    stage_spans: list[tuple] = field(default_factory=list)
    skew: dict = field(default_factory=dict)
    warmup_result: Any = None
    manifest: dict = field(default_factory=dict)
    # active numerics-sanitizer flags (utils/debug.py apply_debug_env);
    # non-empty means every jit call pays a device sync
    debug_flags: dict = field(default_factory=dict)
    # jax bundles only: the device this process holds, as jax.devices()
    # reports it ({"platform", "kind", "count"} — /healthz serves it so a
    # parent can tell what its child runs on without touching jax), the
    # compile-cache directory in force, and the compile counters opened
    # before the handler's first program
    device: dict | None = None
    compile_cache_dir: Path | None = None
    compile_counters: Any = None

    def close(self) -> None:
        if self.compile_counters is not None:
            self.compile_counters.close()


def load_bundle(bundle_dir: Path, *, warmup: bool = True) -> BootReport:
    bundle_dir = Path(bundle_dir)
    done: list[spans.span] = []

    def stage(name: str) -> spans.span:
        done.append(spans.span(f"boot.{name}"))
        return done[-1]

    with stage("manifest"):
        manifest = load_manifest(bundle_dir)
        payload = manifest.get("payload")
        if payload is None:
            raise ValueError(f"bundle {bundle_dir} has no payload; nothing to serve")
        base = manifest.get("base_layer", {"name": "none", "versions": {}})
        skew = check_skew(base.get("versions", {}), base.get("name", "none"))
        if skew:
            log_event(log, "base layer skew detected", skew=skew)

    with stage("syspath"):
        site_dir = bundle_dir / "site"
        for p in reversed(runtime_sys_path(site_dir, base.get("name", "none"))):
            if p not in sys.path:
                sys.path.insert(0, p)

    with stage("compile_cache"):
        from lambdipy_tpu.models import registry as model_registry

        try:
            uses_jax = model_registry.get(payload.get("model", "")).kind == "jax"
        except Exception:
            uses_jax = False
        cache_dir = counters = None
        if uses_jax:
            from lambdipy_tpu.utils.compile_cache import (CompileCounters,
                                                          enable_compile_cache)

            cache_dir = enable_compile_cache(bundle_dir)
            counters = CompileCounters()
            # start PJRT backend init NOW on a worker thread: attaching
            # to a TPU takes seconds, and it overlaps the handler import
            # + params read below instead of serializing in front of
            # them. Backend init is lock-guarded inside jax; the
            # handler's first device call simply joins it.
            import threading

            def _init_backend():
                try:
                    import jax

                    with spans.span("boot.backend"):
                        jax.devices()
                except Exception as e:  # surfaced again, with context, by
                    log.warning("background PJRT init failed: %s", e)

            threading.Thread(target=_init_backend, daemon=True,
                             name="pjrt-init").start()
        from lambdipy_tpu.utils.debug import apply_debug_env

        # opt-in numerics sanitizer (LAMBDIPY_DEBUG_NANS=1 in the
        # deployment env): NaN/Inf in any jit output raises at the
        # producing primitive instead of poisoning responses. Applied
        # regardless of the registry-derived uses_jax flag — a custom
        # handler may use jax directly; without the env vars it is a
        # jax-free no-op
        debug_flags = apply_debug_env()

    try:
        with stage("handler_import"):
            spec = importlib.util.spec_from_file_location(
                f"lambdipy_bundle_handler_{bundle_dir.name}", bundle_dir / "handler.py")
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)

        with stage("init"):
            params_dir = bundle_dir / "params"
            ctx = HandlerContext(
                bundle_dir=bundle_dir,
                manifest=manifest,
                params_dir=params_dir if params_dir.is_dir() else None,
                spec=dict(payload),
            )
            state = module.init(ctx)
            device = None
            if uses_jax:
                from lambdipy_tpu.utils.platform import device_identity

                device = device_identity()

        warmup_result = None
        if warmup:
            with stage("warmup"):
                warmup_result = module.invoke(state, {"warmup": True})
    except BaseException:
        if counters is not None:
            counters.close()  # nobody is left to own them
        raise

    stages = {sp.name.removeprefix("boot."): round(sp.seconds, 4)
              for sp in done}
    stages["total"] = round(sum(sp.seconds for sp in done), 4)
    report = BootReport(
        bundle_dir=bundle_dir,
        handler=module,
        state=state,
        stage_spans=[(sp.name, sp.t0, sp.t0 + sp.seconds) for sp in done],
        stages=stages,
        skew=skew,
        warmup_result=warmup_result,
        manifest=manifest,
        debug_flags=debug_flags,
        device=device,
        compile_cache_dir=cache_dir,
        compile_counters=counters,
    )
    log_event(log, "bundle booted", bundle=str(bundle_dir),
              cold_start=report.stages, skew=bool(skew))
    return report
