"""CLI: ``build / package / deploy / serve / invoke`` + stores admin.

Same command surface shape as the reference's click CLI (SURVEY.md §3.1
#1: ``lambdipy build`` / ``lambdipy package``), extended with the serve-side
commands the TPU rebuild adds (deploy/serve/invoke/stop — SURVEY.md §2
table, publish/deploy row). End state per BASELINE.json:
``lambdipy build jax-resnet50 && lambdipy deploy jax-resnet50``.
"""

from __future__ import annotations

import json
import math
import os
import sys
import tempfile
from pathlib import Path

import click

from lambdipy_tpu.utils.logs import get_logger

log = get_logger("lambdipy.cli")


@click.group()
def main():
    """lambdipy-tpu: TPU-native serverless bundle framework."""


# -- recipe/registry admin --------------------------------------------------


@main.command("recipes")
@click.option("--recipe-dir", type=click.Path(), default=None,
              help="extra recipe dir layered over builtins")
def recipes_cmd(recipe_dir):
    """List available recipes."""
    from lambdipy_tpu.recipes import builtin_store

    store = builtin_store(recipe_dir)
    for name in store.names():
        r = store.get(name)
        kind = "model" if r.is_model else "package"
        click.echo(f"{name:20s} {r.version:10s} {r.device:10s} {kind:8s} {r.description}")


@main.command("show")
@click.argument("recipe_name")
@click.option("--recipe-dir", type=click.Path(), default=None)
def show_cmd(recipe_name, recipe_dir):
    """Show one recipe as JSON."""
    import dataclasses

    from lambdipy_tpu.recipes import builtin_store

    recipe = builtin_store(recipe_dir).get(recipe_name)
    click.echo(json.dumps(dataclasses.asdict(recipe), indent=1, default=str))


@main.command("artifacts")
@click.option("--registry", "registry_dir", type=click.Path(), default=None)
def artifacts_cmd(registry_dir):
    """List artifacts in the local registry."""
    from lambdipy_tpu.resolve.registry import ArtifactRegistry

    for info in ArtifactRegistry(registry_dir).list():
        click.echo(f"{info.artifact_id:45s} {info.size_bytes / 1e6:9.1f}MB  {info.device}")


# -- build / package --------------------------------------------------------


def _pyver() -> str:
    return f"{sys.version_info.major}.{sys.version_info.minor}"


def _registry_lookup(registry, recipe, pyver: str) -> str | None:
    """Artifact id under which this recipe is cached locally, or None.

    Checks the locally computed id, then the ``device=any`` id for the
    same recipe/version/python (a prebuilt asset published for ``any``
    satisfies a device-pinned recipe, but nothing looser does — a
    different python tag or concrete device must not be reused)."""
    import dataclasses

    exact = recipe.artifact_id(pyver)
    any_id = dataclasses.replace(recipe, device="any").artifact_id(pyver)
    for candidate in (exact, any_id):
        if registry.has(candidate):
            return candidate
    return None


def _warm_bundle(recipe, bundle_dir: Path) -> dict:
    """Run the warm step (runtime/warm.py) against ``bundle_dir`` in a
    subprocess — the build process itself stays off the device — and
    return the record the manifest keeps under ``warm``."""
    import subprocess

    from lambdipy_tpu.utils.platform import child_env, operator_pin

    # warm on the device the recipe targets: cpu/any recipes must not
    # touch (or wait on) the chip; tpu recipes take jax's default
    # platform unless the operator pinned this build
    pin = operator_pin()
    if not pin and not recipe.device.startswith("tpu"):
        pin = {"LAMBDIPY_PLATFORM": "cpu"}
    # bounded: a chip held by another process makes the child hang
    warm_timeout = float(os.environ.get("LAMBDIPY_WARM_TIMEOUT", "600"))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "lambdipy_tpu.runtime.warm", str(bundle_dir)],
            capture_output=True, text=True, env=child_env(pin),
            timeout=warm_timeout)
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": f"timeout after {warm_timeout:.0f}s"}
    if proc.returncode != 0:
        return {"ok": False, "error": f"rc={proc.returncode}: "
                                      f"{proc.stderr.strip()[-300:]}"}
    lines = proc.stdout.strip().splitlines()
    record = {"ok": True}
    try:
        parsed = json.loads(lines[-1] if lines else "")
        if isinstance(parsed, dict):
            record.update(parsed)
    except ValueError:
        pass
    return record


def _run_build(recipe, registry, *, out=None, no_smoke=False, no_payload=False,
               warm=True):
    """Build one recipe into a bundle and publish it to the local registry.
    Shared by ``build`` (user path) and ``publish`` (maintainer path).

    The bundle is warmed at the path it will be served from (``--out``, or
    its registry slot): the compile cache it ships is looked up by the
    boot under that same path. A failed or timed-out warm is recorded in
    the manifest either way; for a recipe that targets a TPU it also fails
    the build — such a bundle would pay every compile at boot, on the
    chip, and nothing downstream would say why."""
    from lambdipy_tpu.buildengine import build_recipe
    from lambdipy_tpu.bundle import assemble_bundle
    from lambdipy_tpu.bundle.format import update_manifest

    artifact_id = recipe.artifact_id(_pyver())
    workdir = Path(tempfile.mkdtemp(prefix=f"lambdipy-build-{recipe.name}-"))
    result = build_recipe(recipe, workdir, run_smoke=not no_smoke)
    bundle_dir = Path(out) if out else workdir / "bundle"
    with_payload = not no_payload and recipe.is_model
    manifest = assemble_bundle(result, bundle_dir, with_payload=with_payload)
    if out is None:
        bundle_dir = registry.publish(
            artifact_id, bundle_dir, recipe=recipe.name,
            version=recipe.version, device=recipe.device, manifest=manifest)
    warm_record = None
    if warm and with_payload:
        warm_record = _warm_bundle(recipe, bundle_dir)
        update_manifest(bundle_dir, warm=warm_record)
        if warm_record["ok"]:
            click.echo(f"warmed: {json.dumps(warm_record)}")
        else:
            click.echo(f"warning: warm failed: {warm_record['error']}",
                       err=True)
    if out is None:
        click.echo(f"built + published {artifact_id}")
    else:
        click.echo(f"built {artifact_id} -> {bundle_dir}")
    p = result.prune
    click.echo(f"size {p.bytes_after / 1e6:.1f}MB (saved {p.bytes_saved / 1e6:.1f}MB); "
               f"skipped optional: {result.skipped_optional or 'none'}")
    if warm_record is not None and not warm_record["ok"] \
            and recipe.device.startswith("tpu"):
        raise click.ClickException(
            f"warm step failed for {recipe.device} recipe {recipe.name!r}: "
            f"{warm_record['error']}")
    return artifact_id


@main.command("build")
@click.argument("recipe_name")
@click.option("--out", type=click.Path(), default=None,
              help="bundle output dir (default: temp + registry publish)")
@click.option("--registry", "registry_dir", type=click.Path(), default=None)
@click.option("--recipe-dir", type=click.Path(), default=None)
@click.option("--release-store", "release_store", type=click.Path(), default=None,
              help="prebuilt-release store to consult before building "
                   "(default: $LAMBDIPY_RELEASE_STORE)")
@click.option("--no-prebuilt", is_flag=True,
              help="skip the prebuilt-release lookup and always build locally")
@click.option("--no-smoke", is_flag=True, help="skip the hermetic import smoke")
@click.option("--no-payload", is_flag=True, help="skip params/handler materialization")
@click.option("--force", is_flag=True, help="rebuild even if the artifact is cached")
@click.option("--warm/--no-warm", default=True,
              help="pre-populate the bundle's XLA compile cache (model recipes)")
def build_cmd(recipe_name, out, registry_dir, recipe_dir, release_store,
              no_prebuilt, no_smoke, no_payload, force, warm):
    """Build a recipe into a bundle: local-registry cache hit, then prebuilt
    release fetch, then local build — the reference's hot path (SURVEY.md
    §4 A: release-index hit downloads, miss falls back to the build path)."""
    from lambdipy_tpu.recipes import builtin_store
    from lambdipy_tpu.resolve.registry import ArtifactRegistry
    from lambdipy_tpu.resolve.releases import ReleaseFetcher, default_store

    from lambdipy_tpu.resolve.releases import ReleaseError

    store = builtin_store(recipe_dir)
    recipe = store.get(recipe_name)
    registry = ArtifactRegistry(registry_dir)

    if not force and out is None:
        cached = _registry_lookup(registry, recipe, _pyver())
        if cached is not None:
            click.echo(f"cache hit: {cached} (use --force to rebuild)")
            return

    if not force and out is None and not no_prebuilt:
        releases = default_store(release_store)
        if releases is not None:
            asset = releases.find_asset(recipe=recipe.name, python=_pyver(),
                                        device=recipe.device,
                                        version=recipe.version)
            if asset is not None:
                try:
                    ReleaseFetcher(releases).fetch_into_registry(asset, registry)
                except ReleaseError as e:
                    click.echo(f"warning: prebuilt fetch failed ({e}); "
                               "building locally", err=True)
                else:
                    click.echo(f"fetched prebuilt {asset.name} "
                               f"(release {asset.tag}) -> {asset.artifact_id}")
                    return

    _run_build(recipe, registry, out=out, no_smoke=no_smoke,
               no_payload=no_payload, warm=warm)


# -- prebuilt releases (maintainer publish / user fetch) ---------------------


def _require_store(release_store):
    from lambdipy_tpu.resolve.releases import STORE_ENV, default_store

    store = default_store(release_store)
    if store is None:
        raise click.ClickException(
            f"no release store: pass --release-store or set {STORE_ENV}")
    return store


@main.command("publish")
@click.argument("recipe_names", nargs=-1)
@click.option("--all", "publish_all", is_flag=True,
              help="publish every builtin recipe")
@click.option("--release-store", "release_store", type=click.Path(), default=None)
@click.option("--tag", default=None,
              help="release tag (default: lambdipy-tpu version)")
@click.option("--registry", "registry_dir", type=click.Path(), default=None)
@click.option("--recipe-dir", type=click.Path(), default=None)
@click.option("--rebuild", is_flag=True, help="rebuild even if cached locally")
@click.option("--warm/--no-warm", default=True)
def publish_cmd(recipe_names, publish_all, release_store, tag, registry_dir,
                recipe_dir, rebuild, warm):
    """Maintainer path: build recipes and upload the bundles as prebuilt
    release assets (SURVEY.md §4 C: build each recipe x python version,
    create/append release, upload asset). Users then ``lambdipy fetch`` /
    ``lambdipy build`` without compiling anything."""
    import tempfile as _tempfile

    from lambdipy_tpu import __version__
    from lambdipy_tpu.recipes import builtin_store
    from lambdipy_tpu.resolve.registry import ArtifactRegistry
    from lambdipy_tpu.resolve.releases import ReleaseError, pack_bundle

    if not recipe_names and not publish_all:
        raise click.ClickException("pass recipe names or --all")
    store = builtin_store(recipe_dir)
    names = list(store.names()) if publish_all else list(recipe_names)
    releases = _require_store(release_store)
    registry = ArtifactRegistry(registry_dir)
    tag = tag or f"v{__version__}"
    failed: list[str] = []
    for name in names:
        recipe = store.get(name)
        if _pyver() not in recipe.python:
            click.echo(f"skip {name}: recipe pins python {recipe.python}")
            continue
        artifact_id = recipe.artifact_id(_pyver())
        if rebuild or not registry.has(artifact_id):
            try:
                _run_build(recipe, registry, warm=warm)
            except Exception as e:
                # one unbuildable recipe (e.g. numpy-src without
                # meson-python) must not abort the whole publish sweep
                click.echo(f"FAILED {name}: {e}", err=True)
                failed.append(name)
                continue
        bundle = registry.fetch(artifact_id)
        with _tempfile.TemporaryDirectory(prefix="lambdipy-publish-") as td:
            archive = pack_bundle(bundle, Path(td) / f"{artifact_id}.tar.gz")
            try:
                asset = releases.upload_asset(
                    tag, archive, artifact_id=artifact_id, recipe=recipe.name,
                    version=recipe.version, python=_pyver(), device=recipe.device)
            except ReleaseError as e:
                raise click.ClickException(str(e)) from e
        click.echo(f"published {asset.name} ({asset.size / 1e6:.1f}MB) "
                   f"-> release {tag}")
    if failed:
        raise click.ClickException(
            f"{len(failed)} recipe(s) failed to build: {', '.join(failed)}")


@main.command("fetch")
@click.argument("recipe_name")
@click.option("--release-store", "release_store", type=click.Path(), default=None)
@click.option("--registry", "registry_dir", type=click.Path(), default=None)
@click.option("--recipe-dir", type=click.Path(), default=None)
def fetch_cmd(recipe_name, release_store, registry_dir, recipe_dir):
    """User path: download a prebuilt bundle from the release store into the
    local registry (hash-verified, cached) — the reference's 'download
    matching release asset' branch without any local build."""
    from lambdipy_tpu.recipes import builtin_store
    from lambdipy_tpu.resolve.registry import ArtifactRegistry
    from lambdipy_tpu.resolve.releases import ReleaseError, ReleaseFetcher

    releases = _require_store(release_store)
    store = builtin_store(recipe_dir)
    device = version = None
    if recipe_name in store:
        recipe = store.get(recipe_name)
        device, version = recipe.device, recipe.version
    asset = releases.find_asset(recipe=recipe_name, python=_pyver(),
                                device=device, version=version)
    if asset is None:
        raise click.ClickException(
            f"no prebuilt asset for {recipe_name!r} (python {_pyver()}) in "
            f"{releases.root}")
    try:
        ReleaseFetcher(releases).fetch_into_registry(
            asset, ArtifactRegistry(registry_dir))
    except ReleaseError as e:
        raise click.ClickException(str(e)) from e
    click.echo(f"fetched {asset.name} (release {asset.tag}) -> {asset.artifact_id}")


@main.command("releases")
@click.option("--release-store", "release_store", type=click.Path(), default=None)
def releases_cmd(release_store):
    """List prebuilt assets in the release store."""
    releases = _require_store(release_store)
    for asset in releases.list_assets():
        click.echo(f"{asset.tag:12s} {asset.name:55s} {asset.size / 1e6:8.1f}MB "
                   f"py{asset.python} {asset.device}")


@main.command("package")
@click.argument("requirements", type=click.Path(exists=True))
@click.option("--out", type=click.Path(), required=True, help="output build/ tree")
@click.option("--recipe-dir", type=click.Path(), default=None)
def package_cmd(requirements, out, recipe_dir):
    """Assemble a deployable tree from a project requirements file: recipe-
    covered deps built via their recipes, plain deps vendored directly
    (SURVEY.md §4 B)."""
    from lambdipy_tpu.buildengine import build_recipe
    from lambdipy_tpu.buildengine.vendor import dependency_closure, vendor_distribution
    from lambdipy_tpu.recipes import builtin_store
    from lambdipy_tpu.resolve import resolve_project

    store = builtin_store(recipe_dir)
    res = resolve_project(Path(requirements), store)
    out_dir = Path(out)
    site = out_dir / "site"
    site.mkdir(parents=True, exist_ok=True)
    for req, recipe_name in res.recipe_covered:
        recipe = store.get(recipe_name)
        workdir = Path(tempfile.mkdtemp(prefix=f"lambdipy-pkg-{recipe.name}-"))
        result = build_recipe(recipe, workdir)
        from lambdipy_tpu.utils.fsutil import copy_tree

        copy_tree(result.site_dir, site)
        click.echo(f"recipe {recipe_name}: {req.pin}")
    vendored = set()
    for req in res.plain:
        for dep in dependency_closure([req.raw]):
            if dep not in vendored and not (site / dep.replace("-", "_")).exists():
                vendor_distribution(dep, site)
                vendored.add(dep)
        click.echo(f"plain dep: {req.pin}")
    click.echo(f"packaged -> {out_dir} (add your handler.py and deploy)")


# -- deploy / serve / invoke ------------------------------------------------


def _resolve_bundle(name_or_dir: str, registry_dir) -> Path:
    from lambdipy_tpu.recipes import builtin_store
    from lambdipy_tpu.resolve.registry import ArtifactRegistry

    path = Path(name_or_dir)
    if path.is_dir() and (path / "manifest.json").exists():
        return path
    registry = ArtifactRegistry(registry_dir)
    store = builtin_store()
    if name_or_dir in store:
        artifact_id = _registry_lookup(registry, store.get(name_or_dir), _pyver())
        if artifact_id is not None:
            return registry.fetch(artifact_id)
        raise click.ClickException(
            f"recipe {name_or_dir!r} has no built artifact; run: lambdipy build {name_or_dir}")
    if registry.has(name_or_dir):
        return registry.fetch(name_or_dir)
    # custom recipes (built with --recipe-dir) aren't in the builtin store;
    # resolve them by the recipe name recorded at publish time
    by_recipe = [a for a in registry.list() if a.recipe == name_or_dir]
    if by_recipe:
        latest = max(by_recipe, key=lambda a: a.created)
        return registry.fetch(latest.artifact_id)
    raise click.ClickException(f"{name_or_dir!r} is neither a bundle dir, recipe, nor artifact id")


@main.command("deploy")
@click.argument("bundle")
@click.option("--name", default=None, help="deployment name (default: recipe/artifact)")
@click.option("--port", type=int, default=0)
@click.option("--registry", "registry_dir", type=click.Path(), default=None)
@click.option("--timeout", type=float, default=300.0)
@click.option("--watchdog/--no-watchdog", default=True,
              help="run under the restart supervisor (crash -> respawn)")
def deploy_cmd(bundle, name, port, registry_dir, timeout, watchdog):
    """Deploy a built bundle to the local TPU runtime."""
    from lambdipy_tpu.runtime.deploy import LocalRuntime
    from lambdipy_tpu.utils.platform import operator_pin

    bundle_dir = _resolve_bundle(bundle, registry_dir)
    dep_name = name or bundle.split("/")[-1]
    dep = LocalRuntime().deploy(dep_name, bundle_dir, port=port,
                                ready_timeout=timeout, watchdog=watchdog,
                                env=operator_pin())
    click.echo(json.dumps({"name": dep.name, "url": dep.url,
                           "cold_start": dep.cold_start}))


@main.command("serve")
@click.argument("bundle")
@click.option("--port", type=int, default=8080)
@click.option("--registry", "registry_dir", type=click.Path(), default=None)
@click.option("--sched-policy", default=None,
              type=click.Choice(["fifo", "priority", "fair"]),
              help="dequeue policy between request classes "
                   "(default: bundle sched_policy, else fair)")
@click.option("--sched-concurrency", type=int, default=None,
              help="invokes running at once (default 8)")
@click.option("--sched-queue-cap", type=int, default=None,
              help="bounded queue depth; beyond it requests shed 503 "
                   "(default 64)")
@click.option("--sched-rate", type=float, default=None,
              help="per-tenant admission rate, requests/s (keyed by "
                   "x-api-key/x-tenant; 0 = unlimited)")
@click.option("--sched-burst", type=float, default=None,
              help="per-tenant token-bucket burst (default 2x rate)")
@click.option("--prefix-cache-mb", type=float, default=None,
              help="HBM budget (MB) for the automatic cross-request "
                   "prefix KV cache; 0 disables, explicit value also "
                   "opts kv_quant bundles in (default: bundle "
                   "prefix_cache_mb, else 512)")
@click.option("--prefix-block", type=int, default=None,
              help="token-block granularity of prefix reuse (rounded "
                   "to a pow-2 dividing the context window; default 32)")
@click.option("--session-pin-budget", type=float, default=None,
              help="MB of prefix-cache KV open multi-turn sessions may "
                   "PIN out of eviction's reach (x-session-id header / "
                   "session_id body field); beyond it new sessions shed "
                   "503 reason session_pins with Retry-After from the "
                   "lease-expiry horizon (default: half the prefix "
                   "cache budget; clamped to the cache budget)")
@click.option("--session-ttl", type=float, default=None,
              help="absolute session pin lease in seconds — a pinned "
                   "conversation lapses this long after it OPENED even "
                   "if turns keep renewing the idle lease (default "
                   "3600; idle lease defaults to 600, tunable per "
                   "bundle via session_idle_s)")
@click.option("--pipeline-depth", type=int, default=None,
              help="decode segments kept in flight on the device before "
                   "the host fetches the oldest (continuous engine): 1 "
                   "= synchronous dispatch-fetch-book loop, >= 2 "
                   "overlaps device compute with the fetch RTT + host "
                   "bookkeeping (default: bundle pipeline_depth, "
                   "else 2)")
@click.option("--engine-watchdog", type=float, default=None,
              help="seconds after which a hung device-side engine wait "
                   "(dispatch / segment fetch / group prefill) marks "
                   "the engine wedged, aborts its waiters and flips "
                   "/healthz to wedged (continuous engine; 0 disables "
                   "— size it ABOVE the worst-case first-use compile "
                   "wall; default: bundle engine_watchdog_s, else off)")
@click.option("--kv-paged/--no-kv-paged", default=None,
              help="paged KV memory for the continuous engine: one "
                   "refcounted page arena instead of a full decode "
                   "window per slot — admission charges actual tokens "
                   "(more concurrent rows for mixed-length traffic) and "
                   "prefix-cache hits share pages zero-copy. Outputs "
                   "stay bitwise the dense path's. (default: bundle "
                   "kv_paged, else off)")
@click.option("--kv-pages", type=int, default=None,
              help="page count of the paged KV arena (page width = the "
                   "prefix block); default sizes it to the same HBM the "
                   "dense engine would allocate: batch_max x window "
                   "pages + the reserved null page")
@click.option("--max-logical-ctx", type=int, default=None,
              help="long-context tier: serve prompts up to this many "
                   "LOGICAL tokens over the compiled window by sliding "
                   "a windowed block table — evicted KV pages spill to "
                   "a host offload arena and re-online on demand, so a "
                   "128k-token session runs over a 4k compiled window. "
                   "Needs --kv-paged; 0 disables (default: bundle "
                   "max_logical_ctx, else off). Gauges ride /metrics "
                   "under batching.page_pool.kv_offload")
@click.option("--kv-offload/--no-kv-offload", default=None,
              help="host offload tier for the prefix store's paged KV: "
                   "cache-pressure sweeps SPILL cold pages to host RAM "
                   "(kvwire frames) instead of dropping them, and a "
                   "later hit re-onlines the pages in one batched frame "
                   "decode instead of re-prefilling. Failed re-onlines "
                   "degrade to a counted prefill recompute — never a "
                   "wrong token (default: bundle kv_offload, else off)")
@click.option("--kv-offload-mb", type=float, default=None,
              help="host RAM budget of the KV offload arena in MiB "
                   "(default 256); a spill past it falls back to "
                   "dropping the page, counted as a spill refusal")
@click.option("--long-prefill/--no-long-prefill", default=None,
              help="opt the long-context tier's prefill into the "
                   "ring-attention path (parallel/ring.py) when the "
                   "mesh has an sp axis; without one the knob stands "
                   "down counted, never silently")
@click.option("--prefill-mode", type=click.Choice(["chunked", "sp"]),
              default=None,
              help="cold-prefill schedule: 'chunked' (default) runs the "
                   "serial chunk chain; 'sp' runs the whole prompt as "
                   "sequence-parallel rounds over the mesh's sp axis — "
                   "ONE sharded program per round, ~1/sp the TTFT "
                   "critical path. Without an sp mesh axis the knob "
                   "stands down counted, never silently. Live-retunable "
                   "via /v1/debug/knobs; counters ride /metrics under "
                   "batching.prefill")
@click.option("--spec-k", type=int, default=None,
              help="speculative decoding inside the continuous engine: "
                   "each segment drafts up to K-1 tokens per row by "
                   "prompt lookup and verifies them in ONE multi-token "
                   "dispatch, emitting 1..K tokens per weight read. "
                   "Outputs stay bitwise the plain engine's (greedy AND "
                   "seeded-sampled); acceptance counters ride "
                   "/metrics under batching.spec. 0/1 disables "
                   "(default: bundle spec_k, else off)")
@click.option("--draft-mode", type=click.Choice(
                  ["lookup", "model", "aux", "off"]), default=None,
              help="draft provider for --spec-k rows: 'lookup' = prompt "
                   "n-gram drafting (default), 'model' = self-drafting "
                   "shallow-exit head with per-row adaptive k and "
                   "model->lookup->off fallback (the non-repetitive-"
                   "workload tier), 'off' = verify path armed but no "
                   "drafting. Per-provider acceptance + k histogram "
                   "ride /metrics under batching.spec.draft")
@click.option("--draft-exit", type=int, default=None,
              help="layers the shallow-exit draft head runs before its "
                   "tied lm_head (draft cost ~ exit/layers of a full "
                   "forward per proposed token; default 1, clamped to "
                   "the model depth)")
@click.option("--mesh", "mesh_spec", type=str, default=None,
              help="tensor-parallel sharded serving over a device mesh, "
                   "e.g. 'tp=2' (Megatron layout: attention heads + MLP "
                   "hidden sharded over tp, KV cache over kv_heads, "
                   "per-device HBM ~1/tp). Accepts 'tp=2', bare '2', "
                   "'2x2' (dp x tp), or 'off'. Outputs stay bitwise the "
                   "single-device path's; layout + per-device bytes ride "
                   "/metrics under batching.mesh. CPU testing: "
                   "XLA_FLAGS=--xla_force_host_platform_device_count=2 "
                   "(default: bundle mesh extra, else single-device)")
def serve_cmd(bundle, port, registry_dir, sched_policy, sched_concurrency,
              sched_queue_cap, sched_rate, sched_burst, prefix_cache_mb,
              prefix_block, session_pin_budget, session_ttl,
              pipeline_depth, engine_watchdog, kv_paged,
              kv_pages, max_logical_ctx, kv_offload, kv_offload_mb,
              long_prefill, prefill_mode, spec_k, draft_mode, draft_exit,
              mesh_spec):
    """Serve a bundle in the foreground."""
    from lambdipy_tpu.runtime.server import BundleServer
    from lambdipy_tpu.utils.platform import apply_platform_override

    apply_platform_override()

    # the generate handler builds its prefix store INSIDE load_bundle,
    # before this process's server object exists — the CLI choice
    # reaches it through the environment, like LAMBDIPY_SCHED_POLICY
    if prefix_cache_mb is not None:
        os.environ["LAMBDIPY_PREFIX_CACHE_MB"] = str(prefix_cache_mb)
    if prefix_block is not None:
        os.environ["LAMBDIPY_PREFIX_BLOCK"] = str(prefix_block)
    if session_pin_budget is not None:
        os.environ["LAMBDIPY_SESSION_PIN_BUDGET_MB"] = \
            str(session_pin_budget)
    if session_ttl is not None:
        os.environ["LAMBDIPY_SESSION_TTL_S"] = str(session_ttl)
    if pipeline_depth is not None:
        os.environ["LAMBDIPY_PIPELINE_DEPTH"] = str(pipeline_depth)
    if engine_watchdog is not None:
        os.environ["LAMBDIPY_ENGINE_WATCHDOG_S"] = str(engine_watchdog)
    if kv_paged is not None:
        os.environ["LAMBDIPY_KV_PAGED"] = "1" if kv_paged else "0"
    if kv_pages is not None:
        os.environ["LAMBDIPY_KV_PAGES"] = str(kv_pages)
    if max_logical_ctx is not None:
        os.environ["LAMBDIPY_MAX_LOGICAL_CTX"] = str(max_logical_ctx)
    if kv_offload is not None:
        os.environ["LAMBDIPY_KV_OFFLOAD"] = "1" if kv_offload else "0"
    if kv_offload_mb is not None:
        os.environ["LAMBDIPY_KV_OFFLOAD_MB"] = str(kv_offload_mb)
    if long_prefill is not None:
        os.environ["LAMBDIPY_LONG_PREFILL"] = "1" if long_prefill else "0"
    if prefill_mode is not None:
        os.environ["LAMBDIPY_PREFILL_MODE"] = prefill_mode
    if spec_k is not None:
        os.environ["LAMBDIPY_SPEC_K"] = str(spec_k)
    if draft_mode is not None:
        os.environ["LAMBDIPY_DRAFT_MODE"] = draft_mode
    if draft_exit is not None:
        os.environ["LAMBDIPY_DRAFT_EXIT"] = str(draft_exit)
    if mesh_spec is not None:
        # validate at the CLI so a typo'd mesh fails HERE with a clear
        # message instead of inside the bundle boot
        from lambdipy_tpu.parallel.mesh import parse_mesh_spec

        parse_mesh_spec(mesh_spec)
        os.environ["LAMBDIPY_MESH"] = mesh_spec
    # BundleServer resolves the effective policy (bundle extra <
    # LAMBDIPY_SCHED_POLICY env < these flags) and bridges it to the
    # handler's batch formation itself — no env plumbing needed here
    server = BundleServer(
        _resolve_bundle(bundle, registry_dir), port=port,
        sched={"policy": sched_policy,
               "max_concurrency": sched_concurrency,
               "queue_cap": sched_queue_cap,
               "rate": sched_rate, "burst": sched_burst})
    click.echo(json.dumps({"ready": True, "port": server.port,
                           "sched_policy": server.sched.policy.name,
                           "cold_start": server.boot.stages}))
    server.serve_forever()


@main.command("fleet")
@click.argument("bundle")
@click.option("--replicas", "-n", type=int, default=2, show_default=True,
              help="supervised bundle-server replicas to run (decode-"
                   "class when --prefill-replicas > 0, mixed otherwise)")
@click.option("--prefill-replicas", type=int, default=0, show_default=True,
              help="additional PREFILL-class replicas (deployed as "
                   "NAME-p0..M-1): the router splits cold requests — "
                   "prefill runs on a prefill replica (/v1/kv/export), "
                   "the KV blocks ship to the affinity-chosen decode "
                   "replica, and decode packs its far deeper batch "
                   "isolated from prefill bursts; 0 = no phase split")
@click.option("--port", type=int, default=8080, show_default=True,
              help="router port (replicas pick their own free ports)")
@click.option("--name", default=None,
              help="fleet name; replicas deploy as NAME-r0..N-1")
@click.option("--registry", "registry_dir", type=click.Path(), default=None)
@click.option("--affinity/--no-affinity", default=True, show_default=True,
              help="route by consistent hash of the prompt's leading "
                   "token blocks so shared prefixes reuse one replica's "
                   "radix KV cache")
@click.option("--block", type=int, default=32, show_default=True,
              help="affinity block width in tokens — keep equal to the "
                   "bundle's prefix_block")
@click.option("--probe-interval", type=float, default=1.0, show_default=True,
              help="seconds between /healthz probes per replica")
@click.option("--fail-threshold", type=int, default=1, show_default=True,
              help="consecutive probe/connect failures before ejection")
@click.option("--readmit-passes", type=int, default=2, show_default=True,
              help="consecutive probe passes before an ejected replica "
                   "takes traffic again")
@click.option("--retries", type=int, default=2, show_default=True,
              help="max re-sends of a request onto different replicas")
@click.option("--saturation", type=int, default=8, show_default=True,
              help="outstanding requests at which the affinity target is "
                   "bypassed for the least-loaded replica")
@click.option("--hedge", default="off", show_default=True,
              help="duplicate slow non-streamed requests on a second "
                   "replica: 'off', 'p95' (the router's observed P95), "
                   "or a fixed threshold in ms")
@click.option("--timeout", type=float, default=300.0, show_default=True,
              help="per-replica deploy ready timeout (seconds)")
@click.option("--engine-watchdog", type=float, default=None,
              help="per-replica engine watchdog in seconds (see "
                   "`lambdipy serve --engine-watchdog`): a replica "
                   "whose device wait hangs flips its /healthz to "
                   "wedged and the pool ejects it at probe speed")
@click.option("--attach", "attach_urls", multiple=True,
              metavar="NAME=URL[:class]",
              help="attach an externally managed replica (remote host "
                   "or existing deployment): probed/ejected/readmitted/"
                   "cache-warmed like spawned ones, but never restarted "
                   "or drained by this pool; repeatable, and with "
                   "--replicas 0 the fleet is attach-only. An optional "
                   ":class suffix (prefill|decode|mixed, default mixed) "
                   "sets the replica's phase-split class")
@click.option("--spill-cap", type=int, default=64, show_default=True,
              help="router spill-queue capacity: when the WHOLE fleet "
                   "sheds or nothing is routable, non-streamed requests "
                   "park here and drain as replicas recover instead of "
                   "relaying the 429/503 (0 disables)")
@click.option("--spill-max-wait", type=float, default=30.0,
              show_default=True,
              help="max seconds a spilled request waits before shedding "
                   "with the queue's own Retry-After estimate")
@click.option("--breaker-fails", type=int, default=5, show_default=True,
              help="consecutive forward failures that open a replica's "
                   "circuit breaker; after --breaker-open-s one "
                   "half-open probe decides readmission (0 disables)")
@click.option("--breaker-open-s", type=float, default=2.0,
              show_default=True,
              help="seconds a breaker stays open before its half-open "
                   "probe (doubles on repeated failures, capped)")
@click.option("--retry-budget", type=float, default=0.2, show_default=True,
              help="fleet-wide retry-to-primary ratio over a sliding "
                   "window: when spent, failures relay instead of "
                   "re-sending — no retry storms into a degraded fleet "
                   "(0 disables)")
@click.option("--fault-spec", default=None,
              help="router-side network fault injection "
                   "(runtime/faults.py grammar over the route_connect/"
                   "route_body/route_latency/probe sites), default "
                   "$LAMBDIPY_FLEET_FAULT")
@click.option("--session-pin-budget", type=float, default=None,
              help="per-replica MB of prefix-cache KV open multi-turn "
                   "sessions may pin (see `lambdipy serve "
                   "--session-pin-budget`); the router routes sessions "
                   "STICKY to the replica holding their pinned KV and "
                   "re-ships it on failover")
@click.option("--session-ttl", type=float, default=None,
              help="per-replica absolute session pin lease in seconds "
                   "(see `lambdipy serve --session-ttl`)")
@click.option("--ship-window", type=int, default=4, show_default=True,
              help="pipelined KV shipping: max chunk frames in flight "
                   "between the export and import legs of a phase-split "
                   "ship (each flushed as its prefill chunk completes, "
                   "so cross-host transfer hides under the remaining "
                   "prefill); 0 = the blocking single-frame ship")
@click.option("--autoscale/--no-autoscale", default=False, show_default=True,
              help="close the control loop: a FleetController scrapes "
                   "the fleet's own /metrics and promotes/demotes "
                   "replica classes, spawns/retires replicas, and "
                   "retunes pipeline_depth/spec_k/ship-window from the "
                   "published signals (hysteresis + cooldown built in; "
                   "decisions trace under fleet.controller in /metrics)")
@click.option("--autoscale-dry-run", is_flag=True, default=False,
              help="run the control loop but only LOG decisions as "
                   "intents — no lifecycle action or knob write fires; "
                   "the recommended first step in a new deployment")
@click.option("--slo-p99-ms", type=float, default=250.0, show_default=True,
              help="autoscale target: fleet-level interactive queue-wait "
                   "P99 the controller steers toward")
@click.option("--autoscale-interval", type=float, default=5.0,
              show_default=True,
              help="seconds between controller ticks (scrape + decide)")
def fleet_cmd(bundle, replicas, prefill_replicas, port, name, registry_dir,
              affinity, block, probe_interval, fail_threshold,
              readmit_passes, retries, saturation, hedge, timeout,
              engine_watchdog, attach_urls, spill_cap, spill_max_wait,
              breaker_fails, breaker_open_s, retry_budget, fault_spec,
              session_pin_budget, session_ttl, ship_window, autoscale,
              autoscale_dry_run, slo_p99_ms, autoscale_interval):
    """Serve a bundle from N supervised replicas behind one router.

    Spawns REPLICAS watchdogged deployments of BUNDLE, health-probes
    them (eject on failure, re-admit on recovery), and serves
    /v1/completions + /invoke on PORT with prefix-affinity routing,
    failover retries, and fleet-wide /metrics. With --prefill-replicas
    (or an --attach :prefill class) the fleet serves DISAGGREGATED:
    cold prefills run on the prefill class, their KV blocks ship to the
    affinity-chosen decode replica, and any ship failure falls back to
    mixed-mode local prefill."""
    import signal as _signal
    import threading as _threading

    from lambdipy_tpu.fleet import (
        DECODE,
        MIXED,
        PREFILL,
        FleetError,
        FleetRouter,
        ReplicaPool,
        parse_attach_spec,
    )
    from lambdipy_tpu.runtime.deploy import LocalRuntime
    from lambdipy_tpu.runtime.faults import FaultPlan

    if replicas < 1 and not attach_urls:
        raise click.ClickException(
            "--replicas must be >= 1 (or pass --attach for an "
            "attach-only fleet)")
    if prefill_replicas < 0:
        raise click.ClickException("--prefill-replicas must be >= 0")
    attached: list[tuple[str, str, str]] = []
    for spec in attach_urls:
        try:
            attached.append(parse_attach_spec(spec))
        except FleetError as e:
            raise click.ClickException(str(e))
    try:
        fleet_faults = (FaultPlan.from_spec(fault_spec)
                        if fault_spec is not None
                        else FaultPlan.from_env(var="LAMBDIPY_FLEET_FAULT"))
    except ValueError as e:
        raise click.ClickException(str(e))
    hedge_ms: float | str = 0
    if hedge not in ("off", "0", ""):
        if hedge == "p95":
            hedge_ms = "p95"
        else:
            try:
                hedge_ms = float(hedge)
            except ValueError:
                raise click.ClickException(
                    f"--hedge must be 'off', 'p95' or a threshold in "
                    f"ms, got {hedge!r}")
    # an attach-only fleet (--replicas 0) never deploys the bundle, so
    # don't require it to resolve locally
    bundle_dir = (_resolve_bundle(bundle, registry_dir)
                  if replicas >= 1 or prefill_replicas >= 1 else None)
    fleet_name = name or bundle.split("/")[-1]
    pool = ReplicaPool(probe_interval=probe_interval,
                       fail_threshold=fail_threshold,
                       readmit_passes=readmit_passes,
                       faults=fleet_faults)
    from lambdipy_tpu.utils.platform import operator_pin

    replica_env = operator_pin()
    if engine_watchdog is not None:
        replica_env["LAMBDIPY_ENGINE_WATCHDOG_S"] = str(engine_watchdog)
    if session_pin_budget is not None:
        replica_env["LAMBDIPY_SESSION_PIN_BUDGET_MB"] = \
            str(session_pin_budget)
    if session_ttl is not None:
        replica_env["LAMBDIPY_SESSION_TTL_S"] = str(session_ttl)
    replica_env = replica_env or None
    spawned = []
    try:
        runtime = LocalRuntime()
        if replicas >= 1:
            # with a prefill class configured, the serve replicas are
            # DECODE-class (the phase split is the point); otherwise
            # they stay mixed and the fleet behaves exactly as before
            spawned = pool.spawn_fleet(
                bundle_dir, replicas, base_name=fleet_name,
                runtime=runtime, env=replica_env, ready_timeout=timeout,
                role=(DECODE if prefill_replicas else MIXED))
        for i in range(prefill_replicas):
            spawned.append(pool.spawn(
                f"{fleet_name}-p{i}", bundle_dir, runtime=runtime,
                env=replica_env, ready_timeout=timeout, role=PREFILL))
        for aname, aurl, arole in attached:
            pool.probe_one(pool.attach(aname, aurl, role=arole))
        pool.start()
        # inside the same guard: a router bind failure (port in use)
        # must not leak N supervised replica processes
        router = FleetRouter(pool, port=port, affinity_on=affinity,
                             block=block, max_retries=retries,
                             saturation=saturation, hedge_ms=hedge_ms,
                             spill_cap=spill_cap,
                             spill_max_wait_s=spill_max_wait,
                             breaker_fails=breaker_fails,
                             breaker_open_s=breaker_open_s,
                             retry_budget=retry_budget,
                             ship_window=ship_window,
                             faults=fleet_faults)
        controller = None
        if autoscale or autoscale_dry_run:
            from lambdipy_tpu.fleet import FleetController, PolicyConfig

            spawner = None
            if bundle_dir is not None:
                counter = iter(range(len(spawned), 10_000))

                def spawner(role):
                    nm = f"{fleet_name}-a{next(counter)}"
                    pool.spawn(nm, bundle_dir, runtime=runtime,
                               env=replica_env, ready_timeout=timeout,
                               role=role)
                    return nm

            controller = FleetController(
                router,
                config=PolicyConfig(slo_p99_ms=slo_p99_ms),
                interval_s=autoscale_interval,
                dry_run=autoscale_dry_run,
                spawner=spawner).start()
    except BaseException:
        # a half-spawned fleet must not leak processes — including on
        # Ctrl-C, which lands mid-boot more often than anywhere else
        # (each replica's cold start can take minutes)
        pool.stop_all()
        raise
    click.echo(json.dumps({
        "ready": True, "port": router.port, "replicas": len(spawned),
        "prefill_replicas": prefill_replicas,
        "attached": [a for a, _, _ in attached],
        "classes": {r.name: r.role
                    for r in pool.replicas.values()},
        "affinity": affinity, "block": block,
        "spill_cap": spill_cap, "breaker_fails": breaker_fails,
        "retry_budget": retry_budget,
        "autoscale": bool(autoscale or autoscale_dry_run),
        "autoscale_dry_run": bool(autoscale_dry_run),
        "slo_p99_ms": slo_p99_ms,
        "urls": {r.name: r.url for r in spawned},
    }))

    def _term(signum, frame):
        _threading.Thread(target=router.stop, daemon=True).start()

    _signal.signal(_signal.SIGTERM, _term)
    try:
        router.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if controller is not None:
            controller.close()
        pool.stop_all()


@main.command("invoke")
@click.argument("name")
@click.option("--data", default="{}", help="JSON request body")
@click.option("--stream", is_flag=True,
              help="stream the response (generate handlers): one JSON "
                   "line per decode segment as tokens are emitted")
def invoke_cmd(name, data, stream):
    """Invoke a deployed function."""
    from lambdipy_tpu.runtime.deploy import DeployError, LocalRuntime

    try:
        request = json.loads(data)
    except json.JSONDecodeError as e:
        raise click.ClickException(f"--data is not valid JSON: {e}") from e
    try:
        if stream:
            for chunk in LocalRuntime().invoke_stream(name, request):
                click.echo(json.dumps(chunk))
        else:
            click.echo(json.dumps(LocalRuntime().invoke(name, request)))
    except DeployError as e:
        raise click.ClickException(str(e)) from e


@main.command("deployments")
def deployments_cmd():
    """List deployments."""
    from lambdipy_tpu.runtime.deploy import LocalRuntime

    for dep in LocalRuntime().list():
        click.echo(f"{dep.name:25s} pid={dep.pid:<8d} {dep.url}")


@main.command("doctor")
@click.option("--registry", "registry_dir", type=click.Path(), default=None)
@click.option("--state", "state_path", type=click.Path(), default=None,
              help="deployments state file (default: ~/.lambdipy-tpu)")
@click.option("--probe-timeout", default=90.0, show_default=True,
              help="seconds before the device probe is declared wedged")
def doctor_cmd(registry_dir, state_path, probe_timeout):
    """Environment diagnostics: stack versions, device reachability (the
    probe is a subprocess with a timeout, never an in-process
    jax.devices(): a chip held by another process makes the call hang, and
    this process must not take the chip itself), registry and deployment
    health. Prints one JSON object; exit 1 if the device probe fails while
    the shell is configured for a device platform."""
    import importlib.metadata as md
    import os
    import subprocess

    from lambdipy_tpu.resolve.registry import ArtifactRegistry
    from lambdipy_tpu.runtime.deploy import LocalRuntime

    report: dict = {"python": sys.version.split()[0]}
    report["packages"] = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "optax", "orbax-checkpoint"):
        try:
            report["packages"][pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            report["packages"][pkg] = None

    from lambdipy_tpu.utils.platform import child_env, operator_pin

    probe_env = child_env(operator_pin())
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             # the one place LAMBDIPY_PLATFORM is honored is the shared
             # helper — the probe must diagnose the same environment the
             # real entry points run in. LAMBDIPY_DOCTOR_WEDGE is fault
             # injection: tests prove the timeout->diagnosis path
             # without a chip that really hangs
             "import os, time\n"
             "if os.environ.get('LAMBDIPY_DOCTOR_WEDGE'): time.sleep(3600)\n"
             "from lambdipy_tpu.utils.platform import apply_platform_override\n"
             "apply_platform_override()\n"
             "import jax\n"
             "d = jax.devices()\n"
             "print('DOCTOR', d[0].platform, len(d))"],
            capture_output=True, text=True, env=probe_env,
            timeout=probe_timeout)
        # parse only our marker line: plugins may write banners to the
        # child's stdout
        marker = [ln for ln in proc.stdout.splitlines()
                  if ln.startswith("DOCTOR ")]
        if proc.returncode == 0 and marker:
            _, platform, n = marker[-1].split()
            report["device"] = {"ok": True, "platform": platform,
                                "n_devices": int(n)}
        else:
            report["device"] = {"ok": False,
                                "error": proc.stderr.strip()[-300:]}
    except subprocess.TimeoutExpired:
        report["device"] = {
            "ok": False,
            "error": f"wedge: device enumeration hung for {probe_timeout:.0f}s "
                     "(another process holding the device?)"}

    try:
        arts = ArtifactRegistry(registry_dir).list()
        report["registry"] = {"artifacts": len(arts),
                              "bytes": sum(a.size_bytes for a in arts)}
    except Exception as e:
        report["registry"] = {"error": str(e)}
    deployments = []
    try:
        rt = LocalRuntime(Path(state_path) if state_path else None)
        for dep in rt.list():
            entry = {"name": dep.name, "url": dep.url}
            try:
                entry["healthy"] = bool(rt.health(dep.name).get("ok"))
            except Exception as e:
                entry["healthy"] = False
                entry["error"] = str(e)[:120]
            deployments.append(entry)
    except Exception as e:
        deployments = [{"error": str(e)[:120]}]
    report["deployments"] = deployments

    click.echo(json.dumps(report, indent=1))
    effective = (os.environ.get("LAMBDIPY_PLATFORM")
                 or os.environ.get("JAX_PLATFORMS", ""))
    if not report["device"]["ok"] and effective not in ("", "cpu"):
        raise SystemExit(1)


@main.command("train")
@click.option("--model", "model_name", default="llama-tiny",
              help="registry model (llama-tiny / llama3-8b / llama-moe-tiny ...)")
@click.option("--data", "data_path", type=click.Path(exists=True), required=True,
              help="token file (.npy or raw int32 binary)")
@click.option("--steps", type=int, default=100)
@click.option("--batch", "global_batch", type=int, default=8)
@click.option("--seq-len", type=int, default=128)
@click.option("--lr", type=float, default=1e-3)
@click.option("--ckpt-dir", type=click.Path(), default=None,
              help="checkpoint dir; re-running resumes from the latest step")
@click.option("--ckpt-every", type=int, default=50)
@click.option("--mesh", "mesh_spec", default=None,
              help='mesh axes, e.g. "dp=2,tp=2" (default: all devices on dp)')
@click.option("--seed", type=int, default=0)
def train_cmd(model_name, data_path, steps, global_batch, seq_len, lr,
              ckpt_dir, ckpt_every, mesh_spec, seed):
    """Train a registry model on a token file (resumable SPMD loop)."""
    import jax

    from lambdipy_tpu.data import ShardedLoader, TokenSource
    from lambdipy_tpu.models import registry as model_registry
    from lambdipy_tpu.parallel.distributed import initialize_from_env
    from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
    from lambdipy_tpu.train.loop import Trainer, TrainerConfig
    from lambdipy_tpu.utils.platform import apply_platform_override

    apply_platform_override()
    initialize_from_env()
    adapter = model_registry.get(model_name).build()
    params = adapter.init_params(seed=seed)
    if mesh_spec:
        shape = {}
        for part in mesh_spec.split(","):
            axis, eq, size = part.partition("=")
            try:
                if not eq:
                    raise ValueError("missing '='")
                shape[axis.strip()] = int(size)
            except ValueError as e:
                raise click.ClickException(
                    f"bad --mesh entry {part!r} (want axis=size, e.g. "
                    f"dp=2,tp=4): {e}") from e
        if any(v == -1 for v in shape.values()):
            devices = jax.devices()  # -1 fills: make_mesh needs them all
        else:
            needed = 1
            for v in shape.values():
                needed *= v
            devices = jax.devices()[:needed]
        mesh = make_mesh(shape, devices=devices)
    else:
        mesh = make_mesh({"dp": len(jax.devices())})
    loader = ShardedLoader(TokenSource(data_path, seq_len), global_batch,
                           seed=seed)
    cfg = TrainerConfig(total_steps=steps, learning_rate=lr,
                        ckpt_every=ckpt_every)
    with use_mesh(mesh):
        with Trainer(adapter.forward, params, mesh, adapter.tp_rules,
                     loader, cfg, ckpt_dir=ckpt_dir,
                     model_apply_aux=adapter.forward_with_aux) as trainer:
            report = trainer.run()
    last = report.history[-1] if report.history else {}
    click.echo(json.dumps({
        "model": model_name, "final_step": report.final_step,
        "steps_run": report.steps_run, "resumed_from": report.resumed_from,
        "mesh": dict(mesh.shape), "final_metrics": last,
    }))


@main.command("bench")
@click.argument("name")
@click.option("--data", default='{"random": true}', help="JSON request body")
@click.option("-n", "iters", type=int, default=50, help="measured invokes")
@click.option("--warmup", type=int, default=5)
def bench_cmd(name, data, iters, warmup):
    """Measure invoke latency percentiles against a deployment."""
    import statistics
    import time as _time

    from lambdipy_tpu.runtime.deploy import DeployError, LocalRuntime

    try:
        request = json.loads(data)
    except json.JSONDecodeError as e:
        raise click.ClickException(f"--data is not valid JSON: {e}") from e
    rt = LocalRuntime()
    try:
        for _ in range(warmup):
            rt.invoke(name, request)
        times = []
        for _ in range(iters):
            t0 = _time.monotonic()
            out = rt.invoke(name, request)
            times.append((_time.monotonic() - t0) * 1000.0)
            if not out.get("ok", True):
                raise click.ClickException(f"invoke failed: {out}")
    except DeployError as e:
        raise click.ClickException(str(e)) from e
    times.sort()

    def pct(q):  # nearest-rank percentile: ceil(q*n) - 1, 0-based
        return times[max(0, math.ceil(q * iters) - 1)]

    click.echo(json.dumps({
        "name": name, "n": iters,
        "p50_ms": round(statistics.median(times), 3),
        "p90_ms": round(pct(0.90), 3),
        "p99_ms": round(pct(0.99), 3),
        "mean_ms": round(statistics.fmean(times), 3),
    }))


@main.command("stop")
@click.argument("name")
def stop_cmd(name):
    """Stop a deployment."""
    from lambdipy_tpu.runtime.deploy import DeployError, LocalRuntime

    try:
        LocalRuntime().stop(name)
    except DeployError as e:
        raise click.ClickException(str(e)) from e
    click.echo(f"stopped {name}")


if __name__ == "__main__":
    main()
