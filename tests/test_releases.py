"""Release store / prebuilt-fetch path tests (SURVEY.md §3.1 #4/#8/#9).

Covers the maintainer publish -> user fetch loop that defines the
reference's UX: deterministic packing, hardened extraction, the release
index (find/list, token-protected uploads), hash-verified caching, and the
CLI wiring (publish / fetch / releases / build --release-store).
"""

import json
import sys
import tarfile

import pytest
from click.testing import CliRunner

from lambdipy_tpu.cli import main

# the CLI resolves prebuilt assets against the RUNNING interpreter's
# version — tests exercising that path must not hardcode one
PYVER = f"{sys.version_info.major}.{sys.version_info.minor}"
PYTAG = "py" + PYVER.replace(".", "")
from lambdipy_tpu.resolve.registry import ArtifactRegistry
from lambdipy_tpu.resolve.releases import (
    ReleaseError,
    ReleaseFetcher,
    ReleaseStore,
    pack_bundle,
    unpack_archive,
)


@pytest.fixture()
def bundle_dir(tmp_path):
    d = tmp_path / "bundle"
    (d / "site" / "pkg").mkdir(parents=True)
    (d / "site" / "pkg" / "__init__.py").write_text("VALUE = 42\n")
    (d / "handler.py").write_text("def handler(req): return req\n")
    (d / "manifest.json").write_text(json.dumps({"artifact_id": "demo-1"}))
    return d


def test_pack_is_deterministic(bundle_dir, tmp_path):
    a = pack_bundle(bundle_dir, tmp_path / "a.tar.gz")
    b = pack_bundle(bundle_dir, tmp_path / "b.tar.gz")
    assert a.read_bytes() == b.read_bytes()


def test_pack_unpack_roundtrip(bundle_dir, tmp_path):
    archive = pack_bundle(bundle_dir, tmp_path / "x.tar.gz")
    out = unpack_archive(archive, tmp_path / "out")
    assert (out / "site" / "pkg" / "__init__.py").read_text() == "VALUE = 42\n"
    assert (out / "handler.py").exists() and (out / "manifest.json").exists()


def test_unpack_rejects_path_escape(tmp_path):
    evil = tmp_path / "evil.tar.gz"
    with tarfile.open(evil, "w:gz") as tar:
        info = tarfile.TarInfo("../escape.txt")
        info.size = 0
        tar.addfile(info)
    with pytest.raises(ReleaseError, match="unsafe archive member"):
        unpack_archive(evil, tmp_path / "out")


def test_unpack_rejects_symlink_escape(tmp_path):
    evil = tmp_path / "evil.tar.gz"
    with tarfile.open(evil, "w:gz") as tar:
        info = tarfile.TarInfo("link")
        info.type = tarfile.SYMTYPE
        info.linkname = "../../outside"
        tar.addfile(info)
    with pytest.raises(ReleaseError, match="unsafe link"):
        unpack_archive(evil, tmp_path / "out")


def test_pack_preserves_dir_symlinks_and_empty_dirs(bundle_dir, tmp_path):
    (bundle_dir / "pkg-link").symlink_to("site/pkg", target_is_directory=True)
    (bundle_dir / "empty").mkdir()
    archive = pack_bundle(bundle_dir, tmp_path / "x.tar.gz")
    out = unpack_archive(archive, tmp_path / "out")
    assert (out / "pkg-link").is_symlink()
    assert (out / "pkg-link" / "__init__.py").exists()
    assert (out / "empty").is_dir()


def test_asset_rejects_unsafe_index_fields():
    from lambdipy_tpu.resolve.releases import Asset

    with pytest.raises(ReleaseError, match="unsafe asset"):
        Asset(name="x.tar.gz", tag="v1", size=1, hash="sha256:0",
              artifact_id="../../escape", recipe="demo", version="0.1",
              python="3.12", device="any", uploaded=0.0)


@pytest.fixture()
def store_with_asset(bundle_dir, tmp_path):
    store = ReleaseStore.create(tmp_path / "store")
    archive = pack_bundle(bundle_dir, tmp_path / "demo.tar.gz")
    asset = store.upload_asset(
        "v1", archive, artifact_id="demo-0.1-py312-any", recipe="demo",
        version="0.1", python="3.12", device="any")
    return store, asset


def test_store_index_and_find(store_with_asset):
    store, asset = store_with_asset
    assert store.list_releases() == ["v1"]
    assert [a.name for a in store.list_assets()] == [asset.name]
    found = store.find_asset(recipe="demo", python="3.12", device="cpu")
    assert found is not None and found.hash == asset.hash  # "any" matches cpu
    assert store.find_asset(recipe="demo", python="3.11") is None
    assert store.find_asset(recipe="demo", python="3.12", version="9.9") is None


def test_find_prefers_newest(store_with_asset, bundle_dir, tmp_path):
    store, _ = store_with_asset
    (bundle_dir / "extra.txt").write_text("v2 content\n")
    archive = pack_bundle(bundle_dir, tmp_path / "demo2.tar.gz")
    newer = store.upload_asset(
        "v2", archive, artifact_id="demo-0.2-py312-any", recipe="demo",
        version="0.2", python="3.12", device="any")
    found = store.find_asset(recipe="demo", python="3.12")
    assert found.artifact_id == newer.artifact_id


def test_protected_store_requires_token(bundle_dir, tmp_path, monkeypatch):
    monkeypatch.delenv("LAMBDIPY_RELEASE_TOKEN", raising=False)
    store = ReleaseStore.create(tmp_path / "store", protected=True)
    archive = pack_bundle(bundle_dir, tmp_path / "demo.tar.gz")
    with pytest.raises(ReleaseError, match="protected"):
        store.upload_asset("v1", archive, artifact_id="a", recipe="demo",
                           version="0.1", python="3.12", device="any")
    # token via env unlocks uploads; reads never need one
    monkeypatch.setenv("LAMBDIPY_RELEASE_TOKEN", "tok")
    authed = ReleaseStore(store.root)
    authed.upload_asset("v1", archive, artifact_id="a", recipe="demo",
                        version="0.1", python="3.12", device="any")
    assert ReleaseStore(store.root, token=None).list_assets()


def test_fetch_verifies_and_caches(store_with_asset, tmp_path):
    store, asset = store_with_asset
    fetcher = ReleaseFetcher(store, cache_dir=tmp_path / "cache")
    cached = fetcher.fetch(asset)
    assert cached.exists()
    # cache hit: the store copy can disappear and fetch still succeeds
    store.asset_path(asset).unlink()
    assert fetcher.fetch(asset) == cached


def test_fetch_rejects_tampered_asset(store_with_asset, tmp_path):
    store, asset = store_with_asset
    path = store.asset_path(asset)
    path.write_bytes(path.read_bytes() + b"tampered")
    fetcher = ReleaseFetcher(store, cache_dir=tmp_path / "cache")
    with pytest.raises(ReleaseError, match="failed verification"):
        fetcher.fetch(asset)


def test_fetch_into_registry(store_with_asset, tmp_path):
    store, asset = store_with_asset
    registry = ArtifactRegistry(tmp_path / "registry")
    fetcher = ReleaseFetcher(store, cache_dir=tmp_path / "cache")
    bundle = fetcher.fetch_into_registry(asset, registry)
    assert registry.has(asset.artifact_id)
    assert (bundle / "handler.py").exists()


def test_cli_publish_fetch_loop(tmp_path):
    """End-to-end over the CLI: maintainer publishes certifi, a fresh user
    registry fetches it prebuilt, and `build --release-store` prefers the
    prebuilt asset over a local build."""
    runner = CliRunner()
    store_dir = str(tmp_path / "store")
    maint_reg = str(tmp_path / "maintainer-registry")
    r = runner.invoke(main, ["publish", "certifi", "--release-store", store_dir,
                             "--registry", maint_reg, "--no-warm"])
    assert r.exit_code == 0, r.output
    assert "published certifi-" in r.output

    r = runner.invoke(main, ["releases", "--release-store", store_dir])
    assert r.exit_code == 0 and "certifi-" in r.output

    user_reg = str(tmp_path / "user-registry")
    r = runner.invoke(main, ["fetch", "certifi", "--release-store", store_dir,
                             "--registry", user_reg])
    assert r.exit_code == 0, r.output
    assert ArtifactRegistry(user_reg).list()[0].recipe == "certifi"

    # build on a fresh registry takes the prebuilt path, no local build
    user_reg2 = str(tmp_path / "user-registry-2")
    r = runner.invoke(main, ["build", "certifi", "--release-store", store_dir,
                             "--registry", user_reg2])
    assert r.exit_code == 0, r.output
    assert "fetched prebuilt" in r.output
    # and a second build is a plain local cache hit
    r = runner.invoke(main, ["build", "certifi", "--release-store", store_dir,
                             "--registry", user_reg2])
    assert "cache hit" in r.output


def test_cli_build_any_asset_for_device_pinned_recipe(bundle_dir, tmp_path):
    """A device-pinned recipe must be able to consume an ``any``-device
    prebuilt asset, and later builds/deploy lookups must find the cached
    artifact even though its id differs from the locally computed one."""
    recipes = tmp_path / "recipes"
    recipes.mkdir()
    (recipes / "demo.toml").write_text(
        'schema = 1\nname = "demo"\nversion = "0.1"\ndevice = "cpu"\nrequires = []\n')
    store = ReleaseStore.create(tmp_path / "store")
    archive = pack_bundle(bundle_dir, tmp_path / "demo.tar.gz")
    store.upload_asset("v1", archive, artifact_id=f"demo-0.1-{PYTAG}-any",
                       recipe="demo", version="0.1", python=PYVER,
                       device="any")
    runner = CliRunner()
    reg = str(tmp_path / "registry")
    args = ["build", "demo", "--recipe-dir", str(recipes),
            "--release-store", str(tmp_path / "store"), "--registry", reg]
    r = runner.invoke(main, args)
    assert r.exit_code == 0, r.output
    assert "fetched prebuilt" in r.output
    r = runner.invoke(main, args)
    assert r.exit_code == 0, r.output
    assert f"cache hit: demo-0.1-{PYTAG}-any" in r.output


def test_cli_build_falls_back_when_asset_corrupt(bundle_dir, tmp_path):
    recipes = tmp_path / "recipes"
    recipes.mkdir()
    (recipes / "tinycert.toml").write_text(
        'schema = 1\nname = "tinycert"\nversion = "0.1"\ndevice = "any"\n'
        'requires = ["certifi"]\n')
    store = ReleaseStore.create(tmp_path / "store")
    archive = pack_bundle(bundle_dir, tmp_path / "t.tar.gz")
    asset = store.upload_asset(
        "v1", archive, artifact_id=f"tinycert-0.1-{PYTAG}-any",
        recipe="tinycert", version="0.1", python=PYVER, device="any")
    path = store.asset_path(asset)
    path.write_bytes(path.read_bytes() + b"x")  # corrupt after indexing
    r = CliRunner().invoke(main, [
        "build", "tinycert", "--recipe-dir", str(recipes),
        "--release-store", str(tmp_path / "store"),
        "--registry", str(tmp_path / "registry")])
    assert r.exit_code == 0, r.output
    assert "prebuilt fetch failed" in r.output
    assert f"built + published tinycert-0.1-{PYTAG}-any" in r.output


def test_cli_fetch_missing_asset_fails_cleanly(tmp_path):
    ReleaseStore.create(tmp_path / "store")
    r = CliRunner().invoke(main, ["fetch", "certifi", "--release-store",
                                  str(tmp_path / "store")])
    assert r.exit_code != 0
    assert "no prebuilt asset" in r.output
