"""Prune pass tests over fake trees (SURVEY.md §5 plan item 1), including
the hard XLA-whitelist invariant (§9 hard-parts #2)."""

from pathlib import Path

import pytest

from lambdipy_tpu.buildengine.prune import XLA_WHITELIST, prune_tree
from lambdipy_tpu.recipes.schema import PruneSpec


def make_tree(root: Path, files: dict[str, bytes]) -> None:
    for rel, content in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(content)


@pytest.fixture()
def fake_site(tmp_path):
    site = tmp_path / "site"
    make_tree(site, {
        "pkg/__init__.py": b"x = 1\n",
        "pkg/core.py": b"def f(): pass\n",
        "pkg/core.pyi": b"def f() -> None: ...\n",
        "pkg/tests/test_core.py": b"assert True\n",
        "pkg/tests/data/big.bin": b"\0" * 1024,
        "pkg/__pycache__/core.cpython-312.pyc": b"\0" * 10,
        "pkg/docs/index.rst": b"docs\n",
        "pkg/include/pkg.h": b"#define X 1\n",
        "pkg-1.0.dist-info/METADATA": b"Name: pkg\n",
        "pkg-1.0.dist-info/RECORD": b"pkg/__init__.py,,\n",
        "pkg-1.0.dist-info/WHEEL": b"Wheel-Version: 1.0\n",
        "pkg-1.0.dist-info/random_extra.txt": b"junk\n",
        # the TPU stack that must survive any prune configuration
        "libtpu/libtpu.so": b"ELFFAKE" * 100,
        "jaxlib/libjax_common.so": b"ELFFAKE" * 100,
        "jaxlib/_mlir_libs/_mlir.so": b"ELFFAKE" * 10,
        "vendor_plugin/libvendor_pjrt.so": b"ELFFAKE" * 10,
    })
    return site


def test_default_rules(fake_site):
    spec = PruneSpec(rules=("tests", "pycache", "dist-info-extras", "docs", "pyi", "headers"),
                     strip_so=False)
    report = prune_tree(fake_site, spec)
    assert not (fake_site / "pkg/tests").exists()
    assert not (fake_site / "pkg/__pycache__").exists()
    assert not (fake_site / "pkg/core.pyi").exists()
    assert not (fake_site / "pkg/docs").exists()
    assert not (fake_site / "pkg/include").exists()
    assert not (fake_site / "pkg-1.0.dist-info/RECORD").exists()
    assert not (fake_site / "pkg-1.0.dist-info/random_extra.txt").exists()
    # survivors
    assert (fake_site / "pkg/__init__.py").exists()
    assert (fake_site / "pkg/core.py").exists()
    assert (fake_site / "pkg-1.0.dist-info/METADATA").exists()
    assert (fake_site / "pkg-1.0.dist-info/WHEEL").exists()
    assert report.bytes_saved > 0
    assert report.files_removed > 0 and report.dirs_removed > 0


def test_xla_whitelist_survives_hostile_spec(fake_site):
    """Even a recipe that tries to remove everything cannot touch the
    XLA/PJRT stack (SURVEY.md §9.4 hard-coded invariant)."""
    spec = PruneSpec(rules=("tests", "pycache", "docs", "pyi", "headers"),
                     extra_remove=("libtpu/**", "jaxlib/**", "*.so", "vendor_plugin/**"),
                     strip_so=False)
    before = (fake_site / "libtpu/libtpu.so").read_bytes()
    prune_tree(fake_site, spec)
    assert (fake_site / "libtpu/libtpu.so").read_bytes() == before
    assert (fake_site / "jaxlib/libjax_common.so").exists()
    assert (fake_site / "jaxlib/_mlir_libs/_mlir.so").exists()
    assert (fake_site / "vendor_plugin/libvendor_pjrt.so").exists()


def test_whitelist_blocks_parent_dir_removal(fake_site):
    spec = PruneSpec(rules=(), extra_remove=("jaxlib",), strip_so=False)
    prune_tree(fake_site, spec)
    assert (fake_site / "jaxlib/libjax_common.so").exists()


def test_keep_patterns_respected(tmp_path):
    site = tmp_path / "s"
    make_tree(site, {"pkg/tests/needed.py": b"x\n", "pkg/tests/junk.py": b"y\n"})
    spec = PruneSpec(rules=("tests",), keep=("pkg/tests/needed.py",), strip_so=False)
    prune_tree(site, spec)
    # whole-dir removal is vetoed by the kept file; junk file remains too
    # (directory-level rules are all-or-nothing), which is the safe direction
    assert (site / "pkg/tests/needed.py").exists()


def test_unknown_rule_rejected(tmp_path):
    (tmp_path / "s").mkdir()
    with pytest.raises(ValueError, match="unknown prune rules"):
        prune_tree(tmp_path / "s", PruneSpec(rules=("bogus",)))


def test_strip_real_so(tmp_path):
    """Compile a real shared object and verify stripping shrinks it while a
    whitelisted sibling is untouched."""
    import shutil
    import subprocess

    if not shutil.which("g++"):
        pytest.skip("no g++")
    site = tmp_path / "s"
    site.mkdir()
    src = tmp_path / "x.cc"
    src.write_text("extern \"C\" int forty_two() { return 42; }\n")
    so = site / "mod.so"
    subprocess.run(["g++", "-g", "-shared", "-fPIC", "-o", str(so), str(src)], check=True)
    wl = site / "fake_pjrt.so"
    shutil.copy(so, wl)
    before_wl = wl.read_bytes()
    size_before = so.stat().st_size
    report = prune_tree(site, PruneSpec(rules=(), strip_so=True))
    assert report.sos_stripped == 1
    assert so.stat().st_size < size_before  # debug info gone
    assert wl.read_bytes() == before_wl  # whitelisted: byte-identical


def test_empty_dirs_removed(tmp_path):
    site = tmp_path / "s"
    make_tree(site, {"pkg/sub/tests/t.py": b"x\n"})
    prune_tree(site, PruneSpec(rules=("tests",), strip_so=False))
    assert not (site / "pkg").exists()  # became empty and was dropped


def test_whitelist_patterns_documented():
    assert any("libtpu" in p for p in XLA_WHITELIST)
    assert any("_pjrt" in p for p in XLA_WHITELIST)


def test_strip_guard_restores_on_alignment_break(tmp_path, monkeypatch):
    """Regression: binutils strip corrupts auditwheel-processed .so files
    (observed on numpy's bundled libscipy_openblas64_). The guard must
    restore the original bytes when post-strip LOAD alignment breaks."""
    import shutil
    import subprocess

    from lambdipy_tpu.buildengine import prune as prune_mod

    if not shutil.which("g++"):
        pytest.skip("no g++")
    site = tmp_path / "s"
    site.mkdir()
    src = tmp_path / "x.cc"
    src.write_text("extern \"C\" int f() { return 1; }\n")
    so = site / "mod.so"
    subprocess.run(["g++", "-g", "-shared", "-fPIC", "-o", str(so), str(src)], check=True)
    before = so.read_bytes()

    monkeypatch.setattr(prune_mod, "subprocess", subprocess)
    real_run = subprocess.run

    def corrupting_strip(cmd, **kw):
        if cmd[0] == "strip":
            # simulate strip breaking LOAD congruence: shift a p_offset
            from lambdipy_tpu.utils import elf as elf_mod
            import struct
            data = bytearray(Path(cmd[-1]).read_bytes())
            with open(cmd[-1], "rb") as f:
                hdr = elf_mod._read_header(f)
            off = hdr["phoff"]
            for i in range(hdr["phnum"]):
                ent_off = off + i * hdr["phentsize"]
                p_type = struct.unpack_from("<I", data, ent_off)[0]
                if p_type == 1:  # PT_LOAD
                    p_offset = struct.unpack_from("<Q", data, ent_off + 8)[0]
                    struct.pack_into("<Q", data, ent_off + 8, p_offset + 1)
                    break
            Path(cmd[-1]).write_bytes(bytes(data))
            return subprocess.CompletedProcess(cmd, 0, "", "")
        return real_run(cmd, **kw)

    monkeypatch.setattr(prune_mod.subprocess, "run", corrupting_strip)
    try:
        report = prune_tree(site, PruneSpec(rules=(), strip_so=True))
    finally:
        monkeypatch.undo()
    assert report.sos_stripped == 0
    assert so.read_bytes() == before  # restored


def test_prestripped_so_skipped(tmp_path):
    """A pre-stripped .so (the manylinux norm) must not be re-stripped."""
    import shutil
    import subprocess

    from lambdipy_tpu.utils.elf import strippable_sections

    if not shutil.which("g++"):
        pytest.skip("no g++")
    site = tmp_path / "s"
    site.mkdir()
    src = tmp_path / "x.cc"
    src.write_text("extern \"C\" int f() { return 1; }\n")
    so = site / "mod.so"
    subprocess.run(["g++", "-shared", "-fPIC", "-o", str(so), str(src)], check=True)
    subprocess.run(["strip", "--strip-unneeded", str(so)], check=True)
    assert strippable_sections(so) == []
    before = so.read_bytes()
    report = prune_tree(site, PruneSpec(rules=(), strip_so=True))
    assert report.sos_stripped == 0
    assert so.read_bytes() == before
