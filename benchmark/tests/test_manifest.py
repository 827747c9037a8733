"""BENCHMARK.json against the contract's character rules, and the harness
against its own: driven by data, it names no cell, configuration or mix."""

import json
import re
from pathlib import Path

import pytest

from benchmark import bundle

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    cells = len(MANIFEST["workloads"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) \
        <= max(1, cells // 4)


def test_names_units_and_lines():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert (REPO / c["file"]).is_file()
        assert all(1 <= len(c[k]) <= 200 and "\n" not in c[k]
                   for k in ("why", "source"))
        assert c["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
        assert json.loads((REPO / c["file"]).read_text())["reduced"] \
            == c["reduced"]
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (REPO / "benchmark" / "traffic" / f"{w['traffic']}.json").is_file()


def test_every_cell_reports_what_the_contract_asks():
    for w in MANIFEST["workloads"]:
        e2e = [m["name"] for m in MANIFEST["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in MANIFEST["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer and all(m["moves"] in e2e for m in layer), w["name"]
    for m in MANIFEST["per_layer"]:
        assert (REPO / "benchmark" / "layer_metrics"
                / f"{m['name']}.py").is_file()


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in (REPO / "benchmark").rglob("*"):
        rel = p.relative_to(REPO).as_posix()
        if ".work" in rel or "__pycache__" in rel:
            continue
        assert ok.match(rel) and len(rel) <= 200, rel


def test_the_harness_names_no_cell_configuration_mix_or_family():
    """A later PR adds files and entries and edits nothing: so no python
    file of the harness may know a configuration, a mix, a metric or a
    family (an architecture's own code lies under ``benchmark/families``,
    found by the name in the configuration's file)."""
    from benchmark import families

    rehearsal = json.loads((REPO / "benchmark" / "rehearsal.json").read_text())
    configs = MANIFEST["configs"] + rehearsal["configs"]
    named = {families.name_of(json.loads((REPO / c["file"]).read_text()))
             for c in configs}
    stems = {p.stem for p in (REPO / "benchmark" / "families").glob("*.py")
             if p.stem != "__init__"}
    words = [c["name"] for c in configs] \
        + [w["traffic"] for w in MANIFEST["workloads"] + rehearsal["workloads"]] \
        + sorted(named | stems | {s.replace("_", "-") for s in stems})
    for py in (REPO / "benchmark").glob("*.py"):
        text = py.read_text()
        for word in words:
            assert word not in text, (py.name, word)
    # and every configuration finds a family that has all its parts
    for name in named:
        family = families.load(name)
        assert all(hasattr(family, part) for part in families.PARTS), name


def test_bundle_key_follows_the_configuration_file(tmp_path):
    src = REPO / "benchmark" / "configs" / "mistral7b.json"
    a = tmp_path / "a.json"
    a.write_text(src.read_text())
    assert bundle.bundle_key(a) == bundle.bundle_key(src)
    a.write_text(src.read_text().replace('"weights_seed": 7',
                                         '"weights_seed": 8'))
    assert bundle.bundle_key(a) != bundle.bundle_key(src)


@pytest.mark.parametrize("argv", [["--workload", "nope", "--seed", "1",
                                   "--seconds", "1", "--trace", "0"]])
def test_an_unknown_cell_prints_no_result(argv, capsys):
    from benchmark import run

    assert run.main(argv) != 0
    assert capsys.readouterr().out.strip() == ""
