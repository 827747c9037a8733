"""CPU rehearsal of ``chip_smoke.py`` (on-chip-measurement guide section 2,
first rehearsal): every phase driven end to end at llama-tiny dims through
the same entry points — parameter generation, ``lambdipy build`` with its
warm step, ``LocalRuntime.deploy``, the HTTP requests, the second start,
the float32 reference. Everything the smoke checks must hold here except
the one thing a CPU cannot give it: the serving process is not on a TPU,
so the verdict is not-ok and the exit code non-zero."""

import importlib.util
import json
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_pass_on_cpu_and_verdict_says_not_a_tpu(
        tmp_path, monkeypatch, capsys):
    smoke = _load_smoke()
    # steered here, not through options of the program: toy widths, a toy
    # engine window, a work directory that is not the checkout's
    monkeypatch.setattr(smoke, "WORK", tmp_path / "work")
    monkeypatch.setattr(smoke, "DIMS", dict(
        vocab_size=512, hidden=64, layers=2, heads=4, kv_heads=2, mlp=128))
    monkeypatch.setattr(smoke, "ENGINE_WINDOW", 128)
    (tmp_path / "work").mkdir()
    try:
        checks, device = smoke.smoke_one_chip(seed=0)
    finally:
        smoke.cleanup()
    rc = smoke.report(checks, device)

    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    stages = [ln.get("stage") for ln in lines]
    for stage in ("config", "params", "build", "deploy", "request", "window",
                  "second_start", "reference", "verdict"):
        assert stage in stages, (stage, stages)
    assert rc == 1
    assert lines[-1] == {"ok": False, "device": {"platform": "cpu",
                                                 "kind": "cpu", "count": 8}}
    # the device is the ONLY thing wrong
    assert lines[-2]["failed"] == ["served_on_one_tpu"], lines[-2]
    build = next(ln for ln in lines if ln.get("stage") == "build")
    assert build["warm_ok"] is True and build["warm_compile"]["compiled"] > 0
    window = next(ln for ln in lines if ln.get("stage") == "window")
    assert window["compile_before"] == window["compile_after"]
    assert window["engine"]["rows_in_segments"] > window["engine"]["segments_run"]
    second = next(ln for ln in lines if ln.get("stage") == "second_start")
    assert second["persistent_cache_hits"] > 0
    ref = next(ln for ln in lines if ln.get("stage") == "reference")
    assert ref["max_abs_logprob_delta"] <= smoke.LOGPROB_TOL and ref["finite"]
    # nothing large is left behind, and nothing is left running
    assert not list((tmp_path / "work").glob("*.fpk"))
    assert not list((tmp_path / "work").glob("bundle-*"))
    assert json.loads((tmp_path / "work" / "deployments.json").read_text()) == {}


def test_smoke_main_stops_at_the_probe_without_a_tpu(monkeypatch, tmp_path,
                                                     capsys):
    """As the driver runs it in a sandbox without a chip: the device probe
    (a child process) finds no TPU, no phase starts, the last line is
    not-ok and the exit code non-zero."""
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "WORK", tmp_path / "work")
    rc = smoke.main([])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert rc == 1
    assert [ln.get("stage") for ln in lines[:-1]] == ["probe", "verdict"]
    assert "need 1 TPU chip(s)" in lines[-2]["error"]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
