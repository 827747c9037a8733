"""The whole command on the CPU at toy widths, through the rehearsal
manifest (``benchmark/rehearsal.json``: a configuration and mixes that no
python file of the harness names). Slow for a unit test (~1-2 min): it
builds and deploys a real bundle. Never a measurement: the line it prints
says ``platform: cpu``."""

import json
from pathlib import Path

import pytest

from benchmark import client, run

REPO = Path(__file__).resolve().parents[2]
REHEARSAL = str(REPO / "benchmark" / "rehearsal.json")


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return tmp_path_factory.mktemp("bench-work")


def drive(capsys, work, cell, trace=0, seed=2**31 + 11):
    rc = run.main(["--manifest", REHEARSAL, "--workload", cell, "--seed",
                   str(seed), "--seconds", "3", "--trace", str(trace),
                   "--work-dir", str(work)])
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return rc, [json.loads(ln) for ln in lines if ln.startswith("{")]


def test_a_sound_run_is_correct_and_prints_the_contract_line(capsys, work):
    rc, lines = drive(capsys, work, "rehearsal-tiny.rehearsal-open")
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, lines[-3:]
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]     # the numbers come last
    assert set(last["compared"]) == {"widest_gap", "failed_requests"}
    gap = last["compared"]["widest_gap"]
    assert 0 <= gap["value"] <= gap["limit"]
    assert last["device"]["platform"] == "cpu"      # refused as a measurement
    assert set(last["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert last["attempted"] > 0 and last["failed"] == 0
    window = next(ln for ln in lines if ln.get("stage") == "window")
    assert window["compiles_in_window"] == 0, window


def test_a_later_run_finds_the_bundle_and_a_traced_one_reads_the_layers(
        capsys, work):
    rc, lines = drive(capsys, work, "rehearsal-tiny.rehearsal-closed",
                      trace=1, seed=12)
    assert rc == 0
    found = next(ln for ln in lines if ln.get("stage") == "bundle")
    assert found["built"] is False
    assert not any(ln.get("stage") == "build" for ln in lines)
    warm = next(ln for ln in lines if ln.get("stage") == "warmup")
    assert "cache_misses" in warm and warm["still_missing"] == []
    metrics = lines[-1]["metrics"]
    assert {"boot_ready_s", "boot_cache_miss", "rows_per_segment",
            "window_compiles"} <= set(metrics)
    assert metrics["window_compiles"]["value"] == 0
    # no device plane in a CPU trace: the device readers return nothing
    assert "device_idle_pct" not in metrics and "decode_hbm_pct" not in metrics


def test_a_token_altered_where_it_is_produced_comes_out_not_correct(
        capsys, work, monkeypatch):
    """The timed path broken underneath: every response of the window has
    one served token replaced (still the right count, so only the
    comparison with the reference can see it)."""
    sound = client.complete

    def broken(host, port, rec, *args, **kw):
        out = sound(host, port, rec, *args, **kw)
        if rec.rid >= 0 and rec.tokens:
            rec.tokens[len(rec.tokens) // 2] = \
                (rec.tokens[len(rec.tokens) // 2] + 1) % 500 + 1
        return out

    monkeypatch.setattr(client, "complete", broken)
    rc, lines = drive(capsys, work, "rehearsal-tiny.rehearsal-closed")
    assert rc == 0
    check = next(ln for ln in lines if ln.get("stage") == "check")
    assert check["widest_gap"] > check["limit"]
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0


def test_without_a_tpu_the_real_manifest_prints_no_result(capsys, work):
    rc = run.main(["--workload", "deepseek7b.decode-saturated", "--seed", "1",
                   "--seconds", "1", "--trace", "0", "--work-dir",
                   str(work / "none")])
    assert rc != 0
    assert not [ln for ln in capsys.readouterr().out.splitlines()
                if '"metrics"' in ln]
