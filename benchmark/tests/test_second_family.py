"""A second family, rehearsal only (``benchmark/families/rehearsal_moe.py``:
the repo's own sparse FFN at toy widths, every token seated), to show that
the seam bears weight where the old rules broke: a 2-D float32 router, 3-D
expert stacks with ``[E, 1, out]`` scales, widths that shape the parameter
tree. It came in after the seam as new files and entries only, which the
first test pins. The last two drive its cell through the whole command on
the CPU (~1 min: they build and deploy a real bundle); never a measurement."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import client, families, reference, weights
from benchmark.tests.test_run_rehearsal import drive

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "rehearsal-moe.json").read_text())
CELL = "rehearsal-moe.rehearsal-closed"


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return tmp_path_factory.mktemp("bench-work-moe")


def test_the_family_needed_these_files_and_no_other():
    """What names the family or its configuration, outside the tests: its
    module, its configuration, the rehearsal manifest's entries."""
    naming = set()
    for p in BENCH.rglob("*"):
        rel = p.relative_to(BENCH).as_posix()
        if not p.is_file() or rel.startswith(("tests/", ".work/")) \
                or "__pycache__" in rel or p.suffix == ".pb":
            continue
        text = rel + p.read_text()
        if "rehearsal-moe" in text or "rehearsal_moe" in text:
            naming.add(rel)
    assert naming == {"families/rehearsal_moe.py",
                      "configs/rehearsal-moe.json", "rehearsal.json"}
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert "rehearsal-moe" not in json.dumps(manifest)   # never a cell


def test_the_tree_that_is_filled_is_the_tree_that_is_served(tmp_path):
    family = families.of(CONFIG)
    dims = family.dims_of(CONFIG)
    assert (dims["moe_experts"], dims["moe_top_k"]) == (4, 2)
    # every token seated, whatever the others of its group choose
    assert dims["moe_capacity_factor"] * dims["moe_top_k"] \
        >= dims["moe_experts"]
    info = weights.write_params(CONFIG, tmp_path / "params.fpk")
    h, m, e, kv, v = 64, 96, 4, 32, 512
    per_layer = 2 * h + 2 * (h * h + h) + 2 * (h * kv + kv) + h * e \
        + 2 * (e * h * m + e * m) + e * m * h + e * h
    assert info["n_params"] == 2 * per_layer + v * h + h + h * v + v
    # the leaves the old rules refused (2-D float32, 3-D) or would have
    # made ones (any 1-D leaf)
    router = weights.leaf(CONFIG, "layer_0/moe/router", (h, e), "float32")
    assert router.dtype == np.float32 and router.std() > 0.05
    scale = weights.leaf(CONFIG, "layer_1/moe/experts_down_scale",
                         (e, 1, h), "float32")
    assert scale.shape == (e, 1, h) and (scale == 2.0 ** -10).all()
    stack = weights.leaf(CONFIG, "layer_1/moe/experts_up_int8", (e, h, m),
                         "int8")
    assert stack.dtype == np.int8 and len(np.unique(stack)) == 256
    with pytest.raises(ValueError, match="rehearsal-moe.*route_bias"):
        weights.leaf(CONFIG, "layer_0/moe/route_bias", (e,), "float32")


def test_its_reference_routes_and_its_control_fails_the_limit():
    rows = [(list(map(int, np.random.default_rng(5).integers(
        1, CONFIG["vocab_size"], n))), n) for n in (9, 17, 30, 12)]
    for _ in range(20):
        rows = [(tokens + [int(np.argmax(
            reference.next_token_logits(CONFIG, tokens)))], n)
            for tokens, n in rows]
    out = reference.served_gaps(CONFIG, rows, shape=(4, 64, 24), control=True)
    assert out["served_tokens"] == 80
    assert max(out["gap"]) <= 1e-4          # the reference's own choice
    assert max(out["control_gap"]) > 3 * max(max(out["gap"]), 0.01)
    # the experts matter: with the gates of another routing (top-1 in place
    # of top-2) the same weights choose other tokens
    other = dict(CONFIG, num_experts_per_tok=1)
    gaps = reference.served_gaps(other, rows, shape=(4, 64, 24))["gap"]
    assert max(gaps) > 0.05


def test_what_a_step_needs_counts_the_experts_it_touches():
    family = families.of(CONFIG)
    one = family.decode_step_bytes(CONFIG, rows=1, context=0)
    many = family.decode_step_bytes(CONFIG, rows=64, context=0)
    expert = 3 * 64 * 96
    assert many - one == pytest.approx(2 * 2 * expert, rel=1e-3)  # 4 - 2 of them
    assert family.decode_step_flops(CONFIG, rows=2, context=10) \
        == 2 * family.decode_step_flops(CONFIG, rows=1, context=10)


def test_the_cell_runs_the_whole_command_and_is_correct(capsys, work):
    rc, lines = drive(capsys, work, CELL, seed=2)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, lines[-3:]
    assert last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"tpot_p90_ms", "out_tok_s", "setup_s"}
    assert last["attempted"] > 0 and last["failed"] == 0
    params = next(ln for ln in lines if ln.get("stage") == "params")
    assert params["n_params"] == 241344     # the expert stacks are in it
    window = next(ln for ln in lines if ln.get("stage") == "window")
    assert window["compiles_in_window"] == 0, window


def test_an_altered_token_comes_out_not_correct(capsys, work, monkeypatch):
    sound = client.complete

    def broken(host, port, rec, *args, **kw):
        out = sound(host, port, rec, *args, **kw)
        if rec.rid >= 0 and rec.tokens:
            rec.tokens[len(rec.tokens) // 2] = \
                (rec.tokens[len(rec.tokens) // 2] + 1) % 500 + 1
        return out

    monkeypatch.setattr(client, "complete", broken)
    rc, lines = drive(capsys, work, CELL, seed=2)
    assert rc == 0
    check = next(ln for ln in lines if ln.get("stage") == "check")
    assert check["widest_gap"] > check["limit"]
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0
