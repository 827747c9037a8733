"""The scope split against a small recorded trace: 3 ms of one decode
segment of the traced `mistral7b.decode-saturated` run of PR 24 on a TPU
v5e (my chip run, PR 24, seed 2100000011; cut with
`benchmark/tests/cut_trace.py`: one `jit_seg` run's module event, the
operations that start in 3 ms from its midpoint, `tf_op` and `program_id`
of their metadata, the `eng.*` host events of the slice). The expected
split is made here from the file's op_names with another algorithm than
the reducer's: a backward pass that gives each unnamed wait to the scoped
operation after it."""

from pathlib import Path

import pytest

from benchmark import families, harness, scopes, xplane

TRACE = Path(__file__).parent / "data" / "v5e_seg_3ms.xplane.pb"
FAMILY = families.load("llama-hf")     # the recorded cuts are of its program


@pytest.fixture(scope="module")
def split():
    return scopes.segment_split(TRACE, FAMILY)


def device_ops():
    """(start, duration ns, name) of the device's operations, and the
    program id of the one segment run."""
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(str(TRACE)).planes
                 if p.name == "/device:TPU:0")
    lines = {ln.name: ln for ln in plane.lines}
    (run,) = list(lines["XLA Modules"].events)
    program = scopes.PROGRAM_ID.search(run.name)[1]
    ops = sorted((ev.start_ns, ev.duration_ns, ev.name)
                 for ev in lines["XLA Ops"].events)
    return ops, program, run


def test_op_names_come_from_the_metadata_not_the_events():
    from jax.profiler import ProfileData

    names = scopes.op_names(TRACE)
    ops, program, _ = device_ops()
    assert len(names) > 100
    assert all(prog == program for prog, _ in names)
    named = [names[(program, n)] for _, _, n in ops if (program, n) in names]
    assert len(named) > 100     # most events are compiler-made copies
    assert all(n.startswith("jit(seg)/") and not n.endswith(":")
               for n in named)
    assert any("/mlp/down_proj/dot_general" in n for n in named)
    # what ProfileData shows of an event holds no op_name: the reason the
    # helper reads the protobuf itself
    plane = next(p for p in ProfileData.from_file(str(TRACE)).planes
                 if p.name == "/device:TPU:0")
    ev = next(iter(next(ln for ln in plane.lines
                        if ln.name == "XLA Ops").events))
    assert not any("jit(" in str(v) for _, v in ev.stats)


def test_the_split_adds_up_and_waits_go_to_the_operation_behind(split):
    names = scopes.op_names(TRACE)
    ops, program, run = device_ops()
    expected, waited, nxt = {}, 0.0, ""
    for _, ns, name in reversed(ops):
        if xplane.short(name).startswith(xplane.CONTAINERS):
            continue
        op_name = names.get((program, name))
        if op_name is None:             # compiler-made: a wait
            expected[nxt] = expected.get(nxt, 0.0) + ns / 1e9
            waited += ns / 1e9 if nxt else 0.0
            continue
        scope = scopes.scope_of(op_name, FAMILY.SCOPES)
        expected[scope] = expected.get(scope, 0.0) + ns / 1e9
        if scope:
            nxt = scope
    assert split["runs"] == 1 and split["scoped"]
    assert split["run_s"] == pytest.approx(run.duration_ns / 1e9)
    assert set(split["by_scope"]) == set(expected)
    for scope, seconds in expected.items():
        assert split["by_scope"][scope] == pytest.approx(seconds, abs=1e-9)
    assert split["waited_s"] == pytest.approx(waited, abs=1e-9)
    assert split["op_s"] == pytest.approx(sum(expected.values()))
    # 3 ms of operations, back to back (the last one may end later)
    assert 0.0027 < split["op_s"] <= 0.0032
    assert 0.1 < split["waited_s"] / split["op_s"] < 0.6


def test_what_the_slice_shows(split):
    by = split["by_scope"]
    share = {k: v / split["op_s"] for k, v in by.items()}
    assert {"qkv_proj", "kv_write", "attend", "o_proj", "mlp"} <= set(by)
    assert share["mlp"] > share["qkv_proj"] > share["o_proj"] > 0.02
    assert share.get("", 0.0) < 0.03
    # as recorded: a 196.7 ms run (16 steps of eight rows), 1.787 ms of the
    # slice under the MLP
    assert split["run_s"] == pytest.approx(0.196669149, rel=1e-9)
    assert by["mlp"] == pytest.approx(0.001787067, rel=1e-6)
    # and what gaps the device has there lie under the engine's own phase
    assert xplane.reduce(TRACE)["idle_gaps"][0][0] == "host: eng.wait"


@pytest.mark.parametrize("op_name,scope", [
    ("jit(seg)/while/body/closed_call/LlamaModel/layer_3/mlp/down_proj/"
     "dot_general", "mlp"),
    ("jit(seg)/while/body/closed_call/LlamaModel/layer_0/attend/attend/div",
     "attend"),
    ("jit(seg)/while/body/closed_call/sample/cond/branch_1_fun/sample/lt",
     "sample"),
    ("jit(seg)/while/body/closed_call/LlamaModel/lm_head/lm_head/mul",
     "lm_head"),
    ("jit(seg)/kv_window/dynamic_update_slice", "kv_window"),
    ("jit(seg)/while/body/dynamic_update_slice", ""),
    # a program without named scopes still names flax's modules
    ("jit(seg)/while/body/closed_call/LlamaModel/layer_0/o_proj/mul",
     "o_proj"),
    ("", "")])
def test_scope_of_takes_the_innermost_scope_of_the_list(op_name, scope):
    assert scopes.scope_of(op_name, FAMILY.SCOPES) == scope


def test_step_ms_and_the_four_readers(split, monkeypatch):
    monkeypatch.setattr(scopes, "for_run", lambda family: split)
    ctx = {"trace": {"busy_s": 1.0}, "family": FAMILY,
           "m_close": {"handler": {"batching": {"segment": 16}}}}
    step = scopes.step_ms(ctx)
    assert step == pytest.approx(1e3 * split["run_s"] / 16)
    parts = {name: harness.layer_metric(name).read(ctx) for name in (
        "decode_step_ms", "decode_matmul_ms", "decode_attend_ms",
        "decode_sample_ms")}
    assert parts["decode_step_ms"] == step
    by = split["by_scope"]
    assert parts["decode_matmul_ms"] == pytest.approx(1e3 * sum(
        by.get(s, 0.0) for s in ("qkv_proj", "o_proj", "mlp", "lm_head"))
        / 16)
    assert parts["decode_attend_ms"] == pytest.approx(1e3 * sum(
        by.get(s, 0.0) for s in ("attend", "kv_write", "kv_window")) / 16)
    # an untraced run, a run without the segment counter, a program that
    # names no scopes: no value, and no exception
    assert scopes.step_ms({"m_close": ctx["m_close"]}) is None
    assert scopes.step_ms({"trace": ctx["trace"], "m_close": {}}) is None
    monkeypatch.setattr(scopes, "for_run",
                        lambda family: dict(split, scoped=False))
    assert harness.layer_metric("decode_matmul_ms").read(ctx) is None
    assert harness.layer_metric("decode_step_ms").read(ctx) == step
    monkeypatch.setattr(scopes, "for_run", lambda family: None)
    assert harness.layer_metric("decode_step_ms").read(ctx) is None


def test_runs_cut_off_by_the_profiler_are_left_out():
    runs = [(0, 40, "jit_seg(1)"), (40, 290, "jit_seg(1)"),
            (290, 540, "jit_seg(1)"), (540, 600, "jit_seg(1)")]
    assert scopes.whole_runs(runs) == runs[1:3]
    assert scopes.whole_runs(runs[:3]) == runs[1:2]
    assert scopes.whole_runs(runs[:2]) == runs[:2]
    assert scopes.whole_runs([]) == []


def test_a_trace_without_the_segment_program_splits_to_nothing():
    other = TRACE.parent / "v5e_chat_steady_30ms.xplane.pb"
    assert scopes.segment_split(other, FAMILY) is None
    assert scopes.op_names(other) == {}


def test_the_work_directory_is_found_as_run_py_finds_it(monkeypatch):
    from benchmark.bundle import DEFAULT_WORK

    monkeypatch.setattr("sys.argv", ["run.py", "--workload", "x"])
    assert scopes.work_dir() == DEFAULT_WORK
    monkeypatch.setattr("sys.argv", ["run.py", "--work-dir", "/tmp/w"])
    assert scopes.work_dir() == Path("/tmp/w")
    monkeypatch.setattr("sys.argv", ["run.py", "--work-dir=/tmp/v"])
    assert scopes.work_dir() == Path("/tmp/v")
