"""The xplane reducer against a small recorded trace: the first 30 ms of
device operations of a traced `mistral7b.chat-steady` slice on a TPU v5e
(my chip run, PR 23; cut down with the profiler's own protobuf schema, names
shortened). Busy time is checked against a sweep-line count made here,
another algorithm than the reducer's interval merge."""

from pathlib import Path

import pytest

from benchmark import xplane

TRACE = Path(__file__).parent / "data" / "v5e_chat_steady_30ms.xplane.pb"


def sweep_busy_ns(events) -> int:
    points = []
    for e in events:
        points += [(e.start_ns, 1), (e.start_ns + e.duration_ns, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    depth, busy, last = 0, 0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth, last = depth + d, t
    return busy


def test_busy_time_is_the_union_of_the_device_operations():
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(str(TRACE)).planes
                 if p.name == "/device:TPU:0")
    ops = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    out = xplane.reduce(TRACE)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(sweep_busy_ns(ops.events) / 1e9,
                                          rel=1e-9)
    assert out["busy_s"] == pytest.approx(0.020072466, rel=1e-6)
    assert 0 < out["busy_s"] < out["window_s"] < 0.031
    # a longer window timed by the harness wins over the trace's own span
    assert xplane.reduce(TRACE, window_s=0.05)["window_s"] == 0.05


def test_breakdown_names_programs_operations_and_gaps():
    out = xplane.reduce(TRACE)
    names = [n for n, _ in out["device_ops"]]
    assert names[0] == "module jit_prefill" and "module jit_pack" in names
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 5
    assert all(len(n) <= 87 and " = " not in n for n in names)
    assert not any(n.startswith(xplane.CONTAINERS) for n in names)
    secs = [s for _, s in out["idle_gaps"]]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0.005
    assert out["idle_gaps"][0][0] == "host: PjitFunction(prefill)"


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    assert xplane.reduce(xplane.find_trace(tmp_path)) is None


def test_union_merges_overlaps_and_touching_intervals():
    assert xplane._union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]
