"""Generators reproduce exactly, differ across seeds in order only, and the
coverage list of every traffic file holds every bucket its clips allow."""

import json
from pathlib import Path

import pytest

from benchmark import traffic as T
from benchmark import warmup
from benchmark.generators import open_loop_poisson

HERE = Path(__file__).resolve().parents[1]
MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))
MANIFESTS = [HERE.parent / "BENCHMARK.json", HERE / "rehearsal.json"]


def cells():
    out = []
    for m in MANIFESTS:
        man = json.loads(m.read_text())
        files = {c["name"]: c["file"] for c in man["configs"]}
        out += [(w["name"], files[w["config"]], w["traffic"])
                for w in man["workloads"]]
    return out


@pytest.mark.parametrize("mix", MIXES)
def test_sizes_are_one_set_in_another_order(mix):
    tr = T.load(mix)
    a = T.sizes(tr, 64, 2**31 + 1)
    assert a == T.sizes(tr, 64, 2**31 + 1)
    b = T.sizes(tr, 64, 2)
    assert a != b
    k = a.index(b[0])                   # the same sequence, rotated
    assert any(a[k:] + a[:k] == b for k in range(64))
    for i in (0, 1):
        assert sorted(x[i] for x in a) == sorted(x[i] for x in b)
    env = T.lengths(tr)
    assert all(env["prompt_min"] <= p <= env["prompt_max"]
               and env["new_min"] <= n <= env["new_max"] for p, n in a)


def test_open_loop_plan_reproduces_and_stays_inside_the_window():
    tr = T.load("chat-steady")
    a = open_loop_poisson.plan(tr, 2**31 + 5, 40.0, 32768)
    assert a == open_loop_poisson.plan(tr, 2**31 + 5, 40.0, 32768)
    b = open_loop_poisson.plan(tr, 7, 40.0, 32768)
    assert [x[1] for x in a] != [x[1] for x in b]
    assert len(a) == len(b) == round(tr["rate_rps"] * 40)
    assert sorted(len(x[1]) for x in a) == sorted(len(x[1]) for x in b)
    assert 0 < a[0][0] and a[-1][0] < 40.0
    assert all(0 < t < 32768 for x in a for t in x[1])


@pytest.mark.parametrize("cell,config_file,mix", cells())
def test_coverage_holds_every_bucket_the_clips_allow(cell, config_file, mix):
    config = json.loads((HERE.parent / config_file).read_text())
    tr = T.load(mix)
    cov = warmup.coverage(tr, config)
    env = T.lengths(tr)
    assert env["total_max"] <= config["engine_window"]
    # every prompt length of the mix falls in a covered bucket ...
    for s in range(env["prompt_min"], env["prompt_max"] + 1):
        b = warmup.next_bucket(s)
        assert b in cov["prompt_buckets"]
        assert (b in cov["group_buckets"]) == (s <= warmup.GROUP_PREFILL_MAX) \
            or b in cov["group_buckets"]
    # ... every joiner count up to the slots buckets to a covered count ...
    for k in range(1, cov["slots"] + 1):
        assert warmup.next_bucket(k, 1) in cov["joiner_counts"]
    assert set(cov["burst_of"]) == set(cov["joiner_counts"]) - {1}
    assert all(k < cov["slots"] and warmup.next_bucket(k, 1) == c
               for c, k in cov["burst_of"].items())
    # ... and every decode window a live row can need is walked by a single
    for pos in range(env["prompt_min"], env["total_max"]):
        w = min(warmup.next_bucket(pos + warmup.SEGMENT),
                config["engine_window"])
        assert w in cov["decode_windows"] or w == config["engine_window"]
    walked = set()
    for s, new in cov["singles"]:
        for pos in range(s, s + new, warmup.SEGMENT):
            walked.add(min(warmup.next_bucket(pos + warmup.SEGMENT),
                           config["engine_window"]))
    assert set(cov["decode_windows"]) <= walked


def test_a_mix_that_overflows_the_engine_window_is_refused():
    from benchmark.bundle import BenchFailure

    config = json.loads((HERE / "configs" / "deepseek7b.json").read_text())
    with pytest.raises(BenchFailure):
        warmup.coverage(T.load("chat-steady"), config)
