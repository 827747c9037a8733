"""The family seam (``benchmark/families``): how a configuration finds its
family, and that the llama block behind the seam is what it was before it.

The golden values were taken on the parent of PR 26 (commit 0b247bd:
``benchmark/weights.py`` ``leaf`` / ``write_params``,
``benchmark/reference.py`` ``next_token_logits``, ``benchmark/roofline.py``)
with the calls this file makes, so a leaf, the parameter file and the
reference's logits are what the accepted cells' bundles and limits were
made with: bit for bit, and the logits to 1e-6."""

import hashlib
import json
import zlib
from pathlib import Path

import numpy as np
import pytest

from benchmark import bundle, families, reference, run, scopes, weights
from benchmark.bundle import BenchFailure

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
CONFIGS = HERE.parent / "configs"


def config(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())


# (configuration, leaf path, shape, dtype, crc32 of its bytes)
LEAVES = [
    ("mistral7b", "layer_1/k_proj/kernel_int8", (4096, 1024), "int8", 1887963744),
    ("mistral7b", "layer_1/down_proj/kernel_int8", (14336, 4096), "int8", 4228837928),
    ("mistral7b", "layer_1/down_proj/scale", (1, 4096), "float32", 2227436399),
    ("mistral7b", "lm_head/scale", (1, 32768), "float32", 896388684),
    ("mistral7b", "layer_1/attn_norm/scale", (4096,), "float32", 4022919837),
    ("deepseek7b", "layer_1/k_proj/kernel_int8", (4096, 4096), "int8", 1490515405),
    ("deepseek7b", "layer_1/down_proj/kernel_int8", (11008, 4096), "int8", 2581689341),
    ("deepseek7b", "layer_1/down_proj/scale", (1, 4096), "float32", 2227436399),
    ("deepseek7b", "lm_head/scale", (1, 102400), "float32", 4003434708),
    ("deepseek7b", "layer_1/attn_norm/scale", (4096,), "float32", 4022919837),
    ("rehearsal-tiny", "embed/embedding", (512, 64), "float32", 3283448570),
    ("rehearsal-tiny", "embed/embedding", (512, 64), "bfloat16", 3099822763),
]


@pytest.mark.parametrize("name,path,shape,dtype,crc", LEAVES)
def test_a_leaf_is_bit_for_bit_the_parents(name, path, shape, dtype, crc):
    if dtype == "bfloat16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    leaf = weights.leaf(config(name), path, shape, dtype)
    assert leaf.shape == shape and leaf.dtype == np.dtype(dtype)
    assert zlib.crc32(np.ascontiguousarray(leaf).tobytes()) == crc


@pytest.mark.parametrize("name,sha,size,n_params", [
    ("rehearsal-tiny", "8a9da3880ee98860f2418e9408d8d2a70b2a1d34dedb07cda1"
     "ce3972208f024b", 183488, 141120),
    ("rehearsal-tiny-tp4", "6123024c783599f15229f026d6c53d3fff74a8fc0f98f8"
     "3628eed7dfd417b856", 192192, 149440)])
def test_the_parameter_file_is_bit_for_bit_the_parents(tmp_path, name, sha,
                                                       size, n_params):
    info = weights.write_params(config(name), tmp_path / "params.fpk")
    assert info == {"bytes": size, "n_params": n_params}
    assert hashlib.sha256(
        (tmp_path / "params.fpk").read_bytes()).hexdigest() == sha


ROW = [69, 66, 408, 256, 302, 308, 364, 15, 249, 76, 206, 475, 280, 36, 278,
       67, 386, 485, 501, 318, 444]


def test_the_references_logits_are_the_parents():
    logits = np.asarray(reference.next_token_logits(config("rehearsal-tiny"),
                                                    ROW))
    assert logits.shape == (512,) and logits.dtype == np.float32
    assert logits[:8] == pytest.approx([
        -0.9763239026069641, -0.7281913757324219, 0.2001839578151703,
        -0.35968017578125, -0.6434266567230225, 0.3650543689727783,
        -0.11537274718284607, -0.6838353276252747], abs=1e-6)
    assert int(logits.argmax()) == 429
    assert float(logits.astype(np.float64).sum()) == pytest.approx(
        17.129573319107294, abs=1e-4)
    # the gaps the shared arithmetic makes of them, and the int4 control's
    out = reference.served_gaps(
        config("rehearsal-tiny"),
        [(ROW + [5, 9, 200], 21), ([7] * 12 + [1, 2], 12)],
        shape=(4, 64, 24), control=True)
    assert out["gap"] == pytest.approx(
        [1.668892741203308, 0.26950299739837646, 0.6810706853866577,
         0.42324328422546387, 2.3652477264404297], abs=1e-6)
    assert out["control_gap"] == pytest.approx(
        [0.0, 0.0, 0.07946252822875977, 0.0, 0.0], abs=1e-6)


@pytest.mark.parametrize("name,step_bytes,step_flops,prefill,dims,key", [
    ("mistral7b", 7408451584.0, 107882741760.0, 14363444379648,
     {"vocab_size": 32768, "hidden": 4096, "layers": 32, "heads": 32,
      "kv_heads": 8, "mlp": 14336, "rope_theta": 1000000.0,
      "norm_eps": 1e-05, "max_len": 8192}, "8ad0aff66e5a152a"),
    ("deepseek7b", 7596605440.0, 98466201600.0, 12501710274560,
     {"vocab_size": 102400, "hidden": 4096, "layers": 30, "heads": 32,
      "kv_heads": 32, "mlp": 11008, "rope_theta": 10000.0,
      "norm_eps": 1e-06, "max_len": 4096}, "849bce97e1a9a226")])
def test_what_a_step_needs_and_the_bundle_key_are_the_parents(
        name, step_bytes, step_flops, prefill, dims, key):
    cfg = config(name)
    family = families.of(cfg)
    assert family.decode_step_bytes(cfg, rows=7.5, context=300.0) == step_bytes
    assert family.decode_step_flops(cfg, rows=7.5, context=300.0) == step_flops
    assert family.prefill_flops(cfg, rows=4, seq_len=256) == prefill
    assert family.dims_of(cfg) == dims
    assert bundle.bundle_key(CONFIGS / f"{name}.json") == key


# -- how a configuration finds its family ------------------------------------

def test_the_family_key_wins_and_the_model_key_is_the_fallback():
    assert families.name_of({"model": "a-b"}) == "a-b"
    assert families.name_of({"model": "a-b", "family": "c"}) == "c"
    cfg = config("rehearsal-tiny")
    assert "family" not in cfg      # the accepted files stay byte for byte
    assert families.of(cfg) is families.load(cfg["model"])
    assert families.of(dict(cfg, family=cfg["model"], model="other")) \
        is families.of(cfg)


@pytest.mark.parametrize("name", ["no-such-family", "../weights", "a.b", ""])
def test_an_unknown_family_is_a_failure_of_the_run(name):
    with pytest.raises(BenchFailure, match="no family"):
        families.load(name)


def test_an_unknown_family_prints_no_result_line(tmp_path, capsys):
    cfg = dict(config("rehearsal-tiny"), name="orphan", family="orphan")
    manifest = json.loads((HERE.parent / "rehearsal.json").read_text())
    # a configuration file has to lie in the repo: the work directory does
    (tmp_path / "orphan.json").write_text(json.dumps(cfg))
    manifest["configs"] = [{"name": "orphan", "file": str(
        tmp_path / "orphan.json")}]
    manifest["workloads"] = [{"name": "orphan.cell", "config": "orphan",
                              "traffic": manifest["workloads"][0]["traffic"],
                              "chips": 1}]
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    rc = run.main(["--manifest", str(tmp_path / "manifest.json"),
                   "--workload", "orphan.cell", "--seed", "1", "--seconds",
                   "1", "--trace", "0", "--work-dir", str(tmp_path / "w")])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "no family 'orphan'" in out.err
    assert not (tmp_path / "w").exists()    # nothing was built for it


def test_a_leaf_without_a_rule_raises_naming_family_and_path():
    cfg = config("rehearsal-tiny")
    for path, shape, dtype in [
            ("layer_0/moe/router", (64, 4), "float32"),      # was: refused
            ("layer_0/moe/experts_up_scale", (4, 1, 128), "float32"),
            ("layer_0/moe/route_bias", (4,), "float32"),     # was: ones
            ("layer_0/moe/experts_up_int8", (4, 64, 128), "int8")]:
        with pytest.raises(ValueError) as e:
            weights.leaf(cfg, path, shape, dtype)
        assert "llama-hf" in str(e.value) and path in str(e.value)


def test_every_family_file_has_all_five_parts():
    stems = [p.stem for p in (HERE.parent / "families").glob("*.py")
             if p.stem != "__init__"]
    assert stems
    for stem in stems:
        family = families.load(stem.replace("_", "-"))
        assert all(hasattr(family, part) for part in families.PARTS)
        assert set(family.WITNESS) <= set(family.SCOPES)


# -- scope names come from the family ------------------------------------------

TRACE = HERE / "data" / "v5e_seg_3ms.xplane.pb"


class OtherNames:
    """A family whose program names other scopes than the recorded one."""
    SCOPES = ("experts", "down_proj", "attend")
    WITNESS = ("experts",)


def test_scope_of_reads_the_names_it_is_given():
    op = "jit(seg)/while/body/closed_call/M/layer_3/mlp/experts/dot_general"
    assert scopes.scope_of(op, OtherNames.SCOPES) == "experts"
    assert scopes.scope_of(op, families.load("llama-hf").SCOPES) == "mlp"
    assert scopes.scope_of(op, ()) == ""


def test_the_recorded_cut_splits_by_the_familys_own_names():
    llama = scopes.segment_split(TRACE, families.load("llama-hf"))
    other = scopes.segment_split(TRACE, OtherNames)
    # the same operations and the same runs, under other names
    assert other["run_s"] == llama["run_s"]
    assert other["op_s"] == pytest.approx(llama["op_s"], rel=1e-12)
    assert set(other["by_scope"]) <= {"", "down_proj", "attend"}
    assert other["by_scope"]["down_proj"] > 0
    assert not other["scoped"] and llama["scoped"]
    # llama's split is what was recorded with the module constant (PR 24)
    assert llama["by_scope"]["mlp"] == pytest.approx(0.001787067, rel=1e-6)
