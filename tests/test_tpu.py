"""Device tests (SURVEY.md §5.3): the real chip, through the REAL serve
path — build the flagship bundle, deploy it, and assert the north-star
budgets (BASELINE.json: ResNet-50 < 15 ms p50, < 10 s cold start).

Marked ``tpu`` and deselected by default (pyproject addopts). The suite's
conftest pins this process AND its environment to the CPU, so these tests
do all jax work in subprocesses whose environment lets jax choose the
platform again (``JAX_PLATFORMS=""``); a probe subprocess decides skip vs
run. Run on the chip with: ``pytest -m tpu --override-ini addopts=''``.
``python chip_smoke.py`` is the quicker proof that the main path runs
there.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "scripts"))

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def device_ok():
    from measure_baseline import tpu_reachable

    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_PLATFORMS", "")  # children choose: the TPU if there is one
    if not tpu_reachable():
        mp.undo()
        pytest.skip("no TPU found by a probe subprocess")
    yield True
    mp.undo()


def test_resnet50_serve_path_meets_north_star(device_ok, tmp_path):
    """Config 3 through build -> deploy -> HTTP invoke on the chip: the
    plain end-to-end p50 against the north-star budget."""
    from measure_baseline import measure_config, publish

    rec = measure_config(3, invokes=50, work=tmp_path)
    assert rec["platform"] == "tpu", rec
    assert rec["invoke_p50_ms"] < 15.0, rec   # BASELINE.json north star
    assert rec["cold_start_s"] < 10.0, rec    # cold-start budget
    publish({"config3": rec})


def test_bert_serve_path_on_device(device_ok, tmp_path):
    """Config 4 (jax BERT) boots and serves on the chip; latency recorded."""
    from measure_baseline import measure_config, publish

    rec = measure_config(4, invokes=30, work=tmp_path)
    assert rec["platform"] == "tpu", rec
    assert rec["invoke_p50_ms"] < 100.0, rec  # sanity bound, not the star
    publish({"config4": rec})


def test_pallas_kernels_on_device(device_ok):
    """The Pallas kernels (flash attention, blocked int8 matmul) compile
    with Mosaic and match their pure-jax references on the real chip
    within bf16 tolerance. CPU tests only ever run these in interpret mode
    (tests/test_chip_compile.py compiles them for a described chip); this
    is the one place the compiled kernels are numerics-checked on
    hardware."""
    import json
    import subprocess
    import sys as _sys

    code = (
        "import json, numpy as np, jax, jax.numpy as jnp\n"
        "from lambdipy_tpu.ops.attention import flash_attention, mha_reference\n"
        "from lambdipy_tpu.ops.quant import int8_matmul, int8_matmul_reference\n"
        "rng = np.random.default_rng(0)\n"
        "b, s, h, d = 1, 512, 4, 64\n"
        "q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)\n"
        "           for _ in range(3))\n"
        "got = np.asarray(jax.device_get(jax.jit(\n"
        "    lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)),\n"
        "    np.float32)\n"
        "ref = np.asarray(jax.device_get(mha_reference(q, k, v, causal=True)),\n"
        "                 np.float32)\n"
        "flash_rel = float(np.abs(got - ref).max() / np.abs(ref).max())\n"
        "x = jnp.asarray(rng.standard_normal((256, 512)), jnp.bfloat16)\n"
        "wf = rng.standard_normal((512, 256)).astype(np.float32)\n"
        "sc = (np.abs(wf).max(0, keepdims=True) / 127.0).astype(np.float32)\n"
        "wi = np.round(wf / sc).astype(np.int8)\n"
        "g2 = np.asarray(jax.device_get(jax.jit(int8_matmul)(\n"
        "    x, jnp.asarray(wi), jnp.asarray(sc))), np.float32)\n"
        "r2 = np.asarray(jax.device_get(int8_matmul_reference(\n"
        "    x, jnp.asarray(wi), jnp.asarray(sc))), np.float32)\n"
        "int8_rel = float(np.abs(g2 - r2).max() / np.abs(r2).max())\n"
        "print(json.dumps({'platform': jax.default_backend(),\n"
        "                  'flash_rel': flash_rel, 'int8_rel': int8_rel}))\n"
    )
    from lambdipy_tpu.utils.platform import child_env

    proc = subprocess.run([_sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=420, env=child_env())
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-800:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["platform"] != "cpu", res
    assert res["flash_rel"] < 0.02, res
    assert res["int8_rel"] < 0.02, res


def test_llama_int8_generate_serve_path(device_ok, tmp_path):
    """Config 5's serve path (int8 weights + compile-once decode) on the
    chip, at the single-chip exemplar scale; the full 8B recipe's v5e-4
    sharding is proven by the CPU-mesh dryrun, whose evidence rides in
    the published record."""
    import subprocess
    import sys as _sys
    from pathlib import Path as _Path

    from measure_baseline import measure_config, publish

    rec = measure_config(5, invokes=20, work=tmp_path)
    assert rec["platform"] == "tpu", rec
    assert rec.get("decode_tok_s", 0) > 50, rec  # sanity: real decode speed
    dry = subprocess.run(
        [_sys.executable, str(_Path(__file__).parents[1] / "__graft_entry__.py")],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "GRAFT_DRYRUN_DEVICES": "8"})
    assert dry.returncode == 0, (dry.stdout + dry.stderr)[-500:]
    lines = dry.stdout.strip().splitlines()
    rec["multichip_dryrun"] = "pass: " + (lines[-1] if lines else "(no output)")
    publish({"config5": rec})
