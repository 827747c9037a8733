"""The reference against itself and against its control, at a size a test
run can hold. The control — the reference with its int8 kernels rounded to
int4, put in the program's place — has to come out NOT correct under the
limit a sound program passes."""

import json
from pathlib import Path

import numpy as np

from benchmark import reference, weights

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "rehearsal-tiny.json").read_text())
SHAPE = (4, 64, 24)


def _extend(rows: list) -> list:
    """One greedy step for every row: the reference's own next token."""
    return [(tokens + [int(np.argmax(
        reference.next_token_logits(CONFIG, tokens)))], n)
        for tokens, n in rows]


def test_greedy_tokens_have_no_gap_and_the_control_fails_the_limit():
    rows = [(list(map(int, np.random.default_rng(5).integers(
        1, CONFIG["vocab_size"], n))), n) for n in (9, 17, 30, 12)]
    for _ in range(20):
        rows = _extend(rows)
    out = reference.served_gaps(CONFIG, rows, shape=SHAPE, control=True)
    limit = CONFIG["correct"]["limit"]
    assert out["served_tokens"] == 80
    assert max(out["gap"]) <= 1e-4          # the reference's own choice
    assert max(out["control_gap"]) > 0.05   # int4 picks other tokens ...
    # ... and far enough from the best that a limit between the two holds:
    assert max(out["control_gap"]) > 3 * max(max(out["gap"]), 0.01)
    assert limit is not None


def test_a_wrong_token_shows_as_a_wide_gap():
    rows = [(list(range(1, 12)) + [3, 3, 3, 3], 11)]
    out = reference.served_gaps(CONFIG, rows, shape=SHAPE)
    assert max(out["gap"]) > 0.5


def test_leaves_depend_on_seed_and_path_only():
    a = weights.leaf(CONFIG, "layer_0/q_proj/kernel_int8", (64, 64), "int8")
    b = weights.leaf(CONFIG, "layer_0/q_proj/kernel_int8", (64, 64), "int8")
    c = weights.leaf(CONFIG, "layer_1/q_proj/kernel_int8", (64, 64), "int8")
    d = weights.leaf(dict(CONFIG, weights_seed=4),
                     "layer_0/q_proj/kernel_int8", (64, 64), "int8")
    assert (a == b).all() and (a != c).any() and (a != d).any()
    e = weights.leaf(CONFIG, "embed/embedding", (512, 64), "float32")
    import ml_dtypes
    assert (e.astype(ml_dtypes.bfloat16).astype(np.float32) == e).all()
