"""Traffic mixes: a data file of parameters, read by the generator its
``kind`` names (``benchmark/generators/<kind>.py``), found by name.

Every seed gets the SAME set of sizes and the same set of arrival gaps, in
another order: sizes are the quantiles of the file's distribution at evenly
spaced probabilities, not draws, laid out once in an order fixed by the
file's ``order_seed``; ``--seed`` ROTATES that sequence (and draws the prompt
ids and which requests are checked). So two seeds differ in where the
sequence starts, never in how much work the window holds nor in which long
prompt follows which burst — the queueing pattern, which is what moves a
tail, is the same in every run.
"""

from __future__ import annotations

import importlib
import json
import math
import random
from pathlib import Path
from statistics import NormalDist

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def generator(traffic: dict):
    return importlib.import_module(f"benchmark.generators.{traffic['kind']}")


def quantile(dist: dict, p: float) -> int:
    """The ``p``-quantile of a length distribution of a traffic file,
    clipped to its ``min``..``max``."""
    if dist["dist"] == "uniform":
        v = dist["min"] + p * (dist["max"] - dist["min"])
    elif dist["dist"] == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(p))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(dist["max"], max(dist["min"], round(v))))


def rotated(items: list, seed: int) -> list:
    k = seed % len(items)
    return items[k:] + items[:k]


def sizes(traffic: dict, n: int, seed: int) -> list:
    """``n`` (prompt length, max_tokens) pairs: each list is the n evenly
    spaced quantiles of its distribution in the file's fixed order, the
    pairs rotated by ``seed``."""
    base = random.Random(traffic["order_seed"])
    probs = [(i + 0.5) / n for i in range(n)]
    prompts = [quantile(traffic["prompt_len"], p) for p in probs]
    news = [quantile(traffic["max_tokens"], p) for p in probs]
    base.shuffle(prompts)
    base.shuffle(news)
    return rotated(list(zip(prompts, news)), seed)


def exponential_gaps(traffic: dict, n: int, seed: int) -> list:
    """``n`` inter-arrival gaps of a Poisson process of ``rate_rps``: the
    evenly spaced quantiles of its exponential law in the file's fixed
    order, rotated by ``seed`` like the sizes they go with."""
    base = random.Random(traffic["order_seed"] + 1)
    gaps = [-math.log(1.0 - (i + 0.5) / n) / traffic["rate_rps"]
            for i in range(n)]
    base.shuffle(gaps)
    return rotated(gaps, seed)


def prompt_ids(length: int, vocab: int, rng: random.Random) -> list:
    # id 0 is left out: some tokenizers pad with it
    return [rng.randrange(1, vocab) for _ in range(length)]


def lengths(traffic: dict) -> dict:
    """The mix's envelope, from the file alone: shortest and longest
    prompt, longest answer, longest request."""
    p, m = traffic["prompt_len"], traffic["max_tokens"]
    return {"prompt_min": p["min"], "prompt_max": p["max"],
            "new_min": m["min"], "new_max": m["max"],
            "total_max": p["max"] + m["max"]}
