"""The envelope: every program shape a traffic mix can reach, derived from
the traffic FILE and the configuration (never from the seed), and sent
before the window so that nothing compiles inside it.

What the engine compiles at first use (``runtime/continuous.py`` at commit
fb0103a; the constants below are its defaults, which the cells leave
alone): a ragged group-prefill program per (joiner-count bucket 1/2/4/8 x
power-of-two prompt bucket) for prompts up to ``GROUP_PREFILL_MAX``; a solo
prefill per prompt bucket above it; one segment program per power-of-two
decode window, chosen as the bucket of (furthest live position + segment).
If the program's bucketing changes, the per-layer metric ``window_compiles``
stops reading 0 and says so.

Two phases. Singles: one request per prompt bucket, alone, 16 tokens — the
count-1 prefill of that bucket and the decode window just above it, which
walks every window the mix can reach. Bursts: with a pacer request keeping
the engine in its segment loop, k requests at once join at the same segment
boundary and prefill as one group of bucket(k); the pacer holds one slot,
so the largest group is slots - 1.
"""

from __future__ import annotations

import random
import threading
import time

from benchmark import client, traffic as T
from benchmark.bundle import BenchFailure

MIN_BUCKET = 16
SEGMENT = 16
GROUP_PREFILL_MAX = 256


def next_bucket(n: int, lo: int = MIN_BUCKET) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def coverage(traffic: dict, config: dict) -> dict:
    """The fixed coverage list of a (traffic, configuration) pair."""
    env = T.lengths(traffic)
    window = int(config["engine_window"])
    slots = int(config.get("recipe_extra", {}).get("batch_max", 8))
    if env["total_max"] > window:
        raise BenchFailure(
            f"traffic's longest request ({env['total_max']}) does not fit "
            f"the configuration's engine window ({window})")
    buckets, b = [], next_bucket(env["prompt_min"])
    while True:
        buckets.append(b)
        if b >= env["prompt_max"]:
            break
        b *= 2
    # a prompt that stands for its bucket: the bucket's top, inside the mix
    stand = {b: min(b, env["prompt_max"]) for b in buckets}
    group = [b for b in buckets if stand[b] <= GROUP_PREFILL_MAX]
    counts, c = [], 2
    while c <= slots:
        counts.append(c)
        c *= 2
    # joiner-count bucket -> requests to fire at once to land in it
    burst_of = {c: min(c, slots - 1) for c in counts
                if next_bucket(min(c, slots - 1), 1) == c}
    windows, w = [], next_bucket(env["prompt_min"] + SEGMENT)
    top = min(next_bucket(env["total_max"]), window)
    while w <= top:
        windows.append(w)
        w *= 2
    singles = []
    for b in buckets:
        singles.append((stand[b], SEGMENT))
    for w in windows:
        # a row is served by window w while w/2 < position + segment <= w
        s = min(max(w // 2, env["prompt_min"]), env["prompt_max"])
        new = max(SEGMENT, w // 2 - s + SEGMENT)
        if new <= env["new_max"] and (new > SEGMENT
                                      or (s, SEGMENT) not in singles):
            singles.append((s, new))
    return {"prompt_buckets": buckets, "group_buckets": group,
            "solo_buckets": [b for b in buckets if b not in group],
            "joiner_counts": [1] + counts, "burst_of": burst_of,
            "decode_windows": windows, "singles": singles, "stand": stand,
            "pacer": (env["prompt_min"], env["new_max"]), "slots": slots}


def missing(cov: dict, served) -> list:
    """The (joiner count, prompt bucket) pairs of the coverage list whose
    group-prefill program the server does not list yet (/metrics
    ``handler.decode_buckets``, keys ``["stream", count, bucket, ...]``)."""
    keys = (served.metrics().get("handler") or {}).get("decode_buckets", [])
    have = {(k[1], k[2]) for k in keys if k and k[0] == "stream"}
    return [(c, b) for b in cov["group_buckets"] for c in cov["burst_of"]
            if (c, b) not in have]


def send(served, traffic: dict, config: dict, rounds: int = 4) -> dict:
    """Send the coverage list. Prompt ids are fixed (seed 0): the warm-up
    is the same work in every run. A burst that straddled a segment
    boundary prefilled as two smaller groups: the server's program list
    says which pairs are still missing, and those are sent again."""
    cov = coverage(traffic, config)
    rng = random.Random(0)
    vocab = config["vocab_size"]
    host, port = "127.0.0.1", served.port
    failed: list = []
    rid = [0]
    stand, burst_of = cov["stand"], cov["burst_of"]

    def request(plen, new):
        rid[0] += 1
        rec = client.Record(-rid[0], T.prompt_ids(plen, vocab, rng), new,
                            time.monotonic())
        client.complete(host, port, rec, timeout=1100.0)
        if not rec.ok:
            failed.append(rec.error)

    t0 = time.monotonic()
    for plen, new in cov["singles"]:
        request(plen, new)
    t_singles = time.monotonic() - t0

    stop = threading.Event()
    pacing = threading.Event()

    def pacer():
        while not stop.is_set() and not failed:
            rid[0] += 1
            rec = client.Record(-rid[0], T.prompt_ids(cov["pacer"][0], vocab,
                                                      random.Random(1)),
                                cov["pacer"][1], time.monotonic())
            client.complete(host, port, rec, timeout=1100.0,
                            on_first=pacing.set)
            pacing.clear()
            if not rec.ok:
                failed.append(f"pacer: {rec.error}")

    pace = threading.Thread(target=pacer, daemon=True)
    todo = [(c, b) for b in cov["group_buckets"] for c in burst_of]
    sent_rounds = 0
    if todo:
        pace.start()
    while todo and sent_rounds < rounds and not failed:
        sent_rounds += 1
        for count, bucket in todo:
            if not pacing.wait(timeout=1100.0):
                failed.append("pacer never started decoding")
                break
            threads = [threading.Thread(target=request,
                                        args=(stand[bucket], SEGMENT),
                                        daemon=True)
                       for _ in range(burst_of[count])]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=1100.0)
        todo = missing(cov, served)
    stop.set()
    if pace.is_alive():
        pace.join(timeout=1100.0)
    if failed:
        raise BenchFailure(f"warm-up requests failed: {failed[:3]}")
    return {"coverage": {k: cov[k] for k in
                         ("prompt_buckets", "group_buckets", "solo_buckets",
                          "joiner_counts", "decode_windows")},
            "requests": rid[0], "burst_rounds": sent_rounds,
            "still_missing": todo, "singles_s": round(t_singles, 2),
            "seconds": round(time.monotonic() - t0, 2)}
