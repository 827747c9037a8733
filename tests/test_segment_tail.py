"""A decode segment reads its cache and writes it once (PR 30).

Where ``llama.segment_keeps_tail`` says so (one query a KV head, the XLA
attention), the engine's two plain segment programs
(``LlamaServer._stream_fns``'s ``seg`` and ``_windowed_seg_fn``) keep the
B-slot cache read-only inside their scan: a step's K/V goes to a
segment-long tail the scan carries, a step attends cache and tail under
one softmax, and one scatter a leaf merges the tail after the scan
(``llama._scan_decode``, ``tail_window``). The form it replaced, a scatter
into the cache every step, is still what ``_scan_decode`` does without
``tail_window`` (grouped-query and latent caches, the fused programs, the
speculative segments, the sp-sharded and blocked backends), so it is the
reference here: ``segment_keeps_tail`` patched to False gives the parent's
programs, patched to True the tail whatever the shapes (the ``gqa``
layout: the tail's arithmetic does not depend on the rule that picks it).

An eva cache (a ring beside chunk summaries, PR 34) keeps a tail of its
own: the segment's rows AND the summaries they complete, a merge that wraps
round the ring (``eva._eva_tail_attend``, ``eva.tail_merge``).
Its cases are at the end: the toy twin of ``tests/test_evabyte.py`` (window
32) with chunks of 4, where a 16-step segment completes several, and of 16,
where it completes one, rows placed on every side of a window's edge.

CPU, float32. The two forms are the same function with the softmax's sum
taken in another order (cache keys, then tail keys), and layer 2's K/V is
computed from layer 1's attention, so logprobs, cache and the next step's
logits agree to a few float32 roundings (2e-6 here) and not bit for bit:
1e-5. Tokens are exact. What IS bitwise: the windowed program against the
full-window one, and every position a segment has no business writing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lambdipy_tpu.models import eva as eva_kind
from lambdipy_tpu.models import llama, registry

B, SB, CACHE_LEN, WINDOW, SEGMENT = 4, 32, 128, 64, 16
LENGTHS = (5, 17, 32, 9)            # ragged: every row decodes from its own end
TOL = 1e-5
HF_TOY = dict(vocab_size=512, hidden=128, layers=2, heads=4, kv_heads=4,
              mlp=256, max_len=256)
# layout -> what it changes of HF_TOY
LAYOUTS = {"mha": {}, "mha_int8": {"kv_quant": "int8"}, "gqa": {"kv_heads": 2}}
PROGRAMS = {"full": CACHE_LEN, "windowed": WINDOW}


def build(layout):
    return registry.get("llama-hf").build(
        dtype="float32", quant=None, extra={**HF_TOY, **LAYOUTS[layout]})


class Served:
    """One layout's parameters and, for each form, a server of its own
    (a server caches its programs by shape key, not by form)."""

    def __init__(self, layout):
        self.adapter = build(layout)
        self.params = self.adapter.init_params(seed=0)
        self.servers = {}

    def seg(self, monkeypatch, tail: bool, window: int):
        monkeypatch.setattr(llama, "segment_keeps_tail", lambda cfg: tail)
        server = self.servers.setdefault(
            tail, self.adapter.make_server(self.params))
        if window == CACHE_LEN:
            return server._stream_fns(B, SB, CACHE_LEN, SEGMENT)[1]
        return server._windowed_seg_fn(B, CACHE_LEN, window, SEGMENT)

    def carry(self, eos=None):
        """The ragged carry a group prefill leaves, and the knob operands."""
        server = self.servers.setdefault(
            True, self.adapter.make_server(self.params))
        t, k, p, rng, eos_id = server._knob_operands(0.0, None, None, 0, eos,
                                                     b=B)
        prefill = server._stream_fns(B, SB, CACHE_LEN, SEGMENT)[0]
        prompt = jax.random.randint(jax.random.PRNGKey(1), (B, SB), 1, 500)
        carry = prefill(self.params, prompt, jnp.asarray(LENGTHS, jnp.int32),
                        t, k, p, rng, eos_id)
        return carry, (t, k, p), eos_id

    def run(self, seg, carry, knobs, eos_id, segments=1):
        outs = []
        for _ in range(segments):
            out, carry = seg(self.params, *knobs, *carry, eos_id)
            outs.append(out)
        return outs, carry


@pytest.fixture(scope="module", params=list(LAYOUTS))
def served(request):
    return Served(request.param)


def leaves(cache):
    return [(i, name, np.asarray(val)) for i, entry in enumerate(cache)
            for name, val in entry.items() if name != "index"]


def assert_cache_close(got, want):
    for (i, name, a), (_, _, b) in zip(leaves(got), leaves(want)):
        if a.dtype == np.int8:      # a rounding at a .5 boundary moves one unit
            assert np.abs(a.astype(np.int32) - b).max() <= 1, (i, name)
        else:
            np.testing.assert_allclose(a, b, atol=TOL, rtol=0,
                                       err_msg=f"layer {i} {name}")


def at(carry, pos):
    """``carry`` with every row's position (and cache index) set to ``pos``."""
    first, lp, cache, _, done, keys = carry
    pos = jnp.asarray(pos, jnp.int32)
    return (first, lp, [{**entry, "index": pos} for entry in cache], pos,
            done, keys)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_a_tail_segment_serves_what_the_per_step_write_served(
        served, program, monkeypatch):
    """Two 16-step segments from a ragged carry: the parent's tokens
    exactly; logprobs, the merged cache and the next step's logits within
    1e-5; ``index`` leaves the program as ``pos + segment``."""
    window = PROGRAMS[program]
    carry, knobs, eos_id = served.carry()
    want, want_carry = served.run(served.seg(monkeypatch, False, window),
                                  carry, knobs, eos_id, segments=2)
    got, got_carry = served.run(served.seg(monkeypatch, True, window),
                                carry, knobs, eos_id, segments=2)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g[0], w[0])               # tokens
        np.testing.assert_allclose(g[1], w[1], atol=TOL, rtol=0)  # logprobs
    end = np.asarray(LENGTHS) + 2 * SEGMENT
    np.testing.assert_array_equal(got_carry[3], end)
    for entry in got_carry[2]:
        np.testing.assert_array_equal(entry["index"], end)
    assert_cache_close(got_carry[2], want_carry[2])
    model = served.servers[True].model

    def logits(c):
        return model.apply(served.params, c[0][:, None],
                           positions=c[3][:, None], cache=c[2])[0]

    np.testing.assert_allclose(logits(got_carry), logits(want_carry),
                               atol=TOL, rtol=0)


def test_the_windowed_program_is_bitwise_the_full_window_one(served,
                                                             monkeypatch):
    """Every row's positions stay under the window for both segments (the
    engine's condition for dispatching it): tokens, logprobs and the whole
    merged cache are the full-window program's, bit for bit."""
    carry, knobs, eos_id = served.carry()
    full, full_carry = served.run(served.seg(monkeypatch, True, CACHE_LEN),
                                  carry, knobs, eos_id, segments=2)
    win, win_carry = served.run(served.seg(monkeypatch, True, WINDOW),
                                carry, knobs, eos_id, segments=2)
    for f, w in zip(full, win):
        np.testing.assert_array_equal(f[0], w[0])
        np.testing.assert_array_equal(f[1], w[1])
    for (i, name, a), (_, _, b) in zip(leaves(full_carry[2]),
                                       leaves(win_carry[2])):
        np.testing.assert_array_equal(a, b, err_msg=f"layer {i} {name}")


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_a_position_past_the_cache_drops_and_touches_no_live_one(
        served, program, monkeypatch):
    """The drop contract of the merge: a finished slot's stale position
    runs past the cache (row 1: 5 positions left of 16; row 3: none) or,
    in the windowed program, past the window (row 2). Nothing clamps:
    every position below a row's own start is bitwise what it was, in
    every row; row 1's last 5 positions are written, the other 11 land
    nowhere; the live rows' tokens are the per-step write's."""
    window = PROGRAMS[program]
    carry, knobs, eos_id = served.carry()
    base = np.array([LENGTHS[0], CACHE_LEN - 5, WINDOW + 6, CACHE_LEN + 40])
    carry = at(carry, base)
    before = leaves(carry[2])
    got, got_carry = served.run(served.seg(monkeypatch, True, window),
                                carry, knobs, eos_id)
    want, want_carry = served.run(served.seg(monkeypatch, False, window),
                                  carry, knobs, eos_id)
    np.testing.assert_array_equal(got[0][0][0], want[0][0][0])  # the live row
    np.testing.assert_array_equal(got_carry[3], base + SEGMENT)
    after, stepwise = leaves(got_carry[2]), leaves(want_carry[2])
    for (i, name, old), (_, _, new), (_, _, ref) in zip(before, after,
                                                        stepwise):
        for r in range(B):
            np.testing.assert_array_equal(
                new[r, :base[r]], old[r, :base[r]],
                err_msg=f"layer {i} {name} row {r}: a live position moved")
        if window == CACHE_LEN and old.dtype != np.int8:
            # the tail wrote what the per-step write wrote, where it fits
            np.testing.assert_allclose(new[1, base[1]:], ref[1, base[1]:],
                                       atol=TOL, rtol=0)
            assert np.abs(new[1, base[1]:]).max() > 0
        np.testing.assert_array_equal(new[3], old[3])


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_a_done_rows_garbage_reaches_nothing_a_kept_token_reads(
        served, program, monkeypatch):
    """Row 1 meets its eos a few steps into the segment, row 2 enters it done:
    both step on as garbage (filler tokens, logprob 0) into their OWN
    row's tail. The other rows' tokens, logprobs and cache rows are bit
    for bit a run's in which nobody stopped; row 1's tokens up to its eos
    and the positions they read too; no position below any row's start
    moves."""
    window = PROGRAMS[program]
    seg = served.seg(monkeypatch, True, window)
    carry, knobs, no_eos = served.carry()
    (free,), free_carry = served.run(seg, carry, knobs, no_eos)
    row = np.asarray(free[0][1])    # a step whose token row 1 has not served
    stop_at = next(j for j in range(2, SEGMENT - 2) if row[j] not in row[:j])
    eos_tok = int(row[stop_at])
    eos_id = jnp.asarray([-1, eos_tok, 7, -1], jnp.int32)
    first, lp, cache, pos, done, keys = carry
    stopped_in = (first, lp, cache, pos, done.at[2].set(True), keys)
    (out,), out_carry = served.run(seg, stopped_in, knobs, eos_id)
    toks, lps = np.asarray(out[0]), np.asarray(out[1])
    np.testing.assert_array_equal(out_carry[4], [False, True, True, False])
    for r in (0, 3):
        np.testing.assert_array_equal(toks[r], free[0][r])
        np.testing.assert_array_equal(lps[r], free[1][r])
    upto = slice(0, stop_at + 1)
    np.testing.assert_array_equal(toks[1, upto], free[0][1, upto])
    assert (toks[1, stop_at + 1:] == eos_tok).all()
    assert (lps[1, stop_at + 1:] == 0).all()
    assert (toks[2, 1:] == 7).all() and (lps[2, 1:] == 0).all()
    for (i, name, old), (_, _, new), (_, _, ref) in zip(
            leaves(carry[2]), leaves(out_carry[2]), leaves(free_carry[2])):
        for r in range(B):
            np.testing.assert_array_equal(new[r, :LENGTHS[r]],
                                          old[r, :LENGTHS[r]])
        for r in (0, 3):
            np.testing.assert_array_equal(new[r], ref[r])
        kept = LENGTHS[1] + stop_at + 1     # what row 1's kept tokens read
        np.testing.assert_array_equal(new[1, :kept], ref[1, :kept],
                                      err_msg=f"layer {i} {name}")


def test_a_step_reads_no_tail_position_before_its_step_wrote_it(served):
    """On the chip the compiler hands the scan its tail uninitialised (it
    sees that the loop writes every position: PERF.md section 6, PR 30),
    and 0 x NaN is NaN. One decode step, tail position 3, over a tail of
    zeros and over one whose positions from 3 on are poisoned with NaN
    (an int8 tail: extreme values under NaN scales): the same logits bit
    for bit, finite, and the step wrote its own position and no other."""
    model = served.adapter.make_server(served.params).model
    carry, _, _ = served.carry()
    first, _, cache, pos, _, _ = carry
    j = 3

    def tails(poison):
        def leaf(val):
            shape = (B, SEGMENT) + val.shape[2:]
            if not poison:
                return jnp.zeros(shape, val.dtype)
            bad = -128 if val.dtype == jnp.int8 else jnp.nan
            unwritten = (jnp.arange(SEGMENT) >= j)[None, :, None, None]
            return jnp.where(unwritten, bad, 0).astype(val.dtype) \
                * jnp.ones(shape, val.dtype)
        return [{name: leaf(val) for name, val in entry.items()
                 if name != "index"} for entry in cache]

    def step(tail):
        entries = [{**entry, "tail": t, "step": jnp.int32(j)}
                   for entry, t in zip(cache, tail)]
        return model.apply(served.params, first[:, None],
                           positions=(pos + j)[:, None], cache=entries)

    clean, clean_tail = step(tails(False))
    dirty, dirty_tail = step(tails(True))
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(clean, dirty)
    for a, b, before in zip(clean_tail, dirty_tail, tails(True)):
        for name in a:
            np.testing.assert_array_equal(a[name][:, j], b[name][:, j])
            others = np.arange(SEGMENT) != j
            np.testing.assert_array_equal(np.asarray(b[name])[:, others],
                                          np.asarray(before[name])[:, others])


def test_which_segments_keep_a_tail():
    """One query a KV head under the XLA attention takes the tail: there
    the compiler round-trips the whole cache for a per-step write. Grouped
    queries and the latent cache keep the per-step write, which the
    compiler does in place (``tests/test_chip_compile.py`` holds both on
    the compiled text); the blocked kernel and the sp-sharded step are
    handed one cache to attend."""
    def keeps(**over):
        return llama.segment_keeps_tail(llama.LlamaConfig(**{**HF_TOY, **over}))

    assert keeps() and keeps(kv_quant="int8")
    assert keeps(attn_backend="ring")                # no sp mesh here
    assert not keeps(kv_heads=2)
    assert not keeps(attn_backend="blocked")
    assert not keeps(attn_kind="latent", qk_nope=16, qk_rope=8, v_head=16,
                     kv_lora_rank=32)
    # an eva cache is multi-head by construction and takes its own tail
    assert keeps(attn_kind="eva", window_size=32, chunk_size=4)


def test_a_tp2_engine_keeps_the_tail_sharded_and_serves_the_same_tokens(
        cpu_devices):
    """The tail and the merged cache carry the cache's own hint (KV heads
    over ``tp``): a 4-slot tp=2 engine over a multi-head toy serves, for
    concurrent ragged rows through window-bucketed and full-window
    segments, the tokens of the unsharded server's fused program (which
    writes its cache every step), and afterwards a device still holds
    half the KV: a merge that gathered the cache would show here."""
    from concurrent.futures import ThreadPoolExecutor

    from lambdipy_tpu.parallel.mesh import make_mesh, use_mesh
    from lambdipy_tpu.parallel.sharding import shard_params
    from lambdipy_tpu.runtime.continuous import ContinuousBatcher

    adapter = build("mha")
    assert llama.segment_keeps_tail(adapter.config)
    params = adapter.init_params(seed=0)
    ref = adapter.make_server(params)
    rng = np.random.default_rng(0)
    rows = [rng.integers(1, 500, 5 + 9 * i).tolist() for i in range(4)]
    want = [ref.generate(r, max_new_tokens=40) for r in rows]
    mesh = make_mesh({"tp": 2}, devices=cpu_devices[:2])
    with use_mesh(mesh):
        sharded = shard_params(params, mesh, adapter.tp_rules)
    eng = ContinuousBatcher(adapter.make_server(sharded, mesh=mesh), slots=4,
                            segment=8)
    with ThreadPoolExecutor(max_workers=len(rows)) as ex:
        got = list(ex.map(lambda r: eng.generate(r, max_new_tokens=40), rows))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    with eng._lock:
        while eng._engine_running:
            eng._lock.wait(0.05)
    stats = eng.stats()
    assert stats["mesh"]["segments_sharded"] > 0
    assert 0 < stats["mesh"]["kv_bytes_per_device"] \
        <= 0.55 * stats["mesh"]["kv_bytes_replicated"]
    assert len(stats["decode_window"]["buckets"]) >= 2


# -- an eva cache: a ring tail and a summary tail (PR 34) ----------------------

EVA_WIN, EVA_CACHE, EVA_BUCKET, EVA_SB = 32, 256, 128, 64
# where each row's first segment begins: a window's edge falls at its first
# step (row 0), inside it (row 1: position 64 at step 8), at its last step
# (row 2: position 64 at step 15) and not at all (row 3)
EVA_BASES = (32, 56, 49, 35)
EVA_PROGRAMS = {"full": EVA_CACHE, "windowed": EVA_BUCKET}


class ServedEva(Served):
    """The toy twin of ``benchmark/configs/rehearsal-eva.json`` (window 32,
    4 heads, 2 layers, two prediction heads) at a chunk size."""

    def __init__(self, chunk):
        import json
        from pathlib import Path

        from benchmark import families

        config = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                             / "configs" / "rehearsal-eva.json").read_text())
        dims = families.of(config).dims_of(config)
        assert dims["window_size"] == EVA_WIN
        self.adapter = registry.get("evabyte").build(
            dtype="float32", quant="int8",
            extra={**dims, "chunk_size": chunk})
        self.chunk = chunk
        self.params = self.adapter.init_params(seed=0)
        self.servers = {}

    def seg(self, monkeypatch, tail: bool, window: int):
        monkeypatch.setattr(llama, "segment_keeps_tail", lambda cfg: tail)
        server = self.servers.setdefault(
            tail, self.adapter.make_server(self.params))
        if window == EVA_CACHE:
            return server._stream_fns(B, EVA_SB, EVA_CACHE, SEGMENT)[1]
        return server._windowed_seg_fn(B, EVA_CACHE, window, SEGMENT)

    def carry(self, eos=None, bases=EVA_BASES):
        server = self.servers.setdefault(
            True, self.adapter.make_server(self.params))
        t, k, p, rng, eos_id = server._knob_operands(0.0, None, None, 0, eos,
                                                     b=B)
        prefill = server._stream_fns(B, EVA_SB, EVA_CACHE, SEGMENT)[0]
        prompt = jax.random.randint(jax.random.PRNGKey(1), (B, EVA_SB), 1, 300)
        carry = prefill(self.params, prompt, jnp.asarray(bases, jnp.int32),
                        t, k, p, rng, eos_id)
        return carry, (t, k, p), eos_id


@pytest.fixture(scope="module", params=[4, 16],
                ids=["chunks_of_4", "chunks_of_16"])
def eva(request):
    return ServedEva(request.param)


def edge_steps(base, segments):
    """By hand: the steps of each of ``segments`` segments from ``base``
    that come after a window's edge crossed inside that segment."""
    out = []
    for n in range(segments):
        start = base + n * SEGMENT
        out.append(sum(t // EVA_WIN != start // EVA_WIN
                       for t in range(start, start + SEGMENT)))
    return out


@pytest.mark.parametrize("program", list(EVA_PROGRAMS))
def test_an_eva_tail_segment_serves_what_the_per_step_write_served(
        eva, program, monkeypatch):
    """Three 16-step segments from rows on every side of a window's edge
    (a row that completes a window's last chunk attends its summary at the
    very next step, inside the same segment; a ring tail wraps at the
    merge): the per-step write's tokens exactly; logprobs, ring and
    summaries within 1e-5; the same keys visible and the same chunks
    written, a row a segment; the edge column by hand."""
    window = EVA_PROGRAMS[program]
    carry, knobs, eos_id = eva.carry()
    want, want_carry = eva.run(eva.seg(monkeypatch, False, window),
                               carry, knobs, eos_id, segments=3)
    got, got_carry = eva.run(eva.seg(monkeypatch, True, window),
                             carry, knobs, eos_id, segments=3)
    for n, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g[0], w[0])               # tokens
        np.testing.assert_allclose(g[1], w[1], atol=TOL, rtol=0)  # logprobs
        np.testing.assert_array_equal(g[2][:, :2], w[2][:, :2])
        assert (np.asarray(w[2])[:, 2] == 0).all()
        np.testing.assert_array_equal(
            g[2][:, 2], [edge_steps(b, 3)[n] for b in EVA_BASES])
        starts = np.asarray(EVA_BASES) + n * SEGMENT
        np.testing.assert_array_equal(     # a chunk's end a row, by hand
            g[2][:, 1], [sum(t % eva.chunk == eva.chunk - 1
                             for t in range(b, b + SEGMENT)) for b in starts])
    assert edge_steps(EVA_BASES[1], 1) == [8] \
        and edge_steps(EVA_BASES[2], 1) == [1] \
        and edge_steps(EVA_BASES[0], 3) == [0, 0, 0]
    end = np.asarray(EVA_BASES) + 3 * SEGMENT
    np.testing.assert_array_equal(got_carry[3], end)
    for entry in got_carry[2]:
        np.testing.assert_array_equal(entry["index"], end)
    assert_cache_close(got_carry[2], want_carry[2])
    model = eva.servers[True].model

    def logits(c):
        return model.apply(eva.params, c[0][:, None],
                           positions=c[3][:, None], cache=c[2])[0]

    np.testing.assert_allclose(logits(got_carry), logits(want_carry),
                               atol=TOL, rtol=0)


@pytest.mark.parametrize("program", list(EVA_PROGRAMS))
def test_an_eva_tail_reads_nothing_of_a_done_row_or_of_the_last_tenant(
        eva, program, monkeypatch):
    """Row 1 meets its eos a few steps into the segment and row 2 enters it
    done: both step on as garbage into their OWN row's tails, and every
    slot the masks hide (ring slots at and past a row's place in its
    window, summaries from its open chunk on: what a longer tenant of the
    slot left) holds 1e4. Rows 0 and 3 serve, bit for bit, the tokens and
    logprobs of a run over a clean cache in which nobody stopped, and row
    1 too up to its eos."""
    window = EVA_PROGRAMS[program]
    seg = eva.seg(monkeypatch, True, window)
    carry, knobs, no_eos = eva.carry()
    (free,), _ = eva.run(seg, carry, knobs, no_eos)
    row = np.asarray(free[0][1])
    stop_at = next(j for j in range(2, SEGMENT - 2) if row[j] not in row[:j])
    eos_tok = int(row[stop_at])
    eos_id = jnp.asarray([-1, eos_tok, 7, -1], jnp.int32)
    first, lp, cache, pos, done, keys = carry
    bases = np.asarray(EVA_BASES)
    hidden = {"k": np.arange(EVA_WIN)[None] >= (bases % EVA_WIN)[:, None],
              "sk": np.arange(EVA_CACHE // eva.chunk)[None]
              >= (bases // eva.chunk)[:, None]}
    hidden.update(v=hidden["k"], sv=hidden["sk"])
    dirty = [{name: (val if name == "index" else jnp.where(
                  hidden[name][:, :, None, None], 1e4, val))
              for name, val in entry.items()} for entry in cache]
    stopped_in = (first, lp, dirty, pos, done.at[2].set(True), keys)
    (out,), out_carry = eva.run(seg, stopped_in, knobs, eos_id)
    toks, lps = np.asarray(out[0]), np.asarray(out[1])
    np.testing.assert_array_equal(out_carry[4], [False, True, True, False])
    for r in (0, 3):
        np.testing.assert_array_equal(toks[r], free[0][r])
        np.testing.assert_array_equal(lps[r], free[1][r])
        np.testing.assert_array_equal(out[2][r], free[2][r])
    upto = slice(0, stop_at + 1)
    np.testing.assert_array_equal(toks[1, upto], free[0][1, upto])
    assert (toks[1, stop_at + 1:] == eos_tok).all()
    assert (toks[2, 1:] == 7).all() and (lps[2, 1:] == 0).all()


def test_an_eva_step_reads_no_tail_slot_before_its_step_wrote_it(eva):
    """On the chip the scan's tails arrive uninitialised where the loop
    writes them (PR 30), and 0 x NaN is NaN. Steps 0-9 of a segment, then
    step 10 over the tails they left and over the same tails with every
    ring-tail row of a position from ``base + 10`` on and every summary
    slot no step has completed yet poisoned with NaN: the same logits bit
    for bit, finite; the step wrote its own ring-tail row and the summary
    its rows completed, and nothing else. Row 1 is past the edge it crossed
    at step 8 and attends the summary tail."""
    cfg = eva.adapter.config
    chunk = eva.chunk
    model = eva.adapter.make_server(eva.params).model
    carry, _, _ = eva.carry()
    first, _, cache, pos, _, _ = carry
    tails = eva_kind.tail_init(cfg, cache, pos, SEGMENT)
    n_sum = tails[0]["sk"].shape[1]
    assert n_sum == -(-SEGMENT // chunk)
    assert tails[0]["k"].shape[1] == (1 + n_sum) * chunk

    def step(tails, j, tok):
        plan = eva_kind.tail_plan(cfg, cache[0], tails[0], pos, jnp.int32(j))
        entries = [{**entry, "index": pos, "tail": t, "plan": plan}
                   for entry, t in zip(cache, tails)]
        return model.apply(eva.params, tok[:, None],
                           positions=(pos + j)[:, None], cache=entries)

    tok, j = first, 10
    for i in range(j):
        logits, tails = step(tails, i, tok)
        tok = jnp.argmax(logits[:, 0, :cfg.vocab_size], axis=-1).astype(
            first.dtype)
    bases = np.asarray(EVA_BASES)
    # the position each ring-tail row holds, and the last position of the
    # chunk each summary slot is for
    held_at = (bases // chunk * chunk)[:, None] \
        + np.arange(tails[0]["k"].shape[1])[None]
    ends = (bases[:, None] // chunk + np.arange(n_sum)[None] + 1) * chunk - 1
    unwritten = {"k": held_at >= bases[:, None] + j,
                 "sk": ends >= bases[:, None] + j}
    unwritten.update(v=unwritten["k"], sv=unwritten["sk"])
    assert unwritten["sk"].any()
    poisoned = [{name: jnp.where(unwritten[name][:, :, None, None], jnp.nan,
                                 val) for name, val in t.items()}
                for t in tails]
    clean, clean_tails = step(tails, j, tok)
    dirty, dirty_tails = step(poisoned, j, tok)
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(clean, dirty)
    mine = held_at == bases[:, None] + j       # the row step j writes
    lands = ends == bases[:, None] + j         # the chunk step j completes
    for a, b, before in zip(clean_tails, dirty_tails, poisoned):
        for wrote, names in ((mine, ("k", "v")), (lands, ("sk", "sv"))):
            for name in names:
                np.testing.assert_array_equal(np.asarray(a[name])[wrote],
                                              np.asarray(b[name])[wrote])
                assert np.isfinite(np.asarray(b[name])[wrote]).all()
                np.testing.assert_array_equal(np.asarray(b[name])[~wrote],
                                              np.asarray(before[name])[~wrote])


def test_an_eva_ring_shorter_than_the_segment_keeps_the_per_step_write(
        monkeypatch):
    """One merge would write a ring of 8 slots twice over from a 16-step
    tail (a scatter with duplicate slots): ``_segment_decode`` gives such a
    cache, which only a toy has, the per-step program whatever the rule
    says."""
    eva = ServedEva(4)

    def text(tail):
        monkeypatch.setattr(llama, "segment_keeps_tail", lambda cfg: tail)
        server = eva.adapter.make_server(eva.params)
        key = ("stream", B, 8, 8, SEGMENT)
        seg_ops = jax.eval_shape(lambda: server._aot_examples(key))[1]
        return server._stream_fns(*key[1:])[1].lower(
            eva.params, *seg_ops).as_text()

    assert text(True) == text(False)
