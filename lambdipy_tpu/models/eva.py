"""Attention kind ``eva``: EVA chunked linearized attention, as EvaByte
publishes it, a model's module (one kind for all the layers: ``attn_kind``).

Positions lie in windows of ``window_size``; a query attends the keys of ITS
window exactly (causal) and, under the same float32 softmax, ONE learned
summary (a pooled key and a pooled value, :func:`_eva_pool`) for every
``chunk_size`` positions of every earlier window. A layer's cache entry is
therefore leaves of two lengths: a ring of ``window_size`` multi-head K/V
rows, ``k`` / ``v`` (position t at slot ``t mod window_size``), and one
summary row for every chunk the cache may serve, ``sk`` / ``sv`` (position
t's chunk at ``t // chunk_size``).

A decode segment keeps ring and summaries read-only inside its scan
(:func:`keeps_tail`) behind a tail of two parts with a write, masks and a
merge of their own (:func:`tail_init`, :func:`tail_plan`,
:func:`_eva_tail_attend`, :func:`tail_merge`).

Scopes: ``qkv_proj``, ``kv_write``, ``attend``, ``eva_summarize``. The
interface is ``llama.ATTN_KINDS``'. Keys: ``window_size``, ``chunk_size``
(``LlamaConfig``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from lambdipy_tpu.models.kv import _project_qkv
from lambdipy_tpu.models.llama import Counters, _attend, block_method

NAME = "eva"
PLACES = ("attn_kind",)


def validate(cfg) -> None:
    if cfg.chunk_size < 1 or cfg.window_size < cfg.chunk_size \
            or cfg.window_size % cfg.chunk_size:
        raise ValueError(
            "eva attention needs window_size, a multiple of "
            "chunk_size >= 1")
    if cfg.heads != cfg.kv_heads:
        raise ValueError(
            "eva attention is multi-head: kv_heads must equal heads")
    if cfg.kv_quant is not None:
        raise NotImplementedError(
            f"kv_quant={cfg.kv_quant!r} cannot hold an eva cache: "
            "the int8 cache layout quantizes one K/V row a token "
            "(_kv_store), not a ring beside pooled summaries")
    if cfg.attn_backend != "dense":
        raise NotImplementedError(
            f"attn_backend={cfg.attn_backend!r} attends one K/V "
            "row a token; eva attention runs the dense backend")


def cache_layout(cfg) -> dict:
    """The ring, then the chunk summaries: ``cache_positions`` says how long
    each is and ``cache_slot`` where a position lies."""
    row = (cfg.kv_heads, cfg.head_dim)
    return {"k": row, "v": row, "sk": row, "sv": row}


def cache_positions(cfg, max_len: int) -> dict:
    """A ring never grows past its window, and a summary leaf holds one row
    a chunk."""
    ring = min(cfg.window_size, max_len)
    chunks = -(-max_len // cfg.chunk_size)
    return {"k": ring, "v": ring, "sk": chunks, "sv": chunks}


def cache_dtypes(cfg) -> dict:
    return dict.fromkeys(("k", "v", "sk", "sv"), cfg.dtype)


def cache_slot(cfg, leaf: str, position):
    """A summary lies at the slot of the chunk its span BEGINS in."""
    if leaf in ("sk", "sv"):
        return position // cfg.chunk_size
    return position % cfg.window_size


def refusal(cfg, holder: str) -> str:
    """A ring forgets and summaries pool, so a span of positions is no slice
    of this cache."""
    return (f"{holder} keeps one cache row a token on one position axis "
            "and cannot take the eva cache layout (a ring of "
            f"{cfg.window_size} beside one summary for every "
            f"{cfg.chunk_size} positions; PERF.md section 7)")


def prompt_block(cfg) -> tuple:
    """A prompt past one window prefills at the next whole window: the
    prefill is one body a window, so a bucket of three windows costs three
    turns where the power of two above it would cost four."""
    return cfg.window_size, cfg.window_size


def counters(cfg) -> tuple:
    """The ``handler.eva`` block on ``/metrics``, only growing, from the
    segment programs' own fetch, for the rows the collector books (the rows the
    device stepped for nobody are left out). Layer 0 sows ``eva_stats``, int32
    ``[b, 3]`` a step (every layer's are the same), and a segment program
    returns their sum over its steps. ``row_steps``: booked rows x segment
    steps. ``keys_attended``: the ring rows and chunk summaries those steps had
    visible, summed: ``keys_attended / row_steps`` is the mean number of keys a
    query attended, beside the rows' mean context the compression the traffic
    really got. ``chunks_written``: the summaries those steps wrote; a row
    writes one every ``chunk_size`` steps, so ``chunks_written x chunk_size``
    is ``row_steps`` to within one partial chunk a booked request.
    ``edge_row_steps``: the steps a row took AFTER a window's edge crossed
    inside their segment, the rare branch of a segment that keeps its ring
    read-only (:func:`_eva_tail_attend`; 0 from the per-step write): there the
    frozen ring is masked whole and the row attends its tail alone."""
    def segment(sown, rows: int, steps: int) -> dict:
        keys = sown["eva_stats"]
        return {"row_steps": rows * steps, "keys_attended": keys[:, 0].sum(),
                "chunks_written": keys[:, 1].sum(),
                "edge_row_steps": keys[:, 2].sum()}

    return (Counters(
        "eva", "an eva-attention model",
        dict.fromkeys(("row_steps", "keys_attended", "chunks_written",
                       "edge_row_steps"), 0),
        {"eva_stats": lambda b: jnp.zeros((b, 3), jnp.int32)}, segment),)


def keeps_tail(cfg) -> bool:
    """An eva cache is multi-head (a ring and chunk summaries, one query a
    KV head): with a per-step write the compiler updates 14 of 32 ring
    leaves in the fast memory and copies each home whole, 0.94 GB a step at
    EvaByte widths (PERF.md section 6, PR 34). Its tail is its own
    (:func:`_eva_tail_attend`): the segment's rows behind those of the chunk
    that was open when it began, so that a chunk is pooled from the tail
    alone, AND the summaries they complete, since a row that completes chunk
    127 at position 2047 attends it at 2048; the merge wraps round the ring
    (:func:`tail_merge`)."""
    return True


def tail_fits(cfg, steps: int, spans: dict) -> bool:
    """One merge would write a ring shorter than the segment twice: a cache
    of a few positions keeps the per-step write."""
    return steps <= spans["k"]


def tail_step(cfg, frozen: list, tails: list, base, j) -> list:
    """The entries the layers of step ``j`` read: the frozen leaves, each
    row's position when the segment began, the tail and what every layer
    reads of it alike, computed once a step (:func:`tail_plan`)."""
    plan = tail_plan(cfg, frozen[0], tails[0], base, j)
    return [{**entry, "index": base, "tail": tail, "plan": plan}
            for entry, tail in zip(frozen, tails)]


# queries one turn of an eva prefill's window loop attends. 32 heads x 128 x
# (2048 + 384) float32 scores are 40 MB a row: the v5e compiler keeps them,
# and every pass of the softmax over them, in the fast memory (compiled text
# for a described v5e, PR 33), and a turn reads its window's K/V and the
# summaries from HBM, 40 MB: 1.9 GB a layer of a 6144 prompt. At 512 queries
# a turn the scores (160 MB) go to HBM, written twice and read three times:
# 8.9 GB a layer, half the prefill's time; at 256 one of the two copies does
EVA_QUERY_BLOCK = 128


def _eva_pool(k, v, mu, phi, dtype):
    """One summary a chunk: ``k``, ``v`` ``[..., chunk, heads, d]`` (keys after
    rope) -> ``(sk, sv)`` ``[..., heads, d]`` in ``dtype``. The key summary is
    the chunk's keys under softmax_j(k_j . mu_h), the value summary its values
    under softmax_j(k_j . phi_h / sqrt(d)); both softmaxes and sums in float32,
    as multiply-reduces (no product at the MXU's precision)."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    kw = jax.nn.softmax(jnp.sum(k32 * mu, axis=-1), axis=-2)
    vw = jax.nn.softmax(jnp.sum(k32 * phi, axis=-1)
                        / jnp.sqrt(jnp.float32(k.shape[-1])), axis=-2)
    return (jnp.sum(kw[..., None] * k32, axis=-3).astype(dtype),
            jnp.sum(vw[..., None] * v32, axis=-3).astype(dtype))


def _eva_ring(x, lengths, win: int):
    """``x`` ``[b, s, h, d]`` (a prefill's keys or values) -> ``[b, win, h,
    d]``: of each row the window its next position ``lengths[r]`` lies in,
    position t at slot ``t mod win`` (zeros past the sequence; where the next
    position opens a window past it, the last one, which the step masks
    whole)."""
    b, s = x.shape[:2]
    n_win = -(-s // win)
    x = jnp.pad(x, ((0, 0), (0, n_win * win - s), (0, 0), (0, 0)))
    at = jnp.minimum(lengths // win, n_win - 1)
    return jnp.take_along_axis(x.reshape(b, n_win, win, *x.shape[2:]),
                               at[:, None, None, None, None], axis=1)[:, 0]


def _eva_chunk_rows(leaf, at, chunk: int):
    """``leaf`` ``[b, ring, h, d]``, ``at`` ``[b]`` (or a scalar a row) ->
    ``[b, chunk, h, d]``: of each row the ``chunk`` ring slots from
    ``at[r]``. A slice a row: as ONE gather the compiler copies the whole
    ring into a layout of the gather's liking, every layer of every step
    (compiled text for a v5e, PR 33)."""
    return jnp.concatenate(
        [jax.lax.dynamic_slice(leaf, (r, at[r], 0, 0),
                               (1, chunk) + leaf.shape[2:])
         for r in range(leaf.shape[0])], axis=0)


def _eva_softmax_sum(q, parts):
    """ONE float32 softmax over several key sets: ``q`` ``[b, s, h, d]``;
    ``parts``: ``(keys [b, t, h, d], values [b, t, h, d], mask [b, s, t])``
    each. Returns ``[b, s, h, d]``, float32 sums of the parts cast once."""
    d = q.shape[-1]
    logits = [jnp.where(
        mask[:, None, :, :],
        jnp.einsum("bshd,bthd->bhst", q, keys,
                   preferred_element_type=jnp.float32)
        / jnp.sqrt(d).astype(jnp.float32), jnp.float32(-1e9))
        for keys, _, mask in parts]
    probs = jax.nn.softmax(jnp.concatenate(logits, axis=-1), axis=-1)
    out, at = 0.0, 0
    for _, values, mask in parts:
        t = mask.shape[-1]
        out = out + jnp.einsum(
            "bhst,bthd->bshd", probs[..., at:at + t].astype(values.dtype),
            values, preferred_element_type=jnp.float32)
        at += t
    return out.astype(parts[0][1].dtype)


def _eva_prefill_attend(q, k, v, sk, sv, mask, win: int, chunk: int):
    """Prefill of more than one window: ONE body, a block of at most
    ``EVA_QUERY_BLOCK`` queries a turn (``lax.map``), so the float32 scores
    are ``[heads, block, win + chunks]`` whatever the prompt's length. A
    query of window w attends that window's keys causally and the summaries
    of the chunks before it."""
    b, s, h, d = q.shape
    n_win = -(-s // win)
    pad = n_win * win - s
    block = min(win, EVA_QUERY_BLOCK)
    per_win = win // block

    def cut(x, size):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, -1, size, *x.shape[2:]), 1, 0)

    kw, vw, mw = cut(k, win), cut(v, win), cut(mask, win)
    chunks = jnp.arange(sk.shape[1])

    def body(args):
        i, qi = args
        w = i // per_win
        at = (i % per_win) * block + jnp.arange(block)   # place in the window
        own = jax.lax.dynamic_index_in_dim(mw, w, 0, False)[:, None, :] \
            & (jnp.arange(win)[None, :] <= at[:, None])[None]
        earlier = jnp.broadcast_to(chunks < w * (win // chunk),
                                   (b, block, chunks.shape[0]))
        return _eva_softmax_sum(
            qi, ((jax.lax.dynamic_index_in_dim(kw, w, 0, False),
                  jax.lax.dynamic_index_in_dim(vw, w, 0, False), own),
                 (sk, sv, earlier)))

    out = jax.lax.map(body, (jnp.arange(n_win * per_win), cut(q, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, n_win * win, h, d)[:, :s]


@block_method
def _eva_attend(block, x, positions, mask, cache, lengths):
    """The layer's attention inside ``block`` (a ``LlamaBlock`` under its
    ``nn.compact`` call): ``(the heads' outputs [b, s, heads, head_dim], the
    new cache entry)``.

    Without a cache (prefill, the whole forward) the sequence is cut into
    windows and ONE body runs a block of a window's queries a turn
    (:func:`_eva_prefill_attend`); the entry returned is already a slot's:
    ``k`` / ``v`` the ring ``[b, window_size, ..]`` of the window each row's
    NEXT position ``lengths[r]`` lies in (the whole sequence where ``lengths``
    is None), each position at its ``mod window_size`` slot, and ``sk`` /
    ``sv`` ALL the chunks' summaries (:func:`_eva_ring`; a layer hands on a
    window, not the sequence: 16 layers of an 8192 bucket would hold 2 GB).
    Right padding is safe: a summary is attended only from a LATER window, so a
    chunk that holds padding is attended by padding alone.

    With a cache (one token a row): the step's K/V go to ring slot ``t mod
    window_size``; the ring is attended under ``slot <= t mod window_size`` and
    the summaries under ``chunk < (t // window_size) * chunks a window``; when
    the step completes a chunk its rows, all in the ring, are pooled and
    written at ``t // chunk_size``, and otherwise that write drops (an
    out-of-range index, like a finished slot's write). A window's summaries are
    all written before its ring slots are overwritten, so nothing happens at a
    window's edge."""
    cfg = block.cfg
    d, heads = cfg.head_dim, cfg.heads
    win, chunk = cfg.window_size, cfg.chunk_size
    b, s, _ = x.shape
    q, k, v = _project_qkv(block, x, positions)
    mu = block.param("adaptive_mu_k", nn.initializers.normal(1.0),
                    (heads, d), jnp.float32)
    phi = block.param("adaptive_phi", nn.initializers.normal(1.0),
                     (heads, d), jnp.float32)

    if cache is None:
        with jax.named_scope("eva_summarize"):
            n_chunks = -(-s // chunk)
            pad = ((0, 0), (0, n_chunks * chunk - s), (0, 0), (0, 0))
            sk, sv = _eva_pool(
                jnp.pad(k, pad).reshape(b, n_chunks, chunk, heads, d),
                jnp.pad(v, pad).reshape(b, n_chunks, chunk, heads, d),
                mu, phi, cfg.dtype)
        with jax.named_scope("attend"):
            if s <= win:  # one window: plain causal attention
                causal = jnp.tril(jnp.ones((s, s), dtype=jnp.bool_))
                out = _attend(q, k, v,
                              mask[:, None, :] & causal[None, :, :])
            else:
                out = _eva_prefill_attend(q, k, v, sk, sv, mask, win,
                                          chunk)
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        return out, {"k": _eva_ring(k, lengths, win),
                     "v": _eva_ring(v, lengths, win), "sk": sk, "sv": sv}

    if s != 1:
        raise NotImplementedError(
            "an eva cache is stepped one token a row: a chunk of "
            f"{s} positions against it (a prefix continued, a draft "
            "verified) is not written (PERF.md section 7)")
    if "tail" in cache:
        return _eva_tail_attend(block, q, k, v, mu, phi, cache)
    idx = jnp.broadcast_to(cache["index"], (b,))
    rows = jnp.arange(b)
    ring, n_sum = cache["k"].shape[1], cache["sk"].shape[1]
    slot = cache_slot(cfg, "k", idx)
    with jax.named_scope("kv_write"):
        new_cache = {
            "k": cache["k"].at[rows, slot].set(k[:, 0].astype(cfg.dtype)),
            "v": cache["v"].at[rows, slot].set(v[:, 0].astype(cfg.dtype))}
    with jax.named_scope("attend"):
        seen = jnp.arange(ring)[None, :] <= slot[:, None]
        earlier = (jnp.arange(n_sum)[None, :]
                   < cache_slot(cfg, "sk", idx // win * win)[:, None])
        out = _eva_softmax_sum(
            q, ((new_cache["k"], new_cache["v"], seen[:, None, :]),
                (cache["sk"], cache["sv"], earlier[:, None, :])))
        if block.layer == 0:
            # what a row's step had visible, whether it completed a
            # chunk, and (a tail segment's column: 0 here) whether it
            # came after a window edge inside its segment: every
            # layer's are the same (counters; /metrics handler.eva)
            block.sow("eva_stats", "keys", jnp.stack(
                [seen.sum(-1) + earlier.sum(-1),
                 idx % chunk == chunk - 1, jnp.zeros_like(idx)],
                axis=-1).astype(jnp.int32))
    with jax.named_scope("eva_summarize"):
        # the chunk this position lies in: its rows are ring slots
        # first .. first + chunk - 1, this step's own among them
        first = slot // chunk * chunk
        sk, sv = _eva_pool(_eva_chunk_rows(new_cache["k"], first, chunk),
                           _eva_chunk_rows(new_cache["v"], first, chunk),
                           mu, phi, cfg.dtype)
        at = jnp.where(idx % chunk == chunk - 1,
                       cache_slot(cfg, "sk", idx), n_sum)
        new_cache["sk"] = cache["sk"].at[rows, at].set(sk)
        new_cache["sv"] = cache["sv"].at[rows, at].set(sv)
    return out, new_cache


attend = _eva_attend


@block_method
def _eva_tail_attend(block, q, k, v, mu, phi, cache):
    """A tail segment's step (:func:`_scan_decode`, ``tail_window``): ring and
    summaries are READ as the segment found them and never written. The
    segment's own rows lie in ``cache["tail"]``:

    - ``k``, ``v`` ``[b, whole chunks, ..]``: consecutive positions from the
      first of the chunk that was open when the segment began, ``base[r] //
      chunk_size * chunk_size``: the ring's rows of that chunk
      (:func:`tail_init`), over which, from ``base[r]`` on, the segment's steps
      write theirs;
    - ``sk``, ``sv`` ``[b, chunks, ..]``: slot m holding the summary of chunk
      ``base[r] // chunk_size + m``, the m-th a row can complete inside the
      segment.

    The step, at position ``t = base[r] + j``, writes its K/V where the tail
    holds t and attends under the ONE softmax

    - the frozen ring while the row is in the window it began the segment in,
      the slots written before the segment;
    - the ring tail's positions so far that lie in t's window;
    - the frozen summaries of the chunks of earlier windows that were complete
      when the segment began;
    - the summary tail's chunks of earlier windows: completed inside the
      segment, before an edge the row has crossed since.

    Where each lies is ``cache["plan"]``, the same for every layer
    (:func:`tail_plan`). The same keys, values and probabilities as the
    per-step write, the sum's order apart. The chunk t lies in is pooled as the
    per-step write pools it, from ``chunk_size`` consecutive rows of ``k`` /
    ``v`` (open chunk and tail are one array for that, and t's chunk one of its
    whole chunks), and lands in the summary tail when t completes it; a chunk
    some row of which is not yet written pools to garbage, which nothing
    selects. Nothing of either tail is read as a number before its step wrote
    it: keys are masked, values selected to zero (the v5e compiler hands the
    scan a tail it has not initialised, and 0 x NaN is NaN). Returns the heads'
    outputs and the new tail."""
    cfg = block.cfg
    tail, plan = cache["tail"], cache["plan"]
    with jax.named_scope("kv_write"):
        new_tail = {
            name: tail[name].at[plan["rows"], plan["at"]].set(
                val[:, 0].astype(cfg.dtype))
            for name, val in (("k", k), ("v", v))}
    with jax.named_scope("attend"):
        own = plan["own"][:, :, None, None]
        inside = plan["inside"][:, :, None, None]
        out = _eva_softmax_sum(q, (
            (cache["k"], cache["v"], plan["held"][:, None, :]),
            (new_tail["k"], jnp.where(own, new_tail["v"], 0),
             plan["here"][:, None, :]),
            (cache["sk"], cache["sv"], plan["before"][:, None, :]),
            (tail["sk"], jnp.where(inside, tail["sv"], 0),
             plan["inside"][:, None, :])))
        if block.layer == 0:
            # every layer's are the same (counters; /metrics handler.eva)
            block.sow("eva_stats", "keys", plan["stats"])
    with jax.named_scope("eva_summarize"):
        # the tail begins at a chunk's first position, so the chunk t
        # lies in is one of its whole chunks
        def chunk_of(leaf):
            whole = leaf.reshape(leaf.shape[0], -1, cfg.chunk_size,
                                 *leaf.shape[2:])
            return jnp.take_along_axis(whole, plan["chunk"], axis=1)[:, 0]

        sk, sv = _eva_pool(chunk_of(new_tail["k"]),
                           chunk_of(new_tail["v"]), mu, phi, cfg.dtype)
        lands = plan["lands"][:, :, None, None]
        new_tail["sk"] = jnp.where(lands, sk[:, None], tail["sk"])
        new_tail["sv"] = jnp.where(lands, sv[:, None], tail["sv"])
    return out, new_tail


def tail_init(cfg, frozen: list, base, steps: int) -> list:
    """An eva tail segment's tails, a layer, before its scan
    (:func:`_eva_tail_attend` says what they hold). ``k``, ``v`` begin with the
    ring slots of the chunk each row's position ``base[r]`` lies in, the one
    chunk the segment completes whose first rows may lie BEFORE it (every later
    chunk lies in the tail whole). Fetched here, once a segment: sliced from
    the ring inside the scan, the pooled rows hand the ring the tail's layout
    and the compiler transposes every ring at the head of every segment
    (compiled text for a v5e, PR 34). The rest is zeros; on the chip the
    compiler hands over uninitialised what it sees the loop write."""
    chunk = cfg.chunk_size
    with jax.named_scope("eva_summarize"):
        at = list(cache_slot(cfg, "k", base // chunk * chunk))
        k, sk = frozen[0]["k"], frozen[0]["sk"]
        rest = jnp.zeros((k.shape[0], -(-steps // chunk) * chunk)
                         + k.shape[2:], k.dtype)
        none = jnp.zeros((sk.shape[0], cache_positions(cfg, steps)["sk"])
                         + sk.shape[2:], sk.dtype)
        return [{"k": jnp.concatenate(
                     [_eva_chunk_rows(entry["k"], at, chunk), rest], axis=1),
                 "v": jnp.concatenate(
                     [_eva_chunk_rows(entry["v"], at, chunk), rest], axis=1),
                 "sk": none, "sv": none} for entry in frozen]


def tail_plan(cfg, entry: dict, tail: dict, base, j) -> dict:
    """What every layer of an eva tail segment's step ``j`` reads alike,
    computed once a step (``entry``, ``tail``: one layer's frozen leaves
    and tails, for their lengths; ``base``: each row's position when the
    segment began). With ``t = base[r] + j`` the step's position:

    - ``rows``, ``at``: where the step's K/V go in the ring tail, a row's
      own place (no lockstep: the tail begins at each row's open chunk);
    - ``held`` ``[b, ring]``: the frozen ring's slots written before the
      segment, while no window edge was crossed; ``own`` ``[b, tail]``:
      the tail rows the segment has written so far, and ``here``: those
      of t's window; ``before`` ``[b, summaries]``: the frozen summaries
      of earlier windows complete when the segment began; ``inside``
      ``[b, tail chunks]``: the summary tail's chunks of earlier windows;
    - ``chunk`` ``[b, 1, 1, 1, 1]``: which of the ring tail's chunks t
      lies in; ``lands`` ``[b, tail chunks]``: the slot t completes;
    - ``stats`` int32 ``[b, 3]``: the keys visible, whether t completes a
      chunk, whether t comes after a window edge inside the segment."""
    win, chunk = cfg.window_size, cfg.chunk_size
    t = base + j
    ring, n_sum = entry["k"].shape[1], entry["sk"].shape[1]
    # the position each row of the tail's k / v holds, and the chunk each
    # slot of its sk / sv is for
    held_at = (base // chunk * chunk)[:, None] \
        + jnp.arange(tail["k"].shape[1])[None, :]
    chunks = cache_slot(cfg, "sk", base)[:, None] \
        + jnp.arange(tail["sk"].shape[1])[None, :]
    same = base // win == t // win          # no window edge crossed yet
    held = same[:, None] & (jnp.arange(ring)[None, :]
                            < cache_slot(cfg, "k", base)[:, None])
    own = (held_at >= base[:, None]) & (held_at <= t[:, None])
    here = own & (held_at // win == (t // win)[:, None])
    earlier = cache_slot(cfg, "sk", t // win * win)[:, None]
    before = jnp.arange(n_sum)[None, :] < jnp.minimum(earlier, chunks[:, :1])
    inside = chunks < earlier
    ends = t % chunk == chunk - 1
    return {
        "rows": jnp.arange(base.shape[0]), "at": base % chunk + j,
        "held": held, "own": own, "here": here, "before": before,
        "inside": inside,
        "chunk": (t // chunk - base // chunk)[:, None, None, None, None],
        "lands": (chunks == (t // chunk)[:, None]) & ends[:, None],
        "stats": jnp.stack(
            [held.sum(-1) + here.sum(-1) + before.sum(-1) + inside.sum(-1),
             ends, ~same], axis=-1).astype(jnp.int32)}


def tail_merge(cfg, full: list, tails: list, base,
                    steps: int) -> list:
    """An eva tail segment's ONE write of each layer's cache entry, after
    its scan: the tail's row of position ``base[r] + j'`` goes to slot
    ``(base[r] + j') mod window_size`` for each of the segment's steps j'
    (it wraps where the row crossed a window's edge), and the
    summary tail's slot m to chunk ``base[r] // chunk_size + m`` where the
    segment completed that chunk; the others drop (an out-of-range index,
    as the per-step write drops a step that completes none)."""
    chunk = cfg.chunk_size
    rows = jnp.arange(base.shape[0])[:, None]
    slots = cache_slot(cfg, "k", base[:, None] + jnp.arange(steps)[None, :])
    # where in the ring tail the segment's own rows lie
    own = ((base % chunk)[:, None]
           + jnp.arange(steps)[None, :])[:, :, None, None]
    chunks = cache_slot(cfg, "sk", base)[:, None] \
        + jnp.arange(tails[0]["sk"].shape[1])[None, :]
    complete = (chunks + 1) * chunk <= base[:, None] + steps
    at = jnp.where(complete, chunks, full[0]["sk"].shape[1])
    merged = []
    for entry, tail in zip(full, tails):
        with jax.named_scope("kv_write"):
            ring = {name: entry[name].at[rows, slots].set(
                        jnp.take_along_axis(tail[name], own, axis=1))
                    for name in ("k", "v")}
        with jax.named_scope("eva_summarize"):
            merged.append({**ring, **{
                name: entry[name].at[rows, at].set(tail[name])
                for name in ("sk", "sv")}})
    return merged
