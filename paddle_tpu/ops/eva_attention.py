"""EVA attention over a slot table's TWO caches a layer (Zheng et al.,
*Efficient Attention via Control Variates*, arXiv:2302.04542, in the form
EvaByte serves): exact softmax attention inside the query's own
block-aligned WINDOW of ``window`` positions, and one learned SUMMARY key and
value for every chunk of ``chunk`` positions of every earlier window, all
under ONE softmax.

**The window cache** ``[B, window, H*D]`` holds position ``p`` at slot ``p %
window``. It is no sliding ring: the window a query at ``p`` reads starts at
``(p // window) * window``, so a step reads the slots ``0 .. p % window`` and
the slots above, which still hold the window before, are masked and not
overwritten first.

**The summary cache** ``[B, rung / chunk, H*D]`` holds chunk ``c``
(positions ``chunk * c .. chunk * c + chunk - 1``) at entry ``c``: a cache
written at a stride, and derived from the window cache. With the layer's
learned ``phi`` and ``mu`` ([H*D], a head's ``D`` side by side) and ``s = D
** -0.5``::

    a_j   = softmax_j(s * k_j . phi_h)      over the chunk's positions
    kbar  = sum_j a_j k_j + mu_h            (the rotated keys are pooled)
    vbar  = sum_j a_j v_j

A query at ``p`` reads the entries ``c < (p // window) * (window / chunk)``:
every chunk of every earlier window and none of its own. Entries at and past
``p // chunk`` hold whatever a recycled slot row left there and are never
read.

**A step** (:func:`summarise_step`, :func:`attend_step`) writes its token's
key and value into the window cache first; the step whose position ends a
chunk (``p % chunk == chunk - 1``) pools that chunk's ``chunk`` slots of the
window cache and writes the summary, every other step's write drops.

**A chunk run** of K lanes a row (:func:`summarise_chunk`,
:func:`attend_chunk`; ``K <= window``, so its lanes cross at most one
multiple of ``window``) reads the window cache AS IT WAS BEFORE the run with
its own keys and values beside it (a lane that opens a new window must not
see the slots its run overwrites, and the lanes before the boundary still
need them), and the summary cache AFTER the run's own summary writes: the
chunks a run completes, from lanes of its own and, for the chunk its first
lane continues, from the window cache, are written before any lane reads,
which is right because a lane reads by index alone and no lane's index
reaches a chunk of its own window. A chunk the run leaves open is written by
the step that ends it. A pad lane (``pos >= pad_pos``) completes nothing and
its output means nothing.

Scores are scaled by ``D ** -0.5`` and kept in float32, both softmaxes and
the pooled sums are float32; the probabilities meet the caches in the
caches' type and products accumulate in float32. Every form is strictly
per-row. Device events run under the scopes ``attn.eva`` and
``eva.summary``.

**Which step form runs where.** On ONE TPU a step runs the Pallas kernel
``eva_step.fwd`` (:func:`step_blocks`): a third client of the step kernels'
loop and streaming softmax (``cache_attention._walk_blocks``, ``_stream``),
whose row walks TWO sources one after the other, the window cache's blocks
``0 .. (p % window) // block`` and then the summary cache's blocks that hold
the entries below ``(p // window) * (window / chunk)``, none where ``p <
window``; a pass copies a block of whichever source it falls in from where
the cache is stored, and one running maximum, sum and accumulator go through
both. So a step reads the entries the rows hold and not both caches whole.
:func:`step_plan` decides it from what the trace sees (placement, types, the
two lengths' common block, the VMEM the kernel holds). The CPU, a mesh and
every shape the gate refuses keep :func:`attend_step`, the ``jnp`` form that
reads both caches whole under the mask, whatever the rows hold, and is the
kernel's reference. A chunk run and both summarisers are ``jnp``: a chunk run
walks the caches in blocks under a streaming softmax, up to the last entry a
live lane reads.
"""

import functools
import math

import jax
import jax.numpy as jnp

from . import cache_attention as _steps
from .gates import GateReason, platform_reason
from .kernel_names import named_pallas_call, traced_once

__all__ = ["attend_step", "attend_chunk", "summarise_step",
           "summarise_chunk", "pool", "step_plan", "plan_for", "step_blocks"]

CHUNK_BLOCK = 256   # cache entries a chunk run's block reads at a time

_INTERPRET = False  # tests flip this to run the kernel on the CPU

_F32 = jnp.float32
_LOW = float(jnp.finfo(jnp.float32).min)


def _check(entries, chunk, window=None):
    """A window cache of ``entries`` slots is a whole number of chunks and,
    where the op states it, ``window`` slots long."""
    if chunk < 1 or entries % chunk:
        raise ValueError("a window of %d positions is no whole number of "
                         "chunks of %d" % (entries, chunk))
    if window not in (None, entries):
        raise ValueError("the window cache holds %d positions a row, the "
                         "window is %d" % (entries, window))


def pool(k, v, phi, mu, heads):
    """The summary of chunks of keys and values: k, v [.., C, H*D] (the
    rotated keys as cached), phi, mu [H*D]. Returns (kbar, vbar) [.., H*D]
    float32; every sum elementwise in float32."""
    d = k.shape[-1] // heads
    kf = k.astype(_F32).reshape(k.shape[:-1] + (heads, d))
    vf = v.astype(_F32).reshape(kf.shape)
    phi = phi.astype(_F32).reshape(heads, d)
    logits = jnp.sum(kf * phi, axis=-1) * (d ** -0.5)       # [.., C, H]
    a = jax.nn.softmax(logits, axis=-2)[..., None]
    kbar = jnp.sum(a * kf, axis=-3) + mu.astype(_F32).reshape(heads, d)
    vbar = jnp.sum(a * vf, axis=-3)
    flat = k.shape[:-2] + (heads * d,)
    return kbar.reshape(flat), vbar.reshape(flat)


def _chunk_slots(cache, c, chunk):
    """The ``chunk`` slots of a window cache [B, W, ..] that hold chunk ``c``
    [B] (they are contiguous: ``W`` is a whole number of chunks)."""
    first = jnp.mod(c * chunk, cache.shape[1])
    return jax.vmap(lambda a, s: jax.lax.dynamic_slice_in_dim(
        a, s, chunk, 0))(cache, first)


def _write(sum_k, sum_v, kbar, vbar, row, entry):
    return (sum_k.at[row, entry].set(kbar.astype(sum_k.dtype), mode="drop"),
            sum_v.at[row, entry].set(vbar.astype(sum_v.dtype), mode="drop"))


def summarise_step(win_k, win_v, sum_k, sum_v, pos, phi, mu, heads, chunk):
    """A step's summary write. win_k, win_v [B, W, H*D] with the step's
    token written, sum_k, sum_v [B, L, H*D], pos [B]. The row whose position
    ends a chunk pools the chunk's slots of its window cache and writes
    entry ``pos // chunk``; the other rows write nothing. Returns the two
    summary caches."""
    b = win_k.shape[0]
    chunk = int(chunk)
    _check(win_k.shape[1], chunk)
    pos = pos.reshape(-1).astype(jnp.int32)
    with jax.named_scope("eva.summary"):
        c = pos // chunk
        kbar, vbar = pool(_chunk_slots(win_k, c, chunk),
                          _chunk_slots(win_v, c, chunk), phi, mu, heads)
        entry = jnp.where(jnp.mod(pos, chunk) == chunk - 1, c,
                          sum_k.shape[1])
        return _write(sum_k, sum_v, kbar, vbar, jnp.arange(b), entry)


def summarise_chunk(win_k, win_v, new_k, new_v, sum_k, sum_v, pos, phi, mu,
                    heads, chunk, pad_pos):
    """A chunk run's summary writes. win_k, win_v [B, W, H*D] AS THEY WERE
    BEFORE the run, new_k, new_v [B, K, H*D] the run's own, sum_k, sum_v [B,
    L, H*D], pos [B, K] (a row's live lanes hold consecutive positions from
    lane 0 on; ``>= pad_pos``: a pad lane). Every chunk whose last position
    is a live lane is pooled and written at its entry. Only the chunk the
    row's first lane continues has positions before the run: they come from
    that chunk's slots of the window cache (it lies in the first lane's own
    window), the others from the lanes. Returns the two summary caches."""
    b = win_k.shape[0]
    kq = new_k.shape[1]
    chunk = int(chunk)
    _check(win_k.shape[1], chunk)
    pos = pos.astype(jnp.int32)
    with jax.named_scope("eva.summary"):
        p0 = pos[:, :1]                                         # [B, 1]
        lanes = jnp.sum(pos < int(pad_pos), axis=1, keepdims=True)
        count = -(-kq // chunk) + 1     # chunks K lanes may touch
        c0 = p0[:, 0] // chunk
        # the positions from the first chunk's start on: ``inside`` of them
        # before the run, then the lanes
        inside = p0 - c0[:, None] * chunk                       # [B, 1]
        at = jnp.arange(count * chunk, dtype=jnp.int32)[None]
        where = jnp.clip(jnp.where(at < inside, at, chunk + at - inside), 0,
                         chunk + kq - 1)[..., None]

        def rows(cache, new):
            both = jnp.concatenate([_chunk_slots(cache, c0, chunk),
                                    new.astype(cache.dtype)], axis=1)
            return jnp.take_along_axis(both, where, axis=1).reshape(
                b, count, chunk, -1)

        kbar, vbar = pool(rows(win_k, new_k), rows(win_v, new_v), phi, mu,
                          heads)
        c = c0[:, None] + jnp.arange(count, dtype=jnp.int32)    # [B, N]
        ended = c * chunk + (chunk - 1) - p0 < lanes
        return _write(sum_k, sum_v, kbar, vbar, jnp.arange(b)[:, None],
                      jnp.where(ended, c, sum_k.shape[1]))


def _stream(carry, scores, mask, mix):
    """A source's (or a block's) turn of ONE softmax over several: scores
    [.., S] float32 under ``mask``; ``mix(e)`` the float32 product of the
    unnormalised probabilities with the source's values; the queries'
    running maximum, sum ([.., 1]) and accumulator move on."""
    top, total, acc = carry
    scores = jnp.where(mask, scores, _LOW)
    new_top = jnp.maximum(top, scores.max(axis=-1, keepdims=True))
    e = jnp.where(mask, jnp.exp(scores - new_top), 0.0)
    keep = jnp.exp(top - new_top)
    return (new_top, total * keep + jnp.sum(e, axis=-1, keepdims=True),
            acc * keep + mix(e))


def _opened(shape, width):
    """The streaming softmax before its first source, for queries of
    ``shape`` whose values are ``width`` wide."""
    return (jnp.full(shape + (1,), _LOW, _F32), jnp.zeros(shape + (1,), _F32),
            jnp.zeros(shape + (width,), _F32))


def _closed(carry):
    _, total, acc = carry
    return acc / jnp.maximum(total, 1e-30)


def _reach(pos, window, chunk, entries):
    """What a step's rows at ``pos`` [B] read: the window slots ``<= slot``
    [B], the summary entries ``< read`` [B], and the step's ``Count`` [3]
    int32 (window slots, summary entries, context positions, summed over
    the rows)."""
    pos = pos.reshape(-1).astype(jnp.int32)
    slot = jnp.mod(pos, window)
    read = (pos // window) * (window // chunk)
    count = jnp.stack([jnp.sum(slot + 1), jnp.sum(jnp.minimum(read, entries)),
                       jnp.sum(pos + 1)]).astype(jnp.int32)
    return slot, read, count


def attend_step(q, win_k, win_v, sum_k, sum_v, pos, heads, window, chunk):
    """One query a row over both caches. q [B, H*D], win_k, win_v [B, W,
    H*D] with this step's token written, sum_k, sum_v [B, L, H*D], pos [B].
    Returns ([B, H*D] in q's dtype, [3] int32: the window slots, the summary
    entries and the context positions the rows read and hold, summed over
    the rows).

    The caches are read as they are stored (``cache_attention.attend_step``:
    the scores are the product of a row's cache with its queries laid out
    block-diagonally, the mix the product of the probabilities with the
    value cache, of which a head keeps its own columns), both whole under
    their masks."""
    b, w, hd = win_k.shape
    window, chunk = int(window), int(chunk)
    _check(w, chunk, window)
    d = hd // int(heads)
    entries = sum_k.shape[1]
    with jax.named_scope("attn.eva"):
        own = jnp.eye(heads, dtype=bool)
        q_blocks = jnp.where(own[:, None, :], q.reshape(b, heads, d, 1),
                             0).reshape(b, hd, heads)
        slot, read, count = _reach(pos, window, chunk, entries)
        carry = _opened((b, heads), hd)
        for keys, values, mask in (
                (win_k, win_v, jnp.arange(w, dtype=jnp.int32)[None]
                 <= slot[:, None]),
                (sum_k, sum_v, jnp.arange(entries, dtype=jnp.int32)[None]
                 < read[:, None])):
            scores = jnp.einsum("bck,bkh->bhc", keys, q_blocks,
                                preferred_element_type=_F32) * d ** -0.5
            carry = _stream(
                carry, scores, mask[:, None],
                lambda e, values=values: jnp.einsum(
                    "bhc,bck->bhk", e.astype(q.dtype), values,
                    preferred_element_type=_F32))
        out = jnp.einsum("bhhd->bhd",
                         _closed(carry).reshape(b, heads, heads, d))
    return out.reshape(b, hd).astype(q.dtype), count


def attend_chunk(q, win_k, win_v, sum_k, sum_v, new_k, new_v, pos, heads,
                 window, chunk, pad_pos):
    """K queries a row. q [B, K, H*D], win_k, win_v [B, W, H*D] AS THEY WERE
    BEFORE the run (they hold the positions up to ``pos[b, 0] - 1``), sum_k,
    sum_v [B, L, H*D] with the run's summaries written, new_k, new_v [B, K,
    H*D] the run's own, pos [B, K] (a row's live lanes hold consecutive
    positions from lane 0 on; ``>= pad_pos``: a pad lane, whose output
    means nothing). Lane j reads the window cache's slots and the lanes up
    to itself that lie in its own window, and the summaries of every earlier
    window.

    One streaming softmax over three sources, the two caches in blocks of
    ``CHUNK_BLOCK`` entries: the summary cache up to the last entry a live
    lane reads and the window cache up to the last slot the rows' windows
    hold, so a run early in its context reads what is cached and not 2 x
    2048 entries a row; then the run's own lanes. No [H, K, W + K + L]
    scores exist. Returns [B, K, H*D]."""
    b, w, hd = win_k.shape
    kq = q.shape[1]
    window, chunk = int(window), int(chunk)
    _check(w, chunk, window)
    if kq > window:
        raise ValueError("a chunk run of %d lanes crosses more than one "
                         "multiple of the window of %d" % (kq, window))
    heads = int(heads)
    d = hd // heads
    pos = pos.astype(jnp.int32)
    with jax.named_scope("attn.eva"):
        def a_head(x):
            return x.reshape(x.shape[:2] + (heads, d))

        qh = a_head(q)
        lane = pos[..., None]                                  # [B, K, 1]
        live = lane < int(pad_pos)

        def turn(carry, keys, values, mask):
            """``keys`` / ``values`` [B, S, H*D] under ``mask`` [B, K, S]."""
            scores = jnp.einsum("bqhd,bkhd->bhqk", qh, a_head(keys),
                                preferred_element_type=_F32) * d ** -0.5
            return _stream(carry, scores, mask[:, None], lambda e: jnp.einsum(
                "bhqk,bkhd->bhqd", e.astype(q.dtype), a_head(values),
                preferred_element_type=_F32))

        def blocks(carry, keys, values, mask_at, used):
            """A cache in blocks, up to entry ``used`` (a traced scalar);
            ``mask_at(at)``: which lanes read the entries ``at`` [S]."""
            size = min(CHUNK_BLOCK, keys.shape[1])
            if keys.shape[1] % size:        # no whole number of blocks
                size = keys.shape[1]

            def block(j, carry):
                at = j * size + jnp.arange(size, dtype=jnp.int32)
                kb, vb = (jax.lax.dynamic_slice_in_dim(x, j * size, size, 1)
                          for x in (keys, values))
                return turn(carry, kb, vb, mask_at(at))

            return jax.lax.fori_loop(0, (used + size - 1) // size, block,
                                     carry)

        carry = _opened((b, heads, kq), d)
        # the summaries of every earlier window
        read = (lane // window) * (window // chunk)            # [B, K, 1]
        carry = blocks(
            carry, sum_k, sum_v, lambda at: at < read,
            jnp.minimum(jnp.max(jnp.where(live, read, 0)), sum_k.shape[1]))
        # the window cache as it was: slot s holds the last position before
        # the run with ``p % W == s`` (``cache_attention.ring_positions``),
        # and a lane reads it in its own window
        before = pos[:, :1, None] - 1                          # [B, 1, 1]

        def in_window(at):
            held = before - jnp.mod(before - at, w)            # [B, 1, S]
            return (held >= 0) & (held // window == lane // window)

        # the slots below ``pos[b, 0] % W`` hold the first lane's own window
        used = jnp.where(live[:, 0, 0], jnp.mod(pos[:, 0], w), 0)
        carry = blocks(carry, win_k, win_v, in_window, jnp.max(used))
        # the run's own lanes, up to the lane itself and in its window
        own = pos[:, None]                                     # [B, 1, K]
        carry = turn(carry, new_k.astype(win_k.dtype),
                     new_v.astype(win_v.dtype),
                     (own <= lane) & (own // window == lane // window))
        out = jnp.transpose(_closed(carry), (0, 2, 1, 3))
    return out.reshape(b, kq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# a step on one TPU: the kernel ``eva_step.fwd``
# ---------------------------------------------------------------------------

def step_block(w, entries, row_bytes):
    """Entries a block of the step kernel reads, from either cache: what
    ``cache_attention.step_block`` cuts the two lengths' common measure
    into, a multiple of 128 that divides both; None where they have none.
    For EvaByte's rows of 16 KB that rule gives the smallest, 128: a pass's
    two copies take 2.6 us beside 0.3 us of loop, so a longer block buys
    nothing and reads further past what a row holds, in both caches (on the
    chip 128 is the fastest of 128, 256 and 512 at every fill: ``PERF.md``
    section 6, PR 47)."""
    return _steps.step_block(math.gcd(w, entries), row_bytes)


def _working_set(b, block, heads, hd, itemsize):
    """Bytes the kernel holds in VMEM, counted generously: two blocks of
    keys and of values (double-buffered), the rows' queries and outputs (a
    row a tile of sublanes, twice as operands may be held), a row's queries
    laid out a head a row and its float32 accumulator, and the [heads,
    block] and [heads, hd] float32 tiles live in a pass."""
    hd = _steps._up(hd, 128)
    blocks = 2 * 2 * block * hd * itemsize
    whole = 2 * b * hd * ((32 // itemsize) * itemsize + 8 * 4)
    row = heads * hd * (itemsize + 4)
    live = 4 * heads * block * 4 + 4 * heads * hd * 4
    return blocks + whole + row + live


def step_plan(b, w, entries, heads, hd, itemsize, platform=None):
    """Which way an ``eva_attention`` site reads its caches, as a
    ``GateDecision``: ``eva_step`` (the kernel: a row's window blocks up to
    its slot, then its summary blocks up to the entries it reads) or
    ``rung_xla`` (the ``jnp`` form: both caches whole under the mask) with
    the blocking reasons. ``w`` / ``entries``: the window and the summary
    cache's lengths; ``hd``: the width of a cached row, all heads;
    ``itemsize`` and ``platform`` as ``cache_attention.step_plan``'s."""
    reasons = [platform, _steps._one_type(itemsize, "caches")]
    if reasons[-1] is None and (hd % 128 or heads % (32 // itemsize)):
        reasons.append(GateReason(
            "geometry", "rows of %d are no multiple of 128, or %d heads no "
            "multiple of %d sublanes" % (hd, heads, 32 // itemsize)))
    return _steps._gate(
        "eva_step", reasons,
        lambda: step_block(w, entries, 2 * hd * itemsize),
        "a window of %d slots and %d summary entries share no block of a "
        "multiple of 128 shorter than both" % (w, entries),
        lambda block: _working_set(b, block, heads, hd, itemsize),
        "two blocks of %%d entries of 2 x %d wide rows, double-buffered, "
        "beside %d rows' queries" % (hd, b),
        "blocks of %%d of %d window slots and %d summary entries, each "
        "row's up to what it holds" % (w, entries))


def plan_for(q, win_k, win_v, sum_k, sum_v, heads):
    """:func:`step_plan` of a site's arrays, where the step being traced is
    placed."""
    one = all(x.dtype == q.dtype for x in (win_k, win_v, sum_k, sum_v)) \
        and jnp.issubdtype(q.dtype, jnp.floating)
    return step_plan(win_k.shape[0], win_k.shape[1], sum_k.shape[1],
                     int(heads), win_k.shape[2],
                     q.dtype.itemsize if one else None,
                     platform=platform_reason(_INTERPRET))


class _OneOf:
    """The async copy a pass makes: ``first()`` where ``cond`` (a traced
    scalar), else ``second()``. Both land in the same buffer under the same
    semaphore with the same bytes, so one wait serves either."""

    def __init__(self, cond, first, second):
        self.cond, self.first, self.second = cond, first, second

    def start(self):
        from jax.experimental import pallas as pl

        pl.when(self.cond)(lambda: self.first().start())
        pl.when(jnp.logical_not(self.cond))(lambda: self.second().start())

    def wait(self):
        self.first().wait()


def _step_kernel(slot_ref, read_ref, q_ref, wk_hbm, wv_hbm, sk_hbm, sv_hbm,
                 out_ref, k_buf, v_buf, sems, q_blocks, top_ref, total_ref,
                 acc_ref, *, block, scale):
    """``eva_step.fwd`` (``cache_attention._walk_blocks``: a row's pass
    ``j`` is block ``j`` of its window cache while ``j < windows(row)`` and
    block ``j - windows(row)`` of its summary cache after). slot_ref,
    read_ref [B] int32 (SMEM): a row reads the window slots ``<= slot`` and
    the summary entries ``< read``; q_ref [B, 1, hd]; wk_hbm, wv_hbm [B, W,
    hd] and sk_hbm, sv_hbm [B, L, hd] where they are stored; out_ref [B, 1,
    hd] float32; two slots of a block of keys and of values, which both
    sources share, their copy semaphores, a row's queries laid out
    block-diagonally ([heads, hd]: head h's values in its own columns, zeros
    elsewhere), and its running maximum, sum and accumulator."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = q_ref.shape[0]
    heads, hd = acc_ref.shape
    d = hd // heads

    def own():              # the columns a head keeps
        col = jax.lax.broadcasted_iota(jnp.int32, (heads, hd), 1)
        first = jax.lax.broadcasted_iota(jnp.int32, (heads, hd), 0) * d
        return (col >= first) & (col < first + d)

    def windows(row):       # window blocks up to the one that holds its slot
        return slot_ref[row] // block + 1

    def last(row):
        return windows(row) + (read_ref[row] + block - 1) // block - 1

    def source(row, j):     # is pass j a window block, and its first entry
        n = windows(row)
        in_window = j < n
        return in_window, jnp.where(in_window, j, j - n) * block

    def copies(row, j, slot):
        in_window, first = source(row, j)
        at = pl.ds(pl.multiple_of(first, block), block)

        def copy(cache, buf, i):
            return pltpu.make_async_copy(cache.at[row, at], buf.at[slot],
                                         sems.at[i, slot])

        return tuple(
            _OneOf(in_window, functools.partial(copy, win, buf, i),
                   functools.partial(copy, summ, buf, i))
            for i, (win, summ, buf) in enumerate((
                (wk_hbm, sk_hbm, k_buf), (wv_hbm, sv_hbm, v_buf))))

    def open_row():
        top_ref[...] = jnp.full(top_ref.shape, _LOW, _F32)
        total_ref[...] = jnp.zeros(total_ref.shape, _F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def products(row, j, slot):
        @pl.when(j == 0)
        def _():
            q_blocks[...] = jnp.where(own(), q_ref[row].astype(_F32),
                                      0.0).astype(q_blocks.dtype)

        kb, vb = k_buf[slot], v_buf[slot]
        s = jax.lax.dot_general(q_blocks[...], kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32) * scale
        in_window, first = source(row, j)
        at = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        end = jnp.where(in_window, slot_ref[row] + 1, read_ref[row])
        _steps._stream(s, at < end,
                       lambda probs: jnp.dot(probs.astype(vb.dtype), vb,
                                             preferred_element_type=_F32),
                       top_ref, total_ref, acc_ref)

    def close_row(row):
        y = acc_ref[...] / jnp.maximum(total_ref[...], 1e-30)
        out_ref[row] = jnp.sum(jnp.where(own(), y, 0.0), axis=0,
                               keepdims=True)

    _steps._walk_blocks(rows, last, copies, open_row, products, close_row)


@traced_once("eva_step.fwd", ("heads", "block", "vmem", "interpret"))
def _step_impl(slot, read, q, win_k, win_v, sum_k, sum_v, heads, block, vmem,
               interpret):
    """slot, read [B] int32; q [B, 1, hd]; win_k, win_v [B, W, hd]; sum_k,
    sum_v [B, L, hd]. Returns [B, 1, hd] float32 (:func:`_step_kernel`).
    ``vmem``: the kernel's ``vmem_limit_bytes``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, hd = q.shape
    held = pl.BlockSpec(memory_space=pltpu.VMEM)
    stored = pl.BlockSpec(memory_space=pl.ANY)
    return named_pallas_call(
        "eva_step.fwd",
        functools.partial(_step_kernel, block=block,
                          scale=(hd // heads) ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[held] + [stored] * 4, out_specs=held,
            scratch_shapes=[pltpu.VMEM((2, block, hd), win_k.dtype),
                            pltpu.VMEM((2, block, hd), win_v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((heads, hd), q.dtype),
                            pltpu.VMEM((heads, 1), _F32),
                            pltpu.VMEM((heads, 1), _F32),
                            pltpu.VMEM((heads, hd), _F32)]),
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(slot, read, q, win_k, win_v, sum_k, sum_v)


def step_blocks(q, win_k, win_v, sum_k, sum_v, pos, heads, window, chunk):
    """:func:`attend_step` by the kernel ``eva_step.fwd`` (what
    :func:`plan_for` admits): the same arguments, the same two results, the
    same sums in another order (a row's blocks of both caches one after the
    other under a running maximum and sum, the probabilities cast to the
    caches' type for the mix, one division at the end). A row reads its
    window cache's blocks up to the one that holds slot ``pos % window`` and
    the summary cache's up to entry ``(pos // window) * (window / chunk)``,
    none inside its first window; the count is the entries the rows read and
    hold, not the entries fetched."""
    b, w, hd = win_k.shape
    window, chunk, heads = int(window), int(chunk), int(heads)
    _check(w, chunk, window)
    entries = sum_k.shape[1]
    with jax.named_scope("attn.eva"):
        slot, read, count = _reach(pos, window, chunk, entries)
        out = _step_impl(
            slot, jnp.clip(read, 0, entries), q.reshape(b, 1, hd), win_k,
            win_v, sum_k, sum_v, heads=heads,
            block=step_block(w, entries, 2 * hd * win_k.dtype.itemsize),
            vmem=_steps._VMEM_BUDGET, interpret=_INTERPRET)
    return out.reshape(b, hd).astype(q.dtype), count
