"""Attention over a slot table's key/value caches where the heads are
grouped, keys and values differ in width, a layer reads a window, a learned
sink joins the softmax, or the cache is a ring: what ``cached_attention`` /
``cached_attention_chunk`` compute when one of these is asked of them (the
plain case, one head count and one width over every cached position, stays
in ``core/opimpl/attention_ops.py`` as it was).

**Grouped heads.** ``heads`` query heads of ``Dk`` read ``kv_heads`` cached
key heads of ``Dk`` and value heads of ``Dv``; query head ``h`` reads
key/value head ``h // (heads / kv_heads)``. A cache row is ``[kv_heads *
Dk]`` (``[kv_heads * Dv]``) with the heads side by side, and every form
here reads it once for all the query heads of a group: nothing repeats a
cache to the query heads.

**Window.** ``window`` > 0: the query at position ``p`` reads positions
``p - window < s <= p`` (its own and the ``window - 1`` before it).

**Sink.** ``sink`` [heads]: a learned scalar a head that joins the
softmax's denominator and takes no value, ``p_s = exp(x_s) / (exp(sink) +
sum_s' exp(x_s'))``.

**Ring.** A cache of capacity ``C`` that holds position ``p`` at slot ``p %
C``: a window layer keeps ``C >= window`` positions a row whatever the
context. Rotary positions are applied before a key is cached, so the order
of a ring's slots means nothing to the softmax; which position a slot holds
follows from the last position written (:func:`ring_positions`). A step
writes its token's row and then reads the ring. A chunk of K lanes may be
longer than the ring, so it reads the ring AS IT WAS BEFORE the chunk and
the chunk's own keys and values beside it (:func:`attend_chunk_ring`), and
then only the lanes that no later lane of the chunk overwrites are written
(:func:`ring_slots`): a scatter that names a slot twice resolves in no
defined order.

Scores are scaled by ``Dk ** -0.5`` and kept in float32, the softmax is
float32, products accumulate in float32. Every form is strictly per-row.
Device events run under the scope ``attn.window`` (a window or a ring) or
``attn.full``.

**Which step form runs where.** A step over a cache that holds the whole
context (no window, no ring; plain heads are the case ``kv_heads ==
heads``) on ONE TPU runs the Pallas kernel ``cache_step.fwd``
(:func:`step_blocks`): it walks a row's caches in blocks of positions under
a streaming softmax and stops at the block that holds the row's own
position, so a step reads the positions the rows hold and not the rung.
:func:`step_plan` decides it from what the trace sees (placement, widths,
the rung's length, the VMEM two blocks of keys and values take
double-buffered). A ring, a window, the CPU, a mesh and every shape the
gate refuses keep the ``jnp`` forms (:func:`attend_step`, and the plain
form in ``core/opimpl/attention_ops.py``), which read the whole rung under
a mask and are the kernel's reference.

A step of LATENT attention with no selection (``latent_attention_dense``: K
lanes a row over one cached row ``[latent | rotary key]`` a position) is the
same walk with other parameters, the kernel ``latent_step.fwd``
(:func:`latent_blocks`): one group whose values are the latent columns of
its keys, so one buffer and one copy a block, and a position a query row,
the row's blocks ending at the highest position its live lanes hold. The
two kernels share the loop over the (row, block) pairs and the streaming
softmax (:func:`_walk_blocks`, :func:`_stream`), the block rule and the VMEM
count (:func:`step_block`, :func:`_working_set`); their bodies differ
because the device stores a cache of 576-wide rows with the POSITIONS
innermost, so a latent block is ``[R+P, block]`` and both products run the
other way round. :func:`latent_plan` decides it; the CPU, a mesh and a
refused shape keep ``sparse_latent.latent_attention_dense``'s ``jnp`` form.

**Which chunk form runs where.** A chunk of K lanes over a cache that holds
the whole context (no window, no ring) on ONE TPU runs the Pallas kernel
``cache_chunk.fwd`` (:func:`chunk_blocks`), a fourth client of the same loop
and streaming softmax: :func:`attend_chunk`'s walk, a row's blocks from 0 up,
with a block's scores in VMEM where XLA's loops send them through HBM
several times ([4, 16, 1024, 512] float32, 134 MB a block, at
``mimo2flash.serve.mixedlen.sat``'s full layers). One pass of its grid is a
(row, tile of ``CHUNK_TILE`` lanes, key/value group): the group's query heads
x the tile's lanes are one operand, a block of the group's keys is read once
for all of them, and the walk ends at the block that holds the highest
position a live lane OF THE TILE holds, so a tile early in the chunk does not
pay for the chunk's later lanes and a tile of pad lanes costs no block. A
group's key columns begin at ``gi * Dk``, no multiple of 128 where Dk is 192;
the kernel copies the aligned window of the row that holds them and the
queries carry zeros in the window's other columns (:func:`_key_windows`: 256
for 192, which costs the MXU what 192 does; not the step kernels'
block-diagonal queries over the whole row, which would cost it 4x).
:func:`chunk_plan` decides it from what the trace sees (placement, one type,
no window and no ring, K a multiple of the tile, a block that divides the
rung, widths of whole 128s, the working set). The CPU, a mesh, a window
without a ring, a ring and every refused shape keep the ``jnp`` forms
(:func:`attend_chunk`, :func:`attend_chunk_ring`), which are the kernel's
reference. A sink opens the kernel's softmax as it opens the step kernel's.
"""

import contextlib
import functools
import math
import operator

import jax
import jax.numpy as jnp
import numpy as np

from .gates import GateDecision, GateReason, platform_reason
from .kernel_names import named_pallas_call, traced_once
from .sparse_latent import _block

__all__ = ["attend_step", "attend_chunk", "attend_chunk_ring",
           "ring_positions", "ring_slots", "step_plan", "plan_for",
           "step_blocks", "latent_plan", "latent_plan_for", "latent_blocks",
           "chunk_plan", "chunk_plan_for", "chunk_blocks"]

ATTN_BLOCK = 512     # cache positions a chunk's block reads at a time

_F32 = jnp.float32
_LOW = float(jnp.finfo(jnp.float32).min)


def _scope(window, ring=False):
    return jax.named_scope("attn.window" if window or ring else "attn.full")


def _softmax(s, mask, sink):
    """s [.., C] float32 under ``mask``; ``sink`` broadcastable to s[.., 0]
    or None. Rows with nothing to read and no sink come out 0."""
    s = jnp.where(mask, s, _LOW)
    top = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        top = jnp.maximum(top, sink[..., None])
    e = jnp.where(mask, jnp.exp(s - top), 0.0)
    total = jnp.sum(e, axis=-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink[..., None] - top)
    return e / jnp.maximum(total, 1e-30)


def ring_positions(last, c):
    """The position each slot of a ring of ``c`` holds once position
    ``last`` [..] has been written: [.., c] int32, the largest ``p <= last``
    with ``p % c == slot``; negative where the slot holds nothing yet
    (``last`` -1: an empty ring)."""
    last = last.astype(jnp.int32)[..., None]
    return last - jnp.mod(last - jnp.arange(c, dtype=jnp.int32), c)


def ring_slots(pos, c, pad_pos):
    """Where each lane of a chunk lands in a ring of ``c``: pos [B, K], a
    row's live lanes (``pos < pad_pos``) holding consecutive positions from
    lane 0 on. [B, K] int32: ``pos % c`` for a live lane that no later live
    lane of its row overwrites (lane ``j + c`` is not live), ``c`` (past the
    ring: the write drops) for the others and for pad lanes."""
    pos = pos.astype(jnp.int32)
    live = pos < pad_pos
    b, kq = pos.shape
    if kq > c:
        later = jnp.concatenate(
            [live[:, c:], jnp.zeros((b, c), bool)], axis=1)
        live = live & ~later
    return jnp.where(live, jnp.mod(pos, c), c)


def attend_step(q, k, v, pos, heads, kv_heads, window=0, sink=None,
                ring=False):
    """One query a row. q [B, heads*Dk], k [B, C, kv_heads*Dk], v [B, C,
    kv_heads*Dv] with this step's row written, pos [B]. Returns ([B,
    heads*Dv] in q's dtype, [1] int32 the positions read, summed over the
    rows).

    The caches are read as they are stored: the scores are the product of a
    row's key cache with its queries laid out block-diagonally (``[kv_heads
    * Dk, heads]``: head h's Dk values in the rows of its group, zeros
    elsewhere), the mix the product of the probabilities ``[heads, C]`` with
    the value cache, of which a head keeps its own group's Dv columns. The
    zeros cost MXU passes that hide under the cache's read from HBM, and no
    cache is copied into a layout a head."""
    b, c, kd = k.shape
    g, r = int(kv_heads), int(heads) // int(kv_heads)
    dk, dv = kd // g, v.shape[-1] // g
    pos = pos.reshape(-1).astype(jnp.int32)
    with _scope(window, ring):
        own = jnp.eye(g, dtype=bool)
        qt = jnp.transpose(q.reshape(b, g, r, dk), (0, 3, 1, 2))
        q_blocks = jnp.where(own[None, :, None, :, None], qt[:, None],
                             0).reshape(b, kd, g * r)
        s = jnp.einsum("bck,bkh->bhc", k, q_blocks,
                       preferred_element_type=_F32) * (1.0 / math.sqrt(dk))
        held = (ring_positions(pos, c) if ring
                else jnp.arange(c, dtype=jnp.int32)[None])
        mask = (held >= 0) & (held <= pos[:, None])
        if window:
            mask = mask & (pos[:, None] - held < int(window))
        probs = _softmax(s, mask[:, None, :],
                         None if sink is None
                         else sink.astype(_F32)[None]).astype(q.dtype)
        mixed = jnp.einsum("bhc,bck->bhk", probs, v,
                           preferred_element_type=_F32)
        out = jnp.einsum("bgrgd->bgrd", mixed.reshape(b, g, r, g, dv))
        count = jnp.sum(mask, dtype=jnp.int32).reshape(1)
    return out.reshape(b, g * r * dv).astype(q.dtype), count


def attend_chunk(q, k, v, pos, heads, kv_heads, window=0, sink=None):
    """K queries a row over a cache that holds the context, the chunk's own
    rows written. q [B, K, heads*Dk], k [B, C, kv_heads*Dk], v [B, C,
    kv_heads*Dv], pos [B, K] (``>= C``: a pad lane, whose output means
    nothing). A row's cache is read in blocks of ``ATTN_BLOCK`` positions
    from the lowest its live lanes may read to the highest, under a running
    maximum and sum (the streaming softmax): no [K, heads, C] scores exist,
    a row none of whose lanes is live costs nothing, and a row early in its
    prompt reads only what is cached. Returns [B, K, heads*Dv]."""
    b, c, kd = k.shape
    kq = q.shape[1]
    g, r = int(kv_heads), int(heads) // int(kv_heads)
    dk, dv = kd // g, v.shape[-1] // g
    pos = pos.astype(jnp.int32)
    size = _block(c, ATTN_BLOCK)
    scale = 1.0 / math.sqrt(dk)
    if sink is None:
        top0 = jnp.full((g, r, kq), _LOW, _F32)
        total0 = jnp.zeros((g, r, kq), _F32)
    else:
        # the sink opens the streaming softmax: one term of weight 1 at its
        # own height, and no value
        top0 = jnp.broadcast_to(sink.astype(_F32).reshape(g, r, 1),
                                (g, r, kq))
        total0 = jnp.ones((g, r, kq), _F32)

    def row(bi, out):
        p = jax.lax.dynamic_index_in_dim(pos, bi, 0, keepdims=False)
        qh = jax.lax.dynamic_index_in_dim(q, bi, 0, keepdims=False) \
            .reshape(kq, g, r, dk)
        live = p < c
        high = jnp.max(jnp.where(live, p, -1))
        first = 0
        if window:
            low = jnp.min(jnp.where(live, p, c)) - (int(window) - 1)
            first = jnp.maximum(low, 0) // size

        def block(j, carry):
            top, total, acc = carry
            kb = jax.lax.dynamic_slice(k, (bi, j * size, 0),
                                       (1, size, kd))[0].reshape(size, g, dk)
            vb = jax.lax.dynamic_slice(
                v, (bi, j * size, 0), (1, size, g * dv))[0].reshape(
                    size, g, dv)
            s = jnp.einsum("kgrd,sgd->grks", qh, kb,
                           preferred_element_type=_F32) * scale
            at = j * size + jnp.arange(size, dtype=jnp.int32)
            m = at[None, :] <= p[:, None]
            if window:
                m = m & (p[:, None] - at[None, :] < int(window))
            s = jnp.where(m, s, _LOW)
            new_top = jnp.maximum(top, jnp.max(s, axis=-1))
            probs = jnp.where(m, jnp.exp(s - new_top[..., None]), 0.0)
            keep = jnp.exp(top - new_top)
            mixed = jnp.einsum("grks,sgd->grkd", probs.astype(q.dtype), vb,
                               preferred_element_type=_F32)
            return (new_top, total * keep + jnp.sum(probs, axis=-1),
                    acc * keep[..., None] + mixed)

        _, total, acc = jax.lax.fori_loop(
            first, (high + size) // size, block,
            (top0, total0, jnp.zeros((g, r, kq, dv), _F32)))
        y = acc / jnp.maximum(total, 1e-30)[..., None]
        y = jnp.transpose(y, (2, 0, 1, 3)).reshape(1, kq, g * r * dv)
        return jax.lax.dynamic_update_slice(out, y.astype(q.dtype),
                                            (bi, 0, 0))

    with _scope(window):
        return jax.lax.fori_loop(
            0, b, row, jnp.zeros((b, kq, g * r * dv), q.dtype))


def attend_chunk_ring(q, ring_k, ring_v, new_k, new_v, pos, heads, kv_heads,
                      window, sink=None):
    """K queries a row over a ring and the chunk's own keys and values. q
    [B, K, heads*Dk], ring_k [B, C, kv_heads*Dk] and ring_v [B, C,
    kv_heads*Dv] AS THEY WERE BEFORE the chunk (``C >= window``; they hold
    the positions up to ``pos[b, 0] - 1``), new_k / new_v [B, K, ..] the
    chunk's own, pos [B, K] (a row's live lanes hold consecutive positions
    from lane 0 on; a pad lane holds a position past every live one).
    Lane j reads the ring's slots and the lanes before it that lie inside
    its window. Where K is a multiple of C past it, the lanes go in blocks
    of C, each against the block before it (the ring, for the first) and
    itself: a lane's window never reaches further. Returns [B, K,
    heads*Dv]."""
    b, c, kd = ring_k.shape
    kq = q.shape[1]
    g, r = int(kv_heads), int(heads) // int(kv_heads)
    dk, dv = kd // g, ring_v.shape[-1] // g
    window = int(window)
    if window < 1 or window > c:
        raise ValueError("a ring of %d positions serves a window of 1 to %d,"
                         " not %d" % (c, c, window))
    pos = pos.astype(jnp.int32)
    with _scope(window, True):
        keys = jnp.concatenate([ring_k, new_k.astype(ring_k.dtype)], axis=1)
        vals = jnp.concatenate([ring_v, new_v.astype(ring_v.dtype)], axis=1)
        held = jnp.concatenate([ring_positions(pos[:, 0] - 1, c), pos],
                               axis=1)
        if kq > c and kq % c == 0:
            n = kq // c

            def banded(x):
                x = x.reshape((b, n + 1, c) + x.shape[2:])
                return jnp.concatenate([x[:, :-1], x[:, 1:]], axis=2)

            keys, vals, held = banded(keys), banded(vals), banded(held)
        else:
            n = 1
            keys, vals, held = keys[:, None], vals[:, None], held[:, None]
        qp = pos.reshape(b, n, kq // n)
        s = jnp.einsum("bnqgrd,bnkgd->bngrqk",
                       q.reshape(b, n, kq // n, g, r, dk),
                       keys.reshape(b, n, -1, g, dk),
                       preferred_element_type=_F32) * (1.0 / math.sqrt(dk))
        at = held[:, :, None, :]
        mask = (at >= 0) & (at <= qp[..., None]) \
            & (qp[..., None] - at < window)
        probs = _softmax(s, mask[:, :, None, None],
                         None if sink is None
                         else sink.astype(_F32).reshape(g, r, 1))
        out = jnp.einsum("bngrqk,bnkgd->bnqgrd", probs.astype(q.dtype),
                         vals.reshape(b, n, -1, g, dv),
                         preferred_element_type=_F32)
    return out.reshape(b, kq, g * r * dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# a step on one TPU: the kernel ``cache_step.fwd``
# ---------------------------------------------------------------------------

_INTERPRET = False  # tests flip this to run the kernel on the CPU

_VMEM_BUDGET = 32 * 1024 * 1024
# cache bytes the chip reads in the time a pass of the kernel's loop costs
# beside its copies (0.3 us at 700 GB/s, TPU v5e)
_PASS_BYTES = 200 * 1024


def step_block(c, row_bytes):
    """Positions a block of the step kernel reads, a multiple of 128 that
    divides ``c`` and is shorter than it; None where ``c`` has no such
    divisor (a rung of one block has nothing to cut short). A row reads
    half a block past its position and pays a pass a block, so a row of
    ``p`` positions of ``row_bytes`` (keys and values) is cheapest at
    ``sqrt(2 p _PASS_BYTES / row_bytes)``: the largest divisor under that
    for a row a quarter of the rung long, the smallest where none is."""
    fits = [n for n in range(128, c, 128) if c % n == 0]
    if not fits:
        return None
    want = math.sqrt(c / 2 * _PASS_BYTES / row_bytes)
    return max([n for n in fits if n <= want] or fits[:1])


def _up(n, multiple):
    """``n`` up to the next multiple of ``multiple``: a width as VMEM holds
    it (128 lanes), query rows as their type tiles them (sublanes)."""
    return -(-n // multiple) * multiple


def _working_set(b, block, heads, kd, vd, itemsize, own_values=True):
    """Bytes the step kernel holds in VMEM, counted generously: two blocks
    of keys and (``own_values``: where they are not columns of the keys) of
    values (double-buffered), the rows' queries laid out a head a row and
    the columns a head keeps, the output, the float32 accumulator, and the
    [heads, block] and [heads, vd] float32 tiles live in a pass."""
    kd, vd = _up(kd, 128), _up(vd, 128)
    blocks = 2 * block * (kd + (vd if own_values else 0)) * itemsize
    whole = 2 * b * heads * kd * itemsize + 2 * heads * vd * 4 \
        + 2 * b * heads * vd * 4
    live = 4 * heads * block * 4 + 4 * heads * vd * 4
    return blocks + whole + live


def _itemsize(*arrays):
    """The item size of the one floating type ``arrays`` have, None where
    they have not one: what the plans take as ``itemsize``."""
    first = arrays[0].dtype
    one = all(a.dtype == first for a in arrays) \
        and jnp.issubdtype(first, jnp.floating)
    return first.itemsize if one else None


def _one_type(itemsize, what):
    """The reason that blocks a site whose queries and ``what`` are not of
    one 2- or 4-byte floating type (``itemsize`` None), else None."""
    if itemsize not in (2, 4):
        return GateReason("dtype", "queries and %s are not of one 2- or "
                          "4-byte floating type" % what)


def _gate(kernel, reasons, block, no_block, working_set, over, admitted):
    """A step kernel's ``GateDecision``: ``kernel``, or ``rung_xla`` with
    the blocking reasons. ``reasons``: what the site's placement, types and
    widths block it by (None: a check that passed). Where nothing does,
    ``block()`` is the block the site's lengths are cut into: None blocks by
    geometry (``no_block``), a ``working_set(block)`` over the budget by
    vmem (``over``, a format of the block: what is held), and what neither
    blocks is admitted with ``admitted``, a format of the block."""
    reasons = [r for r in reasons if r is not None]
    if not reasons:
        size = block()
        if size is None:
            reasons.append(GateReason("geometry", no_block))
        elif working_set(size) > _VMEM_BUDGET:
            reasons.append(GateReason(
                "vmem", "%s exceed the %.0f MB VMEM budget"
                % (over % size, _VMEM_BUDGET / 2**20)))
    if reasons:
        return GateDecision(False, "rung_xla", fallback=kernel,
                            reasons=reasons)
    return GateDecision(True, kernel, reasons=[GateReason(
        "shape", admitted % size, blocking=False)])


def step_plan(b, c, heads, kv_heads, kd, vd, itemsize, window=0, ring=False,
              platform=None):
    """Which way a ``cached_attention`` site reads its caches, as a
    ``GateDecision``: ``cache_step`` (the kernel: the blocks up to each
    row's own position) or ``rung_xla`` (the ``jnp`` forms: the whole rung
    under a mask) with the blocking reasons. ``kd`` / ``vd``: the width of a
    cached key / value row, all heads; ``itemsize``: of the one floating
    type the queries and the caches have, None where they have not one;
    ``platform``: what
    ``gates.platform_reason`` says of where the step runs
    (:func:`plan_for`)."""
    whole = GateReason(
        "shape", "a %s of %d positions is read whole: no rung to cut short"
        % ("ring" if ring else "window", c if ring else window))
    reasons = [platform, whole if window or ring else None,
               _one_type(itemsize, "caches")]
    if reasons[-1] is None:
        r = heads // max(kv_heads, 1)
        sublanes = 32 // itemsize
        if kd % 128 or vd % 128 or heads % sublanes or (r > 1 and r % 8):
            reasons.append(GateReason(
                "geometry", "rows of %d and %d are not multiples of 128, or "
                "%d heads no multiple of %d sublanes, or groups of %d query "
                "heads no multiple of 8" % (kd, vd, heads, sublanes, r)))
    return _gate(
        "cache_step", reasons, lambda: step_block(c, (kd + vd) * itemsize),
        "a rung of %d positions is no longer than one block of a multiple "
        "of 128" % c,
        lambda block: _working_set(b, block, heads, kd, vd, itemsize),
        "two blocks of %%d positions of %d + %d wide rows, double-buffered, "
        "beside %d rows' queries" % (kd, vd, b),
        "blocks of %%d of %d positions, each row's up to its own" % c)


def plan_for(q, k, v, heads, kv_heads, window=0, ring=False):
    """:func:`step_plan` of a site's arrays, where the step being traced is
    placed."""
    return step_plan(k.shape[0], k.shape[1], int(heads), int(kv_heads),
                     k.shape[2], v.shape[2], _itemsize(q, k, v), window,
                     ring, platform=platform_reason(_INTERPRET))


def _walk_blocks(rows, last, copies, open_row, products, close_row):
    """The step kernels' loop: one pass a (row, block) pair, the rows in
    their order and a row's blocks from 0 to ``last(row)``, in ONE loop over
    the pairs that exist, so that the copy of the next pair's block runs
    under this pair's products, across rows too. ``copies(row, j, slot)``:
    the async copies that bring block ``j`` of ``row`` into buffer ``slot``;
    ``open_row()`` before a row's first block, ``products(row, j, slot)``
    once the block is there, ``close_row(row)`` after its last."""
    from jax.experimental import pallas as pl

    passes = jax.lax.fori_loop(
        0, rows, lambda row, n: n + last(row) + 1, jnp.int32(0))

    for copy in copies(0, 0, 0):
        copy.start()

    def one(i, carry):
        row, j = carry
        slot = jax.lax.rem(i, 2)
        done = j == last(row)
        next_row = jnp.where(done, row + 1, row)
        next_j = jnp.where(done, 0, j + 1)

        @pl.when(i + 1 < passes)
        def _():
            for copy in copies(next_row, next_j, 1 - slot):
                copy.start()

        pl.when(j == 0)(open_row)
        for copy in copies(row, j, slot):
            copy.wait()
        products(row, j, slot)
        pl.when(done)(functools.partial(close_row, row))
        return next_row, next_j

    jax.lax.fori_loop(0, passes, one, (jnp.int32(0), jnp.int32(0)))


def _stream(s, live, mix, top_ref, total_ref, acc_ref, axis=-1):
    """A block's turn of the streaming softmax: s [heads, block] float32
    scores, ``live`` where a query row reads the position, ``mix(probs)``
    the block's [heads, vd] float32 product with its values; a row's
    running maximum, sum and accumulator move on. ``axis`` 0: the same
    turned round, s [block, heads] and the product [vd, heads], the
    positions along the sublanes and a query row a lane."""
    s = jnp.where(live, s, _LOW)
    top = top_ref[...]
    new_top = jnp.maximum(top, jnp.max(s, axis=axis, keepdims=True))
    probs = jnp.where(live, jnp.exp(s - new_top), 0.0)
    keep = jnp.exp(top - new_top)
    top_ref[...] = new_top
    total_ref[...] = total_ref[...] * keep \
        + jnp.sum(probs, axis=axis, keepdims=True)
    acc_ref[...] = acc_ref[...] * keep + mix(probs)


def _open(sink_ref, top_ref, total_ref, acc_ref):
    """Before a query row's first block: nothing read yet, or, where there
    is a sink (``sink_ref`` of ``top_ref``'s shape), one term of weight 1 at
    its own height, and no value."""
    if sink_ref is None:
        top_ref[...] = jnp.full(top_ref.shape, _LOW, _F32)
        total_ref[...] = jnp.zeros(total_ref.shape, _F32)
    else:
        top_ref[...] = sink_ref[...]
        total_ref[...] = jnp.ones(total_ref.shape, _F32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)


def _step_kernel(pos_ref, q_ref, own_ref, *refs, block, scale, groups,
                 with_sink):
    """``cache_step.fwd`` (:func:`_walk_blocks`: a row's blocks from 0 to
    the one that holds its position). pos_ref [B] int32 (SMEM); q_ref [B,
    heads, kd]: a row's queries laid out block-diagonally; own_ref [heads,
    vd] float32: 1 in the value columns of a head's own group; then
    sink_ref [heads, 1] float32 if there is a sink; k_hbm [B, C, kd], v_hbm
    [B, C, vd] where they are stored; out_ref
    [B, r, vd] float32: head ``gi * r + ri`` in columns ``gi`` of row
    ``ri``; two slots of a block of keys and of values, their copy
    semaphores, and a row's running maximum, sum and accumulator."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sink_ref = None
    if with_sink:
        sink_ref, *refs = refs
    (k_hbm, v_hbm, out_ref, k_buf, v_buf, sems, top_ref, total_ref,
     acc_ref) = refs
    rows, heads, _ = q_ref.shape
    c = k_hbm.shape[1]
    r = heads // groups

    def last(row):          # the block that holds the row's own position
        return jnp.clip(pos_ref[row], 0, c - 1) // block

    def copies(row, j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(k_hbm.at[row, at], k_buf.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[row, at], v_buf.at[slot],
                                      sems.at[1, slot]))

    open_row = functools.partial(_open, sink_ref, top_ref, total_ref, acc_ref)

    def products(row, j, slot):
        kb, vb = k_buf[slot], v_buf[slot]
        s = jax.lax.dot_general(q_ref[row], kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32) * scale
        at = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _stream(s, at <= pos_ref[row],
                lambda probs: jnp.dot(probs.astype(vb.dtype), vb,
                                      preferred_element_type=_F32),
                top_ref, total_ref, acc_ref)

    def close_row(row):
        y = acc_ref[...] / jnp.maximum(total_ref[...], 1e-30)
        y = jnp.where(own_ref[...] != 0, y, 0.0)
        if r == 1:
            out_ref[row] = jnp.sum(y, axis=0, keepdims=True)
        else:
            out_ref[row] = functools.reduce(operator.add, (
                y[gi * r:(gi + 1) * r] for gi in range(groups)))

    _walk_blocks(rows, last, copies, open_row, products, close_row)


@traced_once("cache_step.fwd", ("groups", "block", "vmem", "interpret"))
def _step_impl(pos, q_blocks, own, sink, k, v, groups, block, vmem,
               interpret):
    """pos [B] int32; q_blocks [B, heads, kd]; own [heads, vd] float32; sink
    [heads, 1] float32 or None; k [B, C, kd], v [B, C, vd]. Returns [B,
    heads / groups, vd] float32 (:func:`_step_kernel`). ``vmem``: the
    kernel's ``vmem_limit_bytes``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, kd = q_blocks.shape
    vd = v.shape[2]
    held = pl.BlockSpec(memory_space=pltpu.VMEM)
    stored = pl.BlockSpec(memory_space=pl.ANY)
    arrays = [q_blocks, own] + ([] if sink is None else [sink]) + [k, v]
    return named_pallas_call(
        "cache_step.fwd",
        functools.partial(_step_kernel, block=block, groups=groups,
                          scale=1.0 / math.sqrt(kd // groups),
                          with_sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[held] * (len(arrays) - 2) + [stored, stored],
            out_specs=held,
            scratch_shapes=[pltpu.VMEM((2, block, kd), k.dtype),
                            pltpu.VMEM((2, block, vd), v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((heads, 1), _F32),
                            pltpu.VMEM((heads, 1), _F32),
                            pltpu.VMEM((heads, vd), _F32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads // groups, vd), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(pos, *arrays)


def step_blocks(q, k, v, pos, heads, kv_heads, sink=None, plain=False):
    """:func:`attend_step` without a window or a ring, by the kernel
    ``cache_step.fwd`` (what :func:`plan_for` admits): the same arguments,
    the same two results, the same sums in another order (a row's blocks one
    after the other under a running maximum and sum, the probabilities cast
    to the cache's type for the mix, one division at the end). A row reads
    the blocks up to the one that holds ``pos``, a row fed 0 one block; the
    count is the positions ``<= pos``, not the positions fetched. ``plain``:
    for the op's plain form, which runs under no scope but the op's."""
    b, c, kd = k.shape
    g, r = int(kv_heads), int(heads) // int(kv_heads)
    dk, dv = kd // g, v.shape[-1] // g
    pos = pos.reshape(-1).astype(jnp.int32)
    keeps = np.kron(np.eye(g, dtype=np.float32),
                    np.ones((r, dv), np.float32))
    with contextlib.nullcontext() if plain else _scope(0):
        # the queries block-diagonally, a head a row: [heads, kv_heads *
        # Dk], head h's Dk values in the columns of its group, zeros
        # elsewhere
        own = jnp.eye(g, dtype=bool)
        q_blocks = jnp.where(
            own[None, :, None, :, None], q.reshape(b, g, r, 1, dk),
            0).reshape(b, g * r, kd)
        out = _step_impl(
            pos, q_blocks, jnp.asarray(keeps),
            None if sink is None else sink.astype(_F32).reshape(g * r, 1),
            k, v, groups=g,
            block=step_block(c, (kd + g * dv) * k.dtype.itemsize),
            vmem=_VMEM_BUDGET, interpret=_INTERPRET)
        out = jnp.transpose(out.reshape(b, r, g, dv), (0, 2, 1, 3))
        count = jnp.sum(jnp.clip(pos + 1, 0, c), dtype=jnp.int32).reshape(1)
    return out.reshape(b, g * r * dv).astype(q.dtype), count


# ---------------------------------------------------------------------------
# a latent step on one TPU: the kernel ``latent_step.fwd``
# ---------------------------------------------------------------------------

def _query_rows(lanes, heads, itemsize):
    """A row's ``lanes * heads`` query rows, up to the sublane multiple its
    type tiles by."""
    return _up(lanes * heads, 32 // itemsize)


def latent_plan(b, c, lanes, heads, width, r, itemsize, platform=None):
    """Which way a ``latent_attention_dense`` site reads its cache, as a
    ``GateDecision``: ``latent_step`` (the kernel: a row's blocks up to
    the highest position its lanes hold) or ``rung_xla`` (the ``jnp`` form:
    the whole rung under a mask, twice) with the blocking reasons. ``width``
    / ``r``: of a cached row ``[latent | rotary key]`` and of its latent
    part; ``itemsize`` and ``platform`` as :func:`step_plan`'s.

    The kernel reads a block as the DEVICE stores it: a cache whose rows
    are no multiple of 128 wide lies with the positions innermost there
    (``[B, R+P, C]``: nothing is padded so), and a block is ``[R+P,
    block]``. A cache of rows that are a multiple of 128 lies row-major;
    read this way it would be turned round first, so it keeps the ``jnp``
    form."""
    reasons = [platform, _one_type(itemsize, "cache")]
    queries = None
    if reasons[-1] is None:
        queries = _query_rows(lanes, heads, itemsize)
        if r % 128 or not r < width:
            reasons.append(GateReason(
                "geometry", "a latent part of %d columns of a row of %d is "
                "no multiple of 128 under a rotary tail" % (r, width)))
        elif width % 128 == 0 or width % (32 // itemsize):
            reasons.append(GateReason(
                "geometry", "a cache of rows of %d columns lies row-major on "
                "the device (a multiple of 128), or its rows are no multiple "
                "of %d sublanes: no block with the positions innermost"
                % (width, 32 // itemsize)))
    return _gate(
        "latent_step", reasons, lambda: step_block(c, width * itemsize),
        "a rung of %d positions is no longer than one block of a multiple "
        "of 128" % c,
        lambda block: _working_set(b, block, queries, width, r, itemsize,
                                   own_values=False),
        "two blocks of %%d positions of %d wide rows beside %d rows' %s "
        "queries" % (width, b, queries),
        "blocks of %%d of %d positions, each row's up to its lanes' "
        "highest" % c)


def latent_plan_for(q, cache, r, heads):
    """:func:`latent_plan` of a site's arrays (q [B, K, ..], cache [B, C,
    R+P]), where the step being traced is placed."""
    return latent_plan(cache.shape[0], cache.shape[1], q.shape[1], int(heads),
                       cache.shape[2], int(r), _itemsize(q, cache),
                       platform=platform_reason(_INTERPRET))


def _latent_kernel(pos_ref, q_ref, cache_hbm, out_ref, buf, sems, top_ref,
                   total_ref, acc_ref, *, block, scale, lane_heads):
    """``latent_step.fwd`` (:func:`_walk_blocks`: a row's blocks from 0 to
    the one that holds the highest position its live lanes hold; a row of
    pad lanes alone one block). The step kernel's case of ONE group whose
    values are the first ``r`` columns of its keys, so one buffer and one
    copy a block, with K lanes a row, so a position a query row. pos_ref [B
    * K] int32 (SMEM), ``>= C``: a pad lane, which reaches nothing; q_ref
    [B, M, R+P]: a row's K x ``lane_heads`` absorbed queries lane after
    lane, zero rows up to M; cache_hbm [B, R+P, C]: the cache as the device
    stores it, the positions innermost; out_ref [B, M, r] in the cache's
    type: a query row's mixed latent; two slots of a block ``[R+P,
    block]``, their copy semaphores, and a row's running maximum, sum and
    accumulator."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, m, _ = q_ref.shape
    c = cache_hbm.shape[2]
    r = acc_ref.shape[1]
    lanes = pos_ref.shape[0] // rows

    def held(row):          # its lanes' positions, -1 for a pad lane
        return [jnp.where(p < c, p, -1) for p in (
            pos_ref[row * lanes + k] for k in range(lanes))]

    def last(row):
        return jnp.maximum(functools.reduce(jnp.maximum, held(row)),
                           0) // block

    def copies(row, j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(cache_hbm.at[row, :, at], buf.at[slot],
                                      sems.at[slot]),)

    open_row = functools.partial(_open, None, top_ref, total_ref, acc_ref)

    def products(row, j, slot):
        s = jnp.dot(q_ref[row], buf[slot],
                    preferred_element_type=_F32) * scale
        head = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        ends = jnp.full((m, 1), -1, jnp.int32)      # a pad row: nothing
        for k, p in enumerate(held(row)):
            ends = jnp.where((head >= k * lane_heads)
                             & (head < (k + 1) * lane_heads), p, ends)
        at = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _stream(s, at <= ends,
                lambda probs: jax.lax.dot_general(
                    probs.astype(buf.dtype), buf[slot, :r],
                    (((1,), (1,)), ((), ())), preferred_element_type=_F32),
                top_ref, total_ref, acc_ref)

    def close_row(row):
        y = acc_ref[...] / jnp.maximum(total_ref[...], 1e-30)
        out_ref[row] = y.astype(out_ref.dtype)

    _walk_blocks(rows, last, copies, open_row, products, close_row)


@traced_once("latent_step.fwd", ("lane_heads", "r", "scale", "block", "vmem",
                                 "interpret"))
def _latent_impl(pos, queries, stored, lane_heads, r, scale, block, vmem,
                 interpret):
    """pos [B * K] int32; queries [B, M, R+P]; stored [B, R+P, C]. Returns
    the mixed latent [B, M, r] in the cache's type
    (:func:`_latent_kernel`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, m, width = queries.shape
    return named_pallas_call(
        "latent_step.fwd",
        functools.partial(_latent_kernel, block=block, scale=scale,
                          lane_heads=lane_heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, width, block), stored.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((m, 1), _F32),
                            pltpu.VMEM((m, 1), _F32),
                            pltpu.VMEM((m, r), _F32)]),
        out_shape=jax.ShapeDtypeStruct((b, m, r), stored.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(pos, queries, stored)


def latent_blocks(qa, cache, pos, r, scale):
    """The core of ``sparse_latent.latent_attention_dense`` by the kernel
    ``latent_step.fwd`` (what :func:`latent_plan_for` admits): qa [B, K, H,
    R+P] a row's absorbed queries, cache [B, C, R+P] with the step's rows
    written, pos [B, K] (``>= C``: a pad lane). Returns the mixed latent [B,
    K, H, r] in qa's type, 0 on a pad lane: the ``jnp`` form's sums in
    another order (a row's blocks one after the other under a running
    maximum and sum, a block fetched once for the scores and for the mix,
    the probabilities cast to the cache's type, one division at the end).
    A row reads the blocks up to the one that holds the highest position
    of its live lanes, a row of pad lanes alone one block.

    The kernel is handed the cache with its two last axes exchanged: on
    the device that IS the cache where it lies (:func:`latent_plan`), and
    the compiler makes no copy of it."""
    b, kq, heads, width = qa.shape
    c = cache.shape[1]
    m = _query_rows(kq, heads, cache.dtype.itemsize)
    queries = jnp.pad(qa.reshape(b, kq * heads, width),
                      ((0, 0), (0, m - kq * heads), (0, 0)))
    mixed = _latent_impl(
        pos.reshape(-1).astype(jnp.int32), queries,
        jnp.swapaxes(cache, 1, 2), lane_heads=heads, r=int(r),
        scale=float(scale), block=step_block(c, width * cache.dtype.itemsize),
        vmem=_VMEM_BUDGET, interpret=_INTERPRET)
    return mixed[:, :kq * heads].reshape(b, kq, heads, r)


# ---------------------------------------------------------------------------
# a chunk on one TPU: the kernel ``cache_chunk.fwd``
# ---------------------------------------------------------------------------

CHUNK_TILE = 128     # lanes of a chunk whose scores VMEM holds at a time


def chunk_block(c):
    """Positions a block of the chunk kernel reads: the largest multiple of
    128 that divides ``c`` and is at most ``ATTN_BLOCK``, None where ``c``
    has none. The work is the MXU's and not the cache's read, so what bounds
    a block is the [query rows, block] float32 scores VMEM holds, not
    :func:`step_block`'s price of a pass."""
    fits = [n for n in range(128, min(c, ATTN_BLOCK) + 1, 128) if c % n == 0]
    return max(fits, default=None)


def _key_windows(g, dk):
    """Where the chunk kernel finds each group's key columns in a cached row
    of ``g * dk`` (a multiple of 128): ``(width, [first column a group])``,
    the narrowest windows of one width that begin at a multiple of 128 and
    hold a group's ``dk`` columns. A group's columns begin at ``gi * dk``,
    which is no multiple of 128 where ``dk`` is none (192: 0, 192, 384,
    576); a window of ``width`` (256: from 0, 128, 384, 512) is a slice a
    copy can take, and the queries carry zeros in the window's other
    columns. The MXU contracts 128 columns a pass, so 256 costs what 192
    does."""
    width = max(_up(gi * dk % 128 + dk, 128) for gi in range(g))
    return width, [min(gi * dk // 128 * 128, g * dk - width)
                   for gi in range(g)]


def chunk_plan(b, c, lanes, heads, kv_heads, kd, vd, itemsize, window=0,
               ring=False, platform=None):
    """Which way a ``cached_attention_chunk`` site with grouped heads or a
    sink reads its caches, as a ``GateDecision``: ``cache_chunk`` (the
    kernel: the scores of a tile of lanes and a block of positions stay in
    VMEM) or ``rung_xla`` (:func:`attend_chunk` / :func:`attend_chunk_ring`:
    the same blocks as XLA loops, a block's scores through HBM) with the
    blocking reasons. ``lanes``: K; the other arguments as
    :func:`step_plan`'s."""
    whole = GateReason(
        "shape", "a %s of %d positions: the kernel walks a cache that holds "
        "the context from position 0" % ("ring" if ring else "window",
                                         c if ring else window))
    reasons = [platform, whole if window or ring else None,
               _one_type(itemsize, "caches")]
    g = max(kv_heads, 1)
    r, dv = heads // g, vd // g
    if reasons[-1] is None and (kd % 128 or dv % 128 or lanes % CHUNK_TILE):
        reasons.append(GateReason(
            "geometry", "key rows of %d or a group's values of %d are no "
            "multiple of 128, or %d lanes no multiple of the tile of %d"
            % (kd, dv, lanes, CHUNK_TILE)))
    width = _key_windows(g, kd // g)[0] if kd % 128 == 0 else kd
    return _gate(
        "cache_chunk", reasons, lambda: chunk_block(c),
        "a cache of %d positions has no block of a multiple of 128" % c,
        lambda block: _working_set(1, block, r * CHUNK_TILE, width, dv,
                                   itemsize),
        "two blocks of %%d positions of %d + %d columns, double-buffered, "
        "beside %d heads' scores of %d lanes" % (width, dv, r, CHUNK_TILE),
        "blocks of %%d of %d positions, each tile of %d lanes up to its "
        "highest" % (c, CHUNK_TILE))


def chunk_plan_for(q, k, v, heads, kv_heads, window=0, ring=False):
    """:func:`chunk_plan` of a site's arrays (q [B, K, ..]), where the chunk
    run being traced is placed."""
    return chunk_plan(k.shape[0], k.shape[1], q.shape[1], int(heads),
                      int(kv_heads), k.shape[2], v.shape[2],
                      _itemsize(q, k, v), window, ring,
                      platform=platform_reason(_INTERPRET))


def _chunk_kernel(high_ref, low_ref, first_ref, pos_ref, q_ref, *refs, block,
                  scale, with_sink):
    """``cache_chunk.fwd``, one pass of its grid a (row, tile of lanes,
    key/value group): :func:`_walk_blocks` over the row's blocks from 0 to
    the one that holds the highest position a live lane of the tile holds,
    none where the tile has no live lane. The group's ``r`` query heads x
    the tile's lanes are ONE operand of ``m = r * tile`` query rows, and a
    query row is a LANE: the scores of a block are ``[block, m]``, so the
    softmax's maximum and sum run down the sublanes (the VPU, a vreg at a
    time) and a row's running statistics are ``[1, m]``, 16 vregs and not
    256; with the query rows along the sublanes the kernel spent its time
    in reductions across lanes and in ``[m, 1]`` columns, whatever the
    block (PERF.md, PR 48).

    high_ref [B * tiles] int32 (SMEM): the tile's highest live position, -1
    for none; low_ref [B * tiles] int32 (SMEM): the lowest position a lane
    of the tile holds (a block that ends at or before it is read whole by
    every lane: its turn of the softmax needs no mask); first_ref [groups]
    int32 (SMEM): the first column of a group's key window
    (:func:`_key_windows`); pos_ref [1, m] int32: the query rows' positions;
    q_ref [width, m]: the group's queries turned round, a head's Dk values
    in the rows where its keys lie in the window, zeros elsewhere; then
    sink_ref [1, m] float32 if there is a sink; k_hbm [B, C, kd], v_hbm [B,
    C, vd] where they are stored; out_ref [r, tile, dv]; two slots of a
    block of the group's key window and of its values, their copy
    semaphores, and the query rows' running maximum and sum [1, m] and
    accumulator [dv, m]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sink_ref = None
    if with_sink:
        sink_ref, *refs = refs
    (k_hbm, v_hbm, out_ref, k_buf, v_buf, sems, top_ref, total_ref,
     acc_ref) = refs
    r, tile, dv = out_ref.shape
    width = q_ref.shape[0]
    row, gi = pl.program_id(0), pl.program_id(2)
    tile_at = row * pl.num_programs(1) + pl.program_id(1)
    high, low = high_ref[tile_at], low_ref[tile_at]

    def copies(_, j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        keys = pl.ds(pl.multiple_of(first_ref[gi], 128), width)
        values = pl.ds(pl.multiple_of(gi * dv, 128), dv)
        return (pltpu.make_async_copy(k_hbm.at[row, at, keys],
                                      k_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[row, at, values],
                                      v_buf.at[slot], sems.at[1, slot]))

    open_row = functools.partial(_open, sink_ref, top_ref, total_ref, acc_ref)

    def products(_, j, slot):
        vb = v_buf[slot]
        s = jnp.dot(k_buf[slot], q_ref[...],
                    preferred_element_type=_F32) * scale

        def turn(live):
            _stream(s, live,
                    lambda probs: jax.lax.dot_general(
                        vb, probs.astype(vb.dtype), (((0,), (0,)), ((), ())),
                        preferred_element_type=_F32),
                    top_ref, total_ref, acc_ref, axis=0)

        inside = (j + 1) * block <= low + 1

        @pl.when(inside)
        def _():
            turn(True)

        @pl.when(jnp.logical_not(inside))
        def _():
            at = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            turn(at <= pos_ref[...])

    def close_row(_):
        y = acc_ref[...] / jnp.maximum(total_ref[...], 1e-30)
        out_ref[...] = y.T.reshape(r, tile, dv).astype(out_ref.dtype)

    @pl.when(high >= 0)
    def _():
        _walk_blocks(1, lambda _: high // block, copies, open_row, products,
                     close_row)

    @pl.when(high < 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


@traced_once("cache_chunk.fwd", ("r", "block", "scale", "vmem", "interpret"))
def _chunk_impl(high, low, first, pos, queries, sink, k, v, r, block, scale,
                vmem, interpret):
    """high, low [B * tiles] int32; first [groups] int32; pos [B, tiles, 1,
    m] int32 (``m = r * tile``); queries [B, groups, tiles, width, m]; sink
    [groups, 1, m] float32 or None; k [B, C, kd], v [B, C, vd]. Returns [B,
    groups, r, K, dv] in the queries' type (:func:`_chunk_kernel`).
    ``vmem``: the kernel's ``vmem_limit_bytes``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, g, tiles, width, m = queries.shape
    dv = v.shape[2] // g
    tile = m // r
    stored = pl.BlockSpec(memory_space=pl.ANY)
    specs = [pl.BlockSpec((None, None, 1, m),
                          lambda bi, t, gi, *_: (bi, t, 0, 0)),
             pl.BlockSpec((None, None, None, width, m),
                          lambda bi, t, gi, *_: (bi, gi, t, 0, 0))]
    if sink is not None:
        specs.append(pl.BlockSpec((None, 1, m),
                                  lambda bi, t, gi, *_: (gi, 0, 0)))
    arrays = [pos, queries] + ([] if sink is None else [sink]) + [k, v]
    return named_pallas_call(
        "cache_chunk.fwd",
        functools.partial(_chunk_kernel, block=block, scale=scale,
                          with_sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, tiles, g),
            in_specs=specs + [stored, stored],
            out_specs=pl.BlockSpec((None, None, r, tile, dv),
                                   lambda bi, t, gi, *_: (bi, gi, 0, t, 0)),
            scratch_shapes=[pltpu.VMEM((2, block, width), k.dtype),
                            pltpu.VMEM((2, block, dv), v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((1, m), _F32),
                            pltpu.VMEM((1, m), _F32),
                            pltpu.VMEM((dv, m), _F32)]),
        out_shape=jax.ShapeDtypeStruct((b, g, r, tiles * tile, dv),
                                       queries.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=vmem),
        interpret=interpret,
    )(high, low, first, *arrays)


def chunk_blocks(q, k, v, pos, heads, kv_heads, sink=None):
    """:func:`attend_chunk` without a window, by the kernel
    ``cache_chunk.fwd`` (what :func:`chunk_plan_for` admits): the same
    arguments, the same result on every live lane, the same sums in the
    same order of blocks (the probabilities cast to the cache's type for
    the mix, one division at the end). A tile of ``CHUNK_TILE`` lanes reads
    the blocks up to the one that holds the highest position its live lanes
    hold and a tile of pad lanes alone reads nothing and comes out 0; a pad
    lane beside live ones reads what the tile reads.

    The queries are handed over a group and a tile at a time and turned
    round, [B, groups, tiles, width, r * tile] (:func:`_key_windows`; a
    query row a lane, :func:`_chunk_kernel`), and the result comes back a
    head at a time: two transpositions of the chunk's own activations that
    XLA makes, none of a cache."""
    b, c, kd = k.shape
    kq = q.shape[1]
    g, r = int(kv_heads), int(heads) // int(kv_heads)
    dk, dv = kd // g, v.shape[-1] // g
    tile, tiles = CHUNK_TILE, kq // CHUNK_TILE
    width, first = _key_windows(g, dk)
    pos = pos.astype(jnp.int32).reshape(b, tiles, 1, tile)
    with _scope(0):
        high = jnp.max(jnp.where(pos < c, pos, -1), axis=-1).reshape(-1)
        low = jnp.min(pos, axis=-1).reshape(-1)
        # [B, groups, tiles, Dk, r, tile]: query row ``ri * tile + lane``
        queries = jnp.transpose(q.reshape(b, tiles, tile, g, r, dk),
                                (0, 3, 1, 5, 4, 2))
        if width != dk:
            # a group's Dk rows where its columns lie in its window
            queries = jnp.stack([jnp.pad(queries[:, gi], (
                (0, 0), (0, 0), (gi * dk - at, at + width - (gi + 1) * dk),
                (0, 0), (0, 0))) for gi, at in enumerate(first)], axis=1)
        if sink is not None:
            sink = jnp.repeat(sink.astype(_F32).reshape(g, 1, r), tile, 2)
        out = _chunk_impl(
            high, low, jnp.asarray(np.asarray(first, np.int32)),
            jnp.tile(pos, (1, 1, 1, r)),
            queries.reshape(b, g, tiles, width, r * tile), sink, k, v, r=r,
            block=chunk_block(c), scale=1.0 / math.sqrt(dk),
            vmem=_VMEM_BUDGET, interpret=_INTERPRET)
        out = jnp.transpose(out, (0, 3, 1, 2, 4))
    return out.reshape(b, kq, g * r * dv)
