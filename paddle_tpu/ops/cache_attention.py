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
"""

import math

import jax
import jax.numpy as jnp

from .sparse_latent import _block

__all__ = ["attend_step", "attend_chunk", "attend_chunk_ring",
           "ring_positions", "ring_slots"]

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
