"""Learned sparse selection over a cache, and latent attention over the
selected set: the two cores of a decoder that caches ONE latent row a
position and reads only the positions a small indexer names.

**The indexer.** A position's index key ``k[s]`` [D] is cached; a query has
``H`` index heads ``q[t, j]`` [D] and a weight a head ``w[t, j]``:
``I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])`` for ``s <= pos[t]``, in
float32 (from float32 queries and keys the products themselves are exact:
a set that flips at its last place under a bfloat16 score moves the output
of every layer that reads it). The set of a query is the ``min(top_k, pos + 1)`` positions of
largest ``I`` (ties to the lower position, as ``jax.lax.top_k`` breaks
them), exactly: no approximate top-k. A positive factor on ``I`` (the
published ``D ** -0.5 * H ** -0.5``) cannot move the set and is left out.

* a step (:func:`sparse_index`) names the set by its positions, ``Index``
  [B, top_k] int32, ``C`` (past the cache) where fewer are cached;
* a chunk (:func:`sparse_index_chunk`) names it as a membership mask over
  the cache, ``Mask`` [B, K, C] bool: K queries a row select from up to
  ``pos + 1`` positions each, and what follows reads the cache in blocks
  under the mask, never a [K, top_k, width] gather. The k-th largest score
  of a lane is found by a radix select over the float's sortable bits (32
  counting passes over the row's scores), not by a sort.

**Latent attention (absorbed form).** The cache holds ``[c | k_pe]`` a
position: the normed latent ``c`` [R] and one rotary key [P] for all heads.
Per head ``k_nope[s] = c[s] W_uk``, ``v[s] = c[s] W_uv`` (the two parts of
``kv_b`` [R, H * (N + V)]), so ``q_nope . k_nope[s] = (q_nope W_uk^T) .
c[s]`` and ``sum_s a[s] v[s] = (sum_s a[s] c[s]) W_uv``: the query is taken
into the latent once, scores and the mix run against the cached rows
themselves (R + P and R wide), and ``W_uv`` is applied to the mixed latent.
Scores in float32, softmax in float32 over the set alone.

Both chunk forms walk a row's cache in blocks under a DYNAMIC trip count
that ends at the row's highest live position: a row none of whose lanes
ingests (every ``pos`` past the cache) costs nothing but its projections,
and a row early in its prompt reads only what is cached.

**Without an indexer** (a model whose attention reads the whole latent
cache) nothing selects: :func:`latent_attention_chunk` with no mask lets a
lane read every position up to its own, and a step is
:func:`latent_attention_dense`, a few queries a row against every LIVE
position of the row's cache under that same causal rule, no index and no
gather. On one TPU its core is the Pallas kernel ``latent_step.fwd``
(``cache_attention.latent_blocks``), which reads a row's cache in blocks up
to the highest position its lanes hold; the ``jnp`` form here, which scores
the whole rung under a mask, is what the CPU, a mesh and a shape the gate
refuses run, and what the kernel is tested against.
"""

import jax
import jax.numpy as jnp

__all__ = ["sparse_index", "sparse_index_chunk", "latent_attention",
           "latent_attention_chunk", "latent_attention_dense", "select_top"]

INDEX_BLOCK = 1024   # cache positions an indexer block scores at a time
ATTN_BLOCK = 512     # cache positions an attention block reads at a time

_F32 = jnp.float32
_NEG = float("-inf")


def _block(c, want):
    """The largest divisor of ``c`` that is at most ``want``."""
    for size in range(min(want, c), 0, -1):
        if c % size == 0:
            return size
    return c


def _scores(qh, w, keys):
    """qh [.., H, D], w [.., H] float32, keys [C, D] -> I [.., C] float32.
    The products run in the query's type: a float32 query against float32
    keys exactly (``Precision.HIGHEST``: an MXU rounds float32 operands to
    bfloat16 otherwise), a bfloat16 query in one pass whatever type the
    keys are cached in."""
    exact = qh.dtype == _F32
    s = jnp.einsum("...hd,cd->...hc", qh, keys.astype(qh.dtype),
                   preferred_element_type=_F32,
                   precision=jax.lax.Precision.HIGHEST if exact else None)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=-2)


def _counts(pos, c, top_k):
    """[2] int32: positions selected and positions cached, over the rows or
    lanes whose position lies inside the cache."""
    live = pos < c
    cached = jnp.where(live, pos + 1, 0)
    return jnp.stack([jnp.sum(jnp.minimum(cached, top_k)),
                      jnp.sum(cached)]).astype(jnp.int32)


def sparse_index(q, w, cache_k, pos, heads, top_k):
    """One query a row. q [B, H*D], w [B, H], cache_k [B, C, D] (this
    step's key already written), pos [B]. Returns (index [B, min(top_k, C)]
    int32, ``C`` where the row has fewer positions; count [2] int32)."""
    b, c, d = cache_k.shape
    pos = pos.reshape(-1).astype(jnp.int32)
    with jax.named_scope("indexer.scores"):
        scores = jax.vmap(_scores)(q.reshape(b, heads, d), w.astype(_F32),
                                   cache_k)
        scores = jnp.where(jnp.arange(c)[None, :] <= pos[:, None], scores,
                           _NEG)
    with jax.named_scope("indexer.top_k"):
        values, index = jax.lax.top_k(scores, min(int(top_k), c))
        index = jnp.where(values > _NEG, index, c).astype(jnp.int32)
    return index, _counts(pos, c, top_k)


def _sortable(x):
    """uint32 keys that order as the float32 values do."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select_top(scores, top_k):
    """scores [K, C] float32, ``-inf`` where a position is not to be had.
    Returns [K, C] bool: the ``top_k`` largest finite scores of each lane
    (all of them where a lane has no more), ties to the lower position."""
    finite = scores > _NEG
    keys = jnp.where(finite, _sortable(scores), jnp.uint32(0))
    k = jnp.int32(top_k)

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, prefix)

    # the k-th largest key of a lane, 0 where it has fewer than k
    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:1], jnp.uint32))
    above = keys > kth[:, None]
    tied = finite & (keys == kth[:, None])
    room = k - jnp.sum(above, axis=1, dtype=jnp.int32)
    first = jnp.cumsum(tied.astype(jnp.int32), axis=1) <= room[:, None]
    return above | (tied & first)


def _live_blocks(p, c, size):
    """Blocks of ``size`` positions up to a row's highest live position
    (0 where every lane of ``p`` lies past the cache)."""
    top = jnp.max(jnp.where(p < c, p, -1))
    return (top + size) // size


def sparse_index_chunk(q, w, cache_k, pos, heads, top_k):
    """K queries a row. q [B, K, H*D], w [B, K, H], cache_k [B, C, D] (the
    chunk's keys already written), pos [B, K] (``>= C``: a pad lane, which
    selects nothing). Returns (mask [B, K, C] bool, count [2] int32)."""
    b, c, d = cache_k.shape
    kq = q.shape[1]
    pos = pos.astype(jnp.int32)
    size = _block(c, INDEX_BLOCK)
    at = jnp.arange(size, dtype=jnp.int32)

    def row(bi, mask):
        p = jax.lax.dynamic_index_in_dim(pos, bi, 0, keepdims=False)
        qh = jax.lax.dynamic_index_in_dim(q, bi, 0, keepdims=False) \
            .reshape(kq, heads, d)
        wf = jax.lax.dynamic_index_in_dim(w, bi, 0, keepdims=False) \
            .astype(_F32)
        blocks = _live_blocks(p, c, size)
        reach = jnp.where(p < c, p, -1)     # a pad lane reaches nothing

        def block(j, scores):
            keys = jax.lax.dynamic_slice(cache_k, (bi, j * size, 0),
                                         (1, size, d))[0]
            part = _scores(qh, wf, keys)
            part = jnp.where((j * size + at)[None, :] <= reach[:, None],
                             part, _NEG)
            return jax.lax.dynamic_update_slice(scores, part, (0, j * size))

        with jax.named_scope("indexer.scores"):
            scores = jax.lax.fori_loop(0, blocks, block,
                                       jnp.full((kq, c), _NEG, _F32))
        with jax.named_scope("indexer.top_k"):
            chosen = jax.lax.cond(
                blocks > 0, lambda s: select_top(s, top_k),
                lambda s: jnp.zeros(s.shape, bool), scores)
        return jax.lax.dynamic_update_slice(mask, chosen[None], (bi, 0, 0))

    mask = jax.lax.fori_loop(0, b, row, jnp.zeros((b, kq, c), bool))
    return mask, _counts(pos, c, top_k)


def _split_kv_b(kv_b, heads, nope, v_dim):
    r = kv_b.shape[0]
    both = kv_b.reshape(r, heads, nope + v_dim)
    return both[..., :nope], both[..., nope:]


def _absorb(qh, w_uk, nope):
    """qh [.., H, N + P] -> [.., H, R + P]: the no-position part taken into
    the latent, the rotary part as it is."""
    latent = jnp.einsum("...hn,rhn->...hr", qh[..., :nope], w_uk,
                        preferred_element_type=_F32).astype(qh.dtype)
    return jnp.concatenate([latent, qh[..., nope:]], axis=-1)


def latent_attention(q, kv_b, cache, index, heads, nope, v_dim, scale):
    """One query a row over the positions ``index`` names. q [B, H*(N+P)]
    (rotary applied), kv_b [R, H*(N+V)], cache [B, C, R+P] (this step's row
    already written), index [B, S] int32 (``>= C``: none). Returns
    [B, H*V] in q's dtype."""
    b, c, width = cache.shape
    r = kv_b.shape[0]
    w_uk, w_uv = _split_kv_b(kv_b, heads, nope, v_dim)
    with jax.named_scope("latent_attention.absorb"):
        qa = _absorb(q.reshape(b, heads, -1), w_uk, nope)
    with jax.named_scope("latent_attention.gather"):
        rows = jnp.take_along_axis(
            cache, jnp.minimum(index, c - 1)[:, :, None], axis=1)
    with jax.named_scope("latent_attention.core"):
        s = jnp.einsum("bhw,bsw->bhs", qa, rows,
                       preferred_element_type=_F32) * scale
        s = jnp.where((index < c)[:, None, :], s, jnp.finfo(_F32).min)
        probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        mixed = jnp.einsum("bhs,bsr->bhr", probs, rows[..., :r],
                           preferred_element_type=_F32).astype(q.dtype)
    with jax.named_scope("latent_attention.expand"):
        out = jnp.einsum("bhr,rhv->bhv", mixed, w_uv,
                         preferred_element_type=_F32)
    return out.reshape(b, heads * v_dim).astype(q.dtype)


def latent_attention_dense(q, kv_b, cache, pos, heads, nope, v_dim, scale,
                           plan=None):
    """A step's one or two queries a row over EVERY live position of the
    row's cache: no index, no gather. q [B, K, H*(N+P)] (rotary applied),
    kv_b [R, H*(N+V)], cache [B, C, R+P] (the step's rows already written),
    pos [B, K]: lane k reads the positions ``<= pos[b, k]`` (``>= C``: a pad
    lane; its output is 0). Returns [B, K, H*V] in q's dtype. K stays small
    (a row's K x H queries are held at once): a chunk's lanes go through
    :func:`latent_attention_chunk`.

    ``plan`` (``cache_attention.latent_plan_for``'s decision, admitted): the
    core runs as the kernel ``latent_step.fwd``
    (``cache_attention.latent_blocks``), a row's blocks of positions up to
    the highest its lanes hold under a streaming softmax, a block fetched
    once for the scores and the mix. Without it the ``jnp`` form: the whole
    capacity is scored and masked, twice over the cache (scores, then the
    mix). Absorb and expand are the same XLA either way.

    The ``jnp`` form reads the cache AS IT IS STORED, ``[C, R+P]`` a row:
    the scores are
    the plain product of a row's cache with its K x H absorbed queries laid
    side by side (``[R+P, K*H]``), the mix the plain product of the
    probabilities ``[K*H, C]`` with the cache, of which the latent's R
    columns are kept. Written with the queries first
    (``bkhw,bsw->bkhs``), the compiler turned every cache round so that
    the positions lie innermost, and back for the next step's write: 2.4
    GB of copies a step at 32 rows x 4096 (``cached_attention`` reads its
    caches so for the same reason)."""
    b, c, width = cache.shape
    kq = q.shape[1]
    r = kv_b.shape[0]
    w_uk, w_uv = _split_kv_b(kv_b, heads, nope, v_dim)
    pos = pos.astype(jnp.int32)
    # under the scope of the step form's op, so that what reads a step's
    # ``latent_attention`` from a device trace finds this one too
    with jax.named_scope("latent_attention"):
        with jax.named_scope("latent_attention.absorb"):
            qa = _absorb(q.reshape(b, kq, heads, -1), w_uk, nope)
        with jax.named_scope("latent_attention.core"):
            if plan:
                from .cache_attention import latent_blocks

                mixed = latent_blocks(qa, cache, pos, r, scale)
            else:
                side = qa.reshape(b, kq * heads, width).transpose(0, 2, 1)
                s = jnp.einsum("bsw,bwm->bms", cache, side,
                               preferred_element_type=_F32) * scale
                # a pad lane reaches nothing
                reach = jnp.where(pos < c, pos, -1)
                member = jnp.repeat(
                    jnp.arange(c, dtype=jnp.int32)[None, None, :]
                    <= reach[:, :, None], heads, axis=1)     # [B, K*H, C]
                s = jnp.where(member, s, jnp.finfo(_F32).min)
                probs = jnp.where(member, jax.nn.softmax(s, axis=-1),
                                  0.0).astype(q.dtype)
                mixed = jnp.einsum("bms,bsw->bmw", probs, cache,
                                   preferred_element_type=_F32)[..., :r]
                mixed = mixed.reshape(b, kq, heads, r).astype(q.dtype)
        with jax.named_scope("latent_attention.expand"):
            out = jnp.einsum("bkhr,rhv->bkhv", mixed, w_uv,
                             preferred_element_type=_F32)
    return out.reshape(b, kq, heads * v_dim).astype(q.dtype)


def latent_attention_chunk(q, kv_b, cache, mask, pos, heads, nope, v_dim,
                           scale):
    """K queries a row under a membership mask. q [B, K, H*(N+P)], kv_b
    [R, H*(N+V)], cache [B, C, R+P] (the chunk's rows already written), mask
    [B, K, C] bool, or None where nothing selects: a lane then reads every
    position up to its own; pos [B, K] (``>= C``: a pad lane; its output is 0).
    Returns [B, K, H*V] in q's dtype. A row's cache is read in blocks of
    ``ATTN_BLOCK`` positions up to its highest live one, with a running
    maximum and sum (the streaming softmax), so no [K, H, C] scores
    exist."""
    b, c, width = cache.shape
    kq = q.shape[1]
    r = kv_b.shape[0]
    w_uk, w_uv = _split_kv_b(kv_b, heads, nope, v_dim)
    pos = pos.astype(jnp.int32)
    size = _block(c, ATTN_BLOCK)
    low = jnp.finfo(_F32).min
    at = jnp.arange(size, dtype=jnp.int32)

    def row(bi, out):
        p = jax.lax.dynamic_index_in_dim(pos, bi, 0, keepdims=False)
        qh = jax.lax.dynamic_index_in_dim(q, bi, 0, keepdims=False) \
            .reshape(kq, heads, -1)
        with jax.named_scope("latent_attention.absorb"):
            qa = _absorb(qh, w_uk, nope)                     # [K, H, R+P]
        reach = jnp.where(p < c, p, -1)     # a pad lane reaches nothing

        def block(j, carry):
            top, total, acc = carry
            rows = jax.lax.dynamic_slice(cache, (bi, j * size, 0),
                                         (1, size, width))[0]
            if mask is None:
                member = ((j * size + at)[None, :]
                          <= reach[:, None])[:, None, :]
            else:
                member = jax.lax.dynamic_slice(
                    mask, (bi, 0, j * size), (1, kq, size))[0][:, None, :]
            s = jnp.einsum("khw,sw->khs", qa, rows,
                           preferred_element_type=_F32) * scale
            s = jnp.where(member, s, low)
            new_top = jnp.maximum(top, jnp.max(s, axis=-1))
            probs = jnp.where(member, jnp.exp(s - new_top[..., None]), 0.0)
            keep = jnp.exp(top - new_top)
            mixed = jnp.einsum("khs,sr->khr", probs.astype(q.dtype),
                               rows[:, :r], preferred_element_type=_F32)
            return (new_top, total * keep + jnp.sum(probs, axis=-1),
                    acc * keep[..., None] + mixed)

        with jax.named_scope("latent_attention.core"):
            _, total, acc = jax.lax.fori_loop(
                0, _live_blocks(p, c, size), block,
                (jnp.full((kq, heads), low, _F32),
                 jnp.zeros((kq, heads), _F32),
                 jnp.zeros((kq, heads, r), _F32)))
            mixed = (acc / jnp.maximum(total, 1e-30)[..., None]).astype(
                q.dtype)
        with jax.named_scope("latent_attention.expand"):
            y = jnp.einsum("khr,rhv->khv", mixed, w_uv,
                           preferred_element_type=_F32)
        return jax.lax.dynamic_update_slice(
            out, y.reshape(1, kq, heads * v_dim).astype(q.dtype), (bi, 0, 0))

    return jax.lax.fori_loop(0, b, row,
                             jnp.zeros((b, kq, heads * v_dim), q.dtype))
