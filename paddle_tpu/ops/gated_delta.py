"""Gated delta rule (Gated DeltaNet linear attention), chunked.

A head keeps a state ``S`` [Dk, Dv], from zero, and per token ``t`` with
decay ``g_t <= 0`` and write strength ``beta_t``::

    S <- exp(g_t) * S
    r  = S^T k_t
    S <- S + k_t (x) (beta_t * (v_t - r))
    o_t = S^T q_t

:func:`recurrent_gated_delta_rule` computes exactly that, token by token (a
``lax.scan``; what the tests hold the chunked form to).
:func:`chunk_gated_delta_rule` computes the same in chunks of ``chunk``
tokens (the WY form): inside a chunk the updates of all its tokens are
solved at once from a unit lower-triangular system (``(I + A) U = rhs``,
``A = strict_tril(beta k k^T * decay)``, inverted block-wise by matrix
products), and only the state crosses from
chunk to chunk, so the sequential part is ``T / chunk`` steps of four small
matrix products a head instead of ``T`` rank-one updates. The chunks are
walked in groups of 16: what does not read the state (the triangular
systems, the in-chunk scores) is computed for all chunks of a group at once
before the group's scan.

Two forms compute the chunked rule, one contract, no shared logic; the
site's gate (:func:`kernel_plan`) says which runs and why:

* ``gated_delta``, two Pallas kernels (``gated_delta.fwd`` / ``.bwd``,
  bound by ``jax.custom_vjp``), on one TPU at head dimensions that are
  multiples of 128 and chunk 64. A grid step is one key head's group of
  value heads over four chunks; the chunks of a row are the last,
  sequential grid axis and the heads' states [Dk, Dv] stay in VMEM across
  it. Everything a chunk needs is formed in VMEM and nothing [C, C] goes to
  HBM: L2 norms of q and k, the decay matrix, ``A``, its inverse (the same
  forward substitution and block merges as below, on 128 x 128 tiles that
  hold two chunks), ``U`` and ``W``, the scores. Out go the rule's output
  and the state that entered each grid step. The backward walks the steps
  in reverse with ``dS`` in VMEM, recomputes a step's four chunks from the
  kept state and applies hand-derived gradients; the inverse is not
  differentiated through its construction (for ``X = T R``: ``dR = T^T
  dX``, ``dA = -strict_tril(dR X^T)``). The gates (``sigmoid``,
  ``softplus``) and the in-chunk cumulative decays stay XLA's, round the
  kernels, and are differentiated by it.
* the chunked ``jnp`` form (:func:`chunk_gated_delta_rule`) everywhere else:
  the CPU, a step partitioned over a mesh, other shapes; it is also what
  tier-1 holds the kernels to. Its backward is plain autodiff through the
  chunk scan, with groups of 16 chunks recomputed (``jax.checkpoint``), so
  a layer keeps its inputs and one state a group.

Matrix products take operands in ``mxu_dtype`` (bfloat16 under AMP) and
accumulate in float32; decays, the triangular solve and the carried state
are float32.
"""

import collections
import functools

import jax
import jax.numpy as jnp

from .chunk_scan import in_chunk_decays, scan_groups
from .gates import GateDecision, GateReason, platform_reason
from .kernel_names import named_pallas_call, traced_once

__all__ = ["chunk_gated_delta_rule", "recurrent_gated_delta_rule",
           "kernel_gated_delta_rule", "gated_delta_attention", "kernel_plan"]

_INTERPRET = False  # tests flip this to run the kernels on the CPU


def recurrent_gated_delta_rule(q, k, v, g, beta):
    """q, k: [B, T, H, Dk]; v: [B, T, H, Dv]; g, beta: [B, T, H].
    Returns [B, T, H, Dv], float32. One token at a time."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        r = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t,
                           b_t[..., None] * (v_t - r))
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    return jnp.moveaxis(out, 0, 1)


_HIGHEST = jax.lax.Precision.HIGHEST
_BASE = 16


def _unit_lower_inverse(a):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` [..., C, C], in
    float32, by matrix products (XLA's batched ``triangular_solve`` took
    66 of 126 ms a step on the v5e; my chip run, PR 26). Diagonal blocks of
    16 are inverted by forward substitution, a row at a time (row ``i`` of
    the inverse is ``e_i - A[i, :i] @ rows[:i]``: exact, no power of ``A``
    is formed, so nothing grows where keys repeat); blocks are then merged
    two by two, ``[[P, 0], [-Q A21 P, Q]]``."""
    c = a.shape[-1]
    base = min(_BASE, c)
    if c % base or (c // base) & (c // base - 1):
        base = c        # no power-of-two number of blocks: one block

    def block(i, j, size):
        """Block (i, j) of ``a`` cut into ``size`` x ``size`` blocks (static
        slices: their transpose is a pad, where a gather's is a scatter)."""
        return a[..., i * size:(i + 1) * size, j * size:(j + 1) * size]

    diag = jnp.stack([block(i, i, base) for i in range(c // base)], -3)
    eye = jnp.eye(base, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (base,))]
    for i in range(1, base):
        done = jnp.stack(rows, axis=-2)                    # [.., i, base]
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], done, precision=_HIGHEST))
    inv = jnp.stack(rows, axis=-2)                         # [.., n, b, b]
    size = base
    while size < c:
        p, q = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        a21 = jnp.stack([block(2 * i + 1, 2 * i, size)
                         for i in range(c // (2 * size))], -3)
        low = -jnp.einsum("...ij,...jk,...kl->...il", q, a21, p,
                          precision=_HIGHEST)
        top = jnp.concatenate([p, jnp.zeros_like(p)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([low, q], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _chunks_from(state, q, k, v, g, beta, chunk, mx):
    """The chunked rule over ``n * chunk`` tokens from ``state`` [B, H, Dk,
    Dv]. Returns (final state, out [B, n * chunk, H, Dv])."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(mx), y.astype(mx),
                          preferred_element_type=f32)

    def chunks(x):  # [B, T, H, ...] -> [B, H, N, C, ...]
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = (chunks(x.astype(f32)) for x in (q, k, v))
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    gc, decay = in_chunk_decays(g)           # [B, H, N, C], [.., C, C]
    at = jnp.arange(chunk)
    strict = at[:, None] > at[None, :]
    k_beta = k * beta[..., None]
    a = jnp.where(strict, mm("bhnik,bhnjk->bhnij", k_beta, k) * decay, 0.0)
    rhs = jnp.concatenate(
        [v * beta[..., None], k_beta * jnp.exp(gc)[..., None]], axis=-1)
    solved = jnp.einsum("...ij,...jd->...id", _unit_lower_inverse(a), rhs,
                        precision=_HIGHEST)
    u, w = solved[..., :dv], solved[..., dv:]
    scores = mm("bhnik,bhnjk->bhnij", q, k) * decay
    q_in = q * jnp.exp(gc)[..., None]                    # reads the state
    g_last = gc[..., -1]                                 # [B, H, N]
    k_out = k * jnp.exp(g_last[..., None] - gc)[..., None]

    def step(s, xs):
        u_i, w_i, scores_i, q_i, k_i, decay_i = xs
        v_new = u_i - mm("bhck,bhkv->bhcv", w_i, s)
        o_i = mm("bhck,bhkv->bhcv", q_i, s) \
            + mm("bhij,bhjv->bhiv", scores_i, v_new)
        s = s * decay_i[..., None, None] \
            + mm("bhck,bhcv->bhkv", k_i, v_new)
        return s, o_i

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in
               (u, w.astype(mx), scores.astype(mx), q_in.astype(mx),
                k_out.astype(mx), jnp.exp(g_last)))
    state, out = jax.lax.scan(step, state, xs)
    out = jnp.moveaxis(out, 0, 2)                        # [B, H, N, C, Dv]
    return state, jnp.moveaxis(out, 1, 3).reshape(b, t, h, dv)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk=64, mxu_dtype=None,
                           group=16, prepare=None, heads=None):
    """Same contract as :func:`recurrent_gated_delta_rule`, in chunks.
    ``T`` need not be a multiple of ``chunk``: the tail is padded with
    tokens that neither decay nor write. The chunks are walked in groups of
    ``group``: a group's in-chunk work is done for all its chunks at once,
    and each group is recomputed in the backward pass (``jax.checkpoint``),
    so that what is kept a layer is the inputs and one state a group, not
    every in-chunk tensor of the whole row (2.9 GB a layer at T = 8192,
    compiled for a v5e). ``prepare``, where given, maps a group's slices
    of the five inputs to the rule's (q, k, v, g, beta) inside the
    recomputed region (``heads``: the heads it gives), so that what it
    makes (normalised, repeated float32 heads) is not kept either."""
    f32 = jnp.float32
    mx = mxu_dtype or f32
    b, t = q.shape[:2]
    h, dk, dv = heads or q.shape[2], q.shape[-1], v.shape[-1]
    group = min(group, -(-t // chunk))

    def one_group(state, xs):
        if prepare is not None:
            xs = prepare(*xs)
        return _chunks_from(state, *xs, chunk, mx)

    return scan_groups(one_group, jnp.zeros((b, h, dk, dv), f32),
                       (q, k, v, g, beta), group * chunk)


# ---------------------------------------------------------------------------
# the Pallas kernels: family ``gated_delta``
# ---------------------------------------------------------------------------
#
# A grid step is one key head's group of value heads over ``_PAIRS`` pairs
# of chunks. Two chunks are worked as one [2C, 2C] tile whose off-diagonal
# blocks are zero: at C = 64 that is the MXU's own 128 x 128, and every
# in-chunk tensor is a whole number of (8, 128) registers. What does not read
# the state (decay matrix, A, its inverse, U, W, the scores) is formed first,
# stage by stage over all (head, pair) units of the step, because the
# compiler overlaps independent work only where it is emitted side by side
# (unit by unit the same step took a quarter longer; the chip's compiler,
# PR 27); the chunks then take the state in turn. Per-token scalars come as
# rows of an [8, 2C] tile (``_scalar_rows``) and are turned into columns by a
# product with the identity.
#
# Float32 products at full precision are written out as the six bfloat16
# products that ``Precision.HIGHEST`` is on this chip (``_dot``): Mosaic's own
# float32 contraction costs the same MXU time but cannot use the zeros of a
# block-diagonal operand, which ``_dot_chunks`` and the merges do.

_PAIRS = 2          # pairs of chunks a grid step: 4 chunks, 256 tokens at 64
_ROWS = 8           # rows of the scalar tile; 0..5 are used
_GC, _BETA, _E, _F, _EG = 0, 1, 2, 3, 4     # _EG, _EG + 1: a row a chunk
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))

_Pair = collections.namedtuple("_Pair", "dm a t x p qe ko kb beta_c e_c f_c")
_Masks = collections.namedtuple(
    "_Masks", "lower strict eye blocks pick first sizes")


def _parts(x):
    """float32 -> three bfloat16 whose sum is ``x`` exactly."""
    parts = []
    for _ in range(3):
        parts.append(x.astype(jnp.bfloat16))
        x = x - parts[-1].astype(jnp.float32)
    return parts


def _dot(x, y, dims, mx=None):
    """``mx`` bfloat16: operands in bfloat16 (one pass, as ``mm`` of the
    chunked form under AMP), float32 accumulation. Else float32 at full
    precision, as ``Precision.HIGHEST`` is on this chip: the six products
    of the operands' bfloat16 parts that matter, as one product over the
    parts laid side by side along the contracted axis, smallest terms
    first."""
    def dot(x, y):
        return jax.lax.dot_general(x, y, (dims, ((), ())),
                                   preferred_element_type=jnp.float32)

    if mx == jnp.bfloat16:
        return dot(x.astype(mx), y.astype(mx))
    xs, ys = _parts(x.astype(jnp.float32)), _parts(y.astype(jnp.float32))
    terms = ((1, 1), (0, 2), (2, 0), (0, 1), (1, 0), (0, 0))
    return dot(jnp.concatenate([xs[i] for i, _ in terms], axis=dims[0][0]),
               jnp.concatenate([ys[j] for _, j in terms], axis=dims[1][0]))


def _select(x, y, dims):
    """A product in which one operand (the bfloat16 one) is all 0 and 1 and
    picks at most one entry of the other, float32, a result: exact over the
    float32 operand's three parts side by side."""
    if x.dtype == jnp.bfloat16:
        x, y = jnp.concatenate([x] * 3, axis=dims[0][0]), \
            jnp.concatenate(_parts(y), axis=dims[1][0])
    else:
        x, y = jnp.concatenate(_parts(x), axis=dims[0][0]), \
            jnp.concatenate([y] * 3, axis=dims[1][0])
    return jax.lax.dot_general(x, y, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_chunks(t, r, c, dims):
    """``t @ r`` (or ``t^T @ r``, ``dims`` _TN) at full precision for ``t``
    [2C, 2C] that is zero between the pair's chunks: a chunk at a time, so
    that the contracted axis holds no zeros."""
    return jnp.concatenate(
        [_dot(t[at:at + c, at:at + c], r[at:at + c], dims)
         for at in (0, c)], axis=0)


def _div(x, by):
    """``x // by`` and ``x % by`` of an int32 iota by shifts (the vector unit
    has no integer division): ``by`` is a power of two."""
    assert by & (by - 1) == 0, by
    return jax.lax.shift_right_logical(x, by.bit_length() - 1), x & (by - 1)


def _masks(c):
    """The masks of a [2C, 2C] pair tile, made once a grid step; ``sizes``:
    the block sizes at which the inverse's blocks are merged."""
    n = 2 * c
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    same = _div(i, c)[0] == _div(j, c)[0]
    base = min(_BASE, c)
    lane = jax.lax.broadcasted_iota(jnp.int32, (base, n), 1)
    at = jax.lax.broadcasted_iota(jnp.int32, (base, n), 0)
    return _Masks(same & (i >= j), same & (i > j),
                  (i == j).astype(jnp.bfloat16),
                  _div(i, base)[0] == _div(j, base)[0],
                  (_div(lane, base)[1] == at).astype(jnp.float32),
                  lane - _div(lane, base)[1],
                  [base << level for level in range((c // base).bit_length()
                                                    - 1)])


def _odd_blocks(x, size, fill=None):
    """The rows of ``x`` [2C, .] that lie in odd blocks of ``size`` rows,
    stacked; with ``fill`` (such a stack), ``x`` with ``fill``'s rows in its
    odd blocks. Whole registers: ``size`` is a multiple of 8."""
    odd = range(size, x.shape[0], 2 * size)
    if fill is None:
        return jnp.concatenate([x[at:at + size] for at in odd], axis=0)
    return jnp.concatenate(
        [part for b, at in enumerate(odd)
         for part in (x[at - size:at], fill[b * size:(b + 1) * size])],
        axis=0)


def _block_tensors(q, k, v, sc, m, c, mx):
    """What each pair of chunks needs that does not read the state; lists
    over (value head, pair) of q, k [2C, Dk], v [2C, Dv] (float32) and
    scalar rows. Stage by stage over all of them, so that one pair's
    product can run under another's substitution step."""
    n = 2 * c
    base = min(_BASE, c)
    units = range(len(q))
    cols = [_select(m.eye, s, _NT) for s in sc]                # [2C, 8]
    gc_c, beta_c, e_c, f_c = ([x[:, at:at + 1] for x in cols]
                              for at in (_GC, _BETA, _E, _F))
    # decay from token j to token i of a chunk, i >= j (elsewhere the
    # difference is positive and is never exponentiated)
    dm = [jnp.where(m.lower, jnp.exp(jnp.where(
        m.lower, gc_c[u] - sc[u][_GC:_GC + 1, :], 0.0)), 0.0) for u in units]
    kb = [k[u] * beta_c[u] for u in units]
    a = [jnp.where(m.strict, _dot(kb[u], k[u], _NT, mx) * dm[u], 0.0)
         for u in units]
    # (I + a)^-1, the construction of _unit_lower_inverse: forward
    # substitution in the diagonal blocks of 16, then merges [[P, 0],
    # [-Q A21 P, Q]] that double the block, up to the chunk.
    # The diagonal blocks are worked transposed and side by side, as
    # [16, 2C] tiles (two registers): own[c, i] = a[i, 16 * (i // 16) + c],
    # inv[c, i] likewise of the inverse. Right-looking: once row r of every
    # block is final, inv[c, i] -= own[r, i] * inv[c, first lane of i's
    # block + r] leaves the rows below it, in all blocks at once.
    own = [_select(m.pick.astype(jnp.bfloat16),
                   jnp.where(m.blocks, a[u], 0.0), _NT) for u in units]
    inv = [m.pick] * len(q)
    for r in range(base - 1):
        inv = [inv[u] - own[u][r:r + 1, :] * jnp.take_along_axis(
            inv[u], m.first + r, axis=1) for u in units]
    t = [jnp.where(m.blocks, _dot(inv[u], m.pick, _TN), 0.0) for u in units]
    for size in m.sizes:
        spots = [(u, at, at + size) for u in units
                 for at in range(0, n, 2 * size)]
        inner = [_dot(a[u][mid:mid + size, at:mid], t[u][at:mid, at:mid], _NN)
                 for u, at, mid in spots]
        low = [jnp.pad(-_dot(t[u][mid:mid + size, mid:mid + size], x, _NN),
                       ((0, 0), (at, n - mid)))
               for (u, at, mid), x in zip(spots, inner)]
        per = len(spots) // len(q)
        t = [_odd_blocks(t[u], size, _odd_blocks(t[u], size) + jnp.concatenate(
            low[u * per:(u + 1) * per], axis=0)) for u in units]
    x = [_dot_chunks(t[u], jnp.concatenate(
        [v[u] * beta_c[u], kb[u] * e_c[u]], axis=1), c, _NN) for u in units]
    return [_Pair(dm[u], a[u], t[u], x[u], _dot(q[u], k[u], _NT, mx) * dm[u],
                  q[u] * e_c[u], k[u] * f_c[u], kb[u], beta_c[u], e_c[u],
                  f_c[u]) for u in units]


def _lanes(row, width):
    """A [1, n] row whose lanes are all alike, as [1, width]."""
    n = row.shape[1]
    if width > n:
        row = jnp.concatenate([row] * (-(-width // n)), axis=1)
    return row[:, :width]


def _chunk_decay(sc, half, width):
    """``exp(gc_last)`` of a pair's first or second chunk as a [1, width]
    row (its row of the scalar tile holds it on every lane: Mosaic
    broadcasts one way at a time, so a [1, 1] cannot scale a state)."""
    return _lanes(sc[_EG + half:_EG + half + 1, :], width)


def _rowsum(z):
    return jnp.sum(z, axis=1, keepdims=True)


def _load_pairs(ref, n, at=0, width=None):
    """[float32 [2C, width] of each of the block's pairs], from lane ``at``."""
    width = width or ref.shape[-1]
    return [ref[pair * n:(pair + 1) * n, at:at + width].astype(jnp.float32)
            for pair in range(_PAIRS)]


def _unit_heads(q, k, norm):
    """(q, k as the rule takes them, 1 / |q|, 1 / |k|) of raw q, k [2C, Dk]:
    L2-normalised over the head dimension and q scaled, where ``norm`` =
    (eps, scale) is given."""
    if norm is None:
        return q, k, None, None
    eps, scale = norm
    rq = jax.lax.rsqrt(_rowsum(q * q) + eps)
    rk = jax.lax.rsqrt(_rowsum(k * k) + eps)
    return q * rq * scale, k * rk, rq, rk


def _block_of(q_ref, k_ref, v_ref, sc_ref, c, mx, norm):
    """A grid step's units (value head of the group, pair of the block) and
    what both kernels make of its blocks before any state is read: (units,
    the masks, per pair (q, k as the rule takes them, 1 / |q|, 1 / |k|),
    {unit: v}, {unit: its _Pair})."""
    n, rep = 2 * c, sc_ref.shape[0]
    dv = v_ref.shape[-1] // rep
    qk = [_unit_heads(q, k, norm) for q, k in
          zip(_load_pairs(q_ref, n), _load_pairs(k_ref, n))]
    units = [(h, p) for h in range(rep) for p in range(_PAIRS)]
    v = {(h, p): x for h in range(rep)
         for p, x in enumerate(_load_pairs(v_ref, n, h * dv, dv))}
    m = _masks(c)
    block = _block_tensors([qk[p][0] for _, p in units],
                           [qk[p][1] for _, p in units],
                           [v[u] for u in units],
                           [sc_ref[u] for u in units], m, c, mx)
    return units, m, qk, v, dict(zip(units, block))


def _walk_chunks(block, s, sc_ref, c, dv, mx, enter):
    """The chunks of a block in order, the group's heads side by side:
    ``enter(h, p, half, state)`` is called with the state that enters each
    chunk, ``s`` [a state a head] is left as the states that leave the
    block. Returns {unit: V_new of the pair [2C, Dv]}."""
    v_new = {}
    for p in range(_PAIRS):
        halves = [[None] * 2 for _ in s]
        for half in range(2):
            r = slice(half * c, (half + 1) * c)
            for h in range(len(s)):
                pr = block[h, p]
                enter(h, p, half, s[h])
                halves[h][half] = pr.x[r, :dv] - _dot(pr.x[r, dv:], s[h],
                                                      _NN, mx)
                s[h] = s[h] * _chunk_decay(sc_ref[h, p], half, dv) \
                    + _dot(pr.ko[r], halves[h][half], _TN, mx)
        for h in range(len(s)):
            v_new[h, p] = jnp.concatenate(halves[h], axis=0)
    return v_new


def _fwd_kernel(q_ref, k_ref, v_ref, sc_ref, o_ref, s_ref, state, *, c, mx,
                norm):
    """One key head's group of value heads over a block of ``_PAIRS`` pairs
    of chunks; the states [rep, Dk, Dv] stay in ``state`` from block to
    block."""
    from jax.experimental import pallas as pl

    n, rep = 2 * c, sc_ref.shape[0]
    dv = v_ref.shape[-1] // rep

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    units, _, _, _, block = _block_of(q_ref, k_ref, v_ref, sc_ref, c, mx,
                                      norm)
    s = [state[h] for h in range(rep)]
    for h in range(rep):
        s_ref[h] = s[h]               # the state entering this block
    read = {}                         # (q e^gc) S of each chunk

    def enter(h, p, half, s_h):
        read[h, p, half] = _dot(block[h, p].qe[half * c:(half + 1) * c], s_h,
                                _NN, mx)

    v_new = _walk_chunks(block, s, sc_ref, c, dv, mx, enter)
    for h, p in units:
        o = jnp.concatenate([read[h, p, 0], read[h, p, 1]], axis=0) \
            + _dot(block[h, p].p, v_new[h, p], _NN, mx)
        o_ref[p * n:(p + 1) * n, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
    for h in range(rep):
        state[h] = s[h]


def _bwd_kernel(q_ref, k_ref, v_ref, sc_ref, s_ref, do_ref, dq_ref, dk_ref,
                dv_ref, dsc_ref, dstate, states, *, c, mx, norm):
    """Walks the blocks of a key head's group from the last to the first
    with the heads' ``dS`` in VMEM. A block's chunks are recomputed from
    the states that entered it; then, chunk by chunk in reverse, the
    hand-derived gradients. The inverse is not differentiated through its
    construction: for ``X = T R``, ``dR = T^T dX`` and ``dA =
    -strict_tril(dR X^T)``. dq and dk are summed over the group here."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    n, rep = 2 * c, sc_ref.shape[0]
    dv = v_ref.shape[-1] // rep

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    pairs, heads = range(_PAIRS), range(rep)
    units, m, qk, v, pr = _block_of(q_ref, k_ref, v_ref, sc_ref, c, mx, norm)
    do = {(h, p): x for h in heads
          for p, x in enumerate(_load_pairs(do_ref, n, h * dv, dv))}

    def enter(h, p, half, s_h):
        states[h, 2 * p + half] = s_h

    v_new = _walk_chunks(pr, [s_ref[h] for h in heads], sc_ref, c, dv, mx,
                         enter)

    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, 128), 1)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 128), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 128), 1)
           ).astype(jnp.bfloat16)
    # what reads dS, chunk by chunk from the block's last to its first, the
    # group's heads side by side
    ds = [dstate[h] for h in heads]
    dvn, dko, dqe, dw, deg = ({} for _ in range(5))
    for p in reversed(pairs):
        dv_pair = [_dot(pr[h, p].p, do[h, p], _TN, mx)
                   for h in heads]                          # P^T dO
        for half in (1, 0):
            r = slice(half * c, (half + 1) * c)
            for h in heads:
                unit, s_h = pr[h, p], states[h, 2 * p + half]
                do_h = do[h, p][r]
                dvn[h, p, half] = dv_pair[h][r] + _dot(unit.ko[r], ds[h],
                                                       _NN, mx)
                dko[h, p, half] = _dot(v_new[h, p][r], ds[h], _NT, mx)
                dqe[h, p, half] = _dot(do_h, s_h, _NT, mx)
                dw[h, p, half] = -_dot(dvn[h, p, half], s_h, _NT, mx)
                # d exp(gc_last): the chunk's row holds it on every lane,
                # so its gradient may be left spread over the lanes
                moved = jnp.sum(s_h * ds[h], axis=0, keepdims=True)
                moved = sum(moved[:, at:at + n] for at in range(0, dv, n))
                if dv < n:
                    moved = jnp.concatenate(
                        [moved, jnp.zeros((1, n - dv), f32)], axis=1)
                deg[h, p, half] = jnp.where(row == _EG + half, moved, 0.0)
                ds[h] = ds[h] * _chunk_decay(sc_ref[h, p], half, dv) \
                    + _dot(unit.qe[r], do_h, _TN, mx) \
                    - _dot(unit.x[r, dv:], dvn[h, p, half], _TN, mx)
    for h in heads:
        dstate[h] = ds[h]

    # the rest reads no state: stage by stage over the block's units
    def whole(halves, u):
        return jnp.concatenate([halves[u + (0,)], halves[u + (1,)]], axis=0)

    dvn, dko, dqe, dw = ({u: whole(x, u) for u in units}
                         for x in (dvn, dko, dqe, dw))
    q, k = ({(h, p): qk[p][at] for h, p in units} for at in (0, 1))
    dr = {u: _dot_chunks(pr[u].t, jnp.concatenate([dvn[u], dw[u]], axis=1),
                         c, _TN) for u in units}
    da = {u: jnp.where(m.strict, -_dot(dr[u], pr[u].x, _NT), 0.0)
          for u in units}
    dp = {u: jnp.where(m.lower, _dot(do[u], v_new[u], _NT, mx), 0.0)
          for u in units}
    dkk, dqk = ({u: x[u] * pr[u].dm for u in units} for x in (da, dp))
    dkb = {u: dr[u][:, dv:] * pr[u].e_c + _dot(dkk[u], k[u], _NN, mx)
           for u in units}
    dq = {u: _dot(dqk[u], k[u], _NN, mx) + dqe[u] * pr[u].e_c for u in units}
    dk = {u: _dot(dkk[u], pr[u].kb, _TN, mx) + _dot(dqk[u], q[u], _TN, mx)
          + dko[u] * pr[u].f_c + dkb[u] * pr[u].beta_c for u in units}
    for h, p in units:
        u = h, p
        dv_ref[p * n:(p + 1) * n, h * dv:(h + 1) * dv] = (
            dr[u][:, :dv] * pr[u].beta_c).astype(dv_ref.dtype)
        ddiff = da[u] * pr[u].a + dp[u] * pr[u].p   # d (gc_i - gc_j), i >= j
        grads = {_GC: _rowsum(ddiff),
                 _BETA: _rowsum(dr[u][:, :dv] * v[u])
                 + _rowsum(dkb[u] * k[u]),
                 _E: _rowsum(dr[u][:, dv:] * pr[u].kb + dqe[u] * q[u]),
                 _F: _rowsum(dko[u] * k[u])}
        wide = sum(jnp.where(col == at, g, 0.0) for at, g in grads.items())
        dsc_ref[h, p] = _select(eye, wide, _NT) + deg[h, p, 0] \
            + deg[h, p, 1] - jnp.where(
                row == _GC, jnp.sum(ddiff, axis=0, keepdims=True), 0.0)
    for p in pairs:         # a key head's gradients: the sum over its group
        dq_p, dk_p = (sum(x[h, p] for h in heads) for x in (dq, dk))
        if norm is not None:    # through the L2 norms and q's scale
            (rq, rk), scale = qk[p][2:], norm[1]
            dq_p = (scale * dq_p - qk[p][0] * (
                _rowsum(dq_p * qk[p][0]) / scale)) * rq
            dk_p = (dk_p - qk[p][1] * _rowsum(dk_p * qk[p][1])) * rk
        dq_ref[p * n:(p + 1) * n, :] = dq_p.astype(dq_ref.dtype)
        dk_ref[p * n:(p + 1) * n, :] = dk_p.astype(dk_ref.dtype)


def _specs(t, rep, dk, dv, c, reverse):
    """Block specs by array kind for [B, T, H * D] arrays walked a block of
    ``_PAIRS`` pairs of chunks a step, one key head and its ``rep`` value
    heads (value head ``h`` reads key head ``h // rep``) a step;
    ``reverse``: last block first."""
    from jax.experimental import pallas as pl

    n = 2 * c
    tb = _PAIRS * n
    nb = t // tb

    def at(i):
        return nb - 1 - i if reverse else i

    return {
        "k": pl.BlockSpec((None, tb, dk), lambda b, g, i: (b, at(i), g)),
        "v": pl.BlockSpec((None, tb, rep * dv),
                          lambda b, g, i: (b, at(i), g)),
        "sc": pl.BlockSpec((None, rep, _PAIRS, _ROWS, n),
                           lambda b, g, i: (b, g, at(i), 0, 0)),
        "s": pl.BlockSpec((None, rep, None, dk, dv),
                          lambda b, g, i: (b, g, at(i), 0, 0)),
    }


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


_STATICS = ("num_k_heads", "num_v_heads", "chunk", "mxu", "norm", "interpret")


@traced_once("gated_delta.fwd", _STATICS)
def _fwd_impl(q, k, v, sc, num_k_heads, num_v_heads, chunk, mxu, norm,
              interpret):
    """q, k: [B, T, Hk * Dk]; v: [B, T, Hv * Dv]; ``sc``: the scalar rows
    [B, Hv, T / 2C, 8, 2C]; T a whole number of blocks; ``norm``: None or
    (eps, scale) of the L2 norm the kernels apply to q and k. Returns (out
    [B, T, Hv * Dv] in v's dtype, the state entering each block [B, Hv,
    blocks, Dk, Dv] float32)."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, t = q.shape[:2]
    rep = num_v_heads // num_k_heads
    dk, dv = q.shape[-1] // num_k_heads, v.shape[-1] // num_v_heads
    specs = _specs(t, rep, dk, dv, chunk, False)
    blocks = t // (2 * _PAIRS * chunk)
    return named_pallas_call(
        "gated_delta.fwd",
        functools.partial(_fwd_kernel, c=chunk, mx=jnp.dtype(mxu),
                          norm=norm),
        grid=(b, num_k_heads, blocks),
        in_specs=[specs["k"], specs["k"], specs["v"], specs["sc"]],
        out_specs=[specs["v"], specs["s"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, num_v_heads, blocks, dk, dv),
                                        f32)],
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), f32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, sc)


@traced_once("gated_delta.bwd", _STATICS)
def _bwd_impl(q, k, v, sc, states, do, num_k_heads, num_v_heads, chunk, mxu,
              norm, interpret):
    """Gradients of :func:`_fwd_impl`'s output: dq, dk, dv in the inputs'
    dtypes and d ``sc`` in float32."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, t = q.shape[:2]
    rep = num_v_heads // num_k_heads
    dk, dv = q.shape[-1] // num_k_heads, v.shape[-1] // num_v_heads
    specs = _specs(t, rep, dk, dv, chunk, True)
    return named_pallas_call(
        "gated_delta.bwd",
        functools.partial(_bwd_kernel, c=chunk, mx=jnp.dtype(mxu),
                          norm=norm),
        grid=(b, num_k_heads, t // (2 * _PAIRS * chunk)),
        in_specs=[specs["k"], specs["k"], specs["v"], specs["sc"],
                  specs["s"], specs["v"]],
        out_specs=[specs["k"], specs["k"], specs["v"], specs["sc"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(sc.shape, f32)],
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), f32),
                        pltpu.VMEM((rep, 2 * _PAIRS, dk, dv), f32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, sc, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _core(q, k, v, sc, num_k_heads, num_v_heads, chunk, mxu, norm):
    return _fwd_impl(q, k, v, sc, num_k_heads, num_v_heads, chunk, mxu, norm,
                     _INTERPRET)[0]


def _core_fwd(q, k, v, sc, num_k_heads, num_v_heads, chunk, mxu, norm):
    out, states = _fwd_impl(q, k, v, sc, num_k_heads, num_v_heads, chunk,
                            mxu, norm, _INTERPRET)
    return out, (q, k, v, sc, states)


def _core_bwd(num_k_heads, num_v_heads, chunk, mxu, norm, res, do):
    q, k, v, sc, states = res
    return _bwd_impl(q, k, v, sc, states, do.astype(v.dtype), num_k_heads,
                     num_v_heads, chunk, mxu, norm, _INTERPRET)


_core.defvjp(_core_fwd, _core_bwd)


def _scalar_rows(g, beta, chunk):
    """g, beta [B, T, H] float32 -> [B, H, T / 2C, 8, 2C]: per pair of
    chunks, tokens along the lanes, the rows cumulative decay ``gc``,
    ``beta``, ``exp(gc)``, ``exp(gc_last - gc)``, and ``exp(gc_last)`` of
    the first and of the second chunk, each on every lane."""
    b, t, h = g.shape
    pairs = t // (2 * chunk)
    gc = jnp.cumsum(g.reshape(b, pairs, 2, chunk, h), axis=3)
    last = gc[:, :, :, -1:]
    rows = [gc, beta.reshape(gc.shape), jnp.exp(gc), jnp.exp(last - gc)]
    rows += [jnp.broadcast_to(jnp.exp(last[:, :, half:half + 1]), gc.shape)
             for half in range(2)]
    rows += [jnp.zeros_like(gc)] * (_ROWS - len(rows))
    rows = jnp.stack(rows, axis=0).reshape(_ROWS, b, pairs, 2 * chunk, h)
    return jnp.transpose(rows, (1, 4, 2, 0, 3))


def kernel_gated_delta_rule(q, k, v, g, beta, chunk=64, mxu_dtype=None,
                            l2norm=None):
    """The contract of :func:`chunk_gated_delta_rule` through the
    ``gated_delta`` kernels, with grouped heads: q, k [B, T, Hk, Dk] serve
    the value heads v [B, T, Hv, Dv] in groups of Hv / Hk (value head ``h``
    reads key head ``h // (Hv / Hk)``); g, beta [B, T, Hv]. ``l2norm`` =
    (eps, scale): the kernels L2-normalise q and k over the head dimension
    and scale q themselves, so that no normalised copy is kept. The gates
    and the in-chunk cumulative decays are made, and differentiated, by
    XLA round the kernels. Returns [B, T, Hv, Dv] in v's dtype."""
    f32 = jnp.float32
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    pad = (-t) % (2 * _PAIRS * chunk)

    def flat(x):    # tail padding: k = 0 writes nothing, g = 0 decays nothing
        x = x.reshape(b, t, -1)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    sc = _scalar_rows(flat(g.astype(f32)), flat(beta.astype(f32)), chunk)
    out = _core(flat(q), flat(k), flat(v), sc, hk, hv, chunk,
                jnp.dtype(mxu_dtype or f32).name, l2norm)
    return out[:, :t].reshape(b, t, hv, dv)


# VMEM a grid step of the backward kernel holds, counted generously: the
# double-buffered blocks, the kept chunk states, and the tiles of a block's
# pairs that stay live from the recomputation to the gradients
_VMEM_BUDGET = 12 * 1024 * 1024


def _working_set(rep, dk, dv, chunk):
    n = 2 * chunk
    tb = _PAIRS * n
    blocks = 2 * 4 * tb * (4 * dk + 3 * rep * dv) + 4 * 4 * rep * dk * dv
    states = 4 * rep * (2 * _PAIRS + 1) * dk * dv
    tiles = 4 * rep * _PAIRS * n * (8 * n + 6 * dk + 6 * dv)
    return blocks + states + tiles


def kernel_plan(t, num_k_heads, num_v_heads, dk, dv, chunk, platform=None):
    """Which path a ``gated_delta_rule`` site takes, as a
    ``ops.gates.GateDecision``: ``gated_delta`` (the Pallas kernels) or
    ``chunked_scan_xla`` (the chunked ``jnp`` form) with the blocking
    reasons. ``platform``: what ``gates.platform_reason`` says of where the
    step runs (:func:`plan_for`); the shape-only pass
    (``analysis/resources.py``) reads the same gate and leaves it ``None``."""
    reasons = []
    if platform is not None:
        reasons.append(platform)
    if dk % 128 or dv % 128:
        reasons.append(GateReason(
            "geometry", "head dimensions %d and %d are not multiples of 128 "
            "(the kernels work whole lanes)" % (dk, dv)))
    if chunk != 64:
        reasons.append(GateReason(
            "geometry", "chunk %d: the kernels work pairs of 64-token "
            "chunks as 128 x 128 tiles" % chunk))
    if num_v_heads % num_k_heads:
        reasons.append(GateReason(
            "geometry", "%d value heads are no whole groups of %d key heads"
            % (num_v_heads, num_k_heads)))
    rep = num_v_heads // num_k_heads
    if not reasons and _working_set(rep, dk, dv, chunk) > _VMEM_BUDGET:
        reasons.append(GateReason(
            "vmem", "a block's working set for %d value heads a key head at "
            "Dk=%d Dv=%d, %.1f MB, exceeds the %.0f MB VMEM budget" % (
                rep, dk, dv, _working_set(rep, dk, dv, chunk) / 2**20,
                _VMEM_BUDGET / 2**20)))
    if reasons:
        return GateDecision(False, "chunked_scan_xla", fallback="gated_delta",
                            reasons=reasons)
    return GateDecision(True, "gated_delta", reasons=[GateReason(
        "shape", "%d chunks of %d tokens a head, %d a grid step, the state "
        "in VMEM; the backward keeps one state a step and recomputes its "
        "chunks" % (-(-t // chunk), chunk, 2 * _PAIRS), blocking=False)])


def plan_for(q, v, num_k_heads, num_v_heads, chunk):
    """:func:`kernel_plan` of a site's packed q [B, T, Hk*Dk] and v
    [B, T, Hv*Dv], where the step being traced is placed."""
    return kernel_plan(v.shape[1], num_k_heads, num_v_heads,
                       q.shape[-1] // num_k_heads, v.shape[-1] // num_v_heads,
                       chunk, platform=platform_reason(_INTERPRET))


_L2_EPS = 1e-6


def _l2norm(x, eps=_L2_EPS):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_attention(q, k, v, a, b, a_log, dt_bias, num_k_heads,
                          num_v_heads, chunk=64, mxu_dtype=None, plan=None):
    """The Gated DeltaNet core on packed heads. q, k: [B, T, Hk*Dk] (after
    the causal convolution and SiLU); v: [B, T, Hv*Dv]; ``a``, ``b``:
    [B, T, Hv], the raw decay and write-strength projections; ``a_log``,
    ``dt_bias``: [Hv]. ``beta = sigmoid(b)``, ``g = -exp(a_log) *
    softplus(a + dt_bias)``; q and k are L2-normalised over the head
    dimension, value head ``h`` reads key head ``h // (Hv / Hk)``, q scaled
    by ``Dk ** -0.5``. ``plan``: the site's :func:`kernel_plan` (made here
    if not given); admitted, the ``gated_delta`` kernels run, else the
    chunked ``jnp`` form. Returns [B, T, Hv*Dv] in float32."""
    f32 = jnp.float32
    bsz, t, _ = q.shape
    dk = q.shape[-1] // num_k_heads
    dv = v.shape[-1] // num_v_heads
    rep = num_v_heads // num_k_heads
    if plan is None:
        plan = plan_for(q, v, num_k_heads, num_v_heads, chunk)

    def gates(a, b):
        beta = jax.nn.sigmoid(b.astype(f32))
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            a.astype(f32) + dt_bias.astype(f32))
        return g, beta

    q, k = (x.reshape(bsz, t, num_k_heads, dk) for x in (q, k))
    v = v.reshape(bsz, t, num_v_heads, dv)
    if plan.admitted:
        out = kernel_gated_delta_rule(q, k, v, *gates(a, b), chunk, mxu_dtype,
                                      l2norm=(_L2_EPS, dk ** -0.5))
        return out.reshape(bsz, t, num_v_heads * dv).astype(f32)

    def prepare(q, k, v, a, b):
        def heads(x):
            x = _l2norm(x.astype(f32))
            return jnp.repeat(x, rep, axis=2) if rep > 1 else x

        return (heads(q) * (dk ** -0.5), heads(k), v) + gates(a, b)

    # tail padding (to whole groups of chunks) is raw zeros: such a token
    # has k = 0, so it writes nothing, and it decays a state that nothing
    # reads any more
    out = chunk_gated_delta_rule(q, k, v, a, b, chunk, mxu_dtype,
                                 prepare=prepare, heads=num_v_heads)
    return out.reshape(bsz, t, num_v_heads * dv)
