"""Routed experts: top-k token-choice routing over ``num_experts`` with the
chip holding a contiguous share of them, no token ever dropped.

One routing implementation. The router scores every expert (its width is
the whole model's) by a softmax over all of them or by a sigmoid each (then
a selection bias, which no gradient reaches, may be added for the choice
alone), the ``top_k`` largest are taken and, with ``renormalize``, their
weights divided by their sum over ALL the picks, then multiplied by
``scale``.
The chip then computes only the picks that fall on the experts it holds
(``[lo, lo + held)``): what the absent experts would add is left out, which
is this chip's addend of the layer's sum over the chips that share it.

The work is binned, not one-hot: every (token, pick) assignment that falls
on a held expert gets a row. The first ``slab_rows`` rows of each expert
(four times its mean load, so what seeded weights send it at the first
step) are one STATIC pass an expert: a row of the slab, the expert's dense
matrix products (three for SwiGLU, two for ReLU squared: ``_FORMS``), the
expert's weight gradients written once. What a
routing sends an expert beyond its slab goes to a table laid out expert
after expert, each expert's rows padded up to a multiple of ``block_rows``;
a block belongs to ONE expert, the table is sized for the worst case (every
pick of every token held), and a loop with a DYNAMIC trip count walks only
the blocks that hold rows, so the worst case costs memory for an index table
(ints), not time, and no routing can overflow anything. A row costs the
same in either place (my chip runs, PR 26); the slab's empty rows are paid
for at every step, and in return a step's time does not follow the routing
until an expert passes its slab. Reverse-mode autodiff cannot pass through
a loop of dynamic length, so the experts' products are a ``custom_vjp``
whose backward walks the same rows again (recomputing their hidden
activations).

Expert weights are stacked in the published per-expert layout
``[held, out, in]``: ``gate``/``up`` [held, F, D], ``down`` [held, D, F]
(``swiglu``: ``down(silu(gate x) * up x)``); ``up``, ``down`` alone for
``relu2`` (``down(relu(up x)^2)``). ``D`` is the width the experts read and
write, which need not be the router's (experts that work in a latent).
"""

import functools
import operator

import jax
import jax.numpy as jnp

__all__ = ["route_topk", "bin_assignments", "held_experts", "routed_experts",
           "block_rows_for", "slab_rows_for"]

_HIGHEST = jax.lax.Precision.HIGHEST


def block_rows_for(assignments):
    """Rows of a block, from the number of (token, pick) assignments: 256 at
    training sizes, smaller for small inputs so that several blocks and a
    padded tail are exercised."""
    rows = 8
    while rows < 256 and rows * 32 < assignments:
        rows *= 2
    return rows


def slab_rows_for(assignments, num_experts, block_rows):
    """Rows of an expert's static pass: the power of two at or above four
    times its mean load, at least a block."""
    rows = block_rows
    while rows * num_experts < 4 * assignments:
        rows *= 2
    return rows


def route_topk(x, router_w, top_k, renormalize=True, score="softmax",
               bias=None, scale=1.0):
    """x: [T, D]; router_w: [D, E]. Scores over all E in float32
    (``score``: ``softmax`` over the experts, or ``sigmoid`` of each), the
    ``top_k`` largest of them (of ``score + bias`` where a selection bias
    [E] is given: it chooses, the weights are the scores themselves).
    Returns (weights [T, k] float32, experts [T, k] int32)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32), precision=_HIGHEST)
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError("unknown router score %r" % (score,))
    if bias is None:
        weights, experts = jax.lax.top_k(probs, top_k)
    else:
        _, experts = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


def _max_rows(assignments, held, block_rows):
    rows = assignments + held * (block_rows - 1)
    return -(-rows // block_rows) * block_rows


def bin_assignments(experts, lo, held, block_rows, slab_rows):
    """Lay the assignments that fall on held experts out in rows.

    experts: [T, k] int. The first ``slab_rows`` assignments of each held
    expert go to its row of the slab, the rest to a table laid out expert
    after expert, each expert's rows padded up to a multiple of
    ``block_rows``. Returns (slab_assign [held, slab_rows] int32 and
    row_assign [R] int32: the flat assignment ``t * k + j`` in each row,
    ``T * k`` for an empty row; block_expert [R / block_rows] int32: the
    held expert (0-based) a block of the table belongs to; blocks: how many
    blocks hold rows; counts [held] int32: the tokens each held expert
    took)."""
    flat = experts.reshape(-1).astype(jnp.int32) - lo
    a = flat.shape[0]
    max_rows = _max_rows(a, held, block_rows)
    hit = flat[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]
    running = jnp.cumsum(hit.astype(jnp.int32), axis=0)        # [A, held]
    counts = running[-1]
    rank = jnp.sum(jnp.where(hit, running - 1, 0), axis=1)     # in its expert
    is_held = (flat >= 0) & (flat < held)
    expert = jnp.clip(flat, 0, held - 1)
    in_slab = is_held & (rank < slab_rows)
    order = jnp.arange(a, dtype=jnp.int32)
    slab_assign = jnp.full((held * slab_rows,), a, jnp.int32).at[
        jnp.where(in_slab, expert * slab_rows + rank, held * slab_rows)
    ].set(order, mode="drop").reshape(held, slab_rows)
    over = jnp.maximum(counts - slab_rows, 0)
    padded = -(-over // block_rows) * block_rows
    ends = jnp.cumsum(padded)
    starts = ends - padded
    dest = jnp.where(is_held & ~in_slab,
                     starts[expert] + rank - slab_rows, max_rows)
    row_assign = jnp.full((max_rows,), a, jnp.int32).at[dest].set(
        order, mode="drop")
    first_row = jnp.arange(max_rows // block_rows, dtype=jnp.int32) \
        * block_rows
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, first_row, side="right"),
        held - 1).astype(jnp.int32)
    return (slab_assign, row_assign, block_expert, ends[-1] // block_rows,
            counts)


def _rows(x, weights_flat, ids, top_k):
    """What a run of table rows ``ids`` reads: (token [R], past the end for
    an empty row so that a scatter drops it, as ``ids`` itself is past the
    assignments' end there; weight [R], 0 for an empty row; x rows
    [R, D])."""
    a, t = weights_flat.shape[0], x.shape[0]
    valid = ids < a
    tokens = jnp.where(valid, ids // top_k, t)
    w = jnp.where(valid, weights_flat[jnp.minimum(ids, a - 1)], 0.0)
    return tokens, w, x[jnp.minimum(tokens, t - 1)]


def _mm(x, w, dims):
    """Product with float32 accumulation, operands in the weights' dtype."""
    return jax.lax.dot_general(x.astype(w.dtype), w, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


class _SwiGLU:
    """``down(silu(gate x) * up x)``; mats = (gate, up, down)."""

    @staticmethod
    def act(pre):
        g, u = pre
        return jax.nn.silu(g) * u

    @staticmethod
    def act_saved(pre):
        g, u = pre
        sg = jax.nn.sigmoid(g)
        return g * sg * u, sg

    @staticmethod
    def dact(dh, pre, sg, dtype):
        g, u = pre
        dg = (dh * u * sg * (1.0 + g * (1.0 - sg))).astype(dtype)
        return dg, (dh * g * sg).astype(dtype)


class _ReLU2:
    """``down(relu(up x)^2)``; mats = (up, down)."""

    @staticmethod
    def act(pre):
        return jnp.square(jax.nn.relu(pre[0]))

    @staticmethod
    def act_saved(pre):
        r = jax.nn.relu(pre[0])
        return r * r, r

    @staticmethod
    def dact(dh, pre, r, dtype):
        return ((dh * 2.0 * r).astype(dtype),)


_FORMS = {"swiglu": _SwiGLU, "relu2": _ReLU2}


def _rows_fwd(form, x, weights_flat, ids, top_k, mats, out):
    """``mats``: one expert's matrices, those that read x first, ``down``
    last."""
    tokens, w, xb = _rows(x, weights_flat, ids, top_k)
    pre = [_mm(xb, m, ((1,), (1,))) for m in mats[:-1]]       # [R, F]
    y = _mm(form.act(pre), mats[-1], ((1,), (1,)))            # [R, D]
    return out.at[tokens].add(y * w[:, None], mode="drop")


def _rows_bwd(form, x, weights_flat, dout, ids, top_k, mats, dx, dw):
    """Returns (dx, dw with this run's rows added, this run's addends to
    the expert's weight gradients, in the order of ``mats``)."""
    tokens, w, xb = _rows(x, weights_flat, ids, top_k)
    wd_e = mats[-1]
    pre = [_mm(xb, m, ((1,), (1,))) for m in mats[:-1]]
    h, saved = form.act_saved(pre)
    dyb = dout[jnp.minimum(tokens, x.shape[0] - 1)]           # [R, D]
    y = _mm(h, wd_e, ((1,), (1,)))
    dw = dw.at[ids].add(jnp.sum(y * dyb, -1), mode="drop")
    dy = (dyb * w[:, None]).astype(wd_e.dtype)
    dh = _mm(dy, wd_e, ((1,), (0,)))                          # [R, F]
    dpre = form.dact(dh, pre, saved, mats[0].dtype)
    xbc = xb.astype(mats[0].dtype)
    dxb = functools.reduce(operator.add, (
        _mm(d, m, ((1,), (0,))) for d, m in zip(dpre, mats)))
    return (dx.at[tokens].add(dxb, mode="drop"), dw,
            *(_mm(d.T, xbc, ((1,), (0,))) for d in dpre),
            _mm(dy.T, h.astype(wd_e.dtype), ((1,), (0,))))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def held_experts(x, weights, experts, mats, lo, block_rows, slab_rows,
                 form="swiglu"):
    """The held experts' part of the routed sum. x: [T, D]; weights,
    experts: [T, k] from :func:`route_topk`; ``mats``: the stacked expert
    matrices of ``form`` (``swiglu``: gate, up [held, F, D] and down [held,
    D, F]; ``relu2``: up, down); ``lo``: id of the first held expert.
    Returns (out [T, D] float32, counts [held] int32)."""
    (out, counts), _ = _held_fwd(x, weights, experts, mats, lo, block_rows,
                                 slab_rows, form)
    return out, counts


def _held_fwd(x, weights, experts, mats, lo, block_rows, slab_rows, form):
    held = mats[0].shape[0]
    top_k = weights.shape[1]
    rows_fwd = functools.partial(_rows_fwd, _FORMS[form])
    slab_assign, row_assign, block_expert, blocks, counts = bin_assignments(
        experts, lo, held, block_rows, slab_rows)
    weights_flat = weights.reshape(-1).astype(jnp.float32)

    def slab(out, per):
        ids, *mats_e = per
        return rows_fwd(x, weights_flat, ids, top_k, mats_e, out), None

    out, _ = jax.lax.scan(slab, jnp.zeros(x.shape, jnp.float32),
                          (slab_assign, *mats))

    def body(bi, out):
        ids = jax.lax.dynamic_slice(row_assign, (bi * block_rows,),
                                    (block_rows,))
        e = block_expert[bi]
        return rows_fwd(x, weights_flat, ids, top_k, [m[e] for m in mats],
                        out)

    out = jax.lax.fori_loop(0, blocks, body, out)
    saved = (x, weights, mats, slab_assign, row_assign, block_expert, blocks)
    return (out, counts), saved


def _held_bwd(lo, block_rows, slab_rows, form, saved, cotangent):
    x, weights, mats, slab_assign, row_assign, block_expert, blocks = saved
    dout = cotangent[0].astype(jnp.float32)
    top_k = weights.shape[1]
    rows_bwd = functools.partial(_rows_bwd, _FORMS[form])
    weights_flat = weights.reshape(-1).astype(jnp.float32)
    f32 = jnp.float32

    def slab(carry, per):
        ids, *mats_e = per
        dx, dw, *grads = rows_bwd(x, weights_flat, dout, ids, top_k, mats_e,
                                  *carry)
        return (dx, dw), tuple(grads)

    (dx, dw), grads = jax.lax.scan(
        slab, (jnp.zeros(x.shape, f32), jnp.zeros(weights_flat.shape, f32)),
        (slab_assign, *mats))

    def body(bi, carry):
        dx, dw, *grads = carry
        ids = jax.lax.dynamic_slice(row_assign, (bi * block_rows,),
                                    (block_rows,))
        e = block_expert[bi]
        dx, dw, *more = rows_bwd(x, weights_flat, dout, ids, top_k,
                                 [m[e] for m in mats], dx, dw)
        return (dx, dw, *(g.at[e].add(a) for g, a in zip(grads, more)))

    dx, dw, *grads = jax.lax.fori_loop(0, blocks, body, (dx, dw, *grads))
    return (dx.astype(x.dtype), dw.reshape(weights.shape).astype(
        weights.dtype), None,
        tuple(g.astype(m.dtype) for g, m in zip(grads, mats)))


held_experts.defvjp(_held_fwd, _held_bwd)


def routed_experts(x, router_w, wg, wu, wd, top_k, lo=0, renormalize=True,
                   block_rows=None, slab_rows=None, form="swiglu",
                   score="softmax", bias=None, scale=1.0, router_x=None):
    """x: [..., D], what the experts read; ``wg`` is None for ``relu2``
    experts, which have no gate matrix. The router reads ``router_x`` [...,
    D_model] where given (experts that work in a latent), else x. Returns
    (routed [..., D] float32: the held experts' part of the layer's routed
    sum; counts [held] int32)."""
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    rt = xt if router_x is None else router_x.reshape(-1, router_x.shape[-1])
    weights, experts = route_topk(rt, router_w, top_k, renormalize, score,
                                  bias, scale)
    a = xt.shape[0] * top_k
    rows = block_rows or block_rows_for(a)
    slab = slab_rows or slab_rows_for(a, router_w.shape[1], rows)
    mats = (wu, wd) if form == "relu2" else (wg, wu, wd)
    out, counts = held_experts(xt, weights, experts, mats, int(lo),
                               int(rows), int(slab), form)
    return out.reshape(lead + (d,)), counts
