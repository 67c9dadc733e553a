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

The work is binned, not one-hot, and only rows that hold a token are
computed: every (token, pick) assignment that falls on a held expert gets a
row of ONE table laid out expert after expert, each expert's rows padded up
to a multiple of ``block_rows`` (128 at training sizes), so that a
block belongs to ONE expert (``block_expert``). The table is sized for the
worst case (every pick of every token held), which costs memory for an
index table (ints), not time, and no routing can overflow anything. There
is one layout and two ways to multiply it (``ops/grouped_experts.py``
decides, through the one placement rule of ``ops/gates.py``):

* ``grouped_rows``, on a single TPU: the Pallas kernels
  ``grouped_experts.fwd`` / ``.bwd``. The table is cut into calls of whole
  experts, at most ``call_rows`` rows each (four times the mean held load,
  and never less than one expert can take: every load the cells show is ONE
  call; a loop with a DYNAMIC trip count makes more only past it). A call's
  rows are gathered in chunks under a dynamic trip count too, the kernels'
  grid bound is a traced scalar, and ``grouped_experts.combine`` adds the
  results to their tokens' rows: time follows the rows that hold a token,
  only buffers follow ``call_rows``.
* ``blocks_xla``, on the CPU and under a mesh: a ``jnp`` loop with a
  dynamic trip count over the table's blocks (a gather, the expert's dense
  products, a scatter-add, the weight gradients added a block).

Reverse-mode autodiff cannot pass through a loop of dynamic length, so the
experts' products are a ``custom_vjp`` whose backward walks the same rows
again (recomputing their hidden activations); the expert forms (three
matrices for SwiGLU, two for ReLU squared) share it through ``FORMS``.

Expert weights are stacked in the published per-expert layout
``[held, out, in]``: ``gate``/``up`` [held, F, D], ``down`` [held, D, F]
(``swiglu``: ``down(silu(gate x) * up x)``); ``up``, ``down`` alone for
``relu2`` (``down(relu(up x)^2)``). ``D`` is the width the experts read and
write, which need not be the router's (experts that work in a latent).
"""

import collections
import functools

import jax
import jax.numpy as jnp

from ..ops import grouped_experts

__all__ = ["route_topk", "bin_assignments", "held_experts", "routed_experts",
           "block_rows_for", "call_rows_for", "table_rows"]

_HIGHEST = jax.lax.Precision.HIGHEST
_FORMS = grouped_experts.FORMS


def block_rows_for(assignments):
    """Rows of a block (a kernel's row tile), from the number of (token,
    pick) assignments: 128 at training sizes (an expert's padding is 64
    rows in the mean; tiles of 256 ran no faster and filled 58% of
    ``nemotron3super.train.s8192``'s rows where these fill 73%), smaller for
    small inputs so that several blocks and a padded tail are exercised."""
    rows = 8
    while rows < 128 and rows * 32 < assignments:
        rows *= 2
    return rows


def _chunk_rows(block_rows):
    """Rows gathered, and scatter-added, at a time round the kernels."""
    return 4 * block_rows


def call_rows_for(assignments, num_experts, held, tokens, block_rows):
    """Rows one call of the kernels may take: four times the mean load of
    the held experts with their padding, and at least what ONE expert can
    take (every token once), in whole chunks."""
    chunk = _chunk_rows(block_rows)
    rows = max(-(-4 * assignments * held // num_experts)
               + held * (block_rows - 1), tokens + block_rows - 1)
    return -(-rows // chunk) * chunk


def route_topk(x, router_w, top_k, renormalize=True, score="softmax",
               bias=None, scale=1.0):
    """x: [T, D]; router_w: [D, E]. Scores over all E in float32
    (``score``: ``softmax`` over the experts, or ``sigmoid`` of each), the
    ``top_k`` largest of them (of ``score + bias`` where a selection bias
    [E] is given: it chooses, the weights are the scores themselves).
    Returns (weights [T, k] float32, experts [T, k] int32)."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError("unknown router score %r" % (score,))
    with jax.named_scope("moe.router"):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            router_w.astype(jnp.float32), precision=_HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
    with jax.named_scope("moe.top_k"):
        if bias is None:
            weights, experts = jax.lax.top_k(probs, top_k)
        else:
            _, experts = jax.lax.top_k(
                probs + jax.lax.stop_gradient(bias.astype(jnp.float32)),
                top_k)
            weights = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


def _max_rows(assignments, held, block_rows):
    rows = assignments + held * (block_rows - 1)
    return -(-rows // block_rows) * block_rows


_Table = collections.namedtuple(
    "_Table", "row_assign block_expert blocks counts call_stop calls")


def bin_assignments(experts, lo, held, block_rows, call_rows):
    """Lay the assignments that fall on held experts out in rows.

    experts: [T, k] int. One table laid out expert after expert, each
    expert's rows padded up to a multiple of ``block_rows``. Returns
    (row_assign [R + call_rows] int32: the flat assignment ``t * k + j`` in
    each row, ``T * k`` for an empty row, R the worst case; block_expert
    [R / block_rows] int32: the held expert (0-based) a block belongs to;
    blocks: how many blocks hold rows; counts [held] int32: the tokens
    each held expert took; call_stop [held] int32 and calls: the table cut
    into ``calls`` runs of whole experts of at most ``call_rows`` rows each,
    run ``c`` ending before row ``call_stop[c]``)."""
    flat = experts.reshape(-1).astype(jnp.int32) - lo
    a = flat.shape[0]
    max_rows = _max_rows(a, held, block_rows)
    hit = flat[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]
    running = jnp.cumsum(hit.astype(jnp.int32), axis=0)        # [A, held]
    counts = running[-1]
    rank = jnp.sum(jnp.where(hit, running - 1, 0), axis=1)     # in its expert
    is_held = (flat >= 0) & (flat < held)
    expert = jnp.clip(flat, 0, held - 1)
    padded = -(-counts // block_rows) * block_rows
    ends = jnp.cumsum(padded)
    starts = ends - padded
    dest = jnp.where(is_held, starts[expert] + rank, max_rows + call_rows)
    row_assign = jnp.full((max_rows + call_rows,), a, jnp.int32).at[
        dest].set(jnp.arange(a, dtype=jnp.int32), mode="drop")
    first_row = jnp.arange(max_rows // block_rows, dtype=jnp.int32) \
        * block_rows
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, first_row, side="right"),
        held - 1).astype(jnp.int32)

    def pack(carry, rows):      # whole experts into calls, greedily
        call, used = carry
        opens = used + rows > call_rows
        call, used = call + opens, jnp.where(opens, rows, used + rows)
        return (call, used), call

    zero = jnp.zeros((), jnp.int32)
    _, call_of = jax.lax.scan(pack, (zero, zero), padded, unroll=True)
    call_stop = jnp.zeros((held,), jnp.int32).at[call_of].max(ends)
    calls = jnp.where(ends[-1] > 0, call_of[-1] + 1, 0)
    return _Table(row_assign, block_expert, ends[-1] // block_rows, counts,
                  call_stop, calls)


def table_rows(counts, block_rows):
    """[2] int32 of a step's table: the rows that hold a token, and the rows
    the products ran over (each expert's padded up to whole blocks)."""
    return jnp.stack([jnp.sum(counts), jnp.sum(
        -(-counts // block_rows) * block_rows)]).astype(jnp.int32)


def _tokens(ids, a, t, top_k):
    """The token of each table row ``ids``; ``t``, past the end, for an
    empty row (``ids`` itself is past the assignments' end ``a`` there), so
    that a scatter drops it."""
    return jnp.where(ids < a, ids // top_k, t)


def _weights(weights_flat, ids):
    """The routing weight of each table row ``ids``, 0 for an empty row."""
    a = weights_flat.shape[0]
    return jnp.where(ids < a, weights_flat[jnp.minimum(ids, a - 1)], 0.0)


def _rows(x, weights_flat, ids, top_k):
    """What a run of table rows ``ids`` reads: (token [R], weight [R], x
    rows [R, D])."""
    t = x.shape[0]
    tokens = _tokens(ids, weights_flat.shape[0], t, top_k)
    return tokens, _weights(weights_flat, ids), x[jnp.minimum(tokens, t - 1)]


def _mm(x, w, dims):
    """Product with float32 accumulation, operands in the weights' dtype."""
    return jax.lax.dot_general(x.astype(w.dtype), w, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# -- blocks_xla: the table's blocks one after another -------------------------

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
    dh_raw = _mm(dyb, wd_e, ((1,), (0,)))                     # [R, F]
    dw = dw.at[ids].add(jnp.sum(dh_raw * h, -1), mode="drop")
    dpre = form.dact(dh_raw * w[:, None], pre, saved, mats[0].dtype)
    xbc = xb.astype(mats[0].dtype)
    dxb = sum(_mm(d, m, ((1,), (0,))) for d, m in zip(dpre, mats))
    dy = (dyb * w[:, None]).astype(wd_e.dtype)
    return (dx.at[tokens].add(dxb, mode="drop"), dw,
            *(_mm(d.T, xbc, ((1,), (0,))) for d in dpre),
            _mm(dy.T, h.astype(wd_e.dtype), ((1,), (0,))))


def _blocks_fwd(form, x, weights_flat, table, mats, top_k, block_rows):
    def body(bi, out):
        ids = jax.lax.dynamic_slice(table.row_assign, (bi * block_rows,),
                                    (block_rows,))
        e = table.block_expert[bi]
        return _rows_fwd(form, x, weights_flat, ids, top_k,
                         [m[e] for m in mats], out)

    return jax.lax.fori_loop(0, table.blocks, body,
                             jnp.zeros(x.shape, jnp.float32))


def _blocks_bwd(form, x, weights_flat, dout, table, mats, top_k, block_rows):
    f32 = jnp.float32

    def body(bi, carry):
        dx, dw, *grads = carry
        ids = jax.lax.dynamic_slice(table.row_assign, (bi * block_rows,),
                                    (block_rows,))
        e = table.block_expert[bi]
        dx, dw, *more = _rows_bwd(form, x, weights_flat, dout, ids, top_k,
                                  [m[e] for m in mats], dx, dw)
        return (dx, dw, *(g.at[e].add(a) for g, a in zip(grads, more)))

    return jax.lax.fori_loop(0, table.blocks, body, (
        jnp.zeros(x.shape, f32), jnp.zeros(weights_flat.shape, f32),
        *(jnp.zeros(m.shape, f32) for m in mats)))


# -- grouped_rows: calls of the kernels over runs of whole experts ------------

def _call_rows(table, c):
    """(first row, rows) of the table's call c."""
    first = jnp.where(c > 0, table.call_stop[jnp.maximum(c - 1, 0)], 0)
    return first, table.call_stop[c] - first


def _gathered(sources, weights_flat, table, first, rows, block_rows,
              call_rows, top_k):
    """What a call reads, in [call_rows, .] buffers of which only the
    chunks that hold rows are written: the rows of each of ``sources``
    [T, .], their routing weights [call_rows, 1], and the assignment in each
    row [call_rows] (past the assignments' end for an empty row, and for
    every row the call does not have)."""
    a, t = weights_flat.shape[0], sources[0].shape[0]
    chunk = _chunk_rows(block_rows)

    def body(i, buffers):
        ids = jax.lax.dynamic_slice(table.row_assign, (first + i * chunk,),
                                    (chunk,))
        # past the call's rows lie the next call's
        ids = jnp.where(i * chunk + jnp.arange(chunk) < rows, ids, a)
        at = jnp.minimum(_tokens(ids, a, t, top_k), t - 1)
        read = [s[at] for s in sources] + [
            _weights(weights_flat, ids)[:, None], ids[:, None]]
        return tuple(jax.lax.dynamic_update_slice(b, r, (i * chunk, 0))
                     for b, r in zip(buffers, read))

    *read, ids = jax.lax.fori_loop(0, (rows + chunk - 1) // chunk, body, (
        *(jnp.zeros((call_rows, s.shape[1]), s.dtype) for s in sources),
        jnp.zeros((call_rows, 1), jnp.float32),
        jnp.full((call_rows, 1), a, jnp.int32)))
    return (*read, ids[:, 0])


def _grouped_fwd(form, x, weights_flat, table, mats, top_k, block_rows,
                 call_rows):
    a, t = weights_flat.shape[0], x.shape[0]
    xc = x.astype(mats[0].dtype)

    def call(c, out):
        first, rows = _call_rows(table, c)
        with jax.named_scope("moe.gather"):
            xr, wr, ids = _gathered((xc,), weights_flat, table, first, rows,
                                    block_rows, call_rows, top_k)
        tiles = rows // block_rows
        y = grouped_experts.forward(
            table.block_expert, (first // block_rows)[None], tiles, xr, wr,
            mats, form, block_rows)
        with jax.named_scope("moe.combine"):
            return grouped_experts.combine(
                _tokens(ids, a, t, top_k), tiles, y, out, block_rows)

    return jax.lax.fori_loop(0, table.calls, call,
                             jnp.zeros(x.shape, jnp.float32))


def _grouped_bwd(form, x, weights_flat, dout, table, mats, top_k, block_rows,
                 call_rows):
    f32 = jnp.float32
    a, t = weights_flat.shape[0], x.shape[0]
    dtype = mats[0].dtype
    xc, dyc = x.astype(dtype), dout.astype(dtype)

    def call(c, carry):
        dx, dw, *grads = carry
        first, rows = _call_rows(table, c)
        with jax.named_scope("moe.gather"):
            xr, dyr, wr, ids = _gathered(
                (xc, dyc), weights_flat, table, first, rows, block_rows,
                call_rows, top_k)
        tiles = rows // block_rows
        dxs, dws, grads = grouped_experts.backward(
            table.block_expert, (first // block_rows)[None], tiles, xr, dyr,
            wr, mats, grads, form, block_rows)
        with jax.named_scope("moe.combine"):
            dx = grouped_experts.combine(
                _tokens(ids, a, t, top_k), tiles, dxs, dx, block_rows)
            dw = dw.at[ids].add(jnp.sum(dws, axis=0)[:, 0], mode="drop")
        return (dx, dw, *grads)

    return jax.lax.fori_loop(0, table.calls, call, (
        jnp.zeros(x.shape, f32), jnp.zeros(weights_flat.shape, f32),
        *(jnp.zeros(m.shape, f32) for m in mats)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def held_experts(x, weights, experts, mats, lo, block_rows, call_rows,
                 form="swiglu", kernels=False):
    """The held experts' part of the routed sum. x: [T, D]; weights,
    experts: [T, k] from :func:`route_topk`; ``mats``: the stacked expert
    matrices of ``form`` (``swiglu``: gate, up [held, F, D] and down [held,
    D, F]; ``relu2``: up, down); ``lo``: id of the first held expert;
    ``kernels``: multiply the table by the ``grouped_experts`` kernels, not
    by the ``jnp`` block loop. Returns (out [T, D] float32, counts [held]
    int32)."""
    (out, counts), _ = _held_fwd(x, weights, experts, mats, lo, block_rows,
                                 call_rows, form, kernels)
    return out, counts


def _held_fwd(x, weights, experts, mats, lo, block_rows, call_rows, form,
              kernels):
    with jax.named_scope("moe.bin"):
        table = bin_assignments(experts, lo, mats[0].shape[0], block_rows,
                                call_rows)
    weights_flat = weights.reshape(-1).astype(jnp.float32)
    if kernels:
        out = _grouped_fwd(form, x, weights_flat, table, mats,
                           weights.shape[1], block_rows, call_rows)
    else:
        out = _blocks_fwd(_FORMS[form], x, weights_flat, table, mats,
                          weights.shape[1], block_rows)
    return (out, table.counts), (x, weights, mats, table)


def _held_bwd(lo, block_rows, call_rows, form, kernels, saved, cotangent):
    x, weights, mats, table = saved
    weights_flat = weights.reshape(-1).astype(jnp.float32)
    dout = cotangent[0].astype(jnp.float32)
    if kernels:
        dx, dw, *grads = _grouped_bwd(
            form, x, weights_flat, dout, table, mats, weights.shape[1],
            block_rows, call_rows)
    else:
        dx, dw, *grads = _blocks_bwd(
            _FORMS[form], x, weights_flat, dout, table, mats,
            weights.shape[1], block_rows)
    return (dx.astype(x.dtype), dw.reshape(weights.shape).astype(
        weights.dtype), None,
        tuple(g.astype(m.dtype) for g, m in zip(grads, mats)))


held_experts.defvjp(_held_fwd, _held_bwd)


def routed_experts(x, router_w, wg, wu, wd, top_k, lo=0, renormalize=True,
                   block_rows=None, form="swiglu", score="softmax",
                   bias=None, scale=1.0, router_x=None, plan=None):
    """x: [..., D], what the experts read; ``wg`` is None for ``relu2``
    experts, which have no gate matrix. The router reads ``router_x`` [...,
    D_model] where given (experts that work in a latent), else x. ``plan``:
    the ``GateDecision`` of ``ops.grouped_experts.plan_for`` (default: taken
    here). Returns (routed [..., D] float32: the held experts' part of the
    layer's routed sum; counts [held] int32)."""
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    rt = xt if router_x is None else router_x.reshape(-1, router_x.shape[-1])
    weights, experts = route_topk(rt, router_w, top_k, renormalize, score,
                                  bias, scale)
    a, num_experts = xt.shape[0] * top_k, router_w.shape[1]
    rows = int(block_rows or block_rows_for(a))
    mats = (wu, wd) if form == "relu2" else (wg, wu, wd)
    if plan is None:
        plan = grouped_experts.plan_for(mats, rows)
    out, counts = held_experts(
        xt, weights, experts, mats, int(lo), rows,
        call_rows_for(a, num_experts, mats[0].shape[0], xt.shape[0], rows),
        form, bool(plan))
    return out.reshape(lead + (d,)), counts
