"""The held experts' products over an expert-sorted row table, as Pallas
kernels (family ``grouped_experts``, parts ``fwd``, ``bwd`` and
``combine``).

``parallel/moe.py`` lays every (token, pick) that falls on a held expert
into one table sorted by expert, each expert's rows padded up to a multiple
of the row tile, so that a tile belongs to ONE expert (``block_expert``).
A call of these kernels takes a run of that table's tiles whose rows have
been gathered into ``[call_rows, D]``: the grid is (``F`` tiles, the call's
tiles) with the number of tiles a TRACED scalar, so only the tiles that hold
rows cost time; the tile's expert comes from the scalar-prefetched
``block_expert`` and picks the weight blocks in the ``index_map``.

* ``fwd``: rows x first matrices -> the activation in float32 -> ``down``,
  times the row's routing weight; one partial ``[rows, D]`` an ``F`` tile.
* ``bwd``: recomputes the hidden activations and gives ``dx`` (a partial an
  ``F`` tile), the routing weight's gradient without a second ``down``
  product (``dh_raw = dy @ down``, ``dw = rowsum(dh_raw * h)``, ``dh = w *
  dh_raw``), and the weight gradients: float32 blocks that stay in VMEM
  across all of an expert's consecutive tiles (``F`` is the OUTER grid axis)
  and are written once an expert. The gradient arrays are carried from call
  to call through ``input_output_aliases``, so an expert no call visits
  keeps the zeros it started with.
* ``combine``: adds the rows' partials to their tokens' rows of a [T, D]
  float32 sum (the routed output; ``dx``): a [T, columns] block of the sum
  stays in VMEM across the call's tiles and each row is a read-modify-write
  there, where XLA's scatter-add pays one in HBM a row (0.33 us a row of
  2048 floats; my chip run, PR 31).

Operands are in the weights' dtype (bfloat16 under AMP), accumulation, the
activation and its derivative in float32. The ``F`` tile is the largest
128-multiple divisor of ``F`` whose counted working set fits the VMEM the
kernels ask for (``_VMEM_BUDGET``, also their ``vmem_limit_bytes``): the
whole of ``F`` for three 2048 x 512 matrices, a third of 2688 for the
backward of two 1024 x 2688 ones; ``combine``'s column tile likewise (512
of 1024 or 2048 columns at 8192 tokens).
"""

import functools
import operator

import jax
import jax.numpy as jnp

from .gates import GateDecision, GateReason, platform_reason
from .kernel_names import named_pallas_call, traced_once

__all__ = ["FORMS", "forward", "backward", "combine", "kernel_plan",
           "plan_for", "f_tile", "column_tile"]

_INTERPRET = False  # tests flip this to run the kernels on the CPU

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


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


FORMS = {"swiglu": _SwiGLU, "relu2": _ReLU2}


def _dot(x, y, dims):
    return jax.lax.dot_general(x, y, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# kernels. Scalar prefetch: ``be`` [tiles of the whole table] int32, the
# expert of each tile; ``at`` [1] int32, the call's first tile in it.
# ---------------------------------------------------------------------------

def _fwd_kernel(be_ref, at_ref, x_ref, w_ref, *refs, form):
    *mats, y_ref = refs
    down = mats[-1]
    x = x_ref[...]
    h = form.act([_dot(x, m[...], _NT) for m in mats[:-1]])    # [R, tf]
    y = _dot(h.astype(down.dtype), down[...], _NT)             # [R, D]
    y_ref[...] = y * w_ref[...]


def _bwd_kernel(be_ref, at_ref, x_ref, dy_ref, w_ref, *refs, form, n):
    from jax.experimental import pallas as pl

    # refs[n:2n] are the carried gradients: aliased to the outputs, not read
    mats, (dx_ref, dw_ref, *grads) = refs[:n], refs[2 * n:]
    down = mats[-1]
    dtype = down.dtype
    t = pl.program_id(1)
    tile = at_ref[0] + t
    first = jnp.logical_or(
        t == 0, be_ref[tile] != be_ref[jnp.maximum(tile - 1, 0)])

    @pl.when(first)
    def _():
        for g in grads:
            g[...] = jnp.zeros_like(g)

    x, dy, w = x_ref[...], dy_ref[...], w_ref[...]
    pre = [_dot(x, m[...], _NT) for m in mats[:-1]]
    h, saved = form.act_saved(pre)
    dh_raw = _dot(dy, down[...], _NN)                          # [R, tf]
    dw_ref[...] = jnp.sum(dh_raw * h, axis=-1, keepdims=True)
    dpre = form.dact(dh_raw * w, pre, saved, dtype)
    dx_ref[...] = functools.reduce(operator.add, (
        _dot(d, m[...], _NN) for d, m in zip(dpre, mats)))
    for g, d in zip(grads, dpre):
        g[...] += _dot(d, x, _TN)                              # [tf, D]
    grads[-1][...] += _dot((dy * w).astype(dtype), h.astype(dtype), _TN)


def _combine_kernel(tok_ref, parts_ref, sums_ref, out_ref, rows_ref, sem, *,
                    rows, dc):
    """Adds a tile's rows to their tokens' rows of ``out``: a [T, dc] column
    block of the sums stays in VMEM across all of the call's tiles (the
    columns are the OUTER grid axis), so a row costs a read-modify-write in
    VMEM, not one in HBM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    j, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _():
        fill = pltpu.make_async_copy(
            sums_ref.at[:, pl.ds(j * dc, dc)], out_ref, sem)
        fill.start()
        fill.wait()

    rows_ref[...] = functools.reduce(operator.add, (
        parts_ref[i] for i in range(parts_ref.shape[0])))

    def add(r, carry):
        token = tok_ref[t * rows + r]

        @pl.when(token < out_ref.shape[0])      # an empty row: past the end
        def _():
            out_ref[pl.ds(token, 1), :] += rows_ref[pl.ds(r, 1), :]

        return carry

    jax.lax.fori_loop(0, rows, add, 0)


# ---------------------------------------------------------------------------
# what a grid step holds in VMEM, and the F tile that follows from it
# ---------------------------------------------------------------------------

_VMEM_BUDGET = 48 * 1024 * 1024


def _working_set(part, n, d, tf, rows, itemsize):
    """Bytes a grid step of ``part`` holds, counted generously: the
    double-buffered blocks of the ``n`` matrices and of the row tiles, the
    float32 gradient blocks (backward), and the [rows, tf] float32 tiles
    live between the first product and the last."""
    mats = 2 * n * tf * d * itemsize
    if part == "fwd":
        tiles = 2 * rows * d * (itemsize + 4)
        live = (n + 1) * rows * tf * 4 + rows * d * 4
        return mats + tiles + live
    grads = 2 * n * tf * d * 4
    tiles = 2 * rows * d * (2 * itemsize + 4)
    live = (2 * n + 3) * rows * tf * 4 + 2 * rows * d * 4
    return mats + grads + tiles + live


def f_tile(part, n, d, f, rows, itemsize):
    """The largest tile of ``F`` (``F`` itself, or a divisor of it that is a
    multiple of 128) whose working set fits ``_VMEM_BUDGET``; None if none
    does."""
    for parts in range(1, max(f // 128, 1) + 1):
        if f % parts or (parts > 1 and (f // parts) % 128):
            continue
        if _working_set(part, n, d, f // parts, rows, itemsize) \
                <= _VMEM_BUDGET:
            return f // parts
    return None


def _combine_working_set(tokens, dc, parts, rows):
    """Bytes a grid step of ``combine`` holds: the double-buffered [tokens,
    dc] block of the sums, a tile's rows of each of the ``parts`` addends
    (double-buffered), and their sum."""
    return 4 * dc * (2 * tokens + (2 * parts + 1) * rows)


def column_tile(tokens, d, parts, rows):
    """The widest tile of ``D`` (``D`` itself, or a divisor of it that is a
    multiple of 128) at which ``combine`` fits ``_VMEM_BUDGET``; None if
    none does."""
    for cuts in range(1, max(d // 128, 1) + 1):
        if d % cuts or (cuts > 1 and (d // cuts) % 128):
            continue
        if _combine_working_set(tokens, d // cuts, parts, rows) \
                <= _VMEM_BUDGET:
            return d // cuts
    return None


def kernel_plan(n, d, f, rows, itemsize, platform=None):
    """Which way a ``routed_experts`` site multiplies its table, as a
    ``GateDecision``: ``grouped_rows`` (these kernels) or ``blocks_xla``
    (the ``jnp`` block loop of ``parallel/moe.py``) with the blocking
    reasons. ``n``: matrices an expert; ``rows``: rows of a tile;
    ``platform``: what ``gates.platform_reason`` says of where the step
    runs (:func:`plan_for`)."""
    reasons = []
    if platform is not None:
        reasons.append(platform)
    sublanes = 32 // itemsize
    if d % 128 or f % 128 or rows % sublanes:
        reasons.append(GateReason(
            "geometry", "widths %d x %d are not multiples of 128, or tiles "
            "of %d rows no multiple of %d sublanes" % (d, f, rows, sublanes)))
    tiles = None
    if not reasons:
        tiles = [f_tile(part, n, d, f, rows, itemsize)
                 for part in ("fwd", "bwd")]
        if None in tiles:
            reasons.append(GateReason(
                "vmem", "%d matrices of %d x 128 with tiles of %d rows "
                "exceed the %.0f MB VMEM budget" % (
                    n, d, rows, _VMEM_BUDGET / 2**20)))
    if reasons:
        return GateDecision(False, "blocks_xla", fallback="grouped_rows",
                            reasons=reasons + [GateReason(
                                "shape", "blocks of %d rows of one expert "
                                "each under a dynamic trip count" % rows,
                                blocking=False)])
    return GateDecision(True, "grouped_rows", reasons=[GateReason(
        "shape", "tiles of %d rows of one expert each, F = %d in tiles of "
        "%d forward and %d backward, the weight gradients in VMEM across "
        "an expert's tiles" % (rows, f, tiles[0], tiles[1]),
        blocking=False)])


def plan_for(mats, rows):
    """:func:`kernel_plan` of a site's stacked matrices (``down`` [held, D,
    F] last) and tile rows, where the step being traced is placed."""
    d, f = mats[-1].shape[1:]
    return kernel_plan(len(mats), d, f, rows, mats[-1].dtype.itemsize,
                       platform=platform_reason(_INTERPRET))


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------

def _specs(n, d, tf, rows):
    from jax.experimental import pallas as pl

    def expert(t, be, at):
        return be[at[0] + t]

    row = pl.BlockSpec((rows, d), lambda j, t, be, at: (t, 0))
    first = pl.BlockSpec((None, tf, d),
                         lambda j, t, be, at: (expert(t, be, at), j, 0))
    down = pl.BlockSpec((None, d, tf),
                        lambda j, t, be, at: (expert(t, be, at), 0, j))
    return {"row": row, "w": pl.BlockSpec((rows, 1),
                                          lambda j, t, be, at: (t, 0)),
            "mats": [first] * (n - 1) + [down],
            "part": pl.BlockSpec((None, rows, d),
                                 lambda j, t, be, at: (j, t, 0)),
            "dw": pl.BlockSpec((None, rows, 1),
                               lambda j, t, be, at: (j, t, 0))}


def _compiler_params(vmem):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=vmem)


_STATICS = ("form", "rows", "tf", "vmem", "interpret")


@traced_once("grouped_experts.fwd", _STATICS)
def _fwd_impl(be, at, tiles, x, w, mats, form, rows, tf, vmem, interpret):
    """be [tiles of the table] int32; at [1] int32: the call's first tile;
    ``tiles`` int32 scalar: how many it has; x [call_rows, D]: its rows,
    gathered; w [call_rows, 1] float32: their routing weights, 0 for a
    padding row; ``mats``: the stacked matrices, ``down`` last. Returns
    [F / tf, call_rows, D] float32: the weighted output's addend of each
    ``F`` tile; rows of tiles past ``tiles`` are not written. ``vmem``: the
    kernel's ``vmem_limit_bytes``."""
    from jax.experimental.pallas import tpu as pltpu

    d, f = mats[-1].shape[1:]
    specs = _specs(len(mats), d, tf, rows)
    return named_pallas_call(
        "grouped_experts.fwd",
        functools.partial(_fwd_kernel, form=FORMS[form]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(f // tf, tiles),
            in_specs=[specs["row"], specs["w"], *specs["mats"]],
            out_specs=specs["part"]),
        out_shape=jax.ShapeDtypeStruct((f // tf,) + x.shape, jnp.float32),
        compiler_params=_compiler_params(vmem),
        interpret=interpret,
    )(be, at, x, w, *mats)


@traced_once("grouped_experts.bwd", _STATICS)
def _bwd_impl(be, at, tiles, x, dy, w, mats, grads, form, rows, tf, vmem,
              interpret):
    """As :func:`_fwd_impl`, with dy [call_rows, D]: the rows of the
    output's cotangent (unweighted), and ``grads``: the float32 weight
    gradients so far, which the call updates in place for the experts its
    tiles belong to. Returns (dx's addend of each ``F`` tile [F / tf,
    call_rows, D] float32, the routing weights' [F / tf, call_rows, 1], the
    gradients)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    n = len(mats)
    d, f = mats[-1].shape[1:]
    specs = _specs(n, d, tf, rows)
    nf = f // tf
    out = named_pallas_call(
        "grouped_experts.bwd",
        functools.partial(_bwd_kernel, form=FORMS[form], n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nf, tiles),
            in_specs=[specs["row"], specs["row"], specs["w"],
                      *specs["mats"],
                      *[pl.BlockSpec(memory_space=pl.ANY)] * n],
            out_specs=[specs["part"], specs["dw"], *specs["mats"]]),
        out_shape=[jax.ShapeDtypeStruct((nf,) + x.shape, f32),
                   jax.ShapeDtypeStruct((nf, x.shape[0], 1), f32),
                   *[jax.ShapeDtypeStruct(g.shape, f32) for g in grads]],
        input_output_aliases={5 + n + i: 2 + i for i in range(n)},
        compiler_params=_compiler_params(vmem),
        interpret=interpret,
    )(be, at, x, dy, w, *mats, *grads)
    return out[0], out[1], tuple(out[2:])


@traced_once("grouped_experts.combine", ("rows", "dc", "vmem", "interpret"))
def _combine_impl(tokens, tiles, parts, sums, rows, dc, vmem, interpret):
    """tokens [call_rows] int32: the token of each of a call's rows, past
    the end for an empty one; ``tiles``: how many tiles of ``rows`` rows the
    call has; parts [., call_rows, D] float32: addends of the rows (a
    kernel's partial outputs); sums [T, D] float32. Returns ``sums`` with
    every row's addends added to its token's row, in place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d = sums.shape[1]
    return named_pallas_call(
        "grouped_experts.combine",
        functools.partial(_combine_kernel, rows=rows, dc=dc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(d // dc, tiles),
            in_specs=[pl.BlockSpec((parts.shape[0], rows, dc),
                                   lambda j, t, tok: (0, t, j)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((sums.shape[0], dc),
                                   lambda j, t, tok: (0, j)),
            scratch_shapes=[pltpu.VMEM((rows, dc), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(sums.shape, jnp.float32),
        input_output_aliases={2: 0},
        compiler_params=_compiler_params(vmem),
        interpret=interpret,
    )(tokens, parts, sums)


def _tiles_of(part, mats, rows):
    d, f = mats[-1].shape[1:]
    return f_tile(part, len(mats), d, f, rows, mats[-1].dtype.itemsize)


def forward(be, at, tiles, x, w, mats, form, rows):
    return _fwd_impl(be, at, tiles, x, w, tuple(mats), form=form, rows=rows,
                     tf=_tiles_of("fwd", mats, rows), vmem=_VMEM_BUDGET,
                     interpret=_INTERPRET)


def combine(tokens, tiles, parts, sums, rows):
    """``sums`` [T, D] float32 with the call's rows added to their tokens'
    rows: by the ``combine`` kernel where a column block of ``sums`` fits
    VMEM, else by XLA's scatter-add over all of the call's rows."""
    dc = column_tile(sums.shape[0], sums.shape[1], parts.shape[0], rows)
    if dc is None:
        return sums.at[tokens].add(jnp.sum(parts, axis=0), mode="drop")
    return _combine_impl(tokens, tiles, parts, sums, rows=rows, dc=dc,
                         vmem=_VMEM_BUDGET, interpret=_INTERPRET)


def backward(be, at, tiles, x, dy, w, mats, grads, form, rows):
    return _bwd_impl(be, at, tiles, x, dy, w, tuple(mats), tuple(grads),
                     form=form, rows=rows, tf=_tiles_of("bwd", mats, rows),
                     vmem=_VMEM_BUDGET, interpret=_INTERPRET)
