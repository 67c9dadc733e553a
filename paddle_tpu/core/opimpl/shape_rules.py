"""Static infer-shape/dtype rules for the core op set.

The analog of the per-op ``InferShape`` methods every reference operator
implements (``paddle/fluid/operators/*_op.cc``, run by
``OperatorWithKernel::RunImpl`` before the kernel): each rule derives the
output shapes/dtypes of one op type from its inputs' static shapes and
checks them against the declared output Variables — so a shape bug is
reported at the op that created it (with the user's code line) instead of
surfacing as an XLA trace error inside the jitted step.

Registered via :func:`core.op_registry.register_shape`, alongside the
lowerings. Rules receive an ``analysis.passes.ShapeCtx`` and the symbolic
op; -1 dims are wildcards (the batch dim). Ops without a rule are skipped
by the propagation pass — their declared output shapes are trusted. A rule
raises :class:`core.op_registry.ShapeError` when the inputs are
statically infeasible (e.g. a contraction-dim mismatch).
"""

import numpy as np

from ..op_registry import register_shape, ShapeError, static_bcast_shape
from ..framework import convert_np_dtype


def _prod(dims):
    out = 1
    for d in dims:
        if d == -1:
            return -1
        out *= int(d)
    return out


def _norm_axis(a, rank):
    return a + rank if a < 0 else a


# ---------------------------------------------------------------------------
# elementwise / comparison / logical (reference elementwise_op.h broadcast)
# ---------------------------------------------------------------------------

_ELEMENTWISE = ("elementwise_add", "elementwise_sub", "elementwise_mul",
                "elementwise_div", "elementwise_max", "elementwise_min",
                "elementwise_pow", "elementwise_mod", "elementwise_floordiv")
_COMPARE = ("less_than", "less_equal", "greater_than", "greater_equal",
            "equal", "not_equal")
_LOGICAL = ("logical_and", "logical_or", "logical_xor")


def _binop_rule(bool_out):
    def rule(ctx, op):
        xv, yv = op.input("X"), op.input("Y")
        xs, ys = ctx.shape(xv), ctx.shape(yv)
        dtype = np.dtype(bool) if bool_out else ctx.dtype(xv)
        if xs is None or ys is None:
            ctx.set(op.output("Out"), None, dtype)
            return
        try:
            out = static_bcast_shape(xs, ys, op.attr("axis", -1))
        except ValueError as e:
            raise ShapeError("%s (X='%s' %s, Y='%s' %s)" % (
                e, xv.name, list(xs), yv.name, list(ys)))
        ctx.set(op.output("Out"), out, dtype)
    return rule


for _n in _ELEMENTWISE:
    register_shape(_n)(_binop_rule(bool_out=False))
for _n in _COMPARE + _LOGICAL:
    register_shape(_n)(_binop_rule(bool_out=True))


@register_shape("logical_not")
def _logical_not_shape(ctx, op):
    ctx.set(op.output("Out"), ctx.shape(op.input("X")), np.dtype(bool))


# ---------------------------------------------------------------------------
# shape-preserving unaries (activations, scale, clip, dropout, softmax...)
# ---------------------------------------------------------------------------

_LIKE_X = (
    # activation_op.cc table
    "sigmoid", "logsigmoid", "exp", "tanh", "tanh_shrink", "sqrt", "rsqrt",
    "abs", "ceil", "floor", "round", "cos", "sin", "reciprocal", "log",
    "square", "softplus", "softsign", "relu", "sign", "erf",
    "relu6", "leaky_relu", "elu", "gelu", "brelu", "stanh", "hard_sigmoid",
    "hard_shrink", "soft_shrink", "thresholded_relu", "swish", "selu",
    "prelu",
    # shape-preserving tensor/nn ops
    "scale", "clip", "softmax", "log_softmax", "label_smooth",
    "sigmoid_cross_entropy_with_logits", "increment", "fill_zeros_like",
    "square_error_cost", "assign", "optimization_barrier",
)


def _like_x_rule(ctx, op):
    ctx.set(op.output("Out"), ctx.shape(op.input("X")),
            ctx.dtype(op.input("X")))


for _n in _LIKE_X:
    register_shape(_n)(_like_x_rule)


@register_shape("dropout")
def _dropout_shape(ctx, op):
    xs, dt = ctx.shape(op.input("X")), ctx.dtype(op.input("X"))
    ctx.set(op.output("Out"), xs, dt)
    ctx.set(op.output("Mask"), xs, dt)


@register_shape("cast")
def _cast_shape(ctx, op):
    ctx.set(op.output("Out"), ctx.shape(op.input("X")),
            convert_np_dtype(op.attr("out_dtype")))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

@register_shape("mean")
def _mean_shape(ctx, op):
    ctx.set(op.output("Out"), (), ctx.dtype(op.input("X")))


def _reduce_rule(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    if xs is None:
        ctx.set(op.output("Out"), None, dt)
        return
    dim = op.attr("dim", [0])
    keep = op.attr("keep_dim", False)
    if op.attr("reduce_all", False) or dim is None:
        out = tuple([1] * len(xs)) if keep else ()
        ctx.set(op.output("Out"), out, dt)
        return
    axes = {_norm_axis(d, len(xs)) for d in dim}
    bad = [a for a in axes if a < 0 or a >= len(xs)]
    if bad:
        raise ShapeError("reduce dim %s out of range for rank %d"
                         % (sorted(bad), len(xs)))
    out = tuple(1 if i in axes else d for i, d in enumerate(xs)) if keep \
        else tuple(d for i, d in enumerate(xs) if i not in axes)
    ctx.set(op.output("Out"), out, dt)


for _n in ("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
           "reduce_prod"):
    register_shape(_n)(_reduce_rule)


@register_shape("sum")
def _sum_shape(ctx, op):
    vs = op.input_list("X")
    shapes = [ctx.shape(v) for v in vs]
    out = None
    for v, s in zip(vs, shapes):
        if s is None:
            continue
        if out is None:
            out = s
            continue
        try:
            out = static_bcast_shape(out, s, -1)
        except ValueError:
            raise ShapeError(
                "sum inputs have incompatible shapes; '%s' is %s vs %s"
                % (v.name, list(s), list(out)))
    ctx.set(op.output("Out"), out, ctx.dtype(vs[0]) if vs else None)


# ---------------------------------------------------------------------------
# matmul family
# ---------------------------------------------------------------------------

@register_shape("mul")
def _mul_shape(ctx, op):
    xv, yv = op.input("X"), op.input("Y")
    xs, ys = ctx.shape(xv), ctx.shape(yv)
    if xs is None or ys is None:
        ctx.set(op.output("Out"), None, ctx.dtype(xv))
        return
    xnc = op.attr("x_num_col_dims", 1)
    ync = op.attr("y_num_col_dims", 1)
    if not (0 < xnc < max(len(xs), 1) + 1 and 0 < ync < max(len(ys), 1) + 1):
        raise ShapeError("num_col_dims (%d, %d) out of range for shapes "
                         "%s, %s" % (xnc, ync, list(xs), list(ys)))
    k1, k2 = _prod(xs[xnc:]), _prod(ys[:ync])
    if k1 != -1 and k2 != -1 and k1 != k2:
        raise ShapeError(
            "contraction dims differ: X '%s' %s flattens to [*, %d] but "
            "Y '%s' %s flattens to [%d, *]"
            % (xv.name, list(xs), k1, yv.name, list(ys), k2))
    ctx.set(op.output("Out"), tuple(xs[:xnc]) + tuple(ys[ync:]),
            ctx.dtype(xv))


@register_shape("matmul")
def _matmul_shape(ctx, op):
    xv, yv = op.input("X"), op.input("Y")
    xs, ys = ctx.shape(xv), ctx.shape(yv)
    if xs is None or ys is None or len(xs) < 2 or len(ys) < 2:
        ctx.set(op.output("Out"), None, ctx.dtype(xv))
        return
    if op.attr("transpose_X", False):
        xs = xs[:-2] + (xs[-1], xs[-2])
    if op.attr("transpose_Y", False):
        ys = ys[:-2] + (ys[-1], ys[-2])
    if xs[-1] != -1 and ys[-2] != -1 and xs[-1] != ys[-2]:
        raise ShapeError(
            "matmul contraction mismatch: X '%s' ends in %d but Y '%s' "
            "starts with %d (effective shapes %s x %s)"
            % (xv.name, xs[-1], yv.name, ys[-2], list(xs), list(ys)))
    try:
        batch = static_bcast_shape(xs[:-2], ys[:-2], -1)
    except ValueError:
        raise ShapeError("matmul batch dims %s and %s do not broadcast"
                         % (list(xs[:-2]), list(ys[:-2])))
    ctx.set(op.output("Out"), tuple(batch) + (xs[-2], ys[-1]),
            ctx.dtype(xv))


# ---------------------------------------------------------------------------
# tensor manipulation
# ---------------------------------------------------------------------------

@register_shape("concat")
def _concat_shape(ctx, op):
    vs = op.input_list("X")
    shapes = [ctx.shape(v) for v in vs]
    if any(s is None for s in shapes) or not shapes:
        ctx.set(op.output("Out"), None, ctx.dtype(vs[0]) if vs else None)
        return
    rank = len(shapes[0])
    if any(len(s) != rank for s in shapes):
        raise ShapeError("concat inputs have mixed ranks: %s"
                         % [list(s) for s in shapes])
    axis = _norm_axis(op.attr("axis", 0), rank)
    out = list(shapes[0])
    total = 0
    for s in shapes:
        for i in range(rank):
            if i == axis:
                continue
            if out[i] == -1:
                out[i] = s[i]
            elif s[i] != -1 and s[i] != out[i]:
                raise ShapeError(
                    "concat inputs disagree on non-concat dim %d: %s"
                    % (i, [list(t) for t in shapes]))
        total = -1 if (total == -1 or s[axis] == -1) else total + s[axis]
    out[axis] = total
    ctx.set(op.output("Out"), tuple(out), ctx.dtype(vs[0]))


@register_shape("split")
def _split_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    outs = op.output_list("Out")
    if xs is None:
        for v in outs:
            ctx.set(v, None, dt)
        return
    axis = _norm_axis(op.attr("axis", 0), len(xs))
    sections = op.attr("sections")
    if sections:
        if xs[axis] != -1 and sum(sections) != xs[axis]:
            raise ShapeError("split sections %s do not sum to dim %d"
                             % (sections, xs[axis]))
        sizes = sections
    else:
        num = op.attr("num", 0) or len(outs)
        if xs[axis] != -1 and xs[axis] % num != 0:
            raise ShapeError("split num %d does not divide dim %d"
                             % (num, xs[axis]))
        sizes = [(-1 if xs[axis] == -1 else xs[axis] // num)] * num
    for v, size in zip(outs, sizes):
        ctx.set(v, xs[:axis] + (size,) + xs[axis + 1:], dt)


@register_shape("reshape", "reshape2")
def _reshape_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    shape = list(op.attr("shape") or ())
    if xs is None or not shape:
        ctx.set(op.output("Out"), None, dt)
        return
    out = []
    for i, s in enumerate(shape):
        if s == 0:  # ref reshape_op: 0 copies the input dim
            if i >= len(xs):
                raise ShapeError("reshape dim %d copies input dim %d but "
                                 "input rank is %d" % (i, i, len(xs)))
            out.append(xs[i])
        else:
            out.append(int(s))
    n_in = _prod(xs)
    negs = [i for i, s in enumerate(out) if s == -1]
    if len(negs) > 1:
        raise ShapeError("reshape target %s has more than one -1" % (out,))
    if negs:
        rest = _prod([s for s in out if s != -1])
        if n_in != -1 and rest > 0:
            if n_in % rest != 0:
                raise ShapeError(
                    "cannot reshape %s (%d elements) into %s"
                    % (list(xs), n_in, out))
            out[negs[0]] = n_in // rest
    elif n_in != -1:
        if _prod(out) != n_in:
            raise ShapeError("cannot reshape %s (%d elements) into %s "
                             "(%d elements)" % (list(xs), n_in, out,
                                                _prod(out)))
    ctx.set(op.output("Out"), tuple(out), dt)


@register_shape("squeeze", "squeeze2")
def _squeeze_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    if xs is None:
        ctx.set(op.output("Out"), None, dt)
        return
    axes = op.attr("axes", [])
    if axes:
        axes = {_norm_axis(a, len(xs)) for a in axes}
        for a in axes:
            if xs[a] not in (1, -1):
                raise ShapeError("squeeze axis %d has size %d (must be 1) "
                                 "in %s" % (a, xs[a], list(xs)))
        out = tuple(d for i, d in enumerate(xs) if i not in axes)
    else:
        out = tuple(d for d in xs if d != 1)
    ctx.set(op.output("Out"), out, dt)


@register_shape("unsqueeze", "unsqueeze2")
def _unsqueeze_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    if xs is None:
        ctx.set(op.output("Out"), None, dt)
        return
    out = list(xs)
    for a in sorted(op.attr("axes")):
        out.insert(a if a >= 0 else a + len(out) + 1, 1)
    ctx.set(op.output("Out"), tuple(out), dt)


@register_shape("flatten", "flatten2")
def _flatten_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    if xs is None:
        ctx.set(op.output("Out"), None, dt)
        return
    axis = op.attr("axis", 1)
    lead = _prod(xs[:axis]) if axis > 0 else 1
    trail = _prod(xs[axis:])
    ctx.set(op.output("Out"), (lead, trail), dt)


@register_shape("transpose", "transpose2")
def _transpose_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    perm = op.attr("axis")
    if xs is None or perm is None:
        ctx.set(op.output("Out"), None, dt)
        return
    if sorted(_norm_axis(a, len(xs)) for a in perm) != list(range(len(xs))):
        raise ShapeError("transpose perm %s is not a permutation of rank %d"
                         % (perm, len(xs)))
    ctx.set(op.output("Out"),
            tuple(xs[_norm_axis(a, len(xs))] for a in perm), dt)


@register_shape("stack")
def _stack_shape(ctx, op):
    vs = op.input_list("X")
    shapes = [ctx.shape(v) for v in vs]
    if any(s is None for s in shapes) or not shapes:
        ctx.set(op.output("Out") or op.output("Y"), None,
                ctx.dtype(vs[0]) if vs else None)
        return
    base = shapes[0]
    for s in shapes[1:]:
        if len(s) != len(base) or any(
                a != -1 and b != -1 and a != b for a, b in zip(s, base)):
            raise ShapeError("stack inputs disagree: %s"
                             % [list(t) for t in shapes])
    axis = _norm_axis(op.attr("axis", 0), len(base) + 1)
    out = base[:axis] + (len(vs),) + base[axis:]
    ctx.set(op.output("Out") or op.output("Y"), out, ctx.dtype(vs[0]))


@register_shape("slice")
def _slice_shape(ctx, op):
    xs = ctx.shape(op.input("Input"))
    dt = ctx.dtype(op.input("Input"))
    if xs is None:
        ctx.set(op.output("Out"), None, dt)
        return
    out = list(xs)
    for a, s, e in zip(op.attr("axes"), op.attr("starts"), op.attr("ends")):
        a = _norm_axis(a, len(xs))
        d = xs[a]
        if d == -1:
            out[a] = (e - s) if (s >= 0 and 0 <= e < 10 ** 6) else -1
            continue
        s2 = min(d, s + d if s < 0 else s)
        e2 = min(d, e + d if e < 0 else e)
        out[a] = max(0, e2 - s2)
    ctx.set(op.output("Out"), tuple(out), dt)


@register_shape("gather")
def _gather_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    idx = ctx.shape(op.input("Index"))
    dt = ctx.dtype(op.input("X"))
    if xs is None or idx is None:
        ctx.set(op.output("Out"), None, dt)
        return
    # the lowering flattens the index to 1-D (jnp.take along axis 0)
    ctx.set(op.output("Out"), (_prod(idx),) + tuple(xs[1:]), dt)


@register_shape("expand")
def _expand_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    times = op.attr("expand_times")
    if xs is None or times is None:
        ctx.set(op.output("Out"), None, dt)
        return
    if len(times) != len(xs):
        raise ShapeError("expand_times %s rank != input rank %d"
                         % (times, len(xs)))
    ctx.set(op.output("Out"),
            tuple(-1 if d == -1 else d * t for d, t in zip(xs, times)), dt)


@register_shape("shape")
def _shape_shape(ctx, op):
    xs = ctx.shape(op.input("X") or op.input("Input"))
    ctx.set(op.output("Out"), (len(xs),) if xs is not None else None,
            np.dtype(np.int32))


# ---------------------------------------------------------------------------
# fills / random
# ---------------------------------------------------------------------------

def _attr_shape_rule(ctx, op):
    shape = op.attr("shape")
    ctx.set(op.output("Out"),
            tuple(int(s) for s in shape) if shape is not None else None,
            convert_np_dtype(op.attr("dtype", "float32")))


for _n in ("fill_constant", "uniform_random", "gaussian_random",
           "truncated_gaussian_random"):
    register_shape(_n)(_attr_shape_rule)


def _batch_size_like_rule(ctx, op):
    ref = op.input("Input") or op.input("X")
    rs = ctx.shape(ref)
    shape = op.attr("shape")
    if shape is None:
        ctx.set(op.output("Out"), None, None)
        return
    out = [int(s) for s in shape]
    in_idx = op.attr("input_dim_idx", 0)
    out_idx = op.attr("output_dim_idx", 0)
    if rs is not None and 0 <= in_idx < len(rs) and 0 <= out_idx < len(out):
        out[out_idx] = rs[in_idx]
    ctx.set(op.output("Out"), tuple(out),
            convert_np_dtype(op.attr("dtype", "float32")))


for _n in ("fill_constant_batch_size_like", "uniform_random_batch_size_like",
           "gaussian_random_batch_size_like"):
    register_shape(_n)(_batch_size_like_rule)


# ---------------------------------------------------------------------------
# embedding / indexing
# ---------------------------------------------------------------------------

def _ids_shape(ids):
    """Lowerings squeeze a trailing [.., 1] ids dim (LoD-era convention)."""
    if ids is not None and len(ids) >= 2 and ids[-1] == 1:
        return ids[:-1]
    return ids


@register_shape("lookup_table", "sharded_lookup_table")
def _lookup_table_shape(ctx, op):
    # sharded_lookup_table is the transpiled (mesh-routed) form of
    # lookup_table — identical shape contract, so transpiled programs
    # verify as first-class citizens (ISSUE 13)
    ws = ctx.shape(op.input("W"))
    ids = _ids_shape(ctx.shape(op.input("Ids")))
    if ws is None or ids is None:
        ctx.set(op.output("Out"), None, ctx.dtype(op.input("W")))
        return
    if len(ws) != 2:
        raise ShapeError("%s W '%s' must be 2-D, got %s"
                         % (op.type, op.input("W").name, list(ws)))
    ctx.set(op.output("Out"), tuple(ids) + (ws[1],),
            ctx.dtype(op.input("W")))


@register_shape("scatter")
def _scatter_shape(ctx, op):
    """Row scatter (set/add): Out has X's shape; Updates' trailing dims
    must match X's (the sparse-grad accumulation path — optimizer.py
    grad-acc and the ops/scatter.py kernel's symbolic form)."""
    xs = ctx.shape(op.input("X"))
    us = ctx.shape(op.input("Updates"))
    dt = ctx.dtype(op.input("X"))
    if xs is not None and us is not None and len(us) >= 1 \
            and len(xs) >= 1:
        xt, ut = tuple(xs[1:]), tuple(us[1:])
        if len(xt) == len(ut) and any(
                a != -1 and b != -1 and a != b for a, b in zip(xt, ut)):
            raise ShapeError(
                "scatter Updates '%s' trailing dims %s do not match X "
                "'%s' trailing dims %s"
                % (op.input("Updates").name, list(us), op.input("X").name,
                   list(xs)))
    ctx.set(op.output("Out"), xs, dt)


@register_shape("one_hot")
def _one_hot_shape(ctx, op):
    ids = _ids_shape(ctx.shape(op.input("X")))
    depth = op.attr("depth")
    if ids is None or depth is None:
        ctx.set(op.output("Out"), None, np.dtype(np.float32))
        return
    ctx.set(op.output("Out"), tuple(ids) + (int(depth),),
            np.dtype(np.float32))


# ---------------------------------------------------------------------------
# conv / pool / norm
# ---------------------------------------------------------------------------

def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * 2


def _conv_dim(size, k, pad, stride, dil):
    if size == -1:
        return -1
    eff = dil * (k - 1) + 1
    out = (size + 2 * pad - eff) // stride + 1
    if out <= 0:
        raise ShapeError(
            "conv/pool window (k=%d, pad=%d, stride=%d, dilation=%d) does "
            "not fit input dim %d" % (k, pad, stride, dil, size))
    return out


@register_shape("conv2d", "depthwise_conv2d")
def _conv2d_shape(ctx, op):
    xs = ctx.shape(op.input("Input"))
    ws = ctx.shape(op.input("Filter"))
    dt = ctx.dtype(op.input("Input"))
    if xs is None or ws is None or len(xs) != 4 or len(ws) != 4:
        ctx.set(op.output("Output"), None, dt)
        return
    strides = _pair(op.attr("strides", [1, 1]))
    pads = _pair(op.attr("paddings", [0, 0]))
    dil = _pair(op.attr("dilations", [1, 1]))
    groups = op.attr("groups", 1) or 1
    if op.type == "depthwise_conv2d" and xs[1] != -1:
        groups = xs[1]
    if xs[1] != -1 and ws[1] != -1 and ws[1] * groups != xs[1]:
        raise ShapeError(
            "in-channels mismatch: input '%s' has C=%d but filter '%s' is "
            "%s with groups=%d (needs C = %d)"
            % (op.input("Input").name, xs[1], op.input("Filter").name,
               list(ws), groups, ws[1] * groups))
    oh = _conv_dim(xs[2], ws[2], pads[0], strides[0], dil[0])
    ow = _conv_dim(xs[3], ws[3], pads[1], strides[1], dil[1])
    ctx.set(op.output("Output"), (xs[0], ws[0], oh, ow), dt)


@register_shape("pool2d")
def _pool2d_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    if xs is None or len(xs) != 4:
        ctx.set(op.output("Out"), None, dt)
        return
    ksize = _pair(op.attr("ksize"))
    if op.attr("global_pooling", False) or (
            op.attr("adaptive", False) and ksize == (1, 1)):
        ctx.set(op.output("Out"), (xs[0], xs[1], 1, 1), dt)
        return
    if op.attr("adaptive", False):
        ctx.set(op.output("Out"), (xs[0], xs[1]) + ksize, dt)
        return
    strides = _pair(op.attr("strides", [1, 1]))
    pads = _pair(op.attr("paddings", [0, 0]))
    ceil_mode = op.attr("ceil_mode", False)

    def dim(size, k, pad, stride):
        if size == -1:
            return -1
        if ceil_mode:
            return -(-(size + 2 * pad - k) // stride) + 1
        return (size + 2 * pad - k) // stride + 1

    ctx.set(op.output("Out"),
            (xs[0], xs[1], dim(xs[2], ksize[0], pads[0], strides[0]),
             dim(xs[3], ksize[1], pads[1], strides[1])), dt)


@register_shape("fused_conv2d")
def _fused_conv2d_shape(ctx, op):
    """conv2d+batch_norm(+add)(+relu) chain fused by
    ``core/epilogue_fusion.py``: conv output geometry, BN channel-vector
    checks, and the residual must match the conv output shape."""
    xs = ctx.shape(op.input("Input"))
    ws = ctx.shape(op.input("Filter"))
    dt = ctx.dtype(op.input("Input"))
    out_shape = None
    if xs is not None and ws is not None and len(xs) == 4 and len(ws) == 4:
        strides = _pair(op.attr("strides", [1, 1]))
        pads = _pair(op.attr("paddings", [0, 0]))
        dil = _pair(op.attr("dilations", [1, 1]))
        groups = op.attr("groups", 1) or 1
        if xs[1] != -1 and ws[1] != -1 and ws[1] * groups != xs[1]:
            raise ShapeError(
                "in-channels mismatch: input '%s' has C=%d but filter '%s' "
                "is %s with groups=%d (needs C = %d)"
                % (op.input("Input").name, xs[1], op.input("Filter").name,
                   list(ws), groups, ws[1] * groups))
        oh = _conv_dim(xs[2], ws[2], pads[0], strides[0], dil[0])
        ow = _conv_dim(xs[3], ws[3], pads[1], strides[1], dil[1])
        out_shape = (xs[0], ws[0], oh, ow)
    ctx.set(op.output("Y"), out_shape, dt)
    c = ws[0] if ws is not None else -1
    for slot in ("Scale", "Bias", "Mean", "Variance"):
        v = op.input(slot)
        s = ctx.shape(v)
        if v is not None and s is not None and c != -1 and tuple(s) != (c,):
            raise ShapeError(
                "fused_conv2d %s '%s' has shape %s but the channel dim is "
                "%d" % (slot, v.name, list(s), c))
    rv = op.input("Residual")
    rs = ctx.shape(rv) if rv is not None else None
    if rs is not None and out_shape is not None:
        known = all(a == b or -1 in (a, b) for a, b in zip(rs, out_shape))
        if len(rs) != 4 or not known:
            raise ShapeError(
                "fused_conv2d Residual '%s' has shape %s but the conv "
                "output is %s" % (rv.name, list(rs), list(out_shape)))
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        ctx.set(op.output(slot), (c,) if c != -1 else None, None)


@register_shape("batch_norm")
def _batch_norm_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("X"))
    ctx.set(op.output("Y"), xs, dt)
    if xs is None:
        return
    layout = op.attr("data_layout", "NCHW")
    c = xs[1 if layout == "NCHW" else -1]
    for slot in ("Scale", "Bias", "Mean", "Variance"):
        v = op.input(slot)
        s = ctx.shape(v)
        if v is not None and s is not None and c != -1 and \
                tuple(s) != (c,):
            raise ShapeError(
                "batch_norm %s '%s' has shape %s but the channel dim is %d"
                % (slot, v.name, list(s), c))
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        ctx.set(op.output(slot), (c,) if c != -1 else None, None)


@register_shape("layer_norm")
def _layer_norm_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    ctx.set(op.output("Y"), xs, ctx.dtype(op.input("X")))
    sv = op.input("Scale")
    ss = ctx.shape(sv)
    begin = op.attr("begin_norm_axis", 1)
    if xs is not None and ss is not None and len(ss) == 1:
        norm = _prod(xs[begin:])
        if norm != -1 and ss[0] != -1 and ss[0] != norm:
            raise ShapeError(
                "layer_norm Scale '%s' has %d elements but the normalized "
                "slice of %s has %d" % (sv.name, ss[0], list(xs), norm))


@register_shape("group_norm")
def _group_norm_shape(ctx, op):
    ctx.set(op.output("Y"), ctx.shape(op.input("X")),
            ctx.dtype(op.input("X")))


# ---------------------------------------------------------------------------
# losses / metrics / search
# ---------------------------------------------------------------------------

@register_shape("cross_entropy")
def _cross_entropy_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    if xs is None:
        ctx.set(op.output("Y"), None, ctx.dtype(op.input("X")))
        return
    ctx.set(op.output("Y"), tuple(xs[:-1]) + (1,), ctx.dtype(op.input("X")))


@register_shape("softmax_with_cross_entropy")
def _swce_shape(ctx, op):
    xs = ctx.shape(op.input("Logits"))
    dt = ctx.dtype(op.input("Logits"))
    if xs is None:
        ctx.set(op.output("Loss"), None, dt)
        return
    ctx.set(op.output("Loss"), tuple(xs[:-1]) + (1,), dt)
    ctx.set(op.output("Softmax"), xs, dt)


@register_shape("accuracy")
def _accuracy_shape(ctx, op):
    ctx.set(op.output("Accuracy"), (), np.dtype(np.float32))
    ctx.set(op.output("Correct"), (1,), np.dtype(np.int32))
    ctx.set(op.output("Total"), (1,), np.dtype(np.int32))


@register_shape("top_k")
def _top_k_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    k = int(op.attr("k", 1))
    if xs is None:
        return
    if xs[-1] != -1 and k > xs[-1]:
        raise ShapeError("top_k k=%d exceeds last dim of %s" % (k, list(xs)))
    out = tuple(xs[:-1]) + (k,)
    ctx.set(op.output("Out"), out, ctx.dtype(op.input("X")))
    # int32: the lowering emits int32 indices (x64 is off — see math_ops)
    ctx.set(op.output("Indices"), out, np.dtype(np.int32))


@register_shape("argmax", "argmin")
def _arg_shape(ctx, op):
    xs = ctx.shape(op.input("X"))
    if xs is None:
        ctx.set(op.output("Out"), None, np.dtype(np.int32))
        return
    axis = _norm_axis(op.attr("axis", -1), len(xs))
    ctx.set(op.output("Out"), xs[:axis] + xs[axis + 1:], np.dtype(np.int32))


# ---------------------------------------------------------------------------
# incremental decode (KV-cache step programs — serving/decode_batcher.py)
# ---------------------------------------------------------------------------

@register_shape("kv_cache_write")
def _kv_cache_write_shape(ctx, op):
    cs = ctx.shape(op.input("Cache"))
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("Cache"))
    if cs is not None and xs is not None:
        if len(xs) != len(cs) - 1:
            raise ShapeError(
                "kv_cache_write X '%s' %s must be Cache '%s' %s minus the "
                "capacity axis" % (op.input("X").name, list(xs),
                                   op.input("Cache").name, list(cs)))
        for a, b in zip((xs[0],) + tuple(xs[1:]),
                        (cs[0],) + tuple(cs[2:])):
            if a != -1 and b != -1 and a != b:
                raise ShapeError(
                    "kv_cache_write X '%s' %s does not slot into Cache "
                    "'%s' %s" % (op.input("X").name, list(xs),
                                 op.input("Cache").name, list(cs)))
    ctx.set(op.output("Out"), cs, dt)


def _cached_attention_out(ctx, op, q_rank):
    """Checks and output shape of ``cached_attention`` (Q [B, H*Dk]) and
    ``cached_attention_chunk`` (Q [B, K, H*Dk]): CacheK [B, C, Hkv*Dk],
    CacheV [B, C, Hkv*Dv] of a width of its own, Out [.., H*Dv]; with
    ``ring`` in a chunk, NewK / NewV [B, K, ..] of the caches' tails and a
    window the ring can hold."""
    kind = op.type
    qs = ctx.shape(op.input("Q"))
    ks = ctx.shape(op.input("CacheK"))
    vs = ctx.shape(op.input("CacheV"))
    dt = ctx.dtype(op.input("Q"))
    h = int(op.attr("num_heads", 1))
    kv = int(op.attr("num_kv_heads", 0) or h)
    if kv < 1 or h % kv:
        raise ShapeError("%s num_heads=%d is not a multiple of "
                         "num_kv_heads=%d" % (kind, h, kv))
    if qs is not None and q_rank == 3 and len(qs) != 3:
        raise ShapeError("%s Q '%s' must be [B, K, H*D], got %s" % (
            kind, op.input("Q").name, list(qs)))
    for slot, shape in (("CacheK", ks), ("CacheV", vs)):
        if shape is None:
            continue
        if len(shape) != 3:
            raise ShapeError("%s %s '%s' must be [B, C, H*D], got %s" % (
                kind, slot, op.input(slot).name, list(shape)))
        if shape[-1] != -1 and shape[-1] % kv != 0:
            raise ShapeError(
                "%s %s '%s' last dim %d is not divisible by num_heads=%d"
                % (kind, slot, op.input(slot).name, shape[-1], kv))
    if qs is not None and ks is not None and qs[-1] != -1 \
            and ks[-1] != -1 and qs[-1] * kv != ks[-1] * h:
        raise ShapeError(
            "%s Q '%s' feature dim %d != CacheK '%s' dim %d%s" % (
                kind, op.input("Q").name, qs[-1], op.input("CacheK").name,
                ks[-1], "" if kv == h else " x %d/%d heads" % (h, kv)))
    sink = op.input("Sink")
    if sink is not None and ctx.shape(sink) is not None \
            and tuple(ctx.shape(sink)) != (h,):
        raise ShapeError("%s Sink '%s' must be [num_heads=%d], got %s" % (
            kind, sink.name, h, list(ctx.shape(sink))))
    if op.attr("ring", False) and q_rank == 3:
        window = int(op.attr("window", 0))
        if ks is not None and ks[1] != -1 and not 1 <= window <= ks[1]:
            raise ShapeError(
                "%s CacheK '%s' is a ring of %d positions: it serves a "
                "window of 1 to %d, not %d" % (
                    kind, op.input("CacheK").name, ks[1], ks[1], window))
        for slot, cache in (("NewK", ks), ("NewV", vs)):
            new = op.input(slot)
            if new is None:
                raise ShapeError("%s with ring needs %s: the chunk's own "
                                 "rows, which the ring does not hold yet"
                                 % (kind, slot))
            ns = ctx.shape(new)
            if ns is not None and cache is not None and (
                    len(ns) != 3 or (ns[-1] != -1 and cache[-1] != -1
                                     and ns[-1] != cache[-1])):
                raise ShapeError("%s %s '%s' %s does not slot into its "
                                 "cache %s" % (kind, slot, new.name,
                                               list(ns), list(cache)))
    count = op.output("Count")
    if count is not None:
        ctx.set(count, (1,), np.dtype(np.int32))
    if vs is None or qs is None:
        ctx.set(op.output("Out"), qs, dt)
        return
    ctx.set(op.output("Out"), tuple(qs[:-1]) + (
        vs[-1] if vs[-1] == -1 else vs[-1] // kv * h,), dt)


@register_shape("cached_attention")
def _cached_attention_shape(ctx, op):
    _cached_attention_out(ctx, op, 2)


@register_shape("kv_cache_write_chunk")
def _kv_cache_write_chunk_shape(ctx, op):
    cs = ctx.shape(op.input("Cache"))
    xs = ctx.shape(op.input("X"))
    dt = ctx.dtype(op.input("Cache"))
    if cs is not None and xs is not None:
        if len(xs) != len(cs):
            raise ShapeError(
                "kv_cache_write_chunk X '%s' %s must be [B, K, ...] with "
                "the same rank as Cache '%s' %s (K rows scatter into the "
                "capacity axis)" % (op.input("X").name, list(xs),
                                    op.input("Cache").name, list(cs)))
        for a, b in zip((xs[0],) + tuple(xs[2:]),
                        (cs[0],) + tuple(cs[2:])):
            if a != -1 and b != -1 and a != b:
                raise ShapeError(
                    "kv_cache_write_chunk X '%s' %s does not slot into "
                    "Cache '%s' %s" % (op.input("X").name, list(xs),
                                       op.input("Cache").name, list(cs)))
    ctx.set(op.output("Out"), cs, dt)


@register_shape("cached_attention_chunk")
def _cached_attention_chunk_shape(ctx, op):
    _cached_attention_out(ctx, op, 3)


def _index_inputs(ctx, op, rank):
    """(q, w, cache) shapes of an indexer op, checked; any may be None."""
    heads = int(op.attr("num_heads"))
    qs, ws = ctx.shape(op.input("Q")), ctx.shape(op.input("W"))
    ks = ctx.shape(op.input("CacheK"))
    for var, shape in ((op.input("Q"), qs), (op.input("W"), ws)):
        if shape is not None and len(shape) != rank:
            raise ShapeError("%s '%s' must have rank %d, got %s" % (
                op.type, var.name, rank, list(shape)))
    if ks is not None and len(ks) != 3:
        raise ShapeError("%s CacheK '%s' must be [B, C, D], got %s" % (
            op.type, op.input("CacheK").name, list(ks)))
    if qs is not None and ks is not None and -1 not in (qs[-1], ks[-1]) \
            and qs[-1] != heads * ks[-1]:
        raise ShapeError("%s Q '%s' last dim %d is not %d heads of CacheK's "
                         "%d" % (op.type, op.input("Q").name, qs[-1], heads,
                                 ks[-1]))
    if ws is not None and ws[-1] not in (-1, heads):
        raise ShapeError("%s W '%s' last dim %d is not a weight for each of "
                         "%d heads" % (op.type, op.input("W").name, ws[-1],
                                       heads))
    return qs, ws, ks


@register_shape("sparse_index")
def _sparse_index_shape(ctx, op):
    qs, _, ks = _index_inputs(ctx, op, 2)
    ctx.set(op.output("Count"), (2,), "int32")
    if qs is None or ks is None:
        ctx.set(op.output("Index"), None, "int32")
        return
    top_k = int(op.attr("top_k"))
    ctx.set(op.output("Index"),
            (qs[0], top_k if ks[1] == -1 else min(top_k, ks[1])), "int32")


@register_shape("sparse_index_chunk")
def _sparse_index_chunk_shape(ctx, op):
    qs, _, ks = _index_inputs(ctx, op, 3)
    ctx.set(op.output("Count"), (2,), "int32")
    if qs is None or ks is None:
        ctx.set(op.output("Mask"), None, "bool")
        return
    ctx.set(op.output("Mask"), (qs[0], qs[1], ks[1]), "bool")


def _latent_attention_shape(ctx, op, rank):
    heads, nope = int(op.attr("num_heads")), int(op.attr("nope_dim"))
    v_dim = int(op.attr("v_dim"))
    qs, cs = ctx.shape(op.input("Q")), ctx.shape(op.input("Cache"))
    bs = ctx.shape(op.input("KvB"))
    if qs is not None and len(qs) != rank:
        raise ShapeError("%s Q '%s' must have rank %d, got %s" % (
            op.type, op.input("Q").name, rank, list(qs)))
    if cs is not None and len(cs) != 3:
        raise ShapeError("%s Cache '%s' must be [B, C, R+P], got %s" % (
            op.type, op.input("Cache").name, list(cs)))
    if bs is not None and (len(bs) != 2
                           or bs[1] != heads * (nope + v_dim)):
        raise ShapeError("%s KvB '%s' %s is not [R, %d heads x (%d + %d)]"
                         % (op.type, op.input("KvB").name, list(bs), heads,
                            nope, v_dim))
    if qs is not None and cs is not None and bs is not None \
            and -1 not in (qs[-1], cs[-1]):
        rope = cs[-1] - bs[0]
        if rope < 0 or qs[-1] != heads * (nope + rope):
            raise ShapeError(
                "%s: a cached row of %d holds a latent of %d and a rotary "
                "key of %d, so Q '%s' takes %d heads of %d + %d, not a last "
                "dim of %d" % (op.type, cs[-1], bs[0], rope,
                               op.input("Q").name, heads, nope, rope,
                               qs[-1]))
    ctx.set(op.output("Out"),
            None if qs is None else tuple(qs[:-1]) + (heads * v_dim,),
            ctx.dtype(op.input("Q")))


@register_shape("latent_attention")
def _latent_attention_step_shape(ctx, op):
    _latent_attention_shape(ctx, op, 2)


@register_shape("latent_attention_dense")
def _latent_attention_dense_shape(ctx, op):
    _latent_attention_shape(ctx, op, 3)


@register_shape("last_live_lane")
def _last_live_lane_shape(ctx, op):
    xs, ps = ctx.shape(op.input("X")), ctx.shape(op.input("Pos"))
    if xs is not None and len(xs) != 3:
        raise ShapeError("last_live_lane X '%s' must be [B, K, D], got %s"
                         % (op.input("X").name, list(xs)))
    if ps is not None and len(ps) != 2:
        raise ShapeError("last_live_lane Pos '%s' must be [B, K], got %s"
                         % (op.input("Pos").name, list(ps)))
    ctx.set(op.output("Out"),
            None if xs is None else (xs[0], xs[2]), ctx.dtype(op.input("X")))


@register_shape("self_draft_accept")
def _self_draft_accept_shape(ctx, op):
    rows = None
    for name in ("Tok", "Greedy", "Draft"):
        shape = ctx.shape(op.input(name))
        if shape is None:
            continue
        if len(shape) != 2 or shape[1] not in (-1, 2):
            raise ShapeError("self_draft_accept %s '%s' must be [B, 2], got "
                             "%s" % (name, op.input(name).name, list(shape)))
        rows = shape[0]
    ctx.set(op.output("Yield"), None if rows is None else (rows, 4), "int32")
    ctx.set(op.output("Judged"), (2,), "int32")
    for out, like in (("NextTok", "Tok"), ("NextPos", "Pos")):
        ctx.set(op.output(out), None if rows is None else (rows, 2),
                ctx.dtype(op.input(like)))


@register_shape("latent_attention_chunk")
def _latent_attention_chunk_shape(ctx, op):
    _latent_attention_shape(ctx, op, 3)
    if op.input("Mask") is None:
        return
    ms, qs = ctx.shape(op.input("Mask")), ctx.shape(op.input("Q"))
    cs = ctx.shape(op.input("Cache"))
    if ms is not None and qs is not None and cs is not None \
            and tuple(ms[1:]) != (qs[1], cs[1]) and -1 not in ms[1:] \
            and -1 not in (qs[1], cs[1]):
        raise ShapeError("latent_attention_chunk Mask '%s' %s is not "
                         "[B, %d lanes, %d positions]" % (
                             op.input("Mask").name, list(ms), qs[1], cs[1]))


def _eva_cache_shapes(ctx, op):
    """The four caches of an EVA op, checked: WinK, WinV [B, W, H*D] and
    SumK, SumV [B, L, H*D] of one tail, ``W`` a whole number of chunks.
    Returns the window caches' shape, or None."""
    kind = op.type
    h, chunk = int(op.attr("num_heads", 1)), int(op.attr("chunk", 1))
    shapes = {slot: ctx.shape(op.input(slot))
              for slot in ("WinK", "WinV", "SumK", "SumV")}
    tail = None
    for slot, shape in shapes.items():
        if shape is None:
            continue
        if len(shape) != 3:
            raise ShapeError("%s %s '%s' must be [B, C, H*D], got %s" % (
                kind, slot, op.input(slot).name, list(shape)))
        if shape[-1] != -1:
            if shape[-1] % h or (tail is not None and shape[-1] != tail):
                raise ShapeError(
                    "%s %s '%s' rows of %d: the four caches hold rows of "
                    "one width, %d heads side by side" % (
                        kind, slot, op.input(slot).name, shape[-1], h))
            tail = shape[-1]
    win = shapes["WinK"]
    if win is not None and win[1] != -1:
        window = int(op.attr("window", win[1]))
        if chunk < 1 or win[1] % chunk or win[1] != window:
            raise ShapeError(
                "%s WinK '%s' holds %d positions a row: the window is %d, a "
                "whole number of chunks of %d" % (
                    kind, op.input("WinK").name, win[1], window, chunk))
    for slot in ("Phi", "Mu", "NewK", "NewV", "Q"):
        var = op.input(slot)
        shape = None if var is None else ctx.shape(var)
        if shape is not None and tail is not None and shape[-1] not in (
                -1, tail):
            raise ShapeError("%s %s '%s' %s is not %d wide as the caches' "
                             "rows are" % (kind, slot, var.name, list(shape),
                                           tail))
    return win


@register_shape("eva_summary", "eva_summary_chunk")
def _eva_summary_shape(ctx, op):
    _eva_cache_shapes(ctx, op)
    for slot in ("SumK", "SumV"):
        ctx.set(op.output(slot + "Out"), ctx.shape(op.input(slot)),
                ctx.dtype(op.input(slot)))


@register_shape("eva_attention", "eva_attention_chunk")
def _eva_attention_shape(ctx, op):
    win = _eva_cache_shapes(ctx, op)
    qs = ctx.shape(op.input("Q"))
    if op.type == "eva_attention_chunk" and qs is not None:
        if len(qs) != 3:
            raise ShapeError("eva_attention_chunk Q '%s' must be [B, K, "
                             "H*D], got %s" % (op.input("Q").name, list(qs)))
        if win is not None and -1 not in (qs[1], win[1]) and qs[1] > win[1]:
            raise ShapeError(
                "eva_attention_chunk Q '%s': %d lanes cross more than one "
                "multiple of the window of %d" % (op.input("Q").name, qs[1],
                                                  win[1]))
    if op.output("Count") is not None:
        ctx.set(op.output("Count"), (3,), np.dtype(np.int32))
    ctx.set(op.output("Out"), qs, ctx.dtype(op.input("Q")))


# ---------------------------------------------------------------------------
# the hybrid blocks (models/qwen3_next.py, models/nemotron_h.py)
# ---------------------------------------------------------------------------

@register_shape("rms_norm")
def _rms_norm_shape(ctx, op):
    xv = op.input("X")
    xs = ctx.shape(xv)
    ctx.set(op.output("Y"), xs, ctx.dtype(xv))
    dim = int(op.attr("norm_dim", 0))
    ss = ctx.shape(op.input("Scale"))
    if xs is None:
        return
    last = xs[-1]
    if dim and last != -1 and last % dim:
        raise ShapeError("rms_norm norm_dim %d does not divide the last "
                         "axis of %s" % (dim, list(xs)))
    want = dim or last
    if ss is not None and want != -1 and tuple(ss) not in ((want,), (last,)):
        raise ShapeError("rms_norm Scale has shape %s, the normalised "
                         "slice has %d elements" % (list(ss), want))


@register_shape("rotary")
def _rotary_shape(ctx, op):
    xv = op.input("X")
    xs = ctx.shape(xv)
    ctx.set(op.output("Out"), xs, ctx.dtype(xv))
    if xs is None or len(xs) not in (2, 3) or xs[-1] == -1:
        return
    heads, rot = int(op.attr("num_heads")), int(op.attr("rotary_dim"))
    off = int(op.attr("offset", 0))
    if xs[-1] % heads or rot % 2 or off + rot > xs[-1] // heads:
        raise ShapeError("rotary: %d heads with %d rotary dims from dim %d "
                         "on do not fit a last axis of %d"
                         % (heads, rot, off, xs[-1]))
    ps = ctx.shape(op.input("Pos"))
    if len(xs) == 2 and op.input("Pos") is None:
        raise ShapeError("rotary: X %s has no axis of positions and no Pos "
                         "is fed" % (list(xs),))
    if ps is not None and len(ps) != len(xs) - 1:
        raise ShapeError("rotary: Pos %s does not give a position to each "
                         "row of X %s" % (list(ps), list(xs)))


@register_shape("causal_conv1d")
def _causal_conv1d_shape(ctx, op):
    xv = op.input("X")
    xs, ws = ctx.shape(xv), ctx.shape(op.input("Filter"))
    ctx.set(op.output("Out"), xs, ctx.dtype(xv))
    if xs is not None and ws is not None and xs[-1] != -1 \
            and ws[0] != xs[-1]:
        raise ShapeError("causal_conv1d Filter %s does not match %d "
                         "channels" % (list(ws), xs[-1]))
    bs = ctx.shape(op.input("Bias"))
    if xs is not None and bs is not None and xs[-1] != -1 \
            and tuple(bs) != (xs[-1],):
        raise ShapeError("causal_conv1d Bias %s does not match %d "
                         "channels" % (list(bs), xs[-1]))


@register_shape("gated_delta_rule")
def _gated_delta_rule_shape(ctx, op):
    vv = op.input("V")
    qs, ks, vs = (ctx.shape(op.input(n)) for n in ("Q", "K", "V"))
    ctx.set(op.output("Out"), vs, ctx.dtype(vv))
    if qs is None or ks is None or vs is None:
        return
    hk, hv = int(op.attr("num_k_heads")), int(op.attr("num_v_heads"))
    if tuple(qs) != tuple(ks):
        raise ShapeError("gated_delta_rule Q %s and K %s differ"
                         % (list(qs), list(ks)))
    if hv % hk or (qs[-1] != -1 and qs[-1] % hk) \
            or (vs[-1] != -1 and vs[-1] % hv):
        raise ShapeError("gated_delta_rule: %d key and %d value heads do "
                         "not fit Q %s, V %s" % (hk, hv, list(qs), list(vs)))
    for slot in ("A", "B"):
        s = ctx.shape(op.input(slot))
        if s is not None and s[-1] != -1 and s[-1] != hv:
            raise ShapeError("gated_delta_rule %s has %d columns for %d "
                             "value heads" % (slot, s[-1], hv))


@register_shape("mamba2_ssd")
def _mamba2_ssd_shape(ctx, op):
    xv = op.input("X")
    xs = ctx.shape(xv)
    ctx.set(op.output("Out"), xs, ctx.dtype(xv))
    if xs is None or xs[-1] == -1:
        return
    heads, groups = int(op.attr("num_heads")), int(op.attr("num_groups"))
    if heads % groups or xs[-1] % heads:
        raise ShapeError("mamba2_ssd: %d heads in %d groups do not fit X %s"
                         % (heads, groups, list(xs)))
    bs, cs = ctx.shape(op.input("Bm")), ctx.shape(op.input("Cm"))
    if bs is not None and cs is not None and (
            tuple(bs) != tuple(cs) or (bs[-1] != -1 and bs[-1] % groups)):
        raise ShapeError("mamba2_ssd Bm %s and Cm %s do not fit %d groups"
                         % (list(bs), list(cs), groups))
    ds = ctx.shape(op.input("Dt"))
    if ds is not None and ds[-1] != -1 and ds[-1] != heads:
        raise ShapeError("mamba2_ssd Dt has %d columns for %d heads"
                         % (ds[-1], heads))
    for slot in ("ALog", "DtBias", "D"):
        ps = ctx.shape(op.input(slot))
        if ps is not None and tuple(ps) != (heads,):
            raise ShapeError("mamba2_ssd %s %s is not one value a head of "
                             "%d" % (slot, list(ps), heads))


@register_shape("routed_experts")
def _routed_experts_shape(ctx, op):
    xv = op.input("X")
    xs = ctx.shape(xv)
    latent = op.input("ExpertX")
    es = xs if latent is None else ctx.shape(latent)
    ctx.set(op.output("Out"), es, ctx.dtype(xv))
    if op.output("SharedOut") is not None:
        ctx.set(op.output("SharedOut"), xs, ctx.dtype(xv))
    us = ctx.shape(op.input("ExpertUp"))
    rs = ctx.shape(op.input("Router"))
    if us is not None:
        ctx.set(op.output("Load"), (us[0],), np.dtype("int32"))
    if op.output("Rows") is not None:
        ctx.set(op.output("Rows"), (2,), np.dtype("int32"))
    form = op.attr("form", "swiglu")
    if (op.input("ExpertGate") is None) != (form == "relu2"):
        raise ShapeError("routed_experts: form %r takes %s ExpertGate"
                         % (form, "no" if form == "relu2" else "an"))
    if op.input("SharedUp") is not None \
            and (latent is None) != (op.output("SharedOut") is None):
        raise ShapeError("routed_experts: the shared expert has its own "
                         "output exactly where the experts read ExpertX")
    if xs is None or es is None or us is None or rs is None:
        return
    if (xs[-1] != -1 and rs[0] != xs[-1]) \
            or (es[-1] != -1 and us[2] != es[-1]):
        raise ShapeError("routed_experts: X %s and the experts' input %s "
                         "against Router %s and ExpertUp %s"
                         % (list(xs), list(es), list(rs), list(us)))
    first, top_k = int(op.attr("first_expert", 0)), int(op.attr("top_k"))
    if first < 0 or first + us[0] > rs[1] or top_k > rs[1]:
        raise ShapeError("routed_experts holds experts [%d, %d) and takes "
                         "the top %d of a router over %d"
                         % (first, first + us[0], top_k, rs[1]))
    bs = ctx.shape(op.input("RouterBias"))
    if bs is not None and tuple(bs) != (rs[1],):
        raise ShapeError("routed_experts RouterBias %s against a router "
                         "over %d" % (list(bs), rs[1]))
