"""Elementwise / activation / reduction / matmul ops.

Reference: ``paddle/fluid/operators/`` root + ``elementwise/`` +
``reduce_ops/`` + ``activation_op.cc``. All lower to jnp/lax so XLA fuses
them into neighboring matmuls (the TPU replacement for the reference's
``fused_elemwise_activation`` op and jit/ codegen kernels).
"""

import jax
import jax.numpy as jnp

from ..op_registry import register, get, get_list, put, bcast_y

# ---------------- elementwise binary family ----------------

_BINOPS = {
    "elementwise_add": jnp.add,
    "elementwise_sub": jnp.subtract,
    "elementwise_mul": jnp.multiply,
    "elementwise_div": jnp.divide,
    "elementwise_max": jnp.maximum,
    "elementwise_min": jnp.minimum,
    "elementwise_pow": jnp.power,
    "elementwise_mod": jnp.mod,
    "elementwise_floordiv": jnp.floor_divide,
}


def _make_binop(name, fn, harmonize=True):
    # harmonize=False for comparisons: demoting an f32 operand to bf16
    # would change mask RESULTS at rounding boundaries, and bool outputs
    # gain no bf16-residency benefit.
    @register(name)
    def _impl(env, op, fn=fn):
        x = get(env, op.input("X"))
        y = get(env, op.input("Y"))
        y = bcast_y(x, y, op.attr("axis", -1))
        if harmonize:
            from ..op_registry import amp_harmonize
            x, y = amp_harmonize(x, y)
        put(env, op.output("Out"), fn(x, y))


# mod/floordiv produce discrete outputs that can flip at bf16 rounding
# boundaries (same rationale as comparisons) — keep them out of harmonize
for _n, _f in _BINOPS.items():
    _make_binop(_n, _f,
                harmonize=_n not in ("elementwise_mod",
                                     "elementwise_floordiv"))

_CMPOPS = {
    "less_than": jnp.less,
    "less_equal": jnp.less_equal,
    "greater_than": jnp.greater,
    "greater_equal": jnp.greater_equal,
    "equal": jnp.equal,
    "not_equal": jnp.not_equal,
}

for _n, _f in _CMPOPS.items():
    _make_binop(_n, _f, harmonize=False)


@register("logical_and")
def _logical_and(env, op):
    put(env, op.output("Out"),
        jnp.logical_and(get(env, op.input("X")), get(env, op.input("Y"))))


@register("logical_or")
def _logical_or(env, op):
    put(env, op.output("Out"),
        jnp.logical_or(get(env, op.input("X")), get(env, op.input("Y"))))


@register("logical_xor")
def _logical_xor(env, op):
    put(env, op.output("Out"),
        jnp.logical_xor(get(env, op.input("X")), get(env, op.input("Y"))))


@register("logical_not")
def _logical_not(env, op):
    put(env, op.output("Out"), jnp.logical_not(get(env, op.input("X"))))


# ---------------- activations (ref activation_op.cc) ----------------

def _unary(name, fn):
    @register(name)
    def _impl(env, op, fn=fn):
        put(env, op.output("Out"), fn(get(env, op.input("X"))))


_UNARY = {
    "sigmoid": jax.nn.sigmoid,
    "logsigmoid": jax.nn.log_sigmoid,
    "exp": jnp.exp,
    "tanh": jnp.tanh,
    "tanh_shrink": lambda x: x - jnp.tanh(x),
    "sqrt": jnp.sqrt,
    "rsqrt": jax.lax.rsqrt,
    "abs": jnp.abs,
    "ceil": jnp.ceil,
    "floor": jnp.floor,
    "round": jnp.round,
    "cos": jnp.cos,
    "sin": jnp.sin,
    "reciprocal": lambda x: 1.0 / x,
    "log": jnp.log,
    "square": jnp.square,
    "softplus": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "relu": jax.nn.relu,
    "sign": jnp.sign,
    "erf": jax.scipy.special.erf,
}

for _n, _f in _UNARY.items():
    _unary(_n, _f)


@register("relu6")
def _relu6(env, op):
    t = op.attr("threshold", 6.0)
    put(env, op.output("Out"), jnp.clip(get(env, op.input("X")), 0.0, t))


@register("leaky_relu")
def _leaky_relu(env, op):
    a = op.attr("alpha", 0.02)
    x = get(env, op.input("X"))
    put(env, op.output("Out"), jnp.where(x > 0, x, a * x))


@register("elu")
def _elu(env, op):
    a = op.attr("alpha", 1.0)
    x = get(env, op.input("X"))
    put(env, op.output("Out"), jnp.where(x > 0, x, a * (jnp.exp(x) - 1)))


@register("prelu")
def _prelu(env, op):
    x = get(env, op.input("X"))
    alpha = get(env, op.input("Alpha"))
    mode = op.attr("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    put(env, op.output("Out"), jnp.where(x > 0, x, alpha * x))


@register("gelu")
def _gelu(env, op):
    from ..op_registry import amp_enabled
    # tanh-approx under AMP (the standard TPU BERT choice): erf lowers to
    # a long polynomial and its vjp chain re-fuses into dW matmul
    # operands; exact erf stays the default for f32 runs
    approx = op.attr("approximate", amp_enabled())
    put(env, op.output("Out"),
        jax.nn.gelu(get(env, op.input("X")), approximate=approx))


@register("brelu")
def _brelu(env, op):
    put(env, op.output("Out"),
        jnp.clip(get(env, op.input("X")), op.attr("t_min", 0.0), op.attr("t_max", 24.0)))


@register("stanh")
def _stanh(env, op):
    a = op.attr("scale_a", 0.67)
    b = op.attr("scale_b", 1.7159)
    put(env, op.output("Out"), b * jnp.tanh(a * get(env, op.input("X"))))


@register("hard_sigmoid")
def _hard_sigmoid(env, op):
    slope = op.attr("slope", 0.2)
    offset = op.attr("offset", 0.5)
    put(env, op.output("Out"),
        jnp.clip(slope * get(env, op.input("X")) + offset, 0.0, 1.0))


@register("hard_shrink")
def _hard_shrink(env, op):
    t = op.attr("threshold", 0.5)
    x = get(env, op.input("X"))
    put(env, op.output("Out"), jnp.where(jnp.abs(x) > t, x, 0.0))


@register("soft_shrink")
def _soft_shrink(env, op):
    lam = op.attr("lambda", 0.5)
    x = get(env, op.input("X"))
    put(env, op.output("Out"),
        jnp.where(x > lam, x - lam, jnp.where(x < -lam, x + lam, 0.0)))


@register("thresholded_relu")
def _thresholded_relu(env, op):
    t = op.attr("threshold", 1.0)
    x = get(env, op.input("X"))
    put(env, op.output("Out"), jnp.where(x > t, x, 0.0))


@register("swish")
def _swish(env, op):
    b = op.attr("beta", 1.0)
    x = get(env, op.input("X"))
    put(env, op.output("Out"), x * jax.nn.sigmoid(b * x))


@register("pow")
def _pow(env, op):
    put(env, op.output("Out"),
        jnp.power(get(env, op.input("X")), op.attr("factor", 1.0)))


@register("maxout")
def _maxout(env, op):
    x = get(env, op.input("X"))  # NCHW
    groups = op.attr("groups")
    n, c, h, w = x.shape
    put(env, op.output("Out"),
        x.reshape(n, c // groups, groups, h, w).max(axis=2))


# ---------------- scale / clip ----------------

@register("scale")
def _scale(env, op):
    x = get(env, op.input("X"))
    s = op.attr("scale", 1.0)
    b = op.attr("bias", 0.0)
    if op.attr("bias_after_scale", True):
        out = x * s + b
    else:
        out = (x + b) * s
    put(env, op.output("Out"), out)


@register("optimization_barrier")
def _optimization_barrier(env, op):
    """X as it is computed (``jax.lax.optimization_barrier``): the compiler
    fuses nothing across it and carries no layout through it. For a
    product that the ops after it view a head at a time, where the heads
    are no multiple of 128 wide: left alone, the TPU compiler lays the
    WEIGHT out for that view and copies it anew in every run."""
    put(env, op.output("Out"),
        jax.lax.optimization_barrier(get(env, op.input("X"))))


@register("clip")
def _clip(env, op):
    put(env, op.output("Out"),
        jnp.clip(get(env, op.input("X")), op.attr("min"), op.attr("max")))


@register("clip_by_norm")
def _clip_by_norm(env, op):
    x = get(env, op.input("X"))
    max_norm = op.attr("max_norm")
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    put(env, op.output("Out"),
        jnp.where(norm > max_norm, x * (max_norm / jnp.maximum(norm, 1e-12)), x))


@register("squared_l2_norm")
def _squared_l2_norm(env, op):
    x = get(env, op.input("X"))
    put(env, op.output("Out"), jnp.sum(jnp.square(x)).reshape(()))


@register("norm")
def _norm(env, op):
    # l2_normalize along axis (ref norm_op.cc)
    x = get(env, op.input("X"))
    axis = op.attr("axis", -1)
    eps = op.attr("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    put(env, op.output("Out"), x / norm)
    put(env, op.output("Norm"), norm)


# ---------------- matmul family ----------------

@register("mul")
def _mul(env, op):
    """Reference ``mul_op``: flatten x at x_num_col_dims, y at y_num_col_dims,
    then 2-D matmul (``operators/mul_op.cc``). Lowers to a single MXU matmul.
    Attr ``precision`` (``"highest"``; absent by default): the product of
    float32 operands in as many passes as keep it exact, where the MXU would
    round them to bfloat16; operands of different types meet in the wider.
    """
    x = get(env, op.input("X"))
    y = get(env, op.input("Y"))
    xnc = op.attr("x_num_col_dims", 1)
    ync = op.attr("y_num_col_dims", 1)
    import functools as _ft
    import operator as _operator

    def _prod(dims):
        # NOT np.prod: symbolic batch dims (jax.export shape polymorphism)
        # must stay symbolic through the reshape
        return _ft.reduce(_operator.mul, dims, 1)

    xs, ys = x.shape, y.shape
    x2 = x.reshape((_prod(xs[:xnc]), _prod(xs[xnc:])))
    y2 = y.reshape((_prod(ys[:ync]), _prod(ys[ync:])))
    from ..op_registry import mxu_cast
    x2, y2 = mxu_cast(x2, y2)
    out = jnp.matmul(x2, y2, precision=op.attr("precision", None))
    out_shape = xs[:xnc] + ys[ync:]
    put(env, op.output("Out"), out.reshape(out_shape))


@register("matmul")
def _matmul(env, op):
    x = get(env, op.input("X"))
    y = get(env, op.input("Y"))
    if op.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2)
    if op.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2)
    from ..op_registry import mxu_cast
    x, y = mxu_cast(x, y)
    out = jnp.matmul(x, y)
    alpha = op.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    put(env, op.output("Out"), out)


@register("sum")
def _sum(env, op):
    xs = get_list(env, op, "X")
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    put(env, op.output("Out"), out)


@register("mean")
def _mean(env, op):
    put(env, op.output("Out"), jnp.mean(get(env, op.input("X"))).reshape(()))


# ---------------- reductions (ref reduce_ops/) ----------------

def _reduce(name, fn):
    @register(name)
    def _impl(env, op, fn=fn):
        x = get(env, op.input("X"))
        dim = op.attr("dim", [0])
        keep = op.attr("keep_dim", False)
        if op.attr("reduce_all", False) or dim is None:
            axis = None
        else:
            axis = tuple(d if d >= 0 else d + x.ndim for d in dim)
        out = fn(x, axis=axis, keepdims=keep)
        if axis is None and not keep:
            out = out.reshape(())
        put(env, op.output("Out"), out)


for _n, _f in {
    "reduce_sum": jnp.sum,
    "reduce_mean": jnp.mean,
    "reduce_max": jnp.max,
    "reduce_min": jnp.min,
    "reduce_prod": jnp.prod,
}.items():
    _reduce(_n, _f)


@register("cumsum")
def _cumsum(env, op):
    x = get(env, op.input("X"))
    axis = op.attr("axis", -1)
    if op.attr("flatten", False):
        x = x.reshape(-1)
        axis = 0
    axis = axis % x.ndim
    reverse = op.attr("reverse", False)
    # reverse = flip, cumsum, flip-back; exclusive shifts within the
    # (possibly flipped) frame so the combination composes correctly
    xx = jnp.flip(x, axis) if reverse else x
    out = jnp.cumsum(xx, axis=axis)
    if op.attr("exclusive", False):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (1, 0)
        sl = tuple(slice(0, -1) if i == axis else slice(None)
                   for i in range(x.ndim))
        out = jnp.pad(out, pad)[sl]
    if reverse:
        out = jnp.flip(out, axis)
    put(env, op.output("Out"), out)


# ---------------- search / sort ----------------

# index outputs are int32, not int64: without x64 mode jax truncates an
# explicit int64 request to int32 anyway, emitting a UserWarning per trace
# — request the real dtype
@register("argmax")
def _argmax(env, op):
    put(env, op.output("Out"),
        jnp.argmax(get(env, op.input("X")), axis=op.attr("axis", -1)).astype(jnp.int32))


@register("argmin")
def _argmin(env, op):
    put(env, op.output("Out"),
        jnp.argmin(get(env, op.input("X")), axis=op.attr("axis", -1)).astype(jnp.int32))


@register("argsort")
def _argsort(env, op):
    x = get(env, op.input("X"))
    axis = op.attr("axis", -1)
    idx = jnp.argsort(x, axis=axis)
    put(env, op.output("Indices"), idx.astype(jnp.int32))
    put(env, op.output("Out"), jnp.sort(x, axis=axis))


@register("top_k")
def _top_k(env, op):
    x = get(env, op.input("X"))
    k = op.attr("k", 1)
    vals, idx = jax.lax.top_k(x, k)
    put(env, op.output("Out"), vals)
    put(env, op.output("Indices"), idx.astype(jnp.int32))


@register("isfinite")
def _isfinite(env, op):
    # ref isfinite_op: reduces to a single bool "contains inf/nan"
    x = get(env, op.input("X"))
    put(env, op.output("Out"), jnp.all(jnp.isfinite(x)).reshape((1,)))
