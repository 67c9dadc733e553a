"""Op registry: symbolic op type -> pure jax execution function.

The reference registers C++ kernels per (place, dtype, layout, library)
(``paddle/fluid/framework/op_registry.h:197,237,240``) and dispatches at
runtime per op (``operator.h:449``). Here every op type maps to ONE pure jax
function ``impl(env, op)`` that reads input arrays from ``env`` (a dict of
name -> jax array built during tracing) and writes outputs back. The entire
op list is traced into a single XLA computation, so "kernel dispatch" and
"fusion passes" are both delegated to XLA — the TPU-idiomatic equivalent of
the reference's per-op kernel launch + ir fuse passes.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp

OP_IMPLS = {}

# rng key threading: reserved env entries
RNG_KEY = "@RNG@"
RNG0_KEY = "@RNG0@"  # snapshot at step start, used for autodiff replay
ENV0_KEY = "@ENV0@"  # dict snapshot of env at step start (autodiff replay base)
REPLAY_KEY = "@REPLAY@"  # set in autodiff replay envs (debug ops dedup)
PP_KEY = "@PP@"      # pipeline-parallel config (mesh, axis, boundaries, ...)
GRAD_SCALE_KEY = "@GRAD_SCALE@"  # BuildStrategy.GradientScaleStrategy


def register(*names):
    """Decorator: register an impl under one or more op type names."""

    def deco(fn):
        for n in names:
            if n in OP_IMPLS:
                raise ValueError("op %s registered twice" % n)
            OP_IMPLS[n] = fn
        return fn

    return deco


def registered(name):
    return name in OP_IMPLS


# ---------------------------------------------------------------------------
# Static shape/dtype inference rules (the analog of the reference's per-op
# ``OperatorWithKernel::InferShape``, which C++ ops run BEFORE the kernel —
# ``framework/operator.h``). Each rule is ``rule(ctx, op)`` over an
# ``analysis.passes.ShapeCtx``: read input shapes/dtypes via ``ctx.shape`` /
# ``ctx.dtype`` (entries may be -1 = unknown/batch dim), bind outputs via
# ``ctx.set``, and raise ``ShapeError`` for statically-infeasible inputs.
# Rules live in ``core/opimpl/shape_rules.py``, registered alongside the
# lowerings; ops without a rule are skipped by the propagation pass (their
# declared output shapes are trusted).
# ---------------------------------------------------------------------------

SHAPE_RULES = {}


class ShapeError(ValueError):
    """A shape/dtype rule proved the op statically infeasible."""


def register_shape(*names):
    """Decorator: register a static infer-shape rule for op type(s)."""

    def deco(fn):
        for n in names:
            if n in SHAPE_RULES:
                raise ValueError("shape rule for %s registered twice" % n)
            SHAPE_RULES[n] = fn
        return fn

    return deco


def shape_rule(name):
    return SHAPE_RULES.get(name)


# ---------------------------------------------------------------------------
# Static cost rules (the roofline analog of the shape rules — ISSUE 15).
# Each rule is ``rule(ctx, op)`` over an ``analysis.cost.CostCtx``: read
# input shapes via ``ctx.shape`` / element sizes via ``ctx.esize`` and
# charge the op via ``ctx.add(op, flops=..., hbm_bytes=..., bwd_flops=...,
# bwd_hbm_bytes=..., row_reads=..., bwd_row_writes=...)``. The convention
# is a FLOOR model (minimum achievable traffic under ideal XLA fusion):
# the engine is the repo's single bytes model (the sharded embedding's
# comm line and the SPMD pass's collective volumes read it too).
# Rules live in
# ``core/opimpl/cost_rules.py``; an op without a rule contributes zero and
# is reported in the estimate's ``uncosted`` list (honesty over silence).
# ---------------------------------------------------------------------------

COST_RULES = {}


def register_cost(*names):
    """Decorator: register a static cost rule for op type(s)."""

    def deco(fn):
        for n in names:
            if n in COST_RULES:
                raise ValueError("cost rule for %s registered twice" % n)
            COST_RULES[n] = fn
        return fn

    return deco


def register_zero_cost(*names):
    """Explicit zero-cost registration: the op folds away under fusion
    (views, scalar bookkeeping, trace-time constants). Distinct from
    *missing* a rule — the registry-parity test accepts these, the
    estimate does not report them as uncosted."""

    def _zero(ctx, op):
        ctx.add(op)

    for n in names:
        if n in COST_RULES:
            raise ValueError("cost rule for %s registered twice" % n)
        COST_RULES[n] = _zero
    return _zero


def cost_rule(name):
    return COST_RULES.get(name)


def env_flag(name):
    """gflags-style boolean env: '1'/'true'/'yes'/'on' (any case) = on."""
    import os

    return os.environ.get(name, "").strip().lower() in (
        "1", "true", "yes", "on")


def run_op(env, op):
    impl = OP_IMPLS.get(op.type)
    if impl is None:
        raise NotImplementedError(
            "no TPU impl registered for op type '%s' (inputs=%s)"
            % (op.type, op.input_arg_names)
        )
    cond_name = op.attrs.get("_switch_cond")
    old = None
    if cond_name is not None:
        old = {n: env[n] for n in op.output_arg_names if n in env}
    try:
        # ``framework.trace_scope``: a module of several ops under one name
        scope = op.attrs.get("_trace_scope")
        with jax.named_scope(scope) if scope else contextlib.nullcontext(), \
                jax.named_scope(op.type):
            impl(env, op)
    except NotImplementedError:
        raise  # already names the op type
    except Exception as e:
        # enforce-style context (ref PADDLE_ENFORCE + OpError wrapping):
        # name the failing op and its input shapes so shape/dtype errors
        # point at the program line, not the jnp internals
        shapes = []
        for n in op.input_arg_names:
            v = env.get(n)
            shapes.append("%s=%s" % (
                n, tuple(v.shape) if hasattr(v, "shape") else "?"))
        note = ("  [operator '%s' inputs: %s -> outputs: %s]"
                % (op.type, ", ".join(shapes),
                   list(op.output_arg_names)))
        e.add_note(note)  # keeps the exception's type AND the context
        raise
    if cond_name is not None:
        # Switch-case guard: keep prior value where the case doesn't fire
        pred = env[cond_name].reshape(())
        import jax.numpy as jnp

        for n in op.output_arg_names:
            if n in old:
                env[n] = jnp.where(pred, env[n], old[n])


def get(env, var):
    if var is None:
        return None
    try:
        return env[var.name]
    except KeyError:
        raise KeyError(
            "op input '%s' not materialized; feed it or run the startup "
            "program first" % var.name
        )


def get_list(env, op, slot):
    return [get(env, v) for v in op.input_list(slot)]


def put(env, var, val):
    if var is not None:
        env[var.name] = val


def next_rng(env):
    """Split the threaded PRNG key (functional randomness under jit)."""
    key, sub = jax.random.split(env[RNG_KEY])
    env[RNG_KEY] = key
    return sub


def merge_sparse_rows(rows, vals, sentinel):
    """Merge duplicate rows of a (rows, values) sparse grad at static length:
    each real row appears once carrying the summed value; every duplicate
    slot holds ``sentinel`` (an out-of-range row) with a ZERO value, so both
    scatters (which drop out-of-range rows) and norms (which must not count
    a row twice) are exact. Ref ``math/selected_rows_functor.cc`` MergeAdd."""
    order = jnp.argsort(rows)
    r = rows[order]
    v = vals[order]
    is_start = jnp.concatenate([jnp.ones((1,), bool), r[1:] != r[:-1]])
    seg = jnp.cumsum(is_start) - 1
    totals = jax.ops.segment_sum(v, seg, num_segments=r.shape[0])
    mask = is_start.reshape((-1,) + (1,) * (v.ndim - 1))
    vals_u = jnp.where(mask, totals[seg], 0)
    rows_u = jnp.where(is_start, r, sentinel)
    return rows_u, vals_u


def bcast_y(x, y, axis):
    """Reference elementwise broadcast semantics: y's shape aligns to x
    starting at ``axis`` (ref ``operators/elementwise/elementwise_op.h``).
    axis=-1 means align trailing dims (numpy broadcasting)."""
    if axis is None:
        axis = -1
    if y.ndim >= x.ndim or y.ndim == 0:
        # equal-rank or y-broader: plain numpy broadcasting applies
        return y
    if axis == -1:
        axis = x.ndim - y.ndim
    new_shape = [1] * x.ndim
    for i, s in enumerate(y.shape):
        new_shape[axis + i] = s
    return jnp.reshape(y, new_shape)


def static_bcast_shape(xs, ys, axis=-1):
    """Static-shape mirror of :func:`bcast_y` + numpy broadcasting, with
    -1 as the unknown/batch wildcard. Returns the result shape tuple, or
    None when either side is unknown; raises ValueError for shapes that
    are statically infeasible. Shared by the layer builders (declared
    output shapes) and the analysis shape-inference rules, so the two can
    never disagree."""
    if xs is None or ys is None:
        return None
    xs = tuple(-1 if (d is None or int(d) < 0) else int(d) for d in xs)
    ys = tuple(-1 if (d is None or int(d) < 0) else int(d) for d in ys)
    # y aligns into x's rank at `axis` (reference semantics)
    if 0 < len(ys) < len(xs):
        a = len(xs) - len(ys) if axis in (None, -1) else int(axis)
        if a < 0 or a + len(ys) > len(xs):
            raise ValueError(
                "broadcast axis %d places y shape %s outside x shape %s"
                % (a, list(ys), list(xs)))
        ys = (1,) * a + ys + (1,) * (len(xs) - a - len(ys))
    rank = max(len(xs), len(ys))
    xs = (1,) * (rank - len(xs)) + xs
    ys = (1,) * (rank - len(ys)) + ys
    out = []
    for dx, dy in zip(xs, ys):
        if dx == 1:
            out.append(dy)
        elif dy == 1:
            out.append(dx)
        elif dx == -1 or dy == -1:
            # one side unknown: assume the known side (numpy would demand
            # equality or 1, and 1 was handled above)
            out.append(dx if dy == -1 else dy)
        elif dx == dy:
            out.append(dx)
        else:
            raise ValueError("cannot broadcast shapes %s and %s"
                             % (list(xs), list(ys)))
    return tuple(out)


# ---------------------------------------------------------------------------
# Mixed precision (trace-time flag). The reference's capability is the
# float16_transpiler (``paddle/contrib/float16/float16_transpiler.py``) which
# rewrites the program to fp16 kernels; the TPU-native design keeps fp32
# master params/activations and feeds the MXU bf16 operands with fp32
# accumulation — no loss scaling needed (bf16 keeps fp32's exponent range).
# The flag is set while an AMP-enabled program is being traced
# (``executor.build_step_fn``), so forward AND the autodiff replay see it.
# ---------------------------------------------------------------------------

class _AmpState(threading.local):
    """Per-thread so concurrent traces (two executors compiling in parallel
    threads) cannot cross-contaminate each other's precision."""
    enabled = False


AMP = _AmpState()


def amp_enabled():
    return AMP.enabled


def mxu_cast(*xs):
    """Cast float32 matmul/conv operands to bf16 when AMP is on."""
    if not AMP.enabled:
        return xs if len(xs) > 1 else xs[0]
    out = tuple(
        x.astype(jnp.bfloat16)
        if (x is not None and hasattr(x, "dtype") and x.dtype == jnp.float32)
        else x
        for x in xs)
    return out if len(out) > 1 else out[0]


def amp_harmonize(x, y):
    """Binop promotion under AMP: bf16 wins.

    jnp's default promotion turns every ``bf16_activation (op) f32_param``
    (bias add, residual add against an f32 upstream, mask mul) back into
    f32, so the whole non-matmul stream bounces bf16->f32->bf16 with a
    convert at each matmul boundary (measured ~23 ms/step on
    transformer-base). Demoting the f32 side keeps the activation stream
    bf16-resident; normalization/softmax statistics still upcast
    internally (see ``_layer_norm``)."""
    if AMP.enabled and hasattr(x, "dtype") and hasattr(y, "dtype"):
        if x.dtype == jnp.bfloat16 and y.dtype == jnp.float32:
            return x, y.astype(jnp.bfloat16)
        if x.dtype == jnp.float32 and y.dtype == jnp.bfloat16:
            return x.astype(jnp.bfloat16), y
    return x, y


def amp_out_cast(x):
    """Cast an f32 activation SOURCE (embedding gather output) to bf16
    under AMP, mirroring the matmuls, whose outputs are stored in their
    bf16 operands' dtype (the MXU accumulates fp32 internally either way;
    bf16-resident activations halve the HBM traffic between layers, and
    normalizations and softmax-family ops upcast to fp32 for their
    statistics)."""
    if AMP.enabled and hasattr(x, "dtype") and x.dtype == jnp.float32:
        return x.astype(jnp.bfloat16)
    return x
