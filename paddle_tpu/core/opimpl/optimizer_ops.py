"""Optimizer update ops.

Reference: ``paddle/fluid/operators/optimizers/`` (sgd, momentum +
lars_momentum, adam, adamax, adagrad, decayed_adagrad, adadelta, rmsprop,
ftrl) — dense paths. Each op's "Out" slots alias the state var names, so the
executor's state write-back gives in-place semantics; with buffer donation
XLA updates parameters in place on device (the TPU equivalent of the
reference's in-place ParamOut contract).

Sparse (SelectedRows) gradient paths: when the op carries a ``GradRows``
input (wired by ``Optimizer._create_optimization_pass`` for params whose
grad is sparse), ``Grad`` holds per-row values and the update is a
scatter touching ONLY those rows — parity with the reference's
SelectedRows kernels (``operators/optimizers/adam_op.h`` sparse branch,
``sgd_op.h``), lazy-mode semantics: untouched rows' moments don't decay.
Duplicate rows are merged jit-safely (sort + segment-sum at static length,
duplicates parked on an out-of-range sentinel row dropped by the scatter).
"""

import jax.numpy as jnp

from ..op_registry import register, get, put, merge_sparse_rows


def _lr(env, op):
    lr = get(env, op.input("LearningRate"))
    return lr.reshape(()) if lr.ndim else lr


def _sparse_grad(env, op):
    """Return (grad, rows): rows is None for dense grads."""
    g = get(env, op.input("Grad"))
    rv = op.input("GradRows")
    if rv is None:
        return g, None
    return g, env[rv.name]


_merge_rows = merge_sparse_rows


def _densify(g, rows, shape, op=None):
    """Densify a (rows, values) sparse grad for optimizers whose update
    runs over every row (non-lazy adam — the DeepFM bench path — and the
    optimizers without a dedicated sparse kernel). Routed through the
    Pallas VMEM-resident scatter-add (``ops/scatter.py``) when the table
    qualifies; XLA's ``.at[].add`` otherwise. Exact either way
    (out-of-range sentinel rows drop, duplicates accumulate). The gate's
    structured decision is recorded in ``op``'s attrs
    (``_kernel_choice``) so the chosen kernel — and any refusal's reason
    — is inspectable on the built program."""
    if len(shape) == 2 and g.ndim == 2:
        from ...ops.scatter import record_choice, scatter_add_rows

        record_choice(op, shape[0], shape[1], g.shape[0], g.dtype)
        return scatter_add_rows(jnp.zeros(shape, g.dtype), rows, g)
    return jnp.zeros(shape, g.dtype).at[rows].add(g, mode="drop")


@register("sparse_decay")
def _sparse_decay(env, op):
    """Row-wise weight decay on a sparse (rows, values) grad: values +=
    coeff * param[rows] (l2) or coeff * sign(param[rows]) (l1). Sentinel
    (out-of-range) rows — duplicate/padding slots — stay zero."""
    g = get(env, op.input("Grad"))
    rows = env[op.input("Rows").name]
    p = get(env, op.input("Param"))
    coeff = op.attr("coeff")
    valid = rows < p.shape[0]
    pr = p[jnp.clip(rows, 0, p.shape[0] - 1)]
    if op.attr("mode") == "l1":
        pr = jnp.sign(pr)
    decay = coeff * pr * valid[:, None].astype(g.dtype)
    put(env, op.output("Out"), g + decay)


@register("sgd")
def _sgd(env, op):
    p = get(env, op.input("Param"))
    g, rows = _sparse_grad(env, op)
    if rows is not None:
        # ref sgd_op.h SelectedRows branch: scatter-add handles duplicates
        # (Pallas row-scatter when the table qualifies — ops/scatter.py)
        upd = -_lr(env, op) * g
        if p.ndim == 2 and upd.ndim == 2:
            from ...ops.scatter import record_choice, scatter_add_rows

            record_choice(op, p.shape[0], p.shape[1], upd.shape[0],
                          p.dtype)
            put(env, op.output("ParamOut"), scatter_add_rows(p, rows, upd))
        else:
            put(env, op.output("ParamOut"),
                p.at[rows].add(upd, mode="drop"))
        return
    put(env, op.output("ParamOut"), p - _lr(env, op) * g)


@register("momentum")
def _momentum(env, op):
    p = get(env, op.input("Param"))
    g, rows = _sparse_grad(env, op)
    v = get(env, op.input("Velocity"))
    mu = op.attr("mu")
    lr = _lr(env, op)
    if rows is not None:
        rows_u, g_u = _merge_rows(rows, g, p.shape[0])
        v_rows = mu * v[rows_u] + g_u
        if op.attr("use_nesterov", False):
            upd = (g_u + mu * v_rows) * lr
        else:
            upd = lr * v_rows
        put(env, op.output("ParamOut"),
            p.at[rows_u].add(-upd, mode="drop"))
        put(env, op.output("VelocityOut"),
            v.at[rows_u].set(v_rows, mode="drop"))
        return
    v_new = mu * v + g
    if op.attr("use_nesterov", False):
        p_new = p - (g + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    put(env, op.output("ParamOut"), p_new)
    put(env, op.output("VelocityOut"), v_new)


@register("lars_momentum")
def _lars_momentum(env, op):
    """LARS (ref ``lars_momentum_op.cc``): layer-wise adaptive LR."""
    p = get(env, op.input("Param"))
    g, _rows = _sparse_grad(env, op)
    if _rows is not None:
        g = _densify(g, _rows, p.shape, op)
    v = get(env, op.input("Velocity"))
    mu = op.attr("mu")
    lars_coeff = op.attr("lars_coeff", 0.001)
    lars_wd = op.attr("lars_weight_decay", 0.0005)
    lr = _lr(env, op)
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = jnp.where(
        (p_norm > 0) & (g_norm > 0),
        lr * lars_coeff * p_norm / (g_norm + lars_wd * p_norm + 1e-12),
        lr)
    v_new = mu * v + local_lr * (g + lars_wd * p)
    put(env, op.output("ParamOut"), p - v_new)
    put(env, op.output("VelocityOut"), v_new)


@register("adam")
def _adam(env, op):
    p = get(env, op.input("Param"))
    g, rows = _sparse_grad(env, op)
    m = get(env, op.input("Moment1"))
    v = get(env, op.input("Moment2"))
    b1p = get(env, op.input("Beta1Pow")).reshape(())
    b2p = get(env, op.input("Beta2Pow")).reshape(())
    b1 = op.attr("beta1", 0.9)
    b2 = op.attr("beta2", 0.999)
    eps = op.attr("epsilon", 1e-8)
    lr = _lr(env, op)
    # ref adam_op.h: lr_t = lr * sqrt(1-beta2^t) / (1-beta1^t); the pow
    # accumulators arrive already holding beta^t for the current step t
    # (initialized to beta at t=1), so use them directly.
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    if rows is not None and not op.attr("lazy_mode", False):
        # ref adam_op.h default (lazy_mode=false): sparse grad is merged
        # and the update runs over EVERY row — identical math to the dense
        # branch on the densified grad. On TPU this is also the fast path:
        # one scatter-add (~15 ns/row) replaces the lazy branch's 3 row
        # gathers + 3 row scatters (measured 45 -> ~12 ms/step on the
        # DeepFM bench, an earlier installation).
        g = _densify(g.astype(p.dtype), rows, p.shape, op)
        rows = None
    if rows is not None:
        # ref adam_op.h SparseAdamFunctor (lazy_mode=true): only touched
        # rows' moments advance; pow accumulators still advance every step
        rows_u, g_u = _merge_rows(rows, g, p.shape[0])
        m_rows = b1 * m[rows_u] + (1 - b1) * g_u
        v_rows = b2 * v[rows_u] + (1 - b2) * jnp.square(g_u)
        p_rows = p[rows_u] - lr_t * m_rows / (jnp.sqrt(v_rows) + eps)
        put(env, op.output("ParamOut"),
            p.at[rows_u].set(p_rows, mode="drop"))
        put(env, op.output("Moment1Out"),
            m.at[rows_u].set(m_rows, mode="drop"))
        put(env, op.output("Moment2Out"),
            v.at[rows_u].set(v_rows, mode="drop"))
        put(env, op.output("Beta1PowOut"), (b1p * b1).reshape((1,)))
        put(env, op.output("Beta2PowOut"), (b2p * b2).reshape((1,)))
        return
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * jnp.square(g)
    p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + eps)
    put(env, op.output("ParamOut"), p_new)
    put(env, op.output("Moment1Out"), m_new)
    put(env, op.output("Moment2Out"), v_new)
    put(env, op.output("Beta1PowOut"), (b1p * b1).reshape((1,)))
    put(env, op.output("Beta2PowOut"), (b2p * b2).reshape((1,)))


@register("adamax")
def _adamax(env, op):
    p = get(env, op.input("Param"))
    g, _rows = _sparse_grad(env, op)
    if _rows is not None:
        g = _densify(g, _rows, p.shape, op)
    m = get(env, op.input("Moment"))
    inf_norm = get(env, op.input("InfNorm"))
    b1p = get(env, op.input("Beta1Pow")).reshape(())
    b1 = op.attr("beta1", 0.9)
    b2 = op.attr("beta2", 0.999)
    eps = op.attr("epsilon", 1e-8)
    lr = _lr(env, op)
    m_new = b1 * m + (1 - b1) * g
    inf_new = jnp.maximum(b2 * inf_norm, jnp.abs(g) + eps)
    lr_t = lr / (1 - b1p)
    put(env, op.output("ParamOut"), p - lr_t * m_new / inf_new)
    put(env, op.output("MomentOut"), m_new)
    put(env, op.output("InfNormOut"), inf_new)


@register("adagrad")
def _adagrad(env, op):
    p = get(env, op.input("Param"))
    g, rows = _sparse_grad(env, op)
    mom = get(env, op.input("Moment"))
    eps = op.attr("epsilon", 1e-6)
    lr = _lr(env, op)
    if rows is not None:
        # ref adagrad_op.h SparseAdagradFunctor: merge rows, touched only
        rows_u, g_u = _merge_rows(rows, g, p.shape[0])
        mom_rows = mom[rows_u] + jnp.square(g_u)
        p_rows = p[rows_u] - lr * g_u / (jnp.sqrt(mom_rows) + eps)
        put(env, op.output("ParamOut"),
            p.at[rows_u].set(p_rows, mode="drop"))
        put(env, op.output("MomentOut"),
            mom.at[rows_u].set(mom_rows, mode="drop"))
        return
    mom_new = mom + jnp.square(g)
    put(env, op.output("ParamOut"), p - lr * g / (jnp.sqrt(mom_new) + eps))
    put(env, op.output("MomentOut"), mom_new)


@register("decayed_adagrad")
def _decayed_adagrad(env, op):
    p = get(env, op.input("Param"))
    g, _rows = _sparse_grad(env, op)
    if _rows is not None:
        g = _densify(g, _rows, p.shape, op)
    mom = get(env, op.input("Moment"))
    decay = op.attr("decay", 0.95)
    eps = op.attr("epsilon", 1e-6)
    lr = _lr(env, op)
    mom_new = decay * mom + (1 - decay) * jnp.square(g)
    put(env, op.output("ParamOut"), p - lr * g / (jnp.sqrt(mom_new) + eps))
    put(env, op.output("MomentOut"), mom_new)


@register("adadelta")
def _adadelta(env, op):
    p = get(env, op.input("Param"))
    g, _rows = _sparse_grad(env, op)
    if _rows is not None:
        g = _densify(g, _rows, p.shape, op)
    avg_sq_g = get(env, op.input("AvgSquaredGrad"))
    avg_sq_u = get(env, op.input("AvgSquaredUpdate"))
    rho = op.attr("rho", 0.95)
    eps = op.attr("epsilon", 1e-6)
    g2 = rho * avg_sq_g + (1 - rho) * jnp.square(g)
    upd = jnp.sqrt(avg_sq_u + eps) / jnp.sqrt(g2 + eps) * g
    u2 = rho * avg_sq_u + (1 - rho) * jnp.square(upd)
    put(env, op.output("ParamOut"), p - upd)
    put(env, op.output("AvgSquaredGradOut"), g2)
    put(env, op.output("AvgSquaredUpdateOut"), u2)


@register("rmsprop")
def _rmsprop(env, op):
    p = get(env, op.input("Param"))
    g, _rows = _sparse_grad(env, op)
    if _rows is not None:
        g = _densify(g, _rows, p.shape, op)
    ms = get(env, op.input("MeanSquare"))
    mg = get(env, op.input("MeanGrad"))
    mom = get(env, op.input("Moment"))
    rho = op.attr("decay", 0.95)
    eps = op.attr("epsilon", 1e-6)
    momentum = op.attr("momentum", 0.0)
    centered = op.attr("centered", False)
    lr = _lr(env, op)
    ms_new = rho * ms + (1 - rho) * jnp.square(g)
    if centered:
        mg_new = rho * mg + (1 - rho) * g
        denom = jnp.sqrt(ms_new - jnp.square(mg_new) + eps)
        put(env, op.output("MeanGradOut"), mg_new)
    else:
        denom = jnp.sqrt(ms_new + eps)
    mom_new = momentum * mom + lr * g / denom
    put(env, op.output("ParamOut"), p - mom_new)
    put(env, op.output("MeanSquareOut"), ms_new)
    put(env, op.output("MomentOut"), mom_new)


@register("ftrl")
def _ftrl(env, op):
    p = get(env, op.input("Param"))
    g, _rows = _sparse_grad(env, op)
    if _rows is not None:
        g = _densify(g, _rows, p.shape, op)
    sq = get(env, op.input("SquaredAccumulator"))
    lin = get(env, op.input("LinearAccumulator"))
    l1 = op.attr("l1", 0.0)
    l2 = op.attr("l2", 0.0)
    lr_power = op.attr("lr_power", -0.5)
    lr = _lr(env, op)
    new_sq = sq + jnp.square(g)
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq, -lr_power)) / lr
    new_lin = lin + g - sigma * p
    if lr_power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        denom = jnp.power(new_sq, -lr_power) / lr + 2 * l2
    pre_shrink = (l1 * jnp.sign(new_lin) - new_lin) / denom
    p_new = jnp.where(jnp.abs(new_lin) > l1, pre_shrink, jnp.zeros_like(p))
    put(env, op.output("ParamOut"), p_new)
    put(env, op.output("SquaredAccumOut"), new_sq)
    put(env, op.output("LinearAccumOut"), new_lin)


@register("lamb")
def _lamb(env, op):
    """LAMB optimizer — beyond the reference's 2019 set; standard for BERT
    pretraining at scale on TPU pods."""
    p = get(env, op.input("Param"))
    g, _rows = _sparse_grad(env, op)
    if _rows is not None:
        g = _densify(g, _rows, p.shape, op)
    m = get(env, op.input("Moment1"))
    v = get(env, op.input("Moment2"))
    b1p = get(env, op.input("Beta1Pow")).reshape(())
    b2p = get(env, op.input("Beta2Pow")).reshape(())
    b1 = op.attr("beta1", 0.9)
    b2 = op.attr("beta2", 0.999)
    eps = op.attr("epsilon", 1e-6)
    wd = op.attr("weight_decay", 0.01)
    lr = _lr(env, op)
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * jnp.square(g)
    m_hat = m_new / (1 - b1p)
    v_hat = v_new / (1 - b2p)
    r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    put(env, op.output("ParamOut"), p - lr * trust * r)
    put(env, op.output("Moment1Out"), m_new)
    put(env, op.output("Moment2Out"), v_new)
    put(env, op.output("Beta1PowOut"), (b1p * b1).reshape((1,)))
    put(env, op.output("Beta2PowOut"), (b2p * b2).reshape((1,)))
