"""Fused LayerNorm (forward + backward Pallas kernels).

XLA lowers the composed layer_norm into ~5 HBM passes over the [T, D]
activation per train step (fwd: stats read + normalize read; bwd: two
row-reduction reads + apply read). With the row block VMEM-resident, the
fused kernels do ONE
read + one write in each direction, plus in-kernel dgamma/dbeta
accumulation across the sequential grid.

Reference op pairing: ``operators/layer_norm_op.cc`` (fwd stats + per-row
normalize; grad kernel with the same two row reductions).

Backward note: cotangents arriving through the op's auxiliary Mean /
Variance outputs are ignored (no model in the zoo consumes them as
differentiable values; the reference treats them as saved statistics).
"""

import functools

import jax
import jax.numpy as jnp

from .kernel_names import named_pallas_call

_INTERPRET = False  # tests flip this to run the kernels on CPU


def _use_fused(d):
    """Structured gate (``ops.gates.GateDecision``): the fused kernels, or
    XLA's composed layer norm with the reason."""
    from .gates import GateDecision, GateReason

    def refuse(check, detail):
        return GateDecision(False, "xla_layer_norm",
                            fallback="fused_layer_norm",
                            reasons=[GateReason(check, detail)])

    if _INTERPRET:
        return GateDecision(True, "fused_layer_norm")
    from ..core.op_registry import env_flag, single_tpu

    # OPT-IN (PADDLE_TPU_FUSED_LN=1): measured net-negative on the bench
    # chip (transformer 201.0k -> 193.2k, BERT 130.9k -> 113.2k tok/s) —
    # XLA already fuses the LN normalize pass into neighboring ops, and
    # the custom call breaks those fusions. Kept for chips/configs where
    # the separate-stats passes dominate.
    if not env_flag("PADDLE_TPU_FUSED_LN"):
        return refuse("env", "opt-in: PADDLE_TPU_FUSED_LN is not set")
    if not single_tpu():
        return refuse("platform", "the step is not placed on one TPU chip")
    if d > 4096:
        return refuse("vmem", "row width %d is over 4096" % d)
    return GateDecision(True, "fused_layer_norm")


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, var_ref, *, eps, d):
    x = x_ref[...].astype(jnp.float32)  # [bt, d]
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    if g_ref is not None:
        y = y * g_ref[0:1, :].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[0:1, :].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mu_ref[...] = mu
    var_ref[...] = var


def _bwd_kernel(x_ref, g_ref, dy_ref, mu_ref, var_ref, dx_ref, dg_ref,
                db_ref, *, eps, d):
    from jax.experimental import pallas as pl

    ti = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    rstd = jax.lax.rsqrt(var_ref[...] + eps)  # [bt, 1]
    xhat = (x - mu_ref[...]) * rstd
    dxhat = dy
    if g_ref is not None:
        dxhat = dy * g_ref[0:1, :].astype(jnp.float32)
    m1 = jnp.mean(dxhat, axis=1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (dxhat - m1 - xhat * m2)).astype(dx_ref.dtype)
    # dgamma/dbeta accumulate in the revisited output block (constant
    # index map -> stays in VMEM across the sequential grid)
    if dg_ref is not None:
        @pl.when(ti == 0)
        def _init_g():
            dg_ref[...] = jnp.zeros_like(dg_ref)
        dg_ref[0, :] = dg_ref[0, :] + jnp.sum(dy * xhat, axis=0)
    if db_ref is not None:
        @pl.when(ti == 0)
        def _init_b():
            db_ref[...] = jnp.zeros_like(db_ref)
        db_ref[0, :] = db_ref[0, :] + jnp.sum(dy, axis=0)


def _block_t(t, d):
    # ~bt*d f32 <= 1 MB: the bwd kernel keeps x/dy/dx blocks (double-
    # buffered) plus ~4 f32 temporaries live — larger blocks blow the
    # 16 MB scoped-vmem limit on f32 inputs
    bt = max(8, min(1024, 256 * 1024 // max(d, 1)))
    bt = (bt // 8) * 8
    return min(bt, ((t + 7) // 8) * 8)


def _fwd_impl(x, g, b, eps):
    from jax.experimental import pallas as pl

    t, d = x.shape
    bt = _block_t(t, d)
    tp = ((t + bt - 1) // bt) * bt
    xp = jnp.pad(x, ((0, tp - t), (0, 0))) if tp != t else x

    in_specs = [pl.BlockSpec((bt, d), lambda ti: (ti, 0))]
    args = [xp]
    for v in (g, b):
        if v is not None:
            in_specs.append(pl.BlockSpec((8, d), lambda ti: (0, 0)))
            args.append(jnp.broadcast_to(v.reshape(1, d), (8, d)))

    kernel = functools.partial(_fwd_kernel, eps=eps, d=d)

    def entry(*refs):
        i = 1
        g_ref = b_ref = None
        if g is not None:
            g_ref = refs[i]
            i += 1
        if b is not None:
            b_ref = refs[i]
            i += 1
        kernel(refs[0], g_ref, b_ref, *refs[i:])

    y, mu, var = named_pallas_call(
        "fused_layer_norm.fwd",
        entry,
        grid=(tp // bt,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bt, d), lambda ti: (ti, 0)),
            pl.BlockSpec((bt, 1), lambda ti: (ti, 0)),
            pl.BlockSpec((bt, 1), lambda ti: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, d), x.dtype),
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(*args)
    return y[:t], mu[:t, 0], var[:t, 0]


def _bwd_impl(x, g, mu, var, dy, eps):
    from jax.experimental import pallas as pl

    t, d = x.shape
    bt = _block_t(t, d)
    tp = ((t + bt - 1) // bt) * bt
    if tp != t:
        x = jnp.pad(x, ((0, tp - t), (0, 0)))
        dy = jnp.pad(dy, ((0, tp - t), (0, 0)))
        mu = jnp.pad(mu, (0, tp - t))
        var = jnp.pad(var, (0, tp - t))

    in_specs = [pl.BlockSpec((bt, d), lambda ti: (ti, 0))]
    args = [x]
    if g is not None:
        in_specs.append(pl.BlockSpec((8, d), lambda ti: (0, 0)))
        args.append(jnp.broadcast_to(g.reshape(1, d), (8, d)))
    in_specs += [
        pl.BlockSpec((bt, d), lambda ti: (ti, 0)),
        pl.BlockSpec((bt, 1), lambda ti: (ti, 0)),
        pl.BlockSpec((bt, 1), lambda ti: (ti, 0)),
    ]
    args += [dy, mu.reshape(tp, 1), var.reshape(tp, 1)]

    kernel = functools.partial(_bwd_kernel, eps=eps, d=d)
    with_g = g is not None

    def entry(*refs):
        i = 1
        g_ref = None
        if with_g:
            g_ref = refs[i]
            i += 1
        x_ref = refs[0]
        dy_ref, mu_ref, var_ref = refs[i:i + 3]
        outs = refs[i + 3:]
        dx_ref = outs[0]
        dg_ref = outs[1]
        db_ref = outs[2]
        kernel(x_ref, g_ref, dy_ref, mu_ref, var_ref, dx_ref, dg_ref,
               db_ref)

    dx, dg, db = named_pallas_call(
        "fused_layer_norm.bwd",
        entry,
        grid=(tp // bt,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bt, d), lambda ti: (ti, 0)),
            pl.BlockSpec((8, d), lambda ti: (0, 0)),
            pl.BlockSpec((8, d), lambda ti: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, d), dy.dtype),
            jax.ShapeDtypeStruct((8, d), jnp.float32),
            jax.ShapeDtypeStruct((8, d), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(*args)
    return dx[:t], dg[0], db[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ln(x, g, b, eps):
    return _fwd_impl(x, g, b, eps)


def _fused_ln_fwd(x, g, b, eps):
    y, mu, var = _fwd_impl(x, g, b, eps)
    return (y, mu, var), (x, g, b, mu, var)


def _fused_ln_bwd(eps, res, cts):
    x, g, b, mu, var = res
    gy = cts[0]  # cotangents via Mean/Variance ignored (see module doc)
    dx, dg, db = _bwd_impl(x, g, mu, var, gy, eps)
    dg_out = dg.astype(g.dtype) if g is not None else None
    db_out = db.astype(b.dtype) if b is not None else None
    return dx.astype(x.dtype), dg_out, db_out


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(x, scale, bias, eps):
    """x: [..., D]; normalize over the LAST axis. Returns
    (y [..., D] in x.dtype, mean [...], var [...]) with f32 statistics."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    y, mu, var = _fused_ln(x2, scale, bias, eps)
    return (y.reshape(lead + (d,)), mu.reshape(lead), var.reshape(lead))
