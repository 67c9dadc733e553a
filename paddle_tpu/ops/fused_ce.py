"""Fused vocab-projection + label-smoothed softmax cross-entropy.

The reference pairs a [.., D] x [D, V] projection with
``softmax_with_cross_entropy_op.cc`` (+ ``label_smooth_op.cc``), which
materializes the [.., V] logits (and a soft-label tensor) in memory. On TPU
that tensor dominates the loss head: for transformer-base at batch 128 /
seq 256 / V=30k the logits are 2 GB in bf16 (4 GB f32), pure HBM traffic
plus the layout copies around them.

Here the projection and the CE reduction fuse into one Pallas kernel: the
logits tile lives in VMEM, is consumed by an online (max, sumexp, sum,
logit_y) accumulation, and never reaches HBM. The backward recomputes
logits chunk-by-chunk under ``lax.scan`` from the saved row logsumexp —
peak memory is one [chunk, V] tile instead of [T, V], which also unlocks
larger batches.

Math (matches ``opimpl/nn_ops.py:smooth_softmax_ce``):
    loss = lse(z) - (1-eps) * z[y] - eps * mean(z),   z = x @ W + b
    dz   = g * (softmax(z) - (1-eps) * onehot(y) - eps/V)
"""

import functools

import jax
import jax.numpy as jnp

from . import gates
from .gates import GateDecision, GateReason
from .kernel_names import named_pallas_call

_INTERPRET = False  # tests flip this to run the kernel on CPU


# Above this many logit elements (T*V) the fused path engages. Below it the
# plain projection+CE won: the fused backward RECOMPUTES the projection
# (+2*T*D*V FLOPs) to avoid storing [T, V], a trade that loses with HBM to
# spare (batch 128 transformer-base 199.9k tok/s plain vs 196.2k fused). Set
# on an installation that is gone; not measured since (ROADMAP.md W4).
_FUSED_MIN_LOGITS = 1.5e9


def _use_fused(x, w):
    """Structured gate (``ops.gates.GateDecision``): the fused kernel, or
    the plain projection + CE with the reason (placement, or fewer logits
    than the threshold)."""
    def refuse(reason):
        return GateDecision(False, "xla_projection_ce",
                            fallback="fused_ce", reasons=[reason])

    if _INTERPRET:
        return GateDecision(True, "fused_ce")
    platform = gates.platform_reason()
    if platform is not None:
        return refuse(platform)
    n_logits = (x.size // x.shape[-1]) * w.shape[1]
    if n_logits >= _FUSED_MIN_LOGITS:
        return GateDecision(True, "fused_ce")
    return refuse(GateReason(
        "size", "%.3g logits, under the %.3g from which recomputing the "
        "projection pays" % (n_logits, _FUSED_MIN_LOGITS)))


# ---------------------------------------------------------------------------
# forward kernel: grid (t blocks, v blocks), online stats in VMEM scratch
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, loss_ref, lse_ref,
                m_sc, s_sc, sl_sc, ly_sc, *, v_total, eps, nv):
    from jax.experimental import pallas as pl

    vi = pl.program_id(1)
    bt = x_ref.shape[0]
    bv = w_ref.shape[1]

    @pl.when(vi == 0)
    def _init():
        m_sc[...] = jnp.full((bt, 1), -jnp.inf, jnp.float32)
        s_sc[...] = jnp.zeros((bt, 1), jnp.float32)
        sl_sc[...] = jnp.zeros((bt, 1), jnp.float32)
        ly_sc[...] = jnp.zeros((bt, 1), jnp.float32)

    logits = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [bt, bv]
    if b_ref is not None:
        logits = logits + b_ref[0:1, :].astype(jnp.float32)
    col = vi * bv + jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    valid = col < v_total

    m_old = m_sc[...]
    m_new = jnp.maximum(
        m_old, jnp.max(jnp.where(valid, logits, -jnp.inf), axis=1,
                       keepdims=True))
    alpha = jnp.exp(m_old - m_new)  # 0 on the first block (m_old = -inf)
    s_sc[...] = s_sc[...] * alpha + jnp.sum(
        jnp.where(valid, jnp.exp(logits - m_new), 0.0), axis=1,
        keepdims=True)
    m_sc[...] = m_new
    if eps:
        sl_sc[...] = sl_sc[...] + jnp.sum(
            jnp.where(valid, logits, 0.0), axis=1, keepdims=True)
    y = y_ref[...]  # [bt, 1] int32
    ly_sc[...] = ly_sc[...] + jnp.sum(
        jnp.where(col == y, logits, 0.0), axis=1, keepdims=True)

    @pl.when(vi == nv - 1)
    def _fin():
        lse = m_sc[...] + jnp.log(s_sc[...])
        loss = lse - (1.0 - eps) * ly_sc[...]
        if eps:
            loss = loss - eps * sl_sc[...] / v_total
        loss_ref[...] = loss
        lse_ref[...] = lse


def _fwd_impl(x, w, b, y, eps):
    """x [T, D], w [D, V], b [V] or None, y [T] int32.
    Returns (loss [T] f32, lse [T] f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, d = x.shape
    v = w.shape[1]
    bt = min(512, max(8, ((t + 7) // 8) * 8))
    bv = 1024 if not _INTERPRET else 128
    tp = ((t + bt - 1) // bt) * bt
    vp = ((v + bv - 1) // bv) * bv
    if tp != t:
        x = jnp.pad(x, ((0, tp - t), (0, 0)))
        y = jnp.pad(y, (0, tp - t))
    if vp != v:
        w = jnp.pad(w, ((0, 0), (0, vp - v)))
    nt, nv = tp // bt, vp // bv

    y2 = y.astype(jnp.int32).reshape(tp, 1)
    args = [x, w]
    in_specs = [
        pl.BlockSpec((bt, d), lambda ti, vi: (ti, 0)),
        pl.BlockSpec((d, bv), lambda ti, vi: (0, vi)),
    ]
    if b is not None:
        bb = jnp.broadcast_to(
            jnp.pad(b, (0, vp - v)).reshape(1, vp), (8, vp))
        args.append(bb)
        in_specs.append(pl.BlockSpec((8, bv), lambda ti, vi: (0, vi)))
    args.append(y2)
    in_specs.append(pl.BlockSpec((bt, 1), lambda ti, vi: (ti, 0)))

    kernel = functools.partial(_fwd_kernel, v_total=v, eps=eps, nv=nv)

    def entry(*refs):
        if b is not None:
            x_ref, w_ref, b_ref, y_ref = refs[:4]
            rest = refs[4:]
        else:
            x_ref, w_ref, y_ref = refs[:3]
            b_ref = None
            rest = refs[3:]
        kernel(x_ref, w_ref, b_ref, y_ref, *rest)

    loss, lse = named_pallas_call(
        "fused_ce.fwd",
        entry,
        grid=(nt, nv),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bt, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bt, 1), lambda ti, vi: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)] * 4,
        interpret=_INTERPRET,
    )(*args)
    return loss[:t, 0], lse[:t, 0]


# ---------------------------------------------------------------------------
# backward: chunked recompute under lax.scan (peak memory one [chunk, V])
# ---------------------------------------------------------------------------

def _pick_chunk(t):
    for c in (4096, 2048, 1024, 512, 256, 128):
        if t % c == 0:
            return c
    return t


def _bwd_impl(x, w, b, y, lse, g, eps):
    t, d = x.shape
    v = w.shape[1]
    ct = _pick_chunk(t)
    nch = t // ct
    xs = x.reshape(nch, ct, d)
    ys = y.astype(jnp.int32).reshape(nch, ct)
    ls = lse.reshape(nch, ct)
    gs = g.astype(jnp.float32).reshape(nch, ct)
    bf = b.astype(jnp.float32) if b is not None else None

    def body(carry, inp):
        dw, db = carry
        xc, yc, lsec, gc = inp
        logits = jnp.dot(xc, w, preferred_element_type=jnp.float32)
        if bf is not None:
            logits = logits + bf
        p = jnp.exp(logits - lsec[:, None])
        dl = gc[:, None] * (p - (eps / v if eps else 0.0))
        oh = jax.lax.broadcasted_iota(jnp.int32, (ct, v), 1) == yc[:, None]
        dl = jnp.where(oh, dl - (1.0 - eps) * gc[:, None], dl)
        dlc = dl.astype(x.dtype)
        dxc = jax.lax.dot_general(
            dlc, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        dw = dw + jax.lax.dot_general(
            xc, dlc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if db is not None:
            db = db + jnp.sum(dl, axis=0)
        return (dw, db), dxc

    dw0 = jnp.zeros((d, v), jnp.float32)
    db0 = jnp.zeros((v,), jnp.float32) if b is not None else None
    (dw, db), dxs = jax.lax.scan(body, (dw0, db0), (xs, ys, ls, gs))
    dx = dxs.reshape(t, d)
    return dx, dw.astype(w.dtype), \
        db.astype(b.dtype) if b is not None else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused(x, w, b, y, eps):
    loss, _ = _fwd_impl(x, w, b, y, eps)
    return loss


def _fused_fwd(x, w, b, y, eps):
    loss, lse = _fwd_impl(x, w, b, y, eps)
    return loss, (x, w, b, y, lse)


def _fused_bwd(eps, res, g):
    x, w, b, y, lse = res
    dx, dw, db = _bwd_impl(x, w, b, y, lse, g, eps)
    return dx, dw, db, None


_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# bf16-resident materialized path: logits stored ONCE in bf16 (half the
# plain-f32 HBM traffic), statistics and the softmax in f32 streamed from
# the bf16 tensor, and a custom vjp that hands the backward dots a bf16
# dlogits (XLA's autodiff of the f32 composition would materialize a 4 GB
# f32 dlogits).  Engages under AMP when the Pallas-fused path doesn't.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _bf16_ce(x2, w, b, y2, eps):
    loss, _ = _bf16_ce_fwd(x2, w, b, y2, eps)
    return loss


def _bf16_stats(logits, y2, eps):
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32)          # fused into the streaming pass
    m = jnp.max(lf, axis=-1)
    s = jnp.sum(jnp.exp(lf - m[:, None]), axis=-1)
    lse = m + jnp.log(s)
    logit_y = jnp.take_along_axis(logits, y2[:, None],
                                  axis=-1)[:, 0].astype(jnp.float32)
    loss = lse - (1.0 - eps) * logit_y
    if eps:
        loss = loss - eps * jnp.sum(lf, axis=-1) / v
    return loss, m, s


def _bf16_ce_fwd(x2, w, b, y2, eps):
    xb = x2.astype(jnp.bfloat16)
    wb = w.astype(jnp.bfloat16)
    logits = jnp.dot(xb, wb)                 # bf16-stored [T, V]
    if b is not None:
        logits = logits + b.astype(jnp.bfloat16)
    loss, m, s = _bf16_stats(logits, y2, eps)
    # zero-size dtype carriers: cotangents must match the PRIMAL dtypes
    # (x2 may be f32 while xb is bf16; b may be None)
    protos = (x2[:0], None if b is None else b[:0])
    return loss, (xb, wb, logits, m, s, y2, protos)


def _bf16_ce_bwd(eps, res, g):
    xb, wb, logits, m, s, y2, (x_proto, b_proto) = res
    t, v = logits.shape
    p = jnp.exp(logits.astype(jnp.float32) - m[:, None]) / s[:, None]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (t, v), 1)
              == y2[:, None])
    dz = p - (1.0 - eps) * onehot.astype(jnp.float32)
    if eps:
        dz = dz - eps / v
    dl = (dz * g[:, None].astype(jnp.float32)).astype(jnp.bfloat16)
    # bf16 OPERANDS (the traffic win) with f32-stored dot outputs: the MXU
    # accumulates f32 regardless, storing bf16 would just re-round grads
    dx = jnp.dot(dl, wb.T,
                 preferred_element_type=jnp.float32).astype(x_proto.dtype)
    dw = jnp.dot(xb.T, dl, preferred_element_type=jnp.float32)
    db = (None if b_proto is None
          else jnp.sum(dl.astype(jnp.float32), axis=0).astype(b_proto.dtype))
    return dx, dw, db, None


_bf16_ce.defvjp(_bf16_ce_fwd, _bf16_ce_bwd)


def linear_smooth_ce(x, w, b, y, eps, amp=False):
    """x: [..., D] activations; w: [D, V]; b: [V] or None; y: [...] int
    labels; ``amp``: the step is traced under AMP (the op impl says).
    Returns per-position f32 loss of shape ``x.shape[:-1]``."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    y2 = y.reshape(-1).astype(jnp.int32)

    if gates.note("fused_ce", _use_fused(x, w)):
        loss = _fused(x2, w, b, y2, float(eps))
        return loss.reshape(lead)

    # bf16-stored logits under AMP, or when the caller already runs bf16
    # activations (the dygraph build's per-layer casts)
    if (amp or x.dtype == jnp.bfloat16) and gates.single_tpu():
        return _bf16_ce(x2, w, b, y2, float(eps)).reshape(lead)

    return ce_reference(x2, w, b, y2, eps).reshape(lead)


def ce_reference(x2, w, b, y2, eps):
    """The unfused path (CPU / mesh) and the kernels' numerics oracle:
    plain projection to f32 [T, V] logits + closed-form smooth CE."""
    logits = jnp.dot(x2, w, preferred_element_type=jnp.float32)
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    logit_y = jnp.take_along_axis(logits, y2[:, None], axis=-1)[:, 0]
    loss = lse - (1.0 - eps) * logit_y
    if eps:
        loss = loss - eps * jnp.mean(logits, axis=-1)
    return loss
