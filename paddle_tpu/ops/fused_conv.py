"""Fused conv + batch_norm + activation (+ residual add): Pallas epilogue
kernels for the ResNet-50 bottleneck shapes.

The unfused lowering pays a full HBM round trip per conv->BN->ReLU link:
conv writes its output, the BN statistics pass re-reads it, and the
normalize(+residual+relu) pass reads it again and writes the final
activation (the reference
framework fuses these chains as graph passes —
``framework/details/build_strategy.cc`` ``fuse_elewise_add_act`` /
``fuse_relu_depthwise_conv``). Here the conv runs as a Pallas blocked
matmul over the flattened spatial axis with the epilogues folded in:

  * training: ONE kernel computes the conv and accumulates the per-channel
    sum/sumsq moments in the same VMEM-resident pass (the stats read pass
    disappears), then ONE apply kernel performs scale/shift + residual +
    relu (the separate residual/relu passes disappear). HBM traffic per
    link: W(conv) + R+W(apply) vs the unfused W + R(stats) + R + W.
  * inference / use_global_stats: the BN affine folds entirely into the
    conv epilogue — ONE kernel, the intermediate never reaches HBM.

Conv-as-matmul: a KxK/pad convolution over the lane-flattened [C, H*W]
image is a sum of K*K shifted matmuls — each tap (di, dj) contributes
``W[:, :, di, dj] @ shift(x, (di-ph)*W + (dj-pw))`` with the row-wrap
columns masked. Shifts are static lane slices of a once-padded block, so
the whole tap loop runs on VMEM values (see /opt/skills/guides/
pallas_guide.md on lane layout). Supported: groups=1, dilation 1, 1x1
(stride 1; stride 2 via a pre-slice, exact for 1x1) and 3x3/pad-1 stride 1
— the ResNet-50 bottleneck bodies. Everything else (7x7 stem, stride-2
3x3) replays the original unfused ops (see ``core/epilogue_fusion.py``).

Backward: custom_vjp. The forward saves the conv output (it is in HBM
anyway), and the backward chain-rules ``jax.vjp`` of the *reference*
epilogue (stats recomputed from the saved conv output, so the BN
stat-coupling terms are exact) into ``jax.vjp`` of the plain lax conv —
bit-identical math to differentiating the unfused program, no conv
recompute.

CPU/tests: ``_INTERPRET = True`` routes the Pallas path through the
interpreter (tests/test_fused_conv.py); otherwise non-TPU falls back to
the caller's unfused replay.
"""

import functools

import jax
import jax.numpy as jnp

from .gates import GateDecision, GateReason, platform_reason
from .kernel_names import named_pallas_call, traced_once

_INTERPRET = False  # tests flip this to run the kernels on CPU

# conservative per-program VMEM budget: double-buffered x/co/y blocks plus
# the f32 accumulator and the lane-padded shift transient
_VMEM_BUDGET = 12 * 1024 * 1024


def supported_geometry(x_shape, w_shape, strides, paddings, dilations,
                       groups):
    """True when the Pallas path covers this conv geometry."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    if any(d is None or int(d) <= 0 for d in tuple(x_shape) + tuple(w_shape)):
        return False
    if groups != 1 or tuple(dilations) != (1, 1):
        return False
    o, c, kh, kw = w_shape
    s = tuple(strides)
    p = tuple(paddings)
    if (kh, kw) == (1, 1):
        return p == (0, 0) and s in ((1, 1), (2, 2))
    if (kh, kw) == (3, 3):
        return p == (1, 1) and s == (1, 1)
    return False


def _fits_vmem(c, o, hw, esize, has_residual):
    w_lane_pad = hw + 2 * 512  # worst-case S transient bound
    x_bytes = 2 * c * w_lane_pad * esize          # block + shift transient
    co_bytes = 2 * o * hw * esize                 # double-buffered out
    acc_bytes = o * hw * 4
    res_bytes = 2 * o * hw * esize if has_residual else 0
    return x_bytes + co_bytes + acc_bytes + res_bytes <= _VMEM_BUDGET


def gate(x_shape, w_shape, strides, paddings, dilations, groups, esize,
         has_residual, static_only=False):
    """Structured gate (``ops.gates.GateDecision``) for the fused
    kernels (mirrors the other fused ops' gates). ``static_only=True``
    evaluates only the geometry/VMEM checks — the platform-independent
    view the static resource pass wants."""
    reasons = []
    if not supported_geometry(x_shape, w_shape, strides, paddings,
                              dilations, groups):
        reasons.append(GateReason(
            "geometry", "unsupported conv geometry: filter %s strides %s "
            "paddings %s dilations %s groups %s (Pallas path covers the "
            "1x1 s1/s2 and 3x3 s1 p1 bottleneck shapes)"
            % (list(w_shape), list(strides), list(paddings),
               list(dilations), groups)))
    else:
        o, c, kh, kw = w_shape
        h, w = int(x_shape[2]), int(x_shape[3])
        if tuple(strides) == (2, 2):  # pre-sliced before the kernel
            h, w = (h + 1) // 2, (w + 1) // 2
        if not _fits_vmem(int(c), int(o), h * w, esize, has_residual):
            reasons.append(GateReason(
                "vmem", "[C=%d, HW=%d] image + [O=%d] output blocks "
                "exceed the %.0f MB VMEM budget"
                % (int(c), h * w, int(o), _VMEM_BUDGET / 2**20)))
    if not static_only and not reasons:
        platform = platform_reason(_INTERPRET)
        if platform is not None:
            reasons.append(platform)
    if reasons:
        return GateDecision(False, "unfused_replay",
                            fallback="pallas_fused_conv", reasons=reasons)
    return GateDecision(True, "pallas_fused_conv")


def use_pallas(x_shape, w_shape, strides, paddings, dilations, groups,
               esize, has_residual):
    """Boolean view of :func:`gate` (the pre-ISSUE-15 surface)."""
    return gate(x_shape, w_shape, strides, paddings, dilations, groups,
                esize, has_residual).admitted


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _conv_taps(x, w_ref, taps, shift_pad, width, hw):
    """[O, HW] f32 conv accumulator from the VMEM-resident [C, HW] image:
    one shifted, row-wrap-masked matmul per kernel tap."""
    acc = None
    if shift_pad:
        xp = jnp.pad(x, ((0, 0), (shift_pad, shift_pad)))
        wcol = jax.lax.broadcasted_iota(jnp.int32, (1, hw), 1) % width
    for t, (dy, dx) in enumerate(taps):
        if shift_pad:
            s = dy * width + dx
            xt = xp[:, shift_pad + s:shift_pad + s + hw]
            if dx:
                ok = (wcol + dx >= 0) & (wcol + dx < width)
                xt = jnp.where(ok, xt, jnp.zeros_like(xt))
        else:
            xt = x
        part = jax.lax.dot_general(
            w_ref[t], xt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [O, HW]
        acc = part if acc is None else acc + part
    return acc


def _conv_moments_kernel(x_ref, w_ref, co_ref, s1_ref, s2_ref, *, taps,
                         shift_pad, width, hw):
    """Training kernel: conv + per-channel sum/sumsq of the (rounded)
    output, accumulated across the sequential batch grid into revisited
    [O, 1] outputs — the BN statistics pass never re-reads HBM."""
    from jax.experimental import pallas as pl

    acc = _conv_taps(x_ref[0], w_ref, taps, shift_pad, width, hw)
    co = acc.astype(co_ref.dtype)
    co_ref[0] = co
    # moments from the ROUNDED values: numerics match the unfused BN,
    # which reads the stored (bf16 under AMP) conv output back as f32
    cof = co.astype(jnp.float32)
    ones = jnp.ones((hw, 1), jnp.float32)
    s1 = jax.lax.dot_general(cof, ones, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    s2 = jax.lax.dot_general(cof * cof, ones, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    s1_ref[...] += s1
    s2_ref[...] += s2


def _apply_kernel(co_ref, scale_ref, shift_ref, res_ref, y_ref, *, relu):
    """BN scale/shift (+residual)(+relu) epilogue: one read, one write."""
    y = co_ref[0].astype(jnp.float32) * scale_ref[...] + shift_ref[...]
    y = y.astype(y_ref.dtype)  # activation dtype BEFORE the residual add,
    if res_ref is not None:    # matching the unfused bn.Y -> add chain
        y = y + res_ref[0]
    if relu:
        y = jnp.maximum(y, jnp.zeros_like(y))
    y_ref[0] = y


def _conv_apply_kernel(x_ref, w_ref, scale_ref, shift_ref, res_ref, y_ref,
                       *, taps, shift_pad, width, hw, relu, co_dtype):
    """Inference kernel: conv with the BN affine (+residual)(+relu) folded
    into the epilogue — the conv output never reaches HBM."""
    acc = _conv_taps(x_ref[0], w_ref, taps, shift_pad, width, hw)
    # round through the storage dtype the unfused path would have used, so
    # fused and unfused inference agree bit-for-bit under AMP
    cof = acc.astype(co_dtype).astype(jnp.float32)
    y = (cof * scale_ref[...] + shift_ref[...]).astype(y_ref.dtype)
    if res_ref is not None:
        y = y + res_ref[0]
    if relu:
        y = jnp.maximum(y, jnp.zeros_like(y))
    y_ref[0] = y


# ---------------------------------------------------------------------------
# pallas_call drivers — x flattened to [N, C, H*W]
# ---------------------------------------------------------------------------

def _tap_geometry(kh, kw, ph, pw, width):
    taps = tuple((di - ph, dj - pw) for di in range(kh) for dj in range(kw))
    shift_pad = width + max(pw, 1) if (kh, kw) != (1, 1) else 0
    return taps, shift_pad


def _w_taps(w):
    """[O, C, KH, KW] -> [KH*KW, O, C] so the kernel indexes taps on the
    leading (cheap) axis."""
    o, c, kh, kw = w.shape
    return w.transpose(2, 3, 0, 1).reshape(kh * kw, o, c)


@traced_once("fused_conv.fwd", ("taps", "shift_pad", "width", "interpret"))
def _conv_moments(x2, wt, taps, shift_pad, width, interpret):
    from jax.experimental import pallas as pl

    n, c, hw = x2.shape
    nt, o, _ = wt.shape
    kernel = functools.partial(_conv_moments_kernel, taps=taps,
                               shift_pad=shift_pad, width=width, hw=hw)
    co, s1, s2 = named_pallas_call(
        "fused_conv.fwd",
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, c, hw), lambda i: (i, 0, 0)),
            pl.BlockSpec((nt, o, c), lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, o, hw), lambda i: (i, 0, 0)),
            pl.BlockSpec((o, 1), lambda i: (0, 0)),
            pl.BlockSpec((o, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, o, hw), x2.dtype),
            jax.ShapeDtypeStruct((o, 1), jnp.float32),
            jax.ShapeDtypeStruct((o, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, wt)
    return co, s1[:, 0], s2[:, 0]


@traced_once("fused_conv.apply", ("relu", "interpret"))
def _apply(co, scale, shift, residual, relu, interpret):
    from jax.experimental import pallas as pl

    n, o, hw = co.shape
    in_specs = [
        pl.BlockSpec((1, o, hw), lambda i: (i, 0, 0)),
        pl.BlockSpec((o, 1), lambda i: (0, 0)),
        pl.BlockSpec((o, 1), lambda i: (0, 0)),
    ]
    args = [co, scale.reshape(o, 1), shift.reshape(o, 1)]
    if residual is not None:
        in_specs.append(pl.BlockSpec((1, o, hw), lambda i: (i, 0, 0)))
        args.append(residual)

    def entry(*refs):
        if residual is not None:
            co_ref, sc_ref, sh_ref, res_ref, y_ref = refs
        else:
            co_ref, sc_ref, sh_ref, y_ref = refs
            res_ref = None
        _apply_kernel(co_ref, sc_ref, sh_ref, res_ref, y_ref, relu=relu)

    return named_pallas_call(
        "fused_conv.apply",
        entry,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, o, hw), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, o, hw), co.dtype),
        interpret=interpret,
    )(*args)


@traced_once("fused_conv.infer",
             ("relu", "taps", "shift_pad", "width", "co_dtype", "interpret"))
def _conv_apply(x2, wt, scale, shift, residual, relu, taps, shift_pad,
                width, co_dtype, interpret):
    from jax.experimental import pallas as pl

    n, c, hw = x2.shape
    nt, o, _ = wt.shape
    in_specs = [
        pl.BlockSpec((1, c, hw), lambda i: (i, 0, 0)),
        pl.BlockSpec((nt, o, c), lambda i: (0, 0, 0)),
        pl.BlockSpec((o, 1), lambda i: (0, 0)),
        pl.BlockSpec((o, 1), lambda i: (0, 0)),
    ]
    args = [x2, wt, scale.reshape(o, 1), shift.reshape(o, 1)]
    if residual is not None:
        in_specs.append(pl.BlockSpec((1, o, hw), lambda i: (i, 0, 0)))
        args.append(residual)
    kernel = functools.partial(_conv_apply_kernel, taps=taps,
                               shift_pad=shift_pad, width=width, hw=hw,
                               relu=relu, co_dtype=co_dtype)

    def entry(*refs):
        if residual is not None:
            x_ref, w_ref, sc_ref, sh_ref, res_ref, y_ref = refs
        else:
            x_ref, w_ref, sc_ref, sh_ref, y_ref = refs
            res_ref = None
        kernel(x_ref, w_ref, sc_ref, sh_ref, res_ref, y_ref)

    return named_pallas_call(
        "fused_conv.infer",
        entry,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, o, hw), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, o, hw), x2.dtype),
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# reference composition (the unfused math, used by the backward and by the
# numerics tests) + custom_vjp wiring
# ---------------------------------------------------------------------------

def _bn_stats(co):
    """Per-channel batch mean/var of [N, O, HW] in f32 (one-pass, exactly
    the unfused ``_batch_norm`` formulation)."""
    cof = co.astype(jnp.float32) if co.dtype == jnp.bfloat16 else co
    n = co.shape[0] * co.shape[2]
    s1 = jnp.sum(cof, axis=(0, 2))
    s2 = jnp.sum(cof * cof, axis=(0, 2))
    bm = s1 / n
    bv = jnp.maximum(s2 / n - bm * bm, 0.0)
    return bm, bv


def _epilogue_reference(co, gamma, beta, residual, bm, bv, eps, act):
    """normalize (+residual)(+act) on a conv output, matching the unfused
    batch_norm -> elementwise_add -> relu numerics exactly. ``bm``/``bv``
    None means training (stats from ``co``, differentiably — the BN
    stat-coupling terms of the backward come out of this)."""
    cof = co.astype(jnp.float32) if co.dtype == jnp.bfloat16 else co
    if bm is None:
        bm, bv = _bn_stats(co)
    inv = jax.lax.rsqrt(bv.reshape(1, -1, 1) + eps)
    y = (cof - bm.reshape(1, -1, 1)) * inv * \
        gamma.astype(jnp.float32).reshape(1, -1, 1) + \
        beta.astype(jnp.float32).reshape(1, -1, 1)
    y = y.astype(co.dtype)
    if residual is not None:
        y = y + residual.astype(y.dtype)
    if act == "relu":
        y = jax.nn.relu(y)
    return y


def _conv_reference(x2, w, height, width):
    """Plain stride-1 lax conv on the flattened layout (the pre-slice makes
    every supported geometry stride-1 by the time it reaches the kernel)."""
    n, c, hw = x2.shape
    o, _, kh, kw = w.shape
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    out = jax.lax.conv_general_dilated(
        x2.reshape(n, c, height, width), w, window_strides=(1, 1),
        padding=[(ph, ph), (pw, pw)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return out.reshape(n, o, hw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _fused_train(x2, w, gamma, beta, residual, height, width, eps, act):
    y, bm, bv, _ = _fused_train_fwd_impl(x2, w, gamma, beta, residual,
                                         height, width, eps, act)
    return y, bm, bv


def _fused_train_fwd_impl(x2, w, gamma, beta, residual, height, width, eps,
                          act):
    o, c, kh, kw = w.shape
    taps, shift_pad = _tap_geometry(kh, kw, (kh - 1) // 2, (kw - 1) // 2,
                                    width)
    co, s1, s2 = _conv_moments(x2, _w_taps(w), taps, shift_pad, width,
                               _INTERPRET)
    n = x2.shape[0] * x2.shape[2]
    bm = s1 / n
    bv = jnp.maximum(s2 / n - bm * bm, 0.0)
    inv = jax.lax.rsqrt(bv + eps)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - bm * scale
    y = _apply(co, scale, shift, residual, relu=(act == "relu"),
               interpret=_INTERPRET)
    return y, bm, bv, co


def _fused_train_fwd(x2, w, gamma, beta, residual, height, width, eps, act):
    y, bm, bv, co = _fused_train_fwd_impl(x2, w, gamma, beta, residual,
                                          height, width, eps, act)
    return (y, bm, bv), (x2, w, gamma, beta, residual, co)


def _fused_train_bwd(height, width, eps, act, res, cts):
    x2, w, gamma, beta, residual, co = res
    dy = cts[0]  # bm/bv outputs are stop_gradient'd by the caller
    with_res = residual is not None
    if with_res:
        _, epi_vjp = jax.vjp(
            lambda co_, g_, b_, r_: _epilogue_reference(
                co_, g_, b_, r_, None, None, eps, act),
            co, gamma, beta, residual)
        dco, dgamma, dbeta, dres = epi_vjp(dy)
    else:
        _, epi_vjp = jax.vjp(
            lambda co_, g_, b_: _epilogue_reference(
                co_, g_, b_, None, None, None, eps, act),
            co, gamma, beta)
        dco, dgamma, dbeta = epi_vjp(dy)
        dres = None
    _, conv_vjp = jax.vjp(
        lambda x_, w_: _conv_reference(x_, w_, height, width), x2, w)
    dx2, dw = conv_vjp(dco.astype(co.dtype))
    return (dx2, dw, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype),
            dres.astype(residual.dtype) if with_res else None)


_fused_train.defvjp(_fused_train_fwd, _fused_train_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _fused_infer(x2, w, gamma, beta, mean, var, residual, height, width,
                 eps, act):
    o, c, kh, kw = w.shape
    taps, shift_pad = _tap_geometry(kh, kw, (kh - 1) // 2, (kw - 1) // 2,
                                    width)
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    return _conv_apply(x2, _w_taps(w), scale, shift, residual,
                       relu=(act == "relu"), taps=taps,
                       shift_pad=shift_pad, width=width,
                       co_dtype=x2.dtype, interpret=_INTERPRET)


def _fused_infer_fwd(x2, w, gamma, beta, mean, var, residual, height, width,
                     eps, act):
    y = _fused_infer(x2, w, gamma, beta, mean, var, residual, height, width,
                     eps, act)
    return y, (x2, w, gamma, beta, mean, var, residual)


def _fused_infer_bwd(height, width, eps, act, res, dy):
    x2, w, gamma, beta, mean, var, residual = res

    def ref(x_, w_, g_, b_, r_):
        co = _conv_reference(x_, w_, height, width).astype(x_.dtype)
        return _epilogue_reference(co, g_, b_, r_, mean, var, eps, act)

    with_res = residual is not None
    _, vjp = jax.vjp(ref, x2, w, gamma, beta,
                     residual if with_res else None)
    dx2, dw, dg, db, dres = vjp(dy)
    return (dx2, dw, dg.astype(gamma.dtype), db.astype(beta.dtype),
            jnp.zeros_like(mean), jnp.zeros_like(var),
            dres.astype(residual.dtype) if with_res else None)


_fused_infer.defvjp(_fused_infer_fwd, _fused_infer_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def fused_conv_bn_act(x, w, gamma, beta, mean, var, *, strides, paddings,
                      eps, momentum, act=None, residual=None,
                      is_test=False, use_global_stats=False):
    """NCHW conv + BN + optional residual/relu with the fused kernels.

    Returns ``(y, mean_out, var_out, saved_mean, saved_var)`` with exactly
    the unfused ops' semantics (saved_* are None on the inference path,
    matching ``_batch_norm``). Callers must have checked
    :func:`use_pallas` — this function assumes a supported geometry."""
    n, c, h, w_dim = x.shape
    if tuple(strides) == (2, 2):  # exact for the supported 1x1 geometry
        x = x[:, :, ::2, ::2]
        h, w_dim = x.shape[2], x.shape[3]
    x2 = x.reshape(n, c, h * w_dim)
    o = w.shape[0]
    res2 = None
    if residual is not None:
        res2 = residual.reshape(n, o, h * w_dim)

    if is_test or use_global_stats:
        y2 = _fused_infer(x2, w, gamma, beta, mean, var, res2, h, w_dim,
                          float(eps), act)
        return (y2.reshape(n, o, h, w_dim), mean, var, None, None)

    y2, bm, bv = _fused_train(x2, w, gamma, beta, res2, h, w_dim,
                              float(eps), act)
    bm = jax.lax.stop_gradient(bm)
    bv = jax.lax.stop_gradient(bv)
    mean_out = momentum * mean + (1 - momentum) * bm
    var_out = momentum * var + (1 - momentum) * bv
    return y2.reshape(n, o, h, w_dim), mean_out, var_out, bm, bv
