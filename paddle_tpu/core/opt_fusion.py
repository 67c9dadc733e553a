"""Batched ("foreach") optimizer updates.

The reference runs one CUDA kernel per parameter update (``operators/
optimizers/adam_op.h`` etc.); on TPU one *fusion* per parameter pays a
fixed dispatch/DMA setup, so a transformer-base's ~160 small updates cost
far more than their HBM traffic. This pass batches all dense update ops of
the same family and
hyperparameters into ONE update over the ravel+concat of their operands,
then splits the results back — pure trace-time rewriting, no Program or
checkpoint-format change (parameters remain individual vars).

Only dense ops fuse; SelectedRows (GradRows) updates keep their scatter
kernels. The multi-device path keeps per-param updates so GSPMD sharding
propagation (ZeRO etc.) stays per-tensor.
"""

import jax.numpy as jnp

__all__ = ["plan_opt_fusion", "run_fused_group"]

_FUSIBLE = ("sgd", "momentum", "adam")


def plan_opt_fusion(ops):
    """Return (plan, skip): ``plan`` maps trigger op index -> member op
    list (executed batched at that index); ``skip`` is the set of member
    indices the main loop must not run individually."""
    groups = {}
    for i, op in enumerate(ops):
        if op.type not in _FUSIBLE or not op.attrs.get("is_optimizer_op"):
            continue
        if op.input("GradRows") is not None:
            continue
        if op.attrs.get("_switch_cond") is not None:
            # Switch-guarded update: run_op's conditional output revert
            # must apply, which the batched path would bypass
            continue
        lr = op.input("LearningRate")
        key = (op.type, lr.name if lr is not None else None,
               op.attr("beta1", None), op.attr("beta2", None),
               op.attr("epsilon", None), op.attr("mu", None),
               op.attr("use_nesterov", None))
        groups.setdefault(key, []).append((i, op))

    plan, skip = {}, set()
    for members in groups.values():
        if len(members) < 2:
            continue
        idxs = [i for i, _ in members]
        lo, hi = min(idxs), max(idxs)
        # safety: an op between the members must not read a member's
        # output (it would observe the pre-update value once batched) NOR
        # write a member's input or output (the deferred member would
        # observe the post-write value instead of its program-order one)
        outs, ins = set(), set()
        for _, op in members:
            for vs in op.outputs.values():
                outs.update(v.name for v in vs)
            for vs in op.inputs.values():
                ins.update(v.name for v in vs)
        member_set = set(idxs)
        hazard = False
        for j in range(lo, hi):
            if j in member_set:
                continue
            for vs in ops[j].inputs.values():
                if any(v.name in outs for v in vs):
                    hazard = True
                    break
            for vs in ops[j].outputs.values():
                if any(v.name in outs or v.name in ins for v in vs):
                    hazard = True
                    break
            if hazard:
                break
        if hazard:
            continue
        plan[hi] = [op for _, op in members]
        skip.update(i for i in idxs if i != hi)
    return plan, skip


def _gather(env, ops, slot):
    return [env[op.input(slot).name] for op in ops]


def _flat(xs, dtype):
    return jnp.concatenate([x.reshape(-1).astype(dtype) for x in xs])


def _scatter(env, ops, slot, flat, shapes, dtypes):
    off = 0
    for op, shape, dt in zip(ops, shapes, dtypes):
        n = 1
        for s in shape:
            n *= s
        env[op.output(slot).name] = \
            flat[off:off + n].reshape(shape).astype(dt)
        off += n


def _seg_vec(scalars, sizes, dtype):
    return jnp.concatenate(
        [jnp.broadcast_to(s.astype(dtype), (n,)) for s, n in
         zip(scalars, sizes)])


def run_fused_group(env, ops):
    """Execute one planned group batched. Members were validated dense and
    hyperparameter-identical by ``plan_opt_fusion``."""
    from .op_registry import get

    kind = ops[0].type
    # sub-group by parameter dtype (concat needs one dtype; update math
    # runs in it, matching the per-op promotion rules)
    by_dtype = {}
    for op in ops:
        p = get(env, op.input("Param"))
        by_dtype.setdefault(p.dtype, []).append(op)

    for dtype, grp in by_dtype.items():
        ps = _gather(env, grp, "Param")
        shapes = [p.shape for p in ps]
        dtypes = [p.dtype for p in ps]
        sizes = [int(p.size) for p in ps]
        pf = _flat(ps, dtype)
        gf = _flat(_gather(env, grp, "Grad"), dtype)
        lr = get(env, grp[0].input("LearningRate")).reshape(()).astype(dtype)

        if kind == "sgd":
            out = pf - lr * gf
            _scatter(env, grp, "ParamOut", out, shapes, dtypes)
        elif kind == "momentum":
            mu = grp[0].attr("mu")
            vf = _flat(_gather(env, grp, "Velocity"), dtype)
            v_new = mu * vf + gf
            if grp[0].attr("use_nesterov", False):
                p_new = pf - (gf + mu * v_new) * lr
            else:
                p_new = pf - lr * v_new
            _scatter(env, grp, "ParamOut", p_new, shapes, dtypes)
            _scatter(env, grp, "VelocityOut", v_new, shapes, dtypes)
        elif kind == "adam":
            b1 = grp[0].attr("beta1", 0.9)
            b2 = grp[0].attr("beta2", 0.999)
            eps = grp[0].attr("epsilon", 1e-8)
            mf = _flat(_gather(env, grp, "Moment1"), dtype)
            vf = _flat(_gather(env, grp, "Moment2"), dtype)
            # Beta{1,2}Pow are per-parameter accumulator vars (identical
            # values in practice, but separate state): keep them exact via
            # a per-segment lr_t vector
            b1ps = [get(env, op.input("Beta1Pow")).reshape(()) for op in grp]
            b2ps = [get(env, op.input("Beta2Pow")).reshape(()) for op in grp]
            lrts = [lr * jnp.sqrt(1 - b2p) / (1 - b1p)
                    for b1p, b2p in zip(b1ps, b2ps)]
            lrt = _seg_vec(lrts, sizes, dtype)
            m_new = b1 * mf + (1 - b1) * gf
            v_new = b2 * vf + (1 - b2) * jnp.square(gf)
            p_new = pf - lrt * m_new / (jnp.sqrt(v_new) + eps)
            _scatter(env, grp, "ParamOut", p_new, shapes, dtypes)
            _scatter(env, grp, "Moment1Out", m_new, shapes, dtypes)
            _scatter(env, grp, "Moment2Out", v_new, shapes, dtypes)
            for op, b1p, b2p in zip(grp, b1ps, b2ps):
                env[op.output("Beta1PowOut").name] = \
                    (b1p * b1).reshape((1,))
                env[op.output("Beta2PowOut").name] = \
                    (b2p * b2).reshape((1,))
        else:  # pragma: no cover - plan only admits _FUSIBLE kinds
            raise AssertionError(kind)
