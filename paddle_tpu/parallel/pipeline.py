"""Pipeline parallelism: GPipe-style microbatched execution over a ``pp``
mesh axis.

Absent from the 2019 reference (SURVEY.md §2.5D: "Pipeline parallelism —
no") but first-class here. TPU-native design: the L homogeneous stages'
parameters are stacked on a leading axis sharded ``P('pp')`` (one stage per
device); microbatches ride a ring of ``ppermute``s — device i runs stage i,
passes activations to i+1, so after the fill phase all devices compute every
step. Differentiable end-to-end (jax.grad through ppermute gives the 1F1B
-equivalent reverse schedule automatically; XLA overlaps the ICI sends with
stage compute).
"""

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "stack_stage_params", "pipeline_program_loss"]


def stack_stage_params(param_list):
    """Stack per-stage pytrees into one pytree with leading stage dim."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *param_list)


def pipeline_apply(stage_fn, stacked_params, x, mesh, axis="pp"):
    """Run ``n_stages`` chained applications of ``stage_fn`` over the mesh.

    Args:
      stage_fn: (params_i, h) -> h, one pipeline stage (shape-preserving on
        h — the classic homogeneous-stack formulation, e.g. transformer
        blocks).
      stacked_params: pytree with leading dim n_stages == mesh.shape[axis],
        laid out ``P(axis)`` on the stage dim.
      x: [n_micro, mb, ...] microbatched input (replicated).
      Returns [n_micro, mb, ...] outputs after all stages.
    """
    n = mesh.shape[axis]
    n_micro = x.shape[0]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(params, xs):
        # params: stage dim sharded -> leading dim 1 locally
        p = jax.tree_util.tree_map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        carry = zero  # activation arriving from the previous stage
        total = n_micro + n - 1
        for t in range(total):  # static unroll: small (micro + stages - 1)
            mb = min(t, n_micro - 1)
            inp = jnp.where(idx == 0, xs[mb], carry)
            # bubble steps (t >= n_micro on stage 0 etc.) compute garbage
            # that is never collected — cheaper than predicating compute
            out = stage_fn(p, inp)
            if t >= n - 1:
                # stage n-1 has just finished microbatch t-(n-1)
                outs = jnp.where(
                    (idx == n - 1)
                    & (jnp.arange(n_micro) == t - (n - 1))[
                        (slice(None),) + (None,) * (xs.ndim - 1)],
                    out[None], outs)
            carry = jax.lax.ppermute(out, axis, perm)
        # every device holds outs only on the last stage; share them
        return jax.lax.psum(outs, axis)

    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stacked_params)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
    )(stacked_params, x)


# ---------------------------------------------------------------------------
# Program-integrated pipeline parallelism
# ---------------------------------------------------------------------------
# ``CompiledProgram.with_pipeline`` routes a training program's autodiff
# replay through here: the forward op list is split into stages at named
# boundary variables, each device runs its stage body (lax.switch on
# axis_index), microbatches ride a ppermute ring inside one lax.scan, and
# jax.grad through the scan yields the GPipe reverse schedule. Heterogeneous
# stages are supported by packing each boundary's live set into one flat
# padded f32 carry. The 2019 reference has no pipeline engine (SURVEY §2.5D);
# the capability bar here is the Program-level integration.


# op types whose outputs depend on the RNG stream: never hoisted into the
# replicated per-stage setup subgraph (each stage folds its own key)
_RANDOM_OP_TYPES = frozenset((
    "dropout", "uniform_random", "gaussian_random",
    "truncated_gaussian_random", "randint", "random_crop", "sampling_id",
    "shuffle_channel",
))


def _split_stages(fwd_ops, boundaries):
    """Partition ops at the producers of the boundary vars (program order)."""
    prod_idx = []
    for bname in boundaries:
        idx = None
        for i, op in enumerate(fwd_ops):
            if bname in op.output_arg_names:
                idx = i
        if idx is None:
            raise ValueError("pipeline boundary %r is not produced by any "
                             "forward op" % bname)
        prod_idx.append(idx)
    if prod_idx != sorted(prod_idx):
        raise ValueError("pipeline boundaries must appear in program order; "
                         "got producer indices %s" % prod_idx)
    stages = []
    start = 0
    for idx in prod_idx:
        stages.append(fwd_ops[start:idx + 1])
        start = idx + 1
    stages.append(fwd_ops[start:])
    if not all(stages):
        raise ValueError("a pipeline stage is empty; check boundaries")
    return stages


def _crossing_sets(stages):
    """Per-consumer reaching definitions: for each boundary s, the vars
    whose value at the end of stage s is needed by a later stage.

    A read in stage s2 is *upward-exposed* when it happens before any write
    of the same name inside s2 (op program order); its reaching definition
    is the latest earlier stage ``wd`` that writes the name, and the var
    must ride the carry across every boundary wd..s2-1 (intermediate stages
    pass it through: unpack puts it in their local env, pack re-emits it).
    Because the carry at boundary b always holds the latest write <= b,
    non-SSA programs (a name shadowed by a later stage, or a feed/param
    overwritten by a stage and read downstream) get correct reaching-
    definition semantics instead of silently reading a stale step-start
    value. Names never written by any stage are feeds/params/setup values:
    replicated, never carried."""
    writes, ue_reads = [], []
    for ops in stages:
        w, r = set(), set()
        for op in ops:
            for n in op.input_arg_names:
                if n not in w:
                    r.add(n)
            for n in op.output_arg_names:
                w.add(n)
        writes.append(w)
        ue_reads.append(r)
    crossings = [set() for _ in range(len(stages) - 1)]
    for s2 in range(1, len(stages)):
        for n in ue_reads[s2]:
            defs = [w for w in range(s2) if n in writes[w]]
            if not defs:
                continue  # feed/param/setup value: replicated everywhere
            for b in range(max(defs), s2):
                crossings[b].add(n)
    return [sorted(c) for c in crossings]


def pipeline_program_loss(base_env, fwd_ops, loss_name, cfg, run_op,
                          rng0=None, shape_env=None):
    """Build ``loss_fn(params_dict) -> (mean_loss, {loss_name: value})``
    that executes ``fwd_ops`` as a microbatched pipeline over cfg['mesh']'s
    cfg['axis'].

    cfg keys: mesh, axis, boundaries (list of var names, n_stages-1 of
    them), n_micro, feed_names (env entries carrying a leading batch dim).

    Per-microbatch losses are averaged (the data-parallel convention); ops
    with cross-batch statistics (batch_norm) see microbatch stats.
    """
    mesh = cfg["mesh"]
    axis = cfg["axis"]
    n_stages = mesh.shape[axis]
    n_micro = int(cfg.get("n_micro") or n_stages)
    feed_names = [n for n in cfg["feed_names"] if n in base_env]

    stages = _split_stages(fwd_ops, cfg["boundaries"])
    if len(stages) != n_stages:
        raise ValueError("%d boundaries give %d stages but mesh axis %r has "
                         "size %d" % (len(cfg["boundaries"]), len(stages),
                                      axis, n_stages))

    # batch size: leading dim of the feeds (pipeline feeds must be
    # batch-major so they can be split into microbatches)
    batch = None
    for n in feed_names:
        if base_env[n].ndim == 0:
            raise ValueError(
                "pipeline mode requires batch-major feeds; %r is a scalar "
                "feed — make it a program constant or a [batch]-shaped "
                "feed instead" % n)
        b = base_env[n].shape[0]
        batch = b if batch is None else batch
        if b != batch:
            raise ValueError(
                "pipeline mode requires batch-major feeds; feed %r has "
                "leading dim %d but the batch is %d" % (n, b, batch))
    if batch is None or batch % n_micro:
        raise ValueError("batch %s not divisible into %d microbatches"
                         % (batch, n_micro))
    mb = batch // n_micro

    shapes_from = shape_env if shape_env is not None else base_env

    # batch-independent, RNG-free ops whose inputs are feeds/params or other
    # such ops (position ranges, constants, masks built from hyperparams):
    # replicated into every stage instead of carried across boundaries
    base_names = set(base_env)
    const_ops, const_names = [], set()
    for op in fwd_ops:
        if op.type in _RANDOM_OP_TYPES:
            continue
        if not all(n in base_names or n in const_names
                   for n in op.input_arg_names):
            continue
        outs = [shapes_from.get(n) for n in op.output_arg_names]
        if not outs or any(v is None for v in outs):
            continue
        if all(v.ndim == 0 or v.shape[0] != batch for v in outs):
            const_ops.append(op)
            const_names.update(op.output_arg_names)
    const_op_ids = {id(o) for o in const_ops}
    stages = [[o for o in ops if id(o) not in const_op_ids]
              for ops in stages]
    if not all(stages):
        raise ValueError("a pipeline stage contains only batch-independent "
                         "setup ops; move the boundary")
    crossings = _crossing_sets(stages)

    # carry layout per boundary: (name, mb_shape, dtype, offset, size).
    # shapes come from the already-traced outer forward (shape_env);
    # intermediates do not exist in the step-start base_env
    layouts = []
    flat_max = 1
    for cross in crossings:
        lay = []
        off = 0
        for n in cross:
            v = shapes_from.get(n)
            if v is None:
                raise ValueError("boundary-crossing var %r has no traced "
                                 "value" % n)
            if v.ndim == 0 or v.shape[0] != batch:
                raise ValueError(
                    "pipeline carries per-example activations; %r has shape "
                    "%s (batch is %d)" % (n, v.shape, batch))
            if not jnp.issubdtype(v.dtype, jnp.floating):
                raise ValueError("boundary-crossing var %r is %s; only "
                                 "float activations can cross stages"
                                 % (n, v.dtype))
            size = math.prod(int(d) for d in v.shape[1:])
            lay.append((n, (mb,) + v.shape[1:], v.dtype, off, size))
            off += size
        layouts.append(lay)
        flat_max = max(flat_max, off)

    def pack(local, lay):
        parts = [local[n].astype(jnp.float32).reshape(mb, -1)
                 for n, _, _, _, _ in lay]
        flat = jnp.concatenate(parts, axis=1) if parts else \
            jnp.zeros((mb, 0), jnp.float32)
        pad = flat_max - flat.shape[1]
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        return flat

    def unpack(flat, lay, local):
        for n, shape, dtype, off, size in lay:
            local[n] = jax.lax.dynamic_slice_in_dim(
                flat, off, size, axis=1).reshape(shape).astype(dtype)

    def loss_fn(params):
        replicated = dict(base_env)
        replicated.update(params)
        # pull feeds out and stack them [n_micro, mb, ...]
        stacked_feeds = {}
        for n in feed_names:
            x = replicated.pop(n)
            stacked_feeds[n] = x.reshape((n_micro, mb) + x.shape[1:])
        # drop non-array entries (snapshots, config) and the threaded RNG
        # keys (a fresh per-(tick, stage) key is folded inside) from the
        # captured env; shard_map closures must not capture traced arrays,
        # so everything an op reads is passed explicitly
        from ..core.op_registry import RNG_KEY, RNG0_KEY

        array_env = {k: v for k, v in replicated.items()
                     if k not in (RNG_KEY, RNG0_KEY)
                     and (isinstance(v, jax.Array) or hasattr(v, "aval"))}

        def device_body(env_repl, feeds, rng):
            sid = jax.lax.axis_index(axis)

            def make_stage(s):
                ops, lay_in = stages[s], (None if s == 0
                                          else layouts[s - 1])
                lay_out = layouts[s] if s < n_stages - 1 else None

                def stage_fn(carry_in, m, key):
                    from ..core.op_registry import RNG_KEY

                    local = dict(env_repl)
                    for fn_, fv in feeds.items():
                        local[fn_] = jax.lax.dynamic_index_in_dim(
                            fv, m, axis=0, keepdims=False)
                    local[RNG_KEY] = key
                    for op in const_ops:  # replicated setup subgraph
                        run_op(local, op)
                    if lay_in is not None:
                        unpack(carry_in, lay_in, local)
                    for op in ops:
                        run_op(local, op)
                    out = pack(local, lay_out) if lay_out is not None else \
                        jnp.zeros((mb, flat_max), jnp.float32)
                    # per-microbatch loss as the program computed it (a
                    # batch statistic, e.g. a mean) — averaged over
                    # microbatches below, the data-parallel convention
                    loss = (jnp.sum(local[loss_name]).astype(jnp.float32)
                            if s == n_stages - 1 else jnp.float32(0.0))
                    return out, loss

                return stage_fn

            stage_fns = [make_stage(s) for s in range(n_stages)]
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            total = n_micro + n_stages - 1

            def tick(carry, t):
                act = carry
                m = jnp.clip(t - sid, 0, n_micro - 1)
                key = jax.random.fold_in(jax.random.fold_in(rng, t), sid)
                out, loss = jax.lax.switch(
                    sid, stage_fns, act, m, key)
                valid = (t - sid >= 0) & (t - sid < n_micro)
                loss = jnp.where(valid & (sid == n_stages - 1), loss, 0.0)
                nxt = jax.lax.ppermute(out, axis, perm)
                return nxt, loss

            act0 = jnp.zeros((mb, flat_max), jnp.float32)
            _, losses = jax.lax.scan(tick, act0, jnp.arange(total))
            # per-microbatch losses live on the last stage; share + average
            return jax.lax.psum(jnp.sum(losses), axis) / n_micro

        env_specs = {k: P() for k in array_env}
        feed_specs = {k: P() for k in stacked_feeds}
        rng_spec = P()
        loss = jax.shard_map(
            device_body, mesh=mesh,
            in_specs=(env_specs, feed_specs, rng_spec),
            out_specs=P(),
            check_vma=False,
        )(array_env, stacked_feeds, rng0)
        return loss, {loss_name: loss}

    return loss_fn
