"""Multi-device tests on the 8-device virtual CPU mesh (SURVEY.md §4 item c:
the analog of the reference's ParallelExecutor convergence tests
``test_parallel_executor_*`` — same model single- vs multi-device, compare
losses)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

import paddle_tpu as fluid
from paddle_tpu import models
from paddle_tpu.parallel.ring_attention import ring_attention
from paddle_tpu.parallel.sharded_embedding import sharded_lookup
from paddle_tpu.ops.flash_attention import mha_reference


def _mesh(shape, axes):
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, axes)


def _train_mnist(compiled_mesh=None, steps=5):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        fluid.unique_name.switch()
        spec = models.mnist.mlp(hidden_sizes=(32,))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(spec.loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        prog = main
        if compiled_mesh is not None:
            prog = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=spec.loss.name, mesh=compiled_mesh)
        batch = spec.sample_batch(16, np.random.RandomState(5))
        losses = []
        for _ in range(steps):
            lv, = exe.run(prog, feed=batch, fetch_list=[spec.loss])
            losses.append(float(lv))
    return losses


def test_data_parallel_matches_single_device():
    """Same model + batch: 8-way dp must track the single-device loss
    (the reference's parallel-executor convergence criterion)."""
    single = _train_mnist(None)
    dp = _train_mnist(_mesh((8,), ("dp",)))
    np.testing.assert_allclose(single, dp, rtol=2e-3, atol=2e-3)


def test_dp_mp_transformer_converges():
    mesh = _mesh((2, 2, 2), ("dp", "mp", "sp"))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        spec = models.transformer.transformer_base(
            src_vocab=64, trg_vocab=64, seq_len=16, d_model=32, d_ff=64,
            n_head=2, n_layer=2, dropout_rate=0.0)
        fluid.optimizer.Adam(learning_rate=3e-3).minimize(spec.loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=spec.loss.name, mesh=mesh, sp_axis="sp",
            sequence_feeds=spec.sequence_feeds)
        batch = spec.sample_batch(4, np.random.RandomState(2))
        first = last = None
        for _ in range(6):
            lv, = exe.run(cp, feed=batch, fetch_list=[spec.loss])
            first = first if first is not None else float(lv)
            last = float(lv)
    assert last < first


def test_ring_attention_matches_reference():
    mesh = _mesh((4,), ("sp",))
    rng = np.random.RandomState(0)
    q = rng.randn(2, 2, 16, 8).astype("float32")
    k = rng.randn(2, 2, 16, 8).astype("float32")
    v = rng.randn(2, 2, 16, 8).astype("float32")
    for causal in (False, True):
        ref = mha_reference(jnp.array(q), jnp.array(k), jnp.array(v),
                            causal=causal)
        out = ring_attention(jnp.array(q), jnp.array(k), jnp.array(v),
                             mesh, axis="sp", causal=causal)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)


def test_sharded_lookup_matches_take():
    mesh = _mesh((4,), ("mp",))
    rng = np.random.RandomState(1)
    table = rng.randn(32, 6).astype("float32")
    ids = rng.randint(0, 32, size=(5, 3)).astype("int32")
    out = sharded_lookup(jnp.array(table), jnp.array(ids), mesh, axis="mp")
    np.testing.assert_allclose(np.asarray(out), table[ids], rtol=1e-6)


@pytest.mark.parametrize("strategy", ["alltoall", "psum"])
def test_sharded_lookup_strategies_agree(strategy):
    """ISSUE 13: both formulations must match the unsharded gather —
    duplicate ids, an id count that doesn't divide the shard count
    (the routed path's padding tail), and a packed-width table."""
    mesh = _mesh((4,), ("mp",))
    rng = np.random.RandomState(2)
    table = rng.randn(64, 16).astype("float32")  # K=16 packs (128/16=8)
    ids = rng.randint(0, 64, size=(13,)).astype("int32")
    ids[3] = ids[4] = ids[5]  # duplicates
    out = sharded_lookup(jnp.array(table), jnp.array(ids), mesh,
                         axis="mp", strategy=strategy)
    np.testing.assert_allclose(np.asarray(out), table[ids], rtol=1e-6)


@pytest.mark.parametrize("strategy", ["alltoall", "psum"])
def test_sharded_lookup_pathological_skew(strategy):
    """Every id owned by ONE shard: the routed path's skew-proof
    per-destination capacity (cap = ceil(n/mp)) must stay exact — no
    dropped rows under any distribution (the capacity-factor contract,
    parallel/sharded_embedding.py)."""
    mesh = _mesh((4,), ("mp",))
    rng = np.random.RandomState(3)
    table = rng.randn(32, 8).astype("float32")
    # all ids in the LAST shard's range [24, 32)
    ids = rng.randint(24, 32, size=(21,)).astype("int32")
    out = sharded_lookup(jnp.array(table), jnp.array(ids), mesh,
                         axis="mp", strategy=strategy)
    np.testing.assert_allclose(np.asarray(out), table[ids], rtol=1e-6)


def test_sharded_lookup_out_of_range_rows_zero():
    """Both formulations keep the contract that unowned/out-of-range ids
    read as zero rows (the psum path's mask semantics)."""
    mesh = _mesh((4,), ("mp",))
    rng = np.random.RandomState(4)
    table = rng.randn(32, 8).astype("float32")
    ids = np.array([0, 31, 40, 100], dtype="int32")  # 40,100 out of range
    for strategy in ("alltoall", "psum"):
        out = np.asarray(sharded_lookup(jnp.array(table), jnp.array(ids),
                                        mesh, axis="mp",
                                        strategy=strategy))
        np.testing.assert_allclose(out[:2], table[ids[:2]], rtol=1e-6)
        np.testing.assert_allclose(out[2:], 0.0)


def test_sharded_lookup_strategy_selection():
    from paddle_tpu.parallel.sharded_embedding import choose_strategy

    assert choose_strategy(1024, 8) == "alltoall"
    # degenerate slices: the route/sort overhead can't amortize
    assert choose_strategy(8, 8) == "psum"
    # the threshold: 8 ids a shard
    assert choose_strategy(57, 8) == "alltoall"
    assert choose_strategy(56, 8) == "psum"


def test_sharded_lookup_alltoall_grad_matches():
    """Dense-grad tables differentiate through the routed collectives
    (all_to_all/all_gather transposes) to the same table gradient as
    the plain gather."""
    mesh = _mesh((4,), ("mp",))
    rng = np.random.RandomState(5)
    table = jnp.array(rng.randn(32, 8).astype("float32"))
    ids = jnp.array(rng.randint(0, 32, size=(12,)).astype("int32"))

    def loss_routed(t):
        return jnp.sum(sharded_lookup(t, ids, mesh, axis="mp",
                                      strategy="alltoall") ** 2)

    g = jax.grad(loss_routed)(table)
    g_ref = jax.grad(lambda t: jnp.sum(t[ids] ** 2))(table)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_ids,strategy", [(16, "alltoall"), (5, "psum")])
def test_sharded_lookup_op_padding_idx(n_ids, strategy):
    """padding_idx through the SYMBOLIC op under a live mesh: padding
    rows read as zeros on both formulations (an id count on each side of
    ``choose_strategy``'s threshold over mp=2), matching the single-chip
    lookup_table run of the same program."""
    from paddle_tpu.parallel.sharded_embedding import choose_strategy
    from paddle_tpu.parallel.transpiler import DistributeTranspiler
    from paddle_tpu.parallel.mesh import DistStrategy, mesh_scope

    assert choose_strategy(n_ids, 2) == strategy
    ids_np = np.resize(np.array([0, 3, 7, 0, 15], dtype="int64"),
                       (n_ids, 1))

    def run(sharded):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 9
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), fluid.scope_guard(scope):
            fluid.unique_name.switch()
            x = fluid.layers.data("ids", shape=[1], dtype="int64")
            emb = fluid.layers.embedding(
                x, size=[16, 8], padding_idx=0, is_sparse=True,
                is_distributed=True)
            out = fluid.layers.reduce_sum(emb, dim=1)
            exe = fluid.Executor(fluid.CPUPlace())
            if sharded:
                DistributeTranspiler().transpile(
                    trainer_id=0, program=main, trainers=8,
                    strategy=DistStrategy(dp=4, mp=2,
                                          sharded_embeddings=True))
                assert any(o.type == "sharded_lookup_table"
                           for o in main.global_block().ops)
            exe.run(startup)
            ctx = mesh_scope(main._mesh) if sharded else \
                fluid.scope_guard(scope)
            with ctx:
                ev, = exe.run(main, feed={"ids": ids_np},
                              fetch_list=[emb])
            w = scope.numpy(main.all_parameters()[0].name)
        return np.asarray(ev), w

    plain, w = run(sharded=False)
    shard, _ = run(sharded=True)
    # padding rows exactly zero; non-padding rows match the plain run's
    # contract (w may differ across builds, so compare vs own table)
    pad = ids_np[:, 0] == 0
    np.testing.assert_allclose(plain[pad], 0.0)
    np.testing.assert_allclose(shard[pad], 0.0)
    np.testing.assert_allclose(shard[~pad], w[ids_np[~pad, 0]], rtol=1e-6)


def test_dryrun_sharded_embedding_stage():
    """The ISSUE 13 multichip dryrun stage, run directly on the CPU mesh:
    DeepFM trains with the table mp-sharded, the compiled HLO keeps the
    table sharded with no full-table all-gather, the step jaxpr carries
    the all-to-all lookup with NO full-output psum, and the
    psum-of-partials negative control trips the audit."""
    import __graft_entry__ as graft

    graft._stage_sharded_embedding(fluid.Executor(fluid.XLAPlace(0)),
                                   jax.devices()[:8], 8)


def test_distribute_transpiler_annotates():
    from paddle_tpu.parallel.transpiler import DistributeTranspiler
    from paddle_tpu.parallel.mesh import DistStrategy

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        spec = models.deepfm.deepfm(sparse_feature_dim=64, num_fields=4,
                                    embedding_size=4, dense_dim=3,
                                    hidden_sizes=(8,))
    t = DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, trainers=8,
                strategy=DistStrategy(dp=4, mp=2, sharded_embeddings=True))
    trainer_prog = t.get_trainer_program()
    assert trainer_prog is not None
    emb = main._params.get("fm_table")
    assert emb is not None and emb.sharding is not None
    # lookups on sharded tables route through the shard_map pserver-analog
    assert any(o.type == "sharded_lookup_table"
               for o in main.global_block().ops)


def _train_deepfm(sharded, steps=6):
    """DeepFM loss trajectory: single-chip plain vs (dp=4, mp=2) with the
    embedding tables row-sharded over mp (the pserver-mode sync-equivalent
    whose convergence parity SURVEY §7 requires — ref
    ``distribute_transpiler.py:84`` slice_variable)."""
    from paddle_tpu.parallel.transpiler import DistributeTranspiler
    from paddle_tpu.parallel.mesh import DistStrategy, mesh_scope

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 13
    with fluid.program_guard(main, startup):
        fluid.unique_name.switch()
        spec = models.deepfm.deepfm(sparse_feature_dim=64, num_fields=4,
                                    embedding_size=8, dense_dim=3,
                                    hidden_sizes=(16,))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(spec.loss)
    scope = fluid.Scope()
    batch = spec.sample_batch(8, np.random.RandomState(7))
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if sharded:
            t = DistributeTranspiler()
            t.transpile(trainer_id=0, program=main, trainers=8,
                        strategy=DistStrategy(dp=4, mp=2,
                                              sharded_embeddings=True))
            cp = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=spec.loss.name, mesh=main._mesh, dp_axis="dp")
            with mesh_scope(main._mesh):
                for _ in range(steps):
                    lv, = exe.run(cp, feed=batch, fetch_list=[spec.loss])
                    losses.append(float(lv))
        else:
            for _ in range(steps):
                lv, = exe.run(main, feed=batch, fetch_list=[spec.loss])
                losses.append(float(lv))
    return losses


def test_sharded_deepfm_convergence_parity():
    """Sharded-embedding mode must track the single-chip loss trajectory —
    the sync-equivalence evidence for the dropped async-pserver semantics
    (SURVEY §7; ref capability dist_ctr pserver training)."""
    single = _train_deepfm(False)
    sharded = _train_deepfm(True)
    np.testing.assert_allclose(single, sharded, rtol=2e-3, atol=2e-3)
    assert sharded[-1] < sharded[0]


def test_pipeline_matches_serial():
    from paddle_tpu.parallel.pipeline import (pipeline_apply,
                                              stack_stage_params)

    mesh = _mesh((4,), ("pp",))
    rng = np.random.RandomState(0)
    d = 8
    stages = [{"w": rng.randn(d, d).astype("f4") * 0.3,
               "b": rng.randn(d).astype("f4") * 0.1} for _ in range(4)]

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    x = rng.randn(6, 5, d).astype("f4")  # [n_micro=6, mb=5, d]
    stacked = stack_stage_params([
        {k: jnp.array(v) for k, v in s.items()} for s in stages])
    out = pipeline_apply(stage_fn, stacked, jnp.array(x), mesh, axis="pp")

    ref = jnp.array(x)
    for s in stages:
        ref = jnp.tanh(ref @ jnp.array(s["w"]) + jnp.array(s["b"]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_differentiable():
    from paddle_tpu.parallel.pipeline import (pipeline_apply,
                                              stack_stage_params)

    mesh = _mesh((2,), ("pp",))
    rng = np.random.RandomState(1)
    d = 4
    stacked = stack_stage_params([
        {"w": jnp.array(rng.randn(d, d).astype("f4") * 0.3)}
        for _ in range(2)])

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    x = jnp.array(rng.randn(4, 3, d).astype("f4"))

    def loss_fn(params):
        return jnp.sum(pipeline_apply(stage_fn, params, x, mesh,
                                      axis="pp") ** 2)

    g = jax.grad(loss_fn)(stacked)
    assert np.isfinite(np.asarray(g["w"])).all()
    assert float(jnp.abs(g["w"]).sum()) > 0


# ---------------------------------------------------------------------------
# pipeline parallelism through the Program path (CompiledProgram.with_pipeline)
# ---------------------------------------------------------------------------

def _build_chain_program(seed=3):
    """4-stage MLP chain with named cut points; returns (spec-ish tuple)."""
    fluid.unique_name.switch()
    x = fluid.layers.data("x", shape=[16])
    y = fluid.layers.data("y", shape=[1])
    h = x
    cuts = []
    for i in range(4):
        h = fluid.layers.fc(h, size=16, act="tanh", name="blk%d" % i)
        if i < 3:
            cuts.append(h.name)
    pred = fluid.layers.fc(h, size=1, name="head")
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    return loss, cuts


def test_pipeline_program_matches_single_device():
    rng = np.random.RandomState(7)
    xs = rng.randn(8, 16).astype("float32")
    ys = rng.randn(8, 1).astype("float32")

    def run(pipeline):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), fluid.scope_guard(scope):
            loss, cuts = _build_chain_program()
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            prog = main
            if pipeline:
                mesh = _mesh((4,), ("pp",))
                prog = fluid.CompiledProgram(main).with_pipeline(
                    loss_name=loss.name, mesh=mesh, boundaries=cuts,
                    n_microbatches=4)
            losses = []
            for _ in range(4):
                lv, = exe.run(prog, feed={"x": xs, "y": ys},
                              fetch_list=[loss])
                losses.append(float(lv))
            w = scope.numpy("blk0.w_0_0")
        return losses, w

    ref_losses, ref_w = run(pipeline=False)
    pp_losses, pp_w = run(pipeline=True)
    np.testing.assert_allclose(pp_losses, ref_losses, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(pp_w, ref_w, rtol=2e-4, atol=1e-5)


def test_pipeline_transformer_smoke():
    """Enc-dec transformer under pp=2: heterogeneous carry (enc output
    crosses every decoder boundary); loss finite and decreasing."""
    mesh = _mesh((2,), ("pp",))
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        fluid.unique_name.switch()
        spec = models.transformer.transformer_base(
            src_vocab=64, trg_vocab=64, seq_len=8, d_model=16, d_ff=32,
            n_head=2, n_layer=2, dropout_rate=0.0)
        fluid.optimizer.Adam(learning_rate=2e-3).minimize(spec.loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        # cut after the encoder stack: stage 0 = encoder (+embeds),
        # stage 1 = decoder + loss
        cut = spec.extras["enc_out"]
        prog = fluid.CompiledProgram(main).with_pipeline(
            loss_name=spec.loss.name, mesh=mesh, boundaries=[cut],
            n_microbatches=2)
        feed = spec.sample_batch(4, np.random.RandomState(0))
        losses = [float(exe.run(prog, feed=feed,
                                fetch_list=[spec.loss])[0])
                  for _ in range(6)]
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


def test_zero_reduce_strategy_shards_optimizer_state():
    """BuildStrategy.ReduceStrategy.Reduce = ZeRO-style: losses match
    AllReduce mode and the Adam accumulators live dp-sharded on the mesh."""
    mesh = _mesh((8,), ("dp",))
    rng = np.random.RandomState(4)
    xs = rng.randn(16, 16).astype("float32")
    ys = rng.randn(16, 1).astype("float32")

    def run(reduce_mode):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 17
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), fluid.scope_guard(scope):
            fluid.unique_name.switch()
            x = fluid.layers.data("x", shape=[16])
            y = fluid.layers.data("y", shape=[1])
            h = fluid.layers.fc(x, size=32, act="tanh")
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(h, size=1), y))
            opt = fluid.optimizer.Adam(learning_rate=0.01)
            opt.minimize(loss)
            bs = fluid.BuildStrategy()
            if reduce_mode:
                bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            prog = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, mesh=mesh, build_strategy=bs)
            losses = [float(exe.run(prog, feed={"x": xs, "y": ys},
                                    fetch_list=[loss])[0])
                      for _ in range(4)]
            # moment accumulator for the [16,32] fc weight
            acc_name = next(
                v.name for n, d in opt._accumulators.items()
                for v in d.values()
                if n == "moment1" and tuple(v.shape) == (16, 32))
            acc = scope.get(acc_name)
        return losses, acc

    ref_losses, acc_all = run(reduce_mode=False)
    z_losses, acc_zero = run(reduce_mode=True)
    np.testing.assert_allclose(z_losses, ref_losses, rtol=1e-5, atol=1e-7)
    # state parity AND dp-sharded residency in Reduce mode
    np.testing.assert_allclose(np.asarray(acc_zero), np.asarray(acc_all),
                               rtol=1e-5, atol=1e-8)
    from jax.sharding import PartitionSpec as P
    assert acc_all.sharding.is_fully_replicated
    assert acc_zero.sharding.spec == P("dp", None)


def test_pipeline_crossing_sets_reaching_defs():
    """Non-SSA programs: a name shadowed in a later stage must be carried
    with per-consumer reaching-definition semantics (ADVICE r2 #1)."""
    from paddle_tpu.parallel.pipeline import _crossing_sets

    class Op:
        def __init__(self, ins, outs):
            self.input_arg_names = ins
            self.output_arg_names = outs

    # stage0 writes h; stage1 reads h (old value) AND shadows h; stage2
    # reads h (new value). h must cross boundary 0 (for stage1's read) and
    # boundary 1 (stage1's shadowing write reaches stage2).
    stages = [[Op(["x"], ["h"])],
              [Op(["h"], ["t"]), Op(["t"], ["h"])],
              [Op(["h"], ["loss"])]]
    cross = _crossing_sets(stages)
    assert cross == [["h"], ["h"]]

    # a feed/param name overwritten by stage0 and read by stage2 must be
    # carried (not silently re-read from the replicated step-start value)
    stages = [[Op(["w"], ["w"])], [Op(["x"], ["u"])], [Op(["w", "u"], ["l"])]]
    cross = _crossing_sets(stages)
    assert cross == [["w"], ["u", "w"]]

    # read-after-local-write is NOT upward-exposed: no carry needed
    stages = [[Op(["x"], ["a"])], [Op(["x"], ["h"]), Op(["h"], ["b"])],
              [Op(["a", "b"], ["l"])]]
    cross = _crossing_sets(stages)
    assert cross == [["a"], ["a", "b"]]


def test_compiled_hlo_sharding_quality():
    """VERDICT r3 ask #7: the lowered mesh step's HLO must show (a) no
    full-parameter all-gather in a plain-dp steady state and (b) actually
    sharded mp-annotated params; negative controls prove the checks can
    fail."""
    import pytest
    from paddle_tpu import models
    from paddle_tpu.parallel import sharding_check

    mesh = _mesh((2, 2, 2), ("dp", "mp", "sp"))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        spec = models.transformer.transformer_base(
            src_vocab=64, trg_vocab=64, seq_len=32, d_model=64, d_ff=128,
            n_head=4, n_layer=1, dropout_rate=0.0)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(spec.loss)
    exe = fluid.Executor(fluid.XLAPlace(0))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=spec.loss.name, mesh=mesh, dp_axis="dp",
            sp_axis="sp", sequence_feeds=spec.sequence_feeds)
        feed = spec.sample_batch(4, np.random.RandomState(0))
        lv, = exe.run(cp, feed=feed, fetch_list=[spec.loss])
        hlo = exe.lowered_hlo_text()
    assert np.isfinite(lv).all()

    pshapes = [tuple(p.shape) for p in main.global_block().all_parameters()]
    sharding_check.assert_no_param_allgather(hlo, pshapes)
    sharding_check.assert_param_sharded(hlo, "enc0_ffn_fc1.w", (64, 128))

    # negative controls: a replicated var must FAIL the sharded check;
    # an activation all-gather shape posed as a "param" must FAIL (a)
    with pytest.raises(AssertionError):
        sharding_check.assert_param_sharded(hlo, "src_word_emb")
    ag = [s for s in sharding_check.collect_allgather_shapes(hlo)
          if len(s) >= 2]  # 1-D shapes are filtered by the check itself
    assert ag, "expected >=2-D activation all-gathers under mp/sp sharding"
    with pytest.raises(AssertionError):
        sharding_check.assert_no_param_allgather(hlo, [ag[0]])


def test_pipeline_sparse_embedding_matches_single_device():
    """An ``is_sparse`` embedding trains correctly under pipeline
    parallelism: the table grad densifies through the GPipe scan (rows =
    arange contract — control_ops pp branch) and the loss/weight
    trajectory matches the single-device run."""
    rng = np.random.RandomState(5)
    ids = rng.randint(0, 30, size=(8, 1)).astype("int64")
    ys = rng.randn(8, 1).astype("float32")

    def run(pipeline):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 21
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), fluid.scope_guard(scope):
            fluid.unique_name.switch()
            x = fluid.layers.data("ids", shape=[1], dtype="int64")
            y = fluid.layers.data("y", shape=[1])
            emb = fluid.layers.embedding(x, size=[30, 16], is_sparse=True)
            h = emb
            cuts = []
            for i in range(4):
                h = fluid.layers.fc(h, size=16, act="tanh",
                                    name="sblk%d" % i)
                if i < 3:
                    cuts.append(h.name)
            pred = fluid.layers.fc(h, size=1, name="shead")
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            pg = fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)[1]
            table = main.all_parameters()[0]
            (p, g), = [t for t in pg if t[0].name == table.name]
            assert getattr(g, "sparse_rows_var", None) is not None
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            prog = main
            if pipeline:
                mesh = _mesh((4,), ("pp",))
                prog = fluid.CompiledProgram(main).with_pipeline(
                    loss_name=loss.name, mesh=mesh, boundaries=cuts,
                    n_microbatches=4)
            losses = []
            for _ in range(4):
                lv, = exe.run(prog, feed={"ids": ids, "y": ys},
                              fetch_list=[loss])
                losses.append(float(lv))
            w = scope.numpy(table.name)
        return losses, w

    ref_losses, ref_w = run(pipeline=False)
    pp_losses, pp_w = run(pipeline=True)
    np.testing.assert_allclose(pp_losses, ref_losses, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(pp_w, ref_w, rtol=2e-4, atol=1e-5)
