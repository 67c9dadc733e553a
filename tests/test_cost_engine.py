"""Analysis v2 (ISSUE 15): static cost/roofline engine, SPMD collective
& sharding verifier, and resource lints.

Acceptance pins:
  * registry parity — every op with a shape rule has a cost rule (or an
    explicit zero-cost registration);
  * ResNet-50 static bytes agree with the PREVIOUS ad-hoc model
    (reproduced inline below) within 5%; DeepFM's comm-bytes line agrees
    exactly (it delegates);
  * the cost engine emits a static roofline estimate for all 6 BASELINE
    configs;
  * a deliberately mismatched two-program collective sequence and a
    VMEM-overflowing Pallas shape are both reported as findings with op
    provenance.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis import cost as cost_mod
from paddle_tpu.analysis import resources, spmd
from paddle_tpu.core.op_registry import COST_RULES, SHAPE_RULES


# ---------------------------------------------------------------------------
# registry parity
# ---------------------------------------------------------------------------

def test_every_shape_rule_has_a_cost_rule():
    """A new op cannot silently fall out of the roofline: registering a
    shape rule obliges a cost rule (register_zero_cost counts — that is
    an explicit statement, not an omission)."""
    missing = sorted(set(SHAPE_RULES) - set(COST_RULES))
    assert not missing, (
        "ops with shape rules but no cost rule (add one in "
        "core/opimpl/cost_rules.py, or register_zero_cost): %s" % missing)


# ---------------------------------------------------------------------------
# ResNet-50 agreement with the previous ad-hoc model (<= 5%)
# ---------------------------------------------------------------------------

def _legacy_resnet_bytes(program, batch):
    """The pre-ISSUE-15 ad-hoc bytes model (verbatim accounting): the
    agreement target."""
    e = 2  # bf16
    convs = []
    gb = program.global_block()
    for op in gb.ops:
        if op.type != "conv2d":
            continue
        x, w, o = op.input("Input"), op.input("Filter"), op.output("Output")
        convs.append(((batch,) + tuple(x.shape[1:]), tuple(w.shape),
                      (batch,) + tuple(o.shape[1:])))
    conv_fwd = conv_dx = conv_dw = act_elems = 0
    for i, (xs, ws, os_) in enumerate(convs):
        n, c, h, w_ = xs
        o, _, kh, kw = ws
        _, _, oh, ow = os_
        x_b = n * c * h * w_ * e
        y_b = n * o * oh * ow * e
        w_b = o * c * kh * kw * e
        conv_fwd += x_b + w_b + y_b
        if i != 0:  # stem dX excluded (images carry no gradient)
            conv_dx += y_b + w_b + x_b
        conv_dw += x_b + y_b + o * c * kh * kw * 4
        act_elems += n * o * oh * ow
    pool_bytes = 0
    for op in gb.ops:
        if op.type == "pool2d" and op.attr("pooling_type", "max") == "max":
            xb = batch * int(np.prod(op.input("X").shape[1:])) * e
            ob = batch * int(np.prod(op.output("Out").shape[1:])) * e
            pool_bytes += (xb + ob) + (xb + 2 * ob)
    n_params = sum(int(np.prod(p.shape)) for p in program.all_parameters())
    adam_bytes = 6 * n_params * 4
    res_bytes = 0
    for op in gb.ops:
        if op.type == "elementwise_add":
            x = op.input("X")
            if x is not None and x.shape is not None and len(x.shape) == 4:
                res_bytes += 3 * batch * int(np.prod(x.shape[1:])) * e
    return (conv_fwd + conv_dx + conv_dw + 2 * act_elems * e
            + pool_bytes + adam_bytes + res_bytes)


def _resnet_train_program():
    from paddle_tpu import models

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fluid.unique_name.switch()
        spec = models.resnet.resnet_imagenet(depth=50, class_num=10,
                                             image_shape=(3, 64, 64))
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(spec.loss)
    return main


def test_resnet50_static_bytes_agree_with_legacy_model():
    main = _resnet_train_program()
    batch = 8
    est = cost_mod.estimate_program(main, batch=batch, amp=True)
    legacy = _legacy_resnet_bytes(main, batch)
    assert est.train
    assert not est.uncosted, est.uncosted
    ratio = est.hbm_bytes / legacy
    assert 0.95 <= ratio <= 1.05, (
        "cost engine %.0f vs ad-hoc model %.0f bytes (%.3fx — the 5%% "
        "acceptance bound)" % (est.hbm_bytes, legacy, ratio))


# ---------------------------------------------------------------------------
# DeepFM agreement: comm bytes delegated
# ---------------------------------------------------------------------------

def test_comm_bytes_model_is_single_sourced():
    from paddle_tpu.parallel import sharded_embedding as semb

    n, d, m, e = 851968, 32, 8, 4
    ours = cost_mod.comm_bytes_model(n, d, m, e)
    theirs = semb.comm_bytes_model(n, d, m, e)
    assert ours == theirs
    # the closed forms themselves
    nd = n * d * e
    assert ours["psum_total_bytes"] == m * nd
    assert ours["alltoall_total_bytes"] == n * 4 + nd + int(
        (m - 1) / m * nd)


# ---------------------------------------------------------------------------
# roofline: four constants of the module's own, under one label
# ---------------------------------------------------------------------------

# round-5 readings of an installation that is gone: what the label says,
# and not the peaks the benchmark's rooflines divide by
_CEILINGS = {
    "matmul_flops": 185.3e12, "hbm_bytes_per_s": 552.2e9,
    "gather_ns_per_row": 2.0, "scatter_ns_per_row": 15.0,
    "source": "round-5 v5e readings of an installation that is gone; "
              "not benchmark/peaks.json"}


def test_roofline_sources_committed_ceilings():
    main = _resnet_train_program()
    est = cost_mod.estimate_program(main, batch=2, amp=True)
    r = est.roofline()
    assert r["ceilings"] == _CEILINGS
    assert r["t_compute_s"] == pytest.approx(r["flops"] / 185.3e12)
    assert r["t_hbm_s"] == pytest.approx(r["hbm_bytes"] / 552.2e9)
    assert r["roofline_s"] == pytest.approx(
        max(r["t_compute_s"], r["t_hbm_s"]) + r["t_row_s"])
    assert r["bound"] == "hbm"  # resnet50 is HBM-bound at these constants


# ---------------------------------------------------------------------------
# BASELINE sweep: all 6 configs emit a static roofline estimate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config,batch,bound,rows", [
    # 26 fields an example: one packed gather, one backward scatter-add
    ("deepfm", 16, "rows", 16 * 26),
    ("seq2048", 4, "compute", 20480),
    ("resnet50", 2, "hbm", 0),
    ("bert_dygraph", 4, "hbm", 288),
    ("bert", 4, "hbm", 288),
    ("transformer", 4, "hbm", 640),
])
def test_baseline_cost_records_cover_all_six_configs(config, batch, bound,
                                                     rows):
    from paddle_tpu.analysis.cli import BASELINE_CONFIGS, \
        baseline_cost_records

    assert config in BASELINE_CONFIGS and len(BASELINE_CONFIGS) == 6
    (r,) = baseline_cost_records([config], small=True)  # fast build
    assert (r["config"], r["batch"], r["bound"]) == (config, batch, bound)
    assert r["train"] and r["amp"]
    assert r["flops"] > 0 and r["hbm_bytes"] > 0
    assert (r["row_reads"], r["row_writes"]) == (rows, rows)
    assert r["t_row_s"] == pytest.approx(rows * (2.0 + 15.0) * 1e-9)
    assert r["roofline_s"] == pytest.approx(
        max(r["t_compute_s"], r["t_hbm_s"]) + r["t_row_s"])
    assert r["uncosted_ops"] == [] and r["unresolved_ops"] == []
    assert r["ceilings"] == _CEILINGS
    assert ("note" in r) == (config == "bert_dygraph")
    if config == "deepfm":
        # within a few % of the closed-form MLP (the engine also counts
        # the FM interaction ops)
        mlp = 6 * (429 * 64 + 64 * 64 + 64)
        assert r["flops"] / batch == pytest.approx(mlp, rel=0.05)


@pytest.mark.slow
def test_baseline_cost_records_on_chip_shapes():
    """The sweep at the on-chip widths."""
    from paddle_tpu.analysis.cli import baseline_cost_records

    recs = baseline_cost_records()
    by_name = {r["config"]: r for r in recs}
    assert by_name["resnet50"]["bound"] == "hbm"
    assert by_name["deepfm"]["bound"] == "rows"
    assert by_name["bert"]["bound"] == "compute"


# ---------------------------------------------------------------------------
# SPMD: collective sequences, consistency (the static deadlock check)
# ---------------------------------------------------------------------------

def _lookup_program(strategy, vocab=64, fields=4, width=16):
    main = fluid.Program()
    gb = main.global_block()
    w = gb.create_parameter(name="table", shape=[vocab, width],
                            dtype="float32")
    w.sharding = ("mp", None)
    ids = gb.create_var(name="ids", shape=[-1, fields], dtype="int64",
                        is_data=True)
    out = gb.create_var(name="rows", shape=[-1, fields, width],
                        dtype="float32")
    gb.append_op("sharded_lookup_table", {"W": w, "Ids": ids},
                 {"Out": out},
                 {"mesh_axis": "mp", "emb_strategy": strategy})
    return main


def test_collective_events_volumes_match_comm_model():
    n, width, m = 16 * 4, 16, 4
    events = spmd.collective_events(_lookup_program("alltoall"),
                                    n_shards=m, batch=16)
    assert [e.signature for e in events] == [
        ("all_to_all", "mp"), ("all_to_all", "mp"), ("all_gather", "mp")]
    model = cost_mod.comm_bytes_model(n, width, m, 4)
    assert sum(e.bytes for e in events) == model["alltoall_total_bytes"]
    psum_events = spmd.collective_events(_lookup_program("psum"),
                                         n_shards=m, batch=16)
    assert [e.signature for e in psum_events] == [("psum", "mp")]
    assert psum_events[0].bytes == model["psum_total_bytes"]


def test_mismatched_collective_sequence_is_a_finding_with_provenance():
    """ISSUE 15 acceptance: two mesh programs whose collective sequences
    diverge = a static deadlock finding, with op provenance naming THIS
    file."""
    res = spmd.check_collective_consistency({
        "rank0": spmd.collective_events(_lookup_program("alltoall"),
                                        n_shards=4, batch=16),
        "rank1": spmd.collective_events(_lookup_program("psum"),
                                        n_shards=4, batch=16)})
    errs = [d for d in res.errors if d.check == "collective-mismatch"]
    assert errs, res.report()
    assert "deadlock" in errs[0].message
    assert "test_cost_engine.py" in str(errs[0])  # provenance
    # identical sequences are clean
    ok = spmd.check_collective_consistency({
        "rank0": spmd.collective_events(_lookup_program("alltoall"),
                                        n_shards=4, batch=16),
        "rank1": spmd.collective_events(_lookup_program("alltoall"),
                                        n_shards=4, batch=16)})
    assert ok.ok and not ok.diagnostics


def test_reordered_collective_sequence_is_a_finding():
    a = spmd.collective_events(_lookup_program("alltoall"), n_shards=4,
                               batch=16)
    b = list(reversed(a))
    res = spmd.check_collective_consistency({"rank0": a, "rank1": b})
    assert any(d.check == "collective-mismatch" for d in res.errors)


def test_extra_collective_is_a_finding():
    a = spmd.collective_events(_lookup_program("alltoall"), n_shards=4,
                               batch=16)
    res = spmd.check_collective_consistency({"rank0": a, "rank1": a[:-1]})
    errs = [d for d in res.errors if d.check == "collective-mismatch"]
    assert errs and "blocks forever" in errs[0].message


def test_sharding_mismatch_lint_with_provenance():
    main = fluid.Program()
    gb = main.global_block()
    a = gb.create_parameter(name="wa", shape=[64, 64], dtype="float32")
    a.sharding = ("mp", None)
    b = gb.create_parameter(name="wb", shape=[64, 64], dtype="float32")
    b.sharding = ("dp", None)
    out = gb.create_var(name="merged", shape=[64, 64], dtype="float32")
    gb.append_op("elementwise_add", {"X": a, "Y": b}, {"Out": out},
                 {"axis": -1})
    _, _, diags = spmd.propagate_sharding(main, n_shards=2)
    errs = [d for d in diags if d.check == "sharding-mismatch"]
    assert errs and "test_cost_engine.py" in str(errs[0])


def test_sharding_propagates_through_mp_attention_cleanly():
    """The mp-annotated transformer attention block (row/col-parallel
    projections) propagates with ZERO mismatch findings, and the
    row-parallel output projection implies the psum GSPMD inserts."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fluid.unique_name.switch()
        x = fluid.layers.data("x", shape=[8, 64], dtype="float32")
        fluid.layers.multi_head_attention(x, x, x, n_head=4, name="mha")
    specs, events, diags = spmd.propagate_sharding(main, batch=2,
                                                   n_shards=2)
    assert not diags, diags
    assert any(e.kind == "psum" for e in events)  # out-proj contraction


def test_malformed_sharding_annotation_is_a_finding():
    main = fluid.Program()
    gb = main.global_block()
    w = gb.create_parameter(name="w", shape=[8, 8], dtype="float32")
    w.sharding = ("mp",)  # rank 2 var, 1-entry spec
    _, _, diags = spmd.propagate_sharding(main)
    assert any(d.check == "sharding-annotation" for d in diags)
    main2 = fluid.Program()
    gb2 = main2.global_block()
    w2 = gb2.create_parameter(name="w2", shape=[8, 8], dtype="float32")
    w2.sharding = ("ghost_axis", None)
    _, _, diags2 = spmd.propagate_sharding(main2, mesh_axes={"mp", "dp"})
    assert any(d.check == "sharding-annotation" for d in diags2)


def test_jaxpr_collective_audit_pass():
    import jax

    jaxpr = jax.make_jaxpr(
        lambda x: jax.lax.psum(x, "mp"),
        axis_env=[("mp", 2)])(np.zeros((4, 16), np.float32))
    res = spmd.analyze_jaxpr_collectives(
        jaxpr, forbid_full_output_psum_width=16, require=("all_to_all",))
    checks = {d.check for d in res.errors}
    assert "collective-psum" in checks      # the forbidden [n, 16] psum
    assert "collective-missing" in checks   # no all_to_all traced
    assert res.events and res.events[0][0] == "psum"
    clean = spmd.analyze_jaxpr_collectives(jaxpr, require=("psum",))
    assert clean.ok


# ---------------------------------------------------------------------------
# resource lints: VMEM gates, recompile hazard, compile cache
# ---------------------------------------------------------------------------

def test_vmem_overflow_is_a_finding_with_provenance():
    """ISSUE 15 acceptance: a Pallas shape blocked ONLY by the VMEM
    budget is reported with op provenance."""
    main = fluid.Program()
    gb = main.global_block()
    w = gb.create_parameter(name="big_table", shape=[200000, 32],
                            dtype="float32")
    ids = gb.create_var(name="ids", shape=[-1, 8], dtype="int64",
                        is_data=True)
    out = gb.create_var(name="emb", shape=[-1, 8, 32], dtype="float32")
    gb.append_op("lookup_table", {"W": w, "Ids": ids}, {"Out": out}, {})
    res = resources.check_resources(main, batch=1024)
    finds = [d for d in res.warnings if d.check == "vmem-gate"]
    assert finds, res.report()
    assert "VMEM" in finds[0].message
    assert "test_cost_engine.py" in str(finds[0])  # provenance
    # a small table is clean (fits the budget)
    main2 = fluid.Program()
    gb2 = main2.global_block()
    w2 = gb2.create_parameter(name="small", shape=[1000, 16],
                              dtype="float32")
    ids2 = gb2.create_var(name="ids", shape=[-1, 8], dtype="int64",
                          is_data=True)
    out2 = gb2.create_var(name="emb", shape=[-1, 8, 16], dtype="float32")
    gb2.append_op("lookup_table", {"W": w2, "Ids": ids2}, {"Out": out2},
                  {})
    assert not resources.check_resources(main2, batch=64).diagnostics


def test_fused_conv_vmem_refusal_is_a_finding():
    main = fluid.Program()
    gb = main.global_block()
    # 512-channel 3x3 at 64x64 spatial: far over the fused kernel budget
    x = gb.create_var(name="x", shape=[-1, 512, 64, 64], dtype="float32",
                      is_data=True)
    w = gb.create_parameter(name="w", shape=[512, 512, 3, 3],
                            dtype="float32")
    scale = gb.create_parameter(name="s", shape=[512], dtype="float32")
    bias = gb.create_parameter(name="b", shape=[512], dtype="float32")
    mean = gb.create_parameter(name="m", shape=[512], dtype="float32")
    var = gb.create_parameter(name="v", shape=[512], dtype="float32")
    y = gb.create_var(name="y", shape=[-1, 512, 64, 64], dtype="float32")
    gb.append_op(
        "fused_conv2d",
        {"Input": x, "Filter": w, "Scale": scale, "Bias": bias,
         "Mean": mean, "Variance": var},
        {"Y": y, "MeanOut": mean, "VarianceOut": var},
        {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
         "groups": 1, "epsilon": 1e-5, "momentum": 0.9, "act": "relu",
         "orig_ops": []})
    res = resources.check_resources(main, batch=2)
    finds = [d for d in res.warnings if d.check == "vmem-gate"]
    assert finds and "fused_conv2d" in finds[0].message


def test_flash_kernel_plan_gates():
    from paddle_tpu.ops import flash_attention as fa

    # bf16 transformer-base at T=1024: the copy-free packed path
    plan = fa.kernel_plan((32, 1024, 512), (32, 1024, 512), 8, 2,
                          causal=False, dropout_rate=0.1)
    assert plan.kernel == "packed_stream" and plan.admitted
    # the seq-2048 bench shape: one 128-lane window of the packed head
    # dimension (two heads of 64) fits the chip's scoped VMEM where all
    # eight heads did not, so it stays copy-free too (ISSUE 29)
    plan = fa.kernel_plan((16, 2048, 512), (16, 2048, 512), 8, 2,
                          causal=False, dropout_rate=0.1)
    assert plan.kernel == "packed_stream" and plan.admitted
    assert not plan.reasons
    # past 3072 tokens a window's full-T q/do/dq do not fit: head-split
    # streaming + its relayout copies, and the plan says why
    plan = fa.kernel_plan((8, 4096, 512), (8, 4096, 512), 8, 2,
                          causal=False, dropout_rate=0.1)
    assert plan.kernel == "head_split_stream" and plan.admitted
    assert plan.blocked_only_by("vmem")
    # f32 at a much longer context: falls back to head-split + copies,
    # and says the VMEM budget is why
    plan2 = fa.kernel_plan((16, 16384, 1024), (16, 16384, 1024), 8, 4,
                           causal=False, dropout_rate=0.0)
    assert plan2.kernel == "head_split_stream"
    assert plan2.blocked_only_by("vmem")
    # rich bias form: reference path, reason says so
    plan3 = fa.kernel_plan((4, 64, 64), (4, 64, 64), 4, 4,
                           bias_kind="rich")
    assert plan3.kernel == "reference"
    assert any(r.check == "bias" for r in plan3.reasons)


def test_recompile_hazard_lint():
    main = fluid.Program()
    gb = main.global_block()
    x = gb.create_var(name="x", shape=[-1, 8], dtype="float32",
                      is_data=True)
    dyn = gb.create_var(name="dyn", shape=[-1, -1], dtype="float32")
    gb.append_op("relu", {"X": x}, {"Out": dyn})
    res = resources.check_resources(main, checks=("recompile-hazard",))
    finds = [d for d in res.warnings if d.check == "recompile-hazard"]
    assert finds and "dyn" in finds[0].message
    assert "test_cost_engine.py" in str(finds[0])


def test_decode_cache_verdict():
    spec = {"ctx_cap": 32}
    bound, res = resources.decode_cache_verdict(
        spec, ladder=(1, 2, 4), ctx_ladder=(16, 32), budget=8)
    assert bound == 6 and res.ok and not res.diagnostics
    # over budget: finding; rung above the spec's capacity: finding —
    # but still COUNTED in the bound (nothing stops it being dispatched,
    # so excluding it would understate the executable count)
    bound2, res2 = resources.decode_cache_verdict(
        spec, ladder=(1, 2, 4, 8), ctx_ladder=(16, 32, 64), budget=6)
    assert bound2 == 12
    checks = [d.check for d in res2.diagnostics]
    assert checks.count("compile-cache") == 2
    assert any("64" in d.message for d in res2.diagnostics)
    # duplicate rungs dedup exactly the way DecodeBatcher dedups them
    bound3, _ = resources.decode_cache_verdict(
        spec, ladder=(1, 2, 2), ctx_ladder=(16, 16, 32), budget=64)
    assert bound3 == 4


def test_decode_cache_verdict_prefill_ladder():
    """ISSUE 20: the chunked-prefill extension — the bound grows to
    (batch x ctx x (1 step + prefill rungs)), a prefill rung above the
    spec's capacity is its OWN finding yet stays counted, and duplicate
    prefill rungs dedup like the batcher dedups them."""
    spec = {"ctx_cap": 32}
    bound, res = resources.decode_cache_verdict(
        spec, ladder=(1, 2, 4), ctx_ladder=(16, 32), budget=18,
        prefill_ladder=(8, 16))
    assert bound == 3 * 2 * 3 and res.ok and not res.diagnostics
    # one prefill rung over ctx_cap + the budget breach: two findings,
    # and the budget message names the chunk-rung decomposition
    bound2, res2 = resources.decode_cache_verdict(
        spec, ladder=(1, 2), ctx_ladder=(32,), budget=2,
        prefill_ladder=(16, 64))
    assert bound2 == 2 * 1 * 3
    checks = [d.check for d in res2.diagnostics]
    assert checks.count("compile-cache") == 2
    assert any("prefill ladder rung 64" in d.message
               for d in res2.diagnostics)
    assert any("still counted in the bound" in d.message
               for d in res2.diagnostics)
    assert any("1 step + 2 chunk rungs" in d.message
               for d in res2.diagnostics)
    bound3, _ = resources.decode_cache_verdict(
        spec, ladder=(1,), ctx_ladder=(16,), budget=64,
        prefill_ladder=(8, 8, 16))
    assert bound3 == 1 * 1 * 3


def test_decode_batcher_compile_cache_bound():
    from paddle_tpu.serving.decode_batcher import DecodeBatcher

    class _FakePred:
        fetch_names = ["logits", "k0_out"]

        def run(self, feed, return_numpy=False):
            raise AssertionError("static test: no steps")

    spec = {"token_feed": "tok", "pos_feed": "pos",
            "logits_fetch": "logits", "ctx_cap": 32,
            "cache_feeds": [{"feed": "k0", "fetch": "k0_out",
                             "tail": [4]}]}
    bat = DecodeBatcher(_FakePred(), spec, ladder=(1, 2),
                        ctx_ladder=(16, 32), start=False)
    assert bat.compile_cache_bound() == 4
    assert bat.compiled_shape_counts()[0] <= bat.compile_cache_bound()

    # with a chunk program riding along, the batcher's bound matches the
    # verdict's (batch x ctx x (1 step + prefill rungs)) product
    class _FakeChunkPred:
        fetch_names = ["clogits", "k0c_out"]

        def run(self, feed, return_numpy=False):
            raise AssertionError("static test: no steps")

    cspec = {"token_feed": "ctok", "pos_feed": "cpos",
             "logits_fetch": "clogits", "ctx_cap": 32,
             "cache_feeds": [{"feed": "k0", "fetch": "k0c_out",
                              "tail": [4]}]}
    bat2 = DecodeBatcher(_FakePred(), spec, ladder=(1, 2),
                         ctx_ladder=(16, 32),
                         prefill={"predictor": _FakeChunkPred(),
                                  "spec": cspec, "ladder": (4, 8)},
                         start=False)
    assert bat2.compile_cache_bound() == 2 * 2 * 3
    vbound, _ = resources.decode_cache_verdict(
        spec, ladder=(1, 2), ctx_ladder=(16, 32), budget=64,
        prefill_ladder=bat2.prefill_ladder)
    assert vbound == bat2.compile_cache_bound()


# ---------------------------------------------------------------------------
# kernel choices recorded in op attrs (no silent fallbacks)
# ---------------------------------------------------------------------------

def test_flash_attention_op_records_kernel_choice(rng):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        q = fluid.layers.data("q", shape=[4, 32], dtype="float32")
        out = fluid.layers.scaled_dot_product_attention(q, q, q,
                                                        num_heads=2)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={"q": rng.randn(2, 4, 32).astype("f4")},
                fetch_list=[out])
    fa_ops = [op for op in main.global_block().ops
              if op.type == "flash_attention"]
    assert fa_ops
    choice = fa_ops[0].attrs.get("_kernel_choice")
    assert choice is not None
    # CPU run: the platform gate demotes to the reference path, and the
    # structured reason says so instead of a silent fallback
    assert choice["kernel"] == "reference"
    assert any(r["check"] == "platform" for r in choice["reasons"])


def test_sparse_adam_records_scatter_choice(rng):
    from paddle_tpu import models

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        fluid.unique_name.switch()
        spec = models.deepfm.deepfm(sparse_feature_dim=500,
                                    num_fields=4, embedding_size=8,
                                    dense_dim=3, hidden_sizes=(8,))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(spec.loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = spec.sample_batch(4, np.random.RandomState(0))
        exe.run(main, feed=feed, fetch_list=[spec.loss])
    recorded = [op.attrs["_kernel_choice"]
                for op in main.global_block().ops
                if op.type == "adam" and "_kernel_choice" in op.attrs]
    assert recorded, "sparse adam did not record its scatter choice"
    ch = recorded[0]
    assert ch["kernel"] in ("xla_at_add", "pallas_rowbin")
    if ch["kernel"] == "xla_at_add":
        assert ch["reasons"], "refusal must carry structured reasons"


def test_scatter_gate_structured_reasons():
    from paddle_tpu.ops import scatter as scatter_mod

    # blocked only by vmem: everything else qualifies
    d = scatter_mod.gate(200000, 32, 1000, "float32", static_only=True)
    assert not d.admitted and d.kernel == "xla_at_add"
    assert d.blocked_only_by("vmem")
    # int table: dtype reason
    d2 = scatter_mod.gate(100, 16, 10, "int32", static_only=True)
    assert not d2.admitted
    assert any(r.check == "dtype" for r in d2.reasons)
    # small float table passes the static gate
    d3 = scatter_mod.gate(1000, 16, 100, "float32", static_only=True)
    assert d3.admitted and d3.kernel == "pallas_rowbin"


# ---------------------------------------------------------------------------
# executor verify="strict" (severity levels) + CLI
# ---------------------------------------------------------------------------

def test_executor_strict_verify_warns_on_resource_findings(rng):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        ids = fluid.layers.data("ids", shape=[8, 1], dtype="int64")
        emb = fluid.layers.embedding(ids, size=[200000, 32],
                                     is_sparse=False)
        out = fluid.layers.reduce_sum(emb)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with pytest.warns(UserWarning, match="vmem-gate"):
            exe.run(main,
                    feed={"ids": rng.randint(0, 200000,
                                             (4, 8, 1)).astype("i8")},
                    fetch_list=[out], verify="strict")


def test_cli_demo_defects_exit_nonzero():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for kind in ("collective_mismatch", "vmem_overflow",
                 "sharding_mismatch"):
        p = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis",
             "--demo-defect", kind],
            capture_output=True, text=True, env=env, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(
                __file__))))
        assert p.returncode == 1, (kind, p.stdout, p.stderr)
        assert kind.split("_")[0] in p.stdout.replace("-", "_"), p.stdout


def test_zoo_cost_pass_runs_clean():
    """The lint.sh zoo sweep contract: verification stays at zero
    findings AND the cost pass runs over every zoo program without
    crashing (uncosted op types are allowed — they are the honesty
    list — but a rule crash is not)."""
    from paddle_tpu.analysis.cli import _zoo_builders, analyze_zoo_model

    for name in ("mnist.cnn", "transformer", "deepfm", "word2vec"):
        res_main, res_startup, est = analyze_zoo_model(
            _zoo_builders()[name], train=True, with_cost=True)
        assert not res_main.diagnostics, (name, res_main.report())
        crashed = [r for r in est.records
                   if r.note and "crashed" in str(r.note)]
        assert not crashed, (name, crashed)
        assert est.flops > 0
