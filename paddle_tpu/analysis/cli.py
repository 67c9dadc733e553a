"""``python -m paddle_tpu.analysis`` — one entry point for IR verification
and compiled-HLO sharding checks (the reference splits these across
``inference/analysis/analyzer`` and graph passes; here they share one
diagnostic surface).

    # verify every model-zoo program (the verifier's regression corpus;
    # the cost pass runs over every program — a crashing cost rule fails
    # the sweep)
    python -m paddle_tpu.analysis --zoo
    # a subset, without the optimizer/backward section
    python -m paddle_tpu.analysis --zoo mnist.mlp transformer --no-train
    # static roofline estimates (flops / HBM bytes / floor ms at the
    # cost engine's constants): per zoo model, or the 6 BASELINE configs
    python -m paddle_tpu.analysis --cost --zoo deepfm
    python -m paddle_tpu.analysis --cost --baseline
    # static SPMD pass on the transpiled DeepFM: sharding propagation,
    # per-collective ICI volumes, collective-sequence self-consistency
    python -m paddle_tpu.analysis --comm
    # a saved inference model directory (io.save_inference_model layout)
    python -m paddle_tpu.analysis path/to/model_dir
    # compiled-HLO sharding lint (Executor.lowered_hlo_text dump)
    python -m paddle_tpu.analysis --hlo step.hlo --require-sharded fc_w
    # demonstrate a defect class and the diagnostic it produces (exits 1)
    python -m paddle_tpu.analysis --demo-defect double_write

Exit status: 0 when every requested check is clean (warnings included —
the zoo is held to zero findings), 1 otherwise.
"""

import argparse
import json
import sys

from .passes import analyze_program, analyze_hlo_sharding


def _lm_step_spec():
    """Inference-only zoo entry: ModelSpec with loss=None, fetches = the
    step program's logits + updated caches."""
    from .. import models
    from ..models.common import ModelSpec

    fetch_vars, _spec = models.transformer.transformer_lm_step(
        vocab=64, d_model=32, d_ff=64, n_head=2, n_layer=2, ctx_cap=16)
    return ModelSpec(None, feeds={},
                     fetches={v.name: v for v in fetch_vars})


def _lm_chunk_spec():
    """Inference-only zoo entry for the K-token prefill/verify chunk
    program (ISSUE 20) — same weight-sharing family as lm_step."""
    from .. import models
    from ..models.common import ModelSpec

    fetch_vars, _spec = models.transformer.transformer_lm_chunk(
        vocab=64, d_model=32, d_ff=64, n_head=2, n_layer=2, ctx_cap=16)
    return ModelSpec(None, feeds={},
                     fetches={v.name: v for v in fetch_vars})


def _zoo_builders():
    """name -> zero-arg builder, CPU-sized configs (mirrors tests/
    test_models.py). Each builds into the CURRENT default program."""
    from .. import models

    return {
        "mnist.mlp": lambda: models.mnist.mlp(hidden_sizes=(32,)),
        "mnist.cnn": lambda: models.mnist.cnn(),
        "resnet.cifar10": lambda: models.resnet.resnet_cifar10(depth=8),
        "resnet.imagenet50": lambda: models.resnet.resnet_imagenet(
            depth=50, class_num=100, image_shape=(3, 64, 64)),
        "vgg16": lambda: models.vgg.vgg16(image_shape=(3, 32, 32)),
        "se_resnext50": lambda: models.se_resnext.se_resnext50(
            image_shape=(3, 64, 64), class_num=10),
        "stacked_lstm": lambda: models.stacked_lstm.stacked_lstm_net(
            dict_size=100, emb_dim=16, hid_dim=16, stacked_num=2,
            seq_len=12),
        "transformer": lambda: models.transformer.transformer_base(
            src_vocab=64, trg_vocab=64, seq_len=16, d_model=32, d_ff=64,
            n_head=2, n_layer=2, dropout_rate=0.1),
        "transformer.lm": lambda: models.transformer.transformer_lm(
            vocab=64, seq_len=16, d_model=32, d_ff=64, n_head=2,
            n_layer=2),
        # the serving tier's KV-cache step program (no loss: inference
        # only — the ISSUE 14 acceptance gate "decode programs verify
        # clean"); fetches are the logits + carried caches
        "transformer.lm_step": _lm_step_spec,
        # the chunked-prefill / speculative-verify sibling (ISSUE 20)
        "transformer.lm_chunk": _lm_chunk_spec,
        "bert": lambda: models.bert.bert_base(
            vocab_size=64, seq_len=16, d_model=32, d_ff=64, n_head=2,
            n_layer=2, dropout_rate=0.1),
        "deepfm": lambda: models.deepfm.deepfm(
            sparse_feature_dim=1000, num_fields=6, embedding_size=4,
            dense_dim=3, hidden_sizes=(16, 16)),
        "word2vec": lambda: models.word2vec.ngram_lm(
            dict_size=50, emb_dim=8, hidden_size=16),
        "machine_translation": lambda:
            models.machine_translation.seq2seq_attention(
                src_vocab=40, trg_vocab=40, seq_len=10, emb_dim=16,
                hid_dim=16),
        "ocr_ctc": lambda: models.ocr_ctc.crnn_ctc(
            num_classes=12, image_shape=(1, 16, 48), max_label_len=6,
            hid_dim=16),
        "ssd_lite": lambda: models.ssd.ssd_lite(),
        "label_semantic_roles": lambda:
            models.label_semantic_roles.srl_crf(),
        "books.fit_a_line": lambda: models.books.fit_a_line(),
        "books.understand_sentiment": lambda:
            models.books.understand_sentiment(seq_len=12, stacked_num=2),
        "books.recommender_system": lambda:
            models.books.recommender_system(),
    }


def analyze_zoo_model(builder, train=True, with_cost=False):
    """Build one zoo model into fresh programs and verify main + startup.
    Returns (main_result, startup_result), or with ``with_cost=True``
    (main_result, startup_result, cost_estimate) — the cost pass runs
    over the SAME program build, so the zoo sweep also regression-covers
    every cost rule."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        spec = builder()
        train = train and spec.loss is not None  # inference-only entries
        if train:
            fluid.optimizer.SGD(learning_rate=0.01).minimize(spec.loss)
    fetches = ([spec.loss.name] if spec.loss is not None else []) \
        + [v.name for v in spec.fetches.values()]
    out = (analyze_program(main, fetch_names=fetches, donate_state=train),
           analyze_program(startup))
    if with_cost:
        from .cost import estimate_program

        out = out + (estimate_program(main, batch=4),)
    return out


# the 6 BASELINE model configs; bert_dygraph is estimated on the
# static-equivalent program (same architecture — the dygraph build has no
# Program IR to walk)
BASELINE_CONFIGS = ("deepfm", "seq2048", "resnet50", "bert_dygraph",
                    "bert", "transformer")


def baseline_cost_records(names=None, small=False):
    """Static roofline estimates for the BASELINE configs (ISSUE 15
    acceptance: the cost engine covers all 6). Builds each config's
    Program through ``models.baseline`` and prices it with
    ``estimate_program``; no execution, no trace. Returns one record dict
    per config."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    from .cost import estimate_program

    records = []
    for name in names or BASELINE_CONFIGS:
        model = {"seq2048": "transformer",
                 "bert_dygraph": "bert"}.get(name, name)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            fluid.unique_name.switch()
            spec, batch = models.baseline(
                model, small=small,
                seq_len=2048 if name == "seq2048" else None)
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(spec.loss)
        est = estimate_program(main, batch=batch, amp=True)
        rec = dict(est.roofline())
        rec.update(config=name, batch=batch)
        if name == "bert_dygraph":
            rec["note"] = ("static-equivalent program: the dygraph build "
                           "shares the architecture but has no Program "
                           "IR to walk")
        records.append(rec)
    return records


def comm_report(mp=8, batch=16):
    """The static SPMD pass on the transpiled DeepFM (the comm-carrying
    BASELINE config): sharding propagation lint, the program-level
    collective sequence with per-collective ICI volume estimates, and a
    collective-sequence self-consistency check (two builds of the same
    config must issue identical sequences — the lockstep property).
    Returns (events, AnalysisResult)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    from .passes import AnalysisResult
    from .spmd import (check_collective_consistency, collective_events,
                       propagate_sharding)

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            fluid.unique_name.switch()
            spec = models.deepfm.deepfm(
                sparse_feature_dim=64 * mp, num_fields=4,
                embedding_size=8, dense_dim=3, hidden_sizes=(16,))
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(spec.loss)
        # the DistributeTranspiler sharded_embeddings rewrite, statically
        # (no device mesh — this is a build-time pass, not an execution):
        # row-shard the is_distributed tables over mp and route their
        # lookups through the explicit shard_map op
        sharded = set()
        for p in main.all_parameters():
            if getattr(p, "is_distributed", False) and len(p.shape) == 2:
                p.sharding = ("mp", None)
                sharded.add(p.name)
        for op in main.global_block().ops:
            if (op.type == "lookup_table" and op.input("W") is not None
                    and op.input("W").name in sharded):
                op.type = "sharded_lookup_table"
                op.attrs["mesh_axis"] = "mp"
        return main

    a, b = build(), build()
    _, events, diags = propagate_sharding(a, batch=batch, n_shards=mp)
    consistency = check_collective_consistency({
        "build-0": events,
        "build-1": collective_events(b, n_shards=mp, batch=batch)})
    return events, AnalysisResult(diags + consistency.diagnostics)


def build_defective_program(kind):
    """A deliberately-broken program per defect class, for demos and the
    CLI regression test. Returns (program, analyze_kwargs)."""
    import paddle_tpu as fluid
    from ..core.framework import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        gb = main.global_block()
        if kind == "use_before_def":
            ghost = gb.create_var(name="ghost", shape=[4], dtype="float32")
            out = gb.create_var(name="out", shape=[4], dtype="float32")
            gb.append_op("relu", {"X": ghost}, {"Out": out})
            return main, {"fetch_names": ["out"]}
        if kind == "double_write":
            x = fluid.layers.data("x", shape=[4])
            a = gb.create_var(name="a", shape=[-1, 4], dtype="float32")
            gb.append_op("relu", {"X": x}, {"Out": a})
            gb.append_op("tanh", {"X": x}, {"Out": a})
            return main, {"fetch_names": ["a"]}
        if kind == "shape_mismatch":
            x = fluid.layers.data("x", shape=[4])
            y = gb.create_var(name="y", shape=[5], dtype="float32")
            z = gb.create_var(name="z", shape=[-1, 4], dtype="float32")
            gb.append_op("fill_constant", outputs={"Out": y},
                         attrs={"shape": [5], "value": 1.0,
                                "dtype": "float32"})
            gb.append_op("elementwise_add", {"X": x, "Y": y}, {"Out": z},
                         {"axis": -1})
            return main, {"fetch_names": ["z"]}
        if kind == "donated_fetch":
            x = fluid.layers.data("x", shape=[4])
            h = fluid.layers.fc(x, size=4)
            w = main.all_parameters()[0]
            return main, {"fetch_names": [h.name, w.name],
                          "donate_state": True}
    raise SystemExit("unknown defect kind %r" % kind)


def demo_collective_mismatch():
    """Two mesh programs whose static collective sequences diverge (one
    lookup forced onto the id-routed path, the other onto
    psum-of-partials): in SPMD lockstep that is a deadlock at the first
    collective — the static check reports it with op provenance."""
    import paddle_tpu as fluid

    from .spmd import check_collective_consistency, collective_events

    def build(strategy):
        main = fluid.Program()
        gb = main.global_block()
        w = gb.create_parameter(name="table", shape=[64, 16],
                                dtype="float32")
        w.sharding = ("mp", None)
        ids = gb.create_var(name="ids", shape=[-1, 4], dtype="int64",
                            is_data=True)
        out = gb.create_var(name="rows", shape=[-1, 4, 16],
                            dtype="float32")
        gb.append_op("sharded_lookup_table", {"W": w, "Ids": ids},
                     {"Out": out},
                     {"mesh_axis": "mp", "emb_strategy": strategy})
        return main

    return check_collective_consistency({
        "rank0": collective_events(build("alltoall"), n_shards=4,
                                   batch=16),
        "rank1": collective_events(build("psum"), n_shards=4, batch=16)})


def demo_vmem_overflow():
    """A lookup over a table whose packed layout overflows the Pallas
    scatter's VMEM budget — everything else about the shape qualifies,
    so the sparse backward silently falls off the kernel; the resource
    pass reports it with provenance and the gate's structured reason."""
    import paddle_tpu as fluid

    from .resources import check_resources

    main = fluid.Program()
    gb = main.global_block()
    # [200k, 32] f32: packed 25.6 MB, over the 10 MB default budget
    w = gb.create_parameter(name="big_table", shape=[200000, 32],
                            dtype="float32")
    ids = gb.create_var(name="ids", shape=[-1, 8], dtype="int64",
                        is_data=True)
    out = gb.create_var(name="emb", shape=[-1, 8, 32], dtype="float32")
    gb.append_op("lookup_table", {"W": w, "Ids": ids}, {"Out": out}, {})
    return check_resources(main, batch=1024)


def demo_sharding_mismatch():
    """Two parameters sharding the same logical dim over different mesh
    axes, combined elementwise — GSPMD would reconcile with a silent
    resharding all-gather; the propagation pass makes it a finding."""
    import paddle_tpu as fluid

    from .passes import AnalysisResult
    from .spmd import propagate_sharding

    main = fluid.Program()
    gb = main.global_block()
    a = gb.create_parameter(name="wa", shape=[64, 64], dtype="float32")
    a.sharding = ("mp", None)
    b = gb.create_parameter(name="wb", shape=[64, 64], dtype="float32")
    b.sharding = ("dp", None)
    out = gb.create_var(name="merged", shape=[64, 64], dtype="float32")
    gb.append_op("elementwise_add", {"X": a, "Y": b}, {"Out": out},
                 {"axis": -1})
    _, _, diags = propagate_sharding(main, n_shards=2)
    return AnalysisResult(diags)


# defect demos that exercise the ISSUE-15 passes (result-returning, not
# program-returning — they need two programs / non-default check sets)
PASS_DEFECTS = {
    "collective_mismatch": demo_collective_mismatch,
    "vmem_overflow": demo_vmem_overflow,
    "sharding_mismatch": demo_sharding_mismatch,
}


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="static program verifier over the paddle_tpu IR")
    ap.add_argument("model_dir", nargs="?",
                    help="saved inference model dir to verify")
    ap.add_argument("--zoo", nargs="*", metavar="NAME",
                    help="verify model-zoo programs (all when no names)")
    ap.add_argument("--no-train", action="store_true",
                    help="zoo: skip the optimizer/backward section")
    ap.add_argument("--demo-defect",
                    choices=["use_before_def", "double_write",
                             "shape_mismatch", "donated_fetch",
                             "collective_mismatch", "vmem_overflow",
                             "sharding_mismatch"],
                    help="build a known-bad program and show its diagnostic")
    ap.add_argument("--cost", action="store_true",
                    help="print static roofline estimates (flops / HBM "
                    "bytes / floor ms at the cost engine's constants) for "
                    "the selected zoo models / --baseline configs / model "
                    "dir")
    ap.add_argument("--baseline", action="store_true",
                    help="with --cost: estimate the 6 BASELINE "
                    "configs at their on-chip shapes")
    ap.add_argument("--comm", action="store_true",
                    help="static SPMD pass on the transpiled DeepFM: "
                    "sharding lint, per-collective ICI volumes, "
                    "collective-sequence consistency")
    ap.add_argument("--hlo", metavar="FILE",
                    help="compiled-HLO text to lint for sharding quality")
    ap.add_argument("--require-sharded", nargs="*", default=(),
                    metavar="VAR", help="HLO: state vars that must be "
                    "actually sharded")
    ap.add_argument("--param-shapes", metavar="JSON",
                    help="HLO: JSON list of logical param shapes for the "
                    "no-full-parameter-all-gather check")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    failed = False

    def show(label, result):
        nonlocal failed
        n = len(result.diagnostics)
        if n:
            failed = True
            print("%s: %d finding%s" % (label, n, "s" if n != 1 else ""))
            for d in result.diagnostics:
                print("  %s" % d)
        elif not args.quiet:
            print("%s: ok" % label)

    if args.demo_defect:
        if args.demo_defect in PASS_DEFECTS:
            show("demo[%s]" % args.demo_defect,
                 PASS_DEFECTS[args.demo_defect]())
        else:
            program, kwargs = build_defective_program(args.demo_defect)
            show("demo[%s]" % args.demo_defect,
                 analyze_program(program, **kwargs))

    if args.comm:
        events, result = comm_report()
        if not args.quiet:
            for i, ev in enumerate(events):
                print("comm[deepfm] #%d %s@%s %d bytes (%s) [op '%s']"
                      % (i, ev.kind, ev.axis, ev.bytes, ev.detail,
                         ev.op.type if ev.op is not None else "?"))
        show("comm[deepfm]", result)

    if args.cost and args.baseline:
        for rec in baseline_cost_records():
            out = {k: rec[k] for k in
                   ("config", "batch", "flops",
                    "hbm_bytes", "t_compute_s", "t_hbm_s", "t_row_s",
                    "roofline_s", "bound", "ceilings", "uncosted_ops")}
            print(json.dumps(out))

    if args.hlo:
        with open(args.hlo) as f:
            hlo_text = f.read()
        shapes = json.loads(args.param_shapes) if args.param_shapes else None
        show("hlo[%s]" % args.hlo, analyze_hlo_sharding(
            hlo_text, param_shapes=shapes,
            require_sharded=args.require_sharded))

    if args.zoo is not None:
        builders = _zoo_builders()
        names = args.zoo or sorted(builders)
        unknown = [n for n in names if n not in builders]
        if unknown:
            raise SystemExit("unknown zoo model(s) %s; have %s"
                             % (unknown, sorted(builders)))
        for name in names:
            try:
                res_main, res_startup, est = analyze_zoo_model(
                    builders[name], train=not args.no_train,
                    with_cost=True)
            except Exception as e:
                failed = True
                print("zoo[%s]: cost/verify pass CRASHED: %s: %s"
                      % (name, type(e).__name__, e))
                continue
            show("zoo[%s]" % name, res_main)
            show("zoo[%s].startup" % name, res_startup)
            crashed = [r for r in est.records
                       if r.note and "crashed" in str(r.note)]
            if crashed:
                # estimate_program contains a rule crash per-op so one
                # bad rule can't block analysis — but the ZOO sweep is
                # the cost rules' regression gate, so here it fails loud
                failed = True
                print("zoo[%s].cost: %d cost rule%s CRASHED:"
                      % (name, len(crashed),
                         "s" if len(crashed) != 1 else ""))
                for r in crashed:
                    print("  op '%s': %s" % (r.op.type, r.note))
            if args.cost:
                r = est.roofline()
                print("zoo[%s].cost: %s" % (name, json.dumps(
                    {k: r[k] for k in ("flops", "hbm_bytes", "row_reads",
                                       "row_writes", "roofline_s",
                                       "bound", "uncosted_ops")})))

    if args.model_dir:
        import pickle
        import os

        with open(os.path.join(args.model_dir, "__model__"), "rb") as f:
            model = pickle.load(f)
        show("model[%s]" % args.model_dir, analyze_program(
            model["program"], feed_names=model["feed_names"],
            fetch_names=model["fetch_names"]))
        if args.cost:
            from .cost import estimate_program

            r = estimate_program(model["program"], batch=1).roofline()
            print("model[%s].cost: %s" % (args.model_dir, json.dumps(
                {k: r[k] for k in ("flops", "hbm_bytes", "roofline_s",
                                   "bound", "uncosted_ops")})))

    if (args.model_dir is None and args.zoo is None and not args.hlo
            and not args.demo_defect and not args.comm
            and not (args.cost and args.baseline)):
        ap.print_help()
        return 2
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
