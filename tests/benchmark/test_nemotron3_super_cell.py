"""The cell ``nemotron3super.train.s8192``: a rehearsal of a tiny copy of it
on the CPU (the numbers mean nothing; the control flow, the reference and the
checks are the real ones), the configuration's operation counts against a
hand count, the new readers on recorded device rows, what the manifest says
of the configuration against the catalog's widths, and the parameter count
of the real configuration built as shapes only."""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import appended  # noqa: E402
import tiny  # noqa: E402
from benchmark import control, harness, trace_reduce  # noqa: E402

train = harness.load_module(os.path.join(ROOT, "benchmark", "jobs",
                                         "train.py"))
counts = harness.load_module(os.path.join(ROOT, "benchmark",
                                          "ops_count_nemotron3_super.py"))
CELL = "nemotron3super.train.s8192"
CONFIG = "nemotron-3-super-120b-a12b"


def _real_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """``tiny.make_checkout`` plus, as new files, a tiny copy of the
    configuration (every size cut, a pattern with the three kinds of layer
    and the shares kept) and of its traffic, and the cell in the manifest."""
    root, path = tiny.make_checkout(tmp_path_factory.mktemp("checkout"))
    cfg = _real_config()
    cfg["name"] = "tiny-nemotron"
    cfg["builder_args"].update(
        vocab_size=97, vocab_held=50, hidden_size=64,
        hybrid_override_pattern="M*EME", layers_held=[1, 4],
        mamba_num_heads=8, mamba_head_dim=8, n_groups=4, ssm_state_size=8,
        chunk_size=8, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, n_routed_experts=8, num_experts_per_tok=8,
        moe_intermediate_size=32, moe_latent_size=32,
        moe_shared_expert_intermediate_size=64, heads_held=[1, 2],
        experts_held=[2, 4], shared_units_held=[0, 32])
    for feed in cfg["feeds"].values():
        feed["high"] = 50
    # all 8 experts picked, so that no pick flips under bfloat16 at a width
    # of 64. Limits for the tiny sizes from readings here on the CPU: 58
    # tokens make every leaf's gradient norm noisy (sound runs up to 0.030
    # in the worst leaf and 0.016 in the large-leaf mean, which the control
    # does not always pass), the loss tells (sound runs up to 0.0012 on six
    # seeds, the fp8 control from 0.008 on three); the real limits come
    # from readings on the chip (PERF.md section 2)
    cfg["limits"] = {"loss_rel_gap": 0.003, "grad_norm_gap": 0.06,
                     "grad_large_leaf_mean_gap": 0.05,
                     "change_norm_gap": 0.05}
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-nemotron.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "train.s8192.json")) as f:
        mix = json.load(f)
    mix["sizes"] = {"batch": 2, "seq_len": 29}
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny.s29.json"), "w") as f:
        json.dump(mix, f)
    with open(path) as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-nemotron", "source": "tests",
         "file": "benchmark/configs/tiny-nemotron.json", "reduced": [],
         "why": "tests"})
    manifest["workloads"].append(
        {"name": "tiny.nemotron", "config": "tiny-nemotron",
         "traffic": "tiny.s29", "chips": 1, "why": "tests"})
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in real[g] if CELL in m.get("workloads", ())}
    for group in ("end_to_end", "per_layer"):
        for metric in manifest[group]:
            if metric["name"] in listed:
                metric["workloads"].append("tiny.nemotron")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root, path


def in_process(manifest, workload, seed, trace=0, seconds=0.3):
    run = harness.Run(manifest, workload, seed, seconds, trace, True,
                      time.time())
    return run, train.run(run)


def test_rehearsal_of_the_tiny_cell_is_correct(checkout):
    run, result = in_process(checkout[1], "tiny.nemotron", 2 ** 31 + 3)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(run.metric_names()) == {"train_samples_per_s", "setup_s"}
    rows = {r["name"]: r for r in result["compared"]}
    assert 0 < rows["first_grad_norm_worst_leaf_gap"]["value"]


def test_traced_rehearsal_reads_the_counter_and_the_host_spans(checkout):
    run, result = in_process(checkout[1], "tiny.nemotron", 5, trace=1)
    metrics = result["metrics"]
    # a rehearsal has no device plane: the device readers find nothing,
    # the program's counters are there
    for name in ("ssd_ms", "ssd_roofline", "attn_ms", "ce_ms", "matmul_ms"):
        assert name in run.metric_names() and name not in metrics
    # since PR 32 the cell reports the four readings it shares with
    # ``qwen3next.train.s8192`` (ISSUE 30 asked for them; the pin in
    # ``test_qwen3_next_cell.py`` that kept them out is loosened): the
    # counter is read here, the device readings need a chip
    assert "gdn_ms" not in run.metric_names()
    for name in ("moe_ms", "rms_norm_ms", "conv1d_ms"):
        assert name in run.metric_names() and name not in metrics
    assert metrics["moe_expert_load_max"] > 0
    assert metrics["pallas_calls"] == 0
    assert metrics["jit_call_ms"] > 0 and "trace_s" not in metrics


def test_fp8_control_of_the_tiny_cell_comes_out_not_correct(checkout):
    rows = control.control(checkout[1], "tiny.nemotron", seed=2,
                           rehearse=True)
    assert not all(r["ok"] for r in rows), rows


def _args():
    args = dict(_real_config()["builder_args"])
    args["seq_len"] = 8192
    return args


def test_operation_counts_against_a_hand_count():
    """Multiply-adds a token, forward, by hand from the published sizes and
    the share: 16 Mamba heads of 64 with one group of state 128, 4 query
    heads of 128 on one key/value head, 8 of 512 experts, 672 shared units,
    16384 rows of the vocabulary."""
    core = 64 * 128 + 16 * (64 * 64 + 2 * 64 * 128)
    mamba = 4096 * (1024 + 1280 + 16) + 1280 * 4 + 1024 * 4096 + core
    attention = 4096 * 512 + 2 * 4096 * 128 + 512 * 4096 \
        + 2 * 8192 * 4 * 128 / 2
    moe = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 672 \
        + 22 * 8 / 512 * (2 * 1024 * 2688)
    head = 4096 * 16384
    macs = 5 * mamba + attention + 5 * moe + head
    assert counts.train_flops_per_sample(_args()) == pytest.approx(6 * macs)
    assert 1.41e9 < 6 * macs < 1.43e9          # 11.6 TFLOP a step of 8192
    flops, nbytes = counts.ssd_core_step(_args(), 1)
    assert flops == pytest.approx(6 * 8192 * 5 * core)
    read = (1024 + 2 * 128 + 16) * 2
    assert nbytes == 8192 * 5 * (3 * read + 2 * 1024 * 2)
    # a second row doubles both
    assert counts.ssd_core_step(_args(), 2) == (2 * flops, 2 * nbytes)
    # the uncut model: every head, expert, unit and row, all 88 layers
    whole = dict(_args(), heads_held=None, experts_held=None,
                 shared_units_held=None, vocab_held=None, layers_held=None)
    core = 8 * 64 * 128 + 128 * (64 * 64 + 2 * 64 * 128)
    mamba = 4096 * (8192 + 10240 + 128) + 10240 * 4 + 8192 * 4096 + core
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256 + 2 * 8192 * 32 * 128 / 2
    moe = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 \
        + 22 * (2 * 1024 * 2688)
    macs = 40 * mamba + 8 * attention + 40 * moe + 4096 * 131072
    assert counts.train_flops_per_sample(whole) == pytest.approx(6 * macs)


def _trace():
    """Two steps on one device: a matmul, the state-space scan forward (a
    while that holds one op of its body) and backward, an expert block."""
    rows = [
        ("fusion.1", 0, 100, "jit(step)/autodiff/jvp(matmul)/dot_general"),
        ("while.2", 100, 300, "jit(step)/autodiff/jvp(mamba2_ssd)/while"),
        ("fusion.3", 120, 200,
         "jit(step)/autodiff/jvp(mamba2_ssd)/while/body/dot_general"),
        ("fusion.4", 400, 500, "jit(step)/autodiff/transpose(autodiff)/"
         "jvp(mamba2_ssd)/transpose/while/body/dot_general"),
        ("fusion.5", 900, 150,
         "jit(step)/autodiff/jvp(routed_experts)/while/body/dot_general"),
        ("fusion.6", 1050, 50, "jit(step)/autodiff/jvp(mamba2_ssd_like)/add"),
    ]
    return trace_reduce.Trace([rows], steps=2)


def _reader(name):
    return harness.load_module(os.path.join(ROOT, "benchmark",
                                            "layer_metrics", name + ".py"))


def test_new_readers_on_recorded_rows():
    class Device:
        device_kind = "TPU v5 lite"

    class Run:
        devices = [Device()]
        peaks = harness.Run.peaks

    class Trainer:
        sizes = {"batch": 1}
        builder_args = _args()

    ctx = {"trace": _trace(), "run": Run(), "trainer": Trainer()}
    # a scope is matched as a whole component: mamba2_ssd_like is not
    assert _reader("ssd_ms").read(ctx) == pytest.approx(
        (300 + 500) / 2 / 1e6)
    assert _reader("moe_ms").read(ctx) == pytest.approx(150 / 2 / 1e6)
    share = _reader("ssd_roofline").read(ctx)
    flops, nbytes = counts.ssd_core_step(_args(), 1)
    least_ms = max(flops / 197e12, nbytes / 819e9) * 1e3
    assert nbytes / 819e9 > flops / 197e12      # the bytes bound it
    assert share == pytest.approx(100 * least_ms / (400 / 1e6))
    # nothing to read: no device plane, or a program without the scope (the
    # parent's): None, and the line leaves the metric out
    empty = dict(ctx, trace=trace_reduce.NoDeviceTrace())
    other = dict(ctx, trace=trace_reduce.Trace(
        [[("fusion.1", 0, 100, "jit(step)/jvp(matmul)/dot_general")]],
        steps=1))
    for name in ("ssd_ms", "ssd_roofline"):
        assert _reader(name).read(empty) is None
        assert not _reader(name).read(other)


@pytest.mark.parametrize("case", appended.CASES)
def test_manifest_holds_the_cell_and_the_catalogs_widths(case, tmp_path):
    with open(os.path.join(appended.root(case, tmp_path),
                           "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, = [c for c in manifest["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train.s8192", 1)
    assert len(cell["why"]) <= 200
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    cfg = _real_config()
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "heads_held", "experts_held",
        "shared_units_held", "vocab_size"]
    # membership only: a later cell may join any of these lists, and this
    # cell may join the lists it is not on yet, with no edit to this file
    for name in ("ssd_ms", "ssd_roofline"):
        metric, = [m for m in manifest["per_layer"] if m["name"] == name]
        assert CELL in metric["workloads"]
        assert metric["moves"] == "train_samples_per_s"
    for name in ("train_samples_per_s", "attn_ms", "attn_fwd_ms",
                 "attn_bwd_ms", "ce_ms", "matmul_ms", "optimizer_ms"):
        metric, = [m for g in ("end_to_end", "per_layer")
                   for m in manifest[g] if m["name"] == name]
        assert CELL in metric["workloads"]
    # every width is the published one; the reduced keys state the share,
    # the published counts beside them
    args = cfg["builder_args"]
    same = ("hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "chunk_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size", "moe_latent_size",
            "moe_shared_expert_intermediate_size", "norm_topk_prob",
            "routed_scaling_factor", "layer_norm_epsilon",
            "hybrid_override_pattern")
    for key in same:
        assert args[key] == cfg[key], key
    assert (cfg["num_hidden_layers"], cfg["experts_held"],
            cfg["shared_units_held"], cfg["vocab_size"]) == (
        11, 8, 672, 16384)
    assert cfg["heads_held"] == {
        "share": 0, "ways": 8, "mamba_heads": 16, "mamba_groups": 1,
        "query_heads": 4, "key_value_heads": 1}
    assert cfg["published"]["num_hidden_layers"] == 88
    assert cfg["published"]["vocab_size"] == 131072
    assert (args["layers_held"], args["heads_held"], args["experts_held"],
            args["shared_units_held"], args["vocab_size"],
            args["vocab_held"]) == ([36, 11], [0, 8], [0, 8], [0, 672],
                                    131072, 16384)
    assert args["hybrid_override_pattern"][36:47] == "*EMEMEMEMEM"
    for key in ("departures", "assumed", "deployment", "limits"):
        assert cfg[key], key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
        assert entry["source"] == row["source_url"]
        assert cfg["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
            if key in args and key != "vocab_size":
                assert args[key] == value, key
        assert row["config"]["vocab_size"] == args["vocab_size"]


def test_real_configuration_has_508m_parameters_as_shapes_only():
    """The program of the real configuration, built and never run: the
    share's parameter count (PERF.md section 4 counts it by hand)."""
    run = harness.Run(os.path.join(ROOT, "BENCHMARK.json"), CELL, 1, 0, 0,
                      True, time.time())
    _, main, _, _, _, args = train.build_program(run)
    specs = train.weight_specs(main, run.config)
    sizes = train.leaf_sizes(specs)
    total = sum(sizes.values())
    assert abs(total - 508e6) < 0.01 * 508e6, total
    assert args["seq_len"] == 8192
    by_layer = {}
    for name, size in sizes.items():
        by_layer[name.split(".")[0]] = by_layer.get(name.split(".")[0],
                                                    0) + size
    assert by_layer["l36"] == pytest.approx(5.2e6, rel=0.02)    # attention
    assert by_layer["l37"] == pytest.approx(60.0e6, rel=0.01)   # experts
    assert by_layer["l38"] == pytest.approx(13.7e6, rel=0.01)   # Mamba-2
    assert by_layer["embeddings"] + by_layer["lm_head"] == 2 * 4096 * 16384
    # every leaf has an init rule, and the decays' rule is the stated one
    kinds = {name: kind for name, _, kind in specs}
    assert kinds["l38.mamba.A_log"] == kinds["l38.mamba.dt_bias"] == "fan_in"
    assert kinds["l38.mamba.conv_bias"] == "bias"
    assert kinds["l38.mamba.norm.w"] == kinds["l38.norm.w"] == "scale"
    assert kinds["l37.moe.experts.up"] == "embedding"
    assert not [n for n in kinds if "router_bias" in n]
