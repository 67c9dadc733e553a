"""The cell ``qwen3next.train.s8192``: a rehearsal of a tiny copy of it on
the CPU (the numbers mean nothing; the control flow, the reference and the
checks are the real ones), the configuration's operation counts against a
hand count, the new readers on recorded device rows, and what the manifest
says of the configuration against the catalog's widths."""

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
                                          "ops_count_qwen3_next.py"))
CELL = "qwen3next.train.s8192"
CONFIG = "qwen3-next-80b-a3b"


def _real_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """``tiny.make_checkout`` plus, as new files, a tiny copy of the
    configuration (every size cut, the layer pattern and the share kept)
    and of its traffic, and the cell in the manifest."""
    root, path = tiny.make_checkout(tmp_path_factory.mktemp("checkout"))
    cfg = _real_config()
    cfg["name"] = "tiny-qwen3-next"
    cfg["builder_args"].update(
        vocab_size=97, vocab_held=50, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, rope_theta=1e4,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, num_experts=8,
        experts_held=[2, 4], num_experts_per_tok=8,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        chunk=8)
    for feed in cfg["feeds"].values():
        feed["high"] = 50
    # all 8 experts picked, so that no pick flips under bfloat16 at a width
    # of 64. Limits for the tiny sizes from readings here on the CPU: 58
    # tokens make every leaf's gradient norm noisy (sound runs up to 1.5
    # in the worst leaf, which tells nothing here), the loss tells (sound
    # runs up to 0.011 on six seeds, the fp8 control from 0.025 on three);
    # the real limits come from readings on the chip (PERF.md section 2)
    cfg["limits"] = {"loss_rel_gap": 0.018, "grad_norm_gap": 3.0,
                     "grad_large_leaf_mean_gap": 0.8,
                     "change_norm_gap": 0.2}
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-qwen3-next.json"), "w") as f:
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
        {"name": "tiny-qwen3-next", "source": "tests",
         "file": "benchmark/configs/tiny-qwen3-next.json", "reduced": [],
         "why": "tests"})
    manifest["workloads"].append(
        {"name": "tiny.qwen3next", "config": "tiny-qwen3-next",
         "traffic": "tiny.s29", "chips": 1, "why": "tests"})
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in real[g] if CELL in m.get("workloads", ())}
    for group in ("end_to_end", "per_layer"):
        for metric in manifest[group]:
            if metric["name"] in listed:
                metric["workloads"].append("tiny.qwen3next")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root, path


def in_process(manifest, workload, seed, trace=0, seconds=0.3):
    run = harness.Run(manifest, workload, seed, seconds, trace, True,
                      time.time())
    return run, train.run(run)


def test_rehearsal_of_the_tiny_cell_is_correct(checkout):
    run, result = in_process(checkout[1], "tiny.qwen3next", 2 ** 31 + 3)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(run.metric_names()) == {"train_samples_per_s", "setup_s"}
    rows = {r["name"]: r for r in result["compared"]}
    assert 0 < rows["first_grad_norm_worst_leaf_gap"]["value"]


def test_traced_rehearsal_reads_the_counter_and_the_host_spans(checkout):
    run, result = in_process(checkout[1], "tiny.qwen3next", 5, trace=1)
    metrics = result["metrics"]
    # a rehearsal has no device plane: the device readers find nothing,
    # the program's counters are there
    for name in ("gdn_ms", "gdn_roofline", "moe_ms", "attn_ms"):
        assert name in run.metric_names() and name not in metrics
    assert 0 < metrics["moe_expert_load_max"] <= 2 * 29
    assert metrics["pallas_calls"] == 0
    # the program's host spans are real on the CPU; its compile phases are
    # reported on the chip only
    assert metrics["jit_call_ms"] > 0 and "trace_s" not in metrics


def test_fp8_control_of_the_tiny_cell_comes_out_not_correct(checkout):
    rows = control.control(checkout[1], "tiny.qwen3next", seed=2,
                           rehearse=True)
    assert not all(r["ok"] for r in rows), rows


def _args():
    args = dict(_real_config()["builder_args"])
    args["seq_len"] = 8192
    return args


def test_operation_counts_against_a_hand_count():
    """Multiply-adds a token, forward, by hand from the published sizes."""
    linear_proj = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048
    core = 32 * (64 * 128 + 32 * 256 + 32 * 128 + 3 * 128 * 128)
    full_proj = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    full_core = 2 * 8192 * 16 * 256 / 2
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048 \
        + 10 * 16 / 512 * (3 * 2048 * 512)
    head = 2048 * 18992
    macs = 3 * (linear_proj + core) + full_proj + full_core + 4 * moe + head
    assert counts.train_flops_per_sample(_args()) == pytest.approx(6 * macs)
    assert 1.36e9 < 6 * macs < 1.38e9          # 11.2 TFLOP a step of 8192
    flops, nbytes = counts.gated_delta_core_step(_args(), 1)
    assert flops == pytest.approx(6 * 8192 * 3 * core)
    a_token = (2 * 2048 + 4096) * 2 + 2 * 32 * 4
    assert nbytes == 8192 * 3 * (3 * a_token + 2 * 4096 * 2)
    # a second row doubles both
    assert counts.gated_delta_core_step(_args(), 2) == (2 * flops,
                                                        2 * nbytes)


def _trace():
    """Two steps on one device: a matmul, the delta rule's scan forward (a
    while that holds one op of its body) and backward, an expert block."""
    rows = [
        ("fusion.1", 0, 100, "jit(step)/autodiff/jvp(matmul)/dot_general"),
        ("while.2", 100, 300,
         "jit(step)/autodiff/jvp(gated_delta_rule)/while"),
        ("fusion.3", 120, 200,
         "jit(step)/autodiff/jvp(gated_delta_rule)/while/body/dot_general"),
        ("fusion.4", 400, 500, "jit(step)/autodiff/transpose(autodiff)/"
         "jvp(gated_delta_rule)/transpose/while/body/dot_general"),
        ("fusion.5", 900, 150,
         "jit(step)/autodiff/jvp(routed_experts)/while/body/dot_general"),
        ("fusion.6", 1050, 50,
         "jit(step)/autodiff/jvp(routed_experts_like)/add"),
        ("fusion.7", 1100, 40, "jit(step)/autodiff/jvp(rms_norm)/mul"),
        ("fusion.8", 1140, 20,
         "jit(step)/autodiff/transpose(jvp(rms_norm))/reduce_sum"),
        ("fusion.9", 1160, 30,
         "jit(step)/autodiff/transpose(jvp(causal_conv1d))/reduce_sum"),
    ]
    return trace_reduce.Trace([rows], steps=2)


def _ctx(run=None, trainer=None):
    return {"trace": _trace(), "run": run, "trainer": trainer}


def _reader(name):
    return harness.load_module(os.path.join(ROOT, "benchmark",
                                            "layer_metrics", name + ".py"))


def test_new_readers_on_recorded_rows():
    assert _reader("gdn_ms").read(_ctx()) == pytest.approx(
        (300 + 500) / 2 / 1e6)
    # a scope is matched as a whole component: routed_experts_like is not
    assert _reader("moe_ms").read(_ctx()) == pytest.approx(150 / 2 / 1e6)
    assert _reader("rms_norm_ms").read(_ctx()) == pytest.approx(
        (40 + 20) / 2 / 1e6)
    assert _reader("conv1d_ms").read(_ctx()) == pytest.approx(30 / 2 / 1e6)

    class Device:
        device_kind = "TPU v5 lite"

    class Run:
        devices = [Device()]
        peaks = harness.Run.peaks

    class Trainer:
        sizes = {"batch": 1}
        builder_args = _args()

    share = _reader("gdn_roofline").read(_ctx(Run(), Trainer()))
    flops, nbytes = counts.gated_delta_core_step(_args(), 1)
    least_ms = max(flops / 197e12, nbytes / 819e9) * 1e3
    assert share == pytest.approx(100 * least_ms / (400 / 1e6))
    # nothing to read: no device plane, no such scope, no such counter
    empty = {"trace": trace_reduce.NoDeviceTrace(), "run": Run(),
             "trainer": Trainer()}
    for name in ("gdn_ms", "gdn_roofline", "moe_ms", "rms_norm_ms",
                 "conv1d_ms"):
        assert _reader(name).read(empty) is None

    class Scope:
        def __init__(self, values):
            self.values = values

        def var_names(self):
            return list(self.values)

        def get(self, name):
            return self.values[name]

    class Holder:
        pass

    holder = Holder()
    holder.scope = Scope({"l0.moe.load": np.array([3, 9], np.int32),
                          "l1.moe.load": np.array([11, 2], np.int32),
                          "l0.moe.router": np.zeros((2, 2))})
    assert _reader("moe_expert_load_max").read({"trainer": holder}) == 11
    holder.scope = Scope({"fc.w": np.zeros(3)})
    assert _reader("moe_expert_load_max").read({"trainer": holder}) is None


@pytest.mark.parametrize("case", appended.CASES)
def test_manifest_holds_the_cell_and_the_catalogs_widths(case, tmp_path):
    root = appended.root(case, tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, = [c for c in manifest["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train.s8192", 1)
    assert len(cell["why"]) <= 200
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    cfg = _real_config()
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "experts_held", "vocab_size"]
    with open(os.path.join(root, "benchmark", "traffic",
                           "train.s8192.json")) as f:
        mix = json.load(f)
    assert mix["sizes"] == {"batch": 1, "seq_len": 8192}
    assert (mix["pool"], mix["in_flight"], mix["reference_row_block"],
            mix["job"], mix.get("mesh")) == (8, 2, None, "train", None)
    for name in ("gdn_ms", "gdn_roofline", "moe_ms", "moe_expert_load_max",
                 "rms_norm_ms", "conv1d_ms"):
        metric, = [m for m in manifest["per_layer"] if m["name"] == name]
        assert CELL in metric["workloads"]
        assert metric["moves"] == "train_samples_per_s"
    roofline, = [m for m in manifest["per_layer"]
                 if m["name"] == "attn_roofline"]
    assert CELL not in roofline["workloads"]
    # every width is the published one; the three reduced keys state the
    # share, the published counts beside them
    published = {
        "num_experts": 512, "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 2, "head_dim": 256,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
        "num_experts_per_tok": 10, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "rms_norm_eps": 1e-6}
    args = cfg["builder_args"]
    for key, value in published.items():
        assert cfg[key] == value and args[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["experts_held"], cfg["vocab_size"]) == (4, 512, 16, 18992)
    assert cfg["published"] == {"num_hidden_layers": 48, "experts_held": 512,
                                "vocab_size": 151936}
    assert (args["num_experts"], args["experts_held"],
            args["vocab_held"]) == (512, [0, 16], 18992)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
        assert entry["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
