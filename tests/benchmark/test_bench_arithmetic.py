"""The benchmark's own arithmetic: trace reduction on a small recorded
trace, operation counts against hand-worked values, the window's counting,
the manifest against the files it names."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import appended  # noqa: E402
from benchmark import harness, ops_count, seeded, trace_reduce  # noqa: E402


def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    devices = [[tuple(r) for r in rows] for rows in rec["devices"]]
    host = [tuple(r) for r in rec["host"]]
    return trace_reduce.Trace(devices, host, rec["steps"]), rec


# -- trace reduction ---------------------------------------------------------

def test_busy_and_window_are_the_union_of_device_intervals():
    trace, rec = recorded()
    assert trace.window_s == pytest.approx(rec["expect"]["window_s"])
    assert trace.busy_s == pytest.approx(rec["expect"]["busy_s"])
    assert 0 < trace.busy_s <= trace.window_s


def test_nested_events_are_counted_once():
    rows = [("while.1", 0.0, 100.0, "jit(step)/while"),
            ("fusion.1", 10.0, 30.0, "jit(step)/while/body/matmul/dot"),
            ("fusion.2", 50.0, 40.0, "jit(step)/while/body/adam/mul")]
    own = {r[0]: s for r, s in trace_reduce.self_times(rows)}
    assert own == {"while.1": 30.0, "fusion.1": 30.0, "fusion.2": 40.0}
    trace = trace_reduce.Trace([rows], steps=2)
    assert trace.busy_s == pytest.approx(100e-9)
    assert trace.ms_a_step_under(("matmul",)) == pytest.approx(30e-6 / 2)


@pytest.mark.parametrize("scope,types,hit", [
    ("jit(step)/jit(main)/matmul/dot_general", ("matmul", "mul"), True),
    ("jit(step)/jit(main)/transpose(jvp(matmul))/dot_general",
     ("matmul",), True),
    ("jit(step)/jit(main)/mul/dot_general", ("matmul", "mul"), True),
    ("jit(step)/jit(main)/elementwise_mul/mul", ("matmul",), False),
    ("jit(step)/jit(main)/fused_conv2d/conv", ("conv2d",), False),
    ("jit(step)/jit(main)/fused_conv2d/conv", ("conv2d", "fused_conv2d"),
     True),
    ("", ("matmul",), False),
])
def test_scope_matches_whole_components_only(scope, types, hit):
    assert bool(trace_reduce.scope_pattern(types).search(scope)) is hit


def test_per_scope_time_on_the_recorded_trace():
    trace, rec = recorded()
    for types, expect in rec["expect"]["ms_a_step_under"]:
        got = trace.ms_a_step_under(tuple(types))
        if expect is None:
            assert got is None
        else:
            assert got == pytest.approx(expect)


def test_collective_time_and_its_exposed_part():
    rows = [("fusion.1", 0.0, 100.0, "a/matmul"),
            ("all-reduce-start.3", 100.0, 5.0, ""),
            ("fusion.2", 105.0, 45.0, "a/matmul"),
            ("all-reduce-done.3", 190.0, 10.0, ""),
            ("all-reduce.7", 300.0, 20.0, ""),
            ("fusion.4", 310.0, 30.0, "a/adam")]
    trace = trace_reduce.Trace([rows])
    under_way, exposed = trace.collective_seconds("all-reduce")
    # [100, 200) and [300, 320): 120 ns under way; other ops cover
    # [105, 150) and [310, 320) of it
    assert under_way == pytest.approx(120e-9)
    assert exposed == pytest.approx((120 - 45 - 10) * 1e-9)
    assert trace_reduce.Trace([rows[:1]]).collective_seconds() is None


def test_breakdown_names_the_heaviest_ops_and_labels_the_gaps():
    trace, rec = recorded()
    out = trace.breakdown()
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"][0][0].startswith(rec["expect"]["heaviest"])
    seconds = [s for _, s in out["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    assert out["idle_gaps"][0][0] == rec["expect"]["longest_gap_during"]
    json.dumps(out)


def test_scopes_are_looked_up_in_the_compiled_hlo_text():
    hlo = """
HloModule jit_step
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %add.3 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/adam/add"}
}
ENTRY %main {
  %fusion.2254 = (f32[32768,512]{1,0:T(8,128)}, bf16[32768,512]) fusion(%a), kind=kOutput, calls=%fused_computation.9, metadata={op_name="jit(step)/autodiff/transpose(jvp(matmul))/dot_general" source_file="x.py" source_line=3}
  jvp_flash_attention_.56 = bf16[128,256,512]{2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/autodiff/jvp(flash_attention)/pallas_call"}
  %copy.1 = f32[8]{0} copy(%b)
}
"""
    scopes = trace_reduce.hlo_scopes(hlo)
    assert scopes["fusion.2254"].endswith("transpose(jvp(matmul))/dot_general")
    assert scopes["jvp_flash_attention_.56"].endswith("pallas_call")
    assert scopes["add.3"] == "jit(step)/adam/add"
    assert "copy.1" not in scopes
    event = ("%fusion.2254 = (f32[32768,512]{1,0:T(8,128)}, bf16[32768,512]"
             "{1,0}) fusion(%a), kind=kOutput")
    assert trace_reduce.instruction_name(event) == "fusion.2254"


@pytest.mark.parametrize("scope,op", [
    ("jit(step)/autodiff/transpose(jvp(matmul))/dot_general", "matmul"),
    ("jit(step)/adam/mul", "adam"),
    ("jit(step)/autodiff/jvp(flash_attention)/pallas_call",
     "flash_attention"),
    ("jit(step)/autodiff/transpose(jvp(fused_conv2d))/transpose(jvp())/add",
     "fused_conv2d"),
    ("jit(step)/autodiff/transpose(autodiff)/jvp(lookup_table)/scatter-add",
     "lookup_table"),
    ("", "(none)"),
])
def test_op_type_of_a_scope(scope, op):
    assert trace_reduce.op_type_of(scope) == op


def test_time_by_op_type_on_the_recorded_trace():
    trace, _ = recorded()
    table = dict(trace.ms_a_step_by_op_type())
    assert table["matmul"] == pytest.approx(300e-6)
    assert table["fused_linear_smooth_ce"] == pytest.approx(200e-6)
    assert sum(table.values()) == pytest.approx(trace.busy_s / 2 * 1e3)


def test_a_trace_without_device_events_is_an_error():
    with pytest.raises(RuntimeError):
        trace_reduce.Trace([[]])


# -- operation counts --------------------------------------------------------

TBASE = dict(d_model=512, d_ff=2048, n_layer=6, n_head=8, seq_len=256,
             trg_vocab=30000)


def test_transformer_flops_per_token_hand_worked():
    # per token: layer projections 4*512^2 = 1,048,576; FFN 2*512*2048 =
    # 2,097,152; attention core 2*256*512 = 262,144
    # encoder 6*(1,048,576+2,097,152+262,144)            = 20,447,232
    # decoder 6*(2*1,048,576+2,097,152+2*262,144)        = 28,311,552
    # output 512*30000                                   = 15,360,000
    # x2 (multiply-add) x3 (forward + backward)          = 384,712,704
    assert ops_count.transformer_train_flops_per_sample(
        TBASE, causal_halved=False) == 384712704
    # causal self-attention needs half of 6 cores: 6*131,072 MACs fewer
    assert ops_count.transformer_train_flops_per_sample(TBASE) == \
        384712704 - 6 * 6 * 131072


def test_resnet50_flops_per_image_hand_worked():
    convs = ops_count._resnet50_convs(224)
    assert len(convs) == 53
    assert convs[0] == (3, 64, 7, 112) and convs[-1] == (512, 2048, 1, 7)
    forward = sum(i * o * k * k * s * s for i, o, k, s in convs)
    # the well-known 4.1 GMACs of ResNet-50 (stride on the 3x3) w/o the fc
    assert forward == pytest.approx(4.087e9, rel=2e-3)
    stem = 3 * 64 * 49 * 112 * 112
    expect = 2 * (3 * forward - stem + 3 * 2048 * 1000)
    assert ops_count.resnet50_train_flops_per_sample(
        {"image_shape": [3, 224, 224], "class_num": 1000}) == expect


def test_attention_core_counts():
    args = dict(TBASE, seq_len=2048)
    flops, nbytes = ops_count.attention_core_step(args, batch=16)
    site = 6 * 2 * 2048 * 2048 * 512 * 16      # 2 fwd + 4 bwd products
    assert flops == site * (12 + 6 / 2)
    assert nbytes == 12 * (16 * 2048 * 512 * 2) * 18


# -- seeds, batches, the window ----------------------------------------------

def test_same_seed_same_inputs_and_large_seeds_work():
    feeds = {"ids": {"kind": "int", "low": 0, "high": 50, "dtype": "int64",
                     "shape": ["batch", "seq_len"]},
             "len": {"kind": "full", "value": "seq_len", "dtype": "int64",
                     "shape": ["batch"]}}
    sizes = {"batch": 4, "seq_len": 6}
    big = 2 ** 31 + 12345
    a = seeded.make_batches(feeds, sizes, big, 3)
    b = seeded.make_batches(feeds, sizes, big, 3)
    c = seeded.make_batches(feeds, sizes, big + 1, 3)
    assert all(np.array_equal(x["ids"], y["ids"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["ids"], c[0]["ids"])
    assert not np.array_equal(a[0]["ids"], a[1]["ids"])  # a pool, not one
    assert (a[0]["len"] == 6).all()
    w1 = seeded.make_weights_fn([("w", (3, 4), "fan_in")], big)()
    w2 = seeded.make_weights_fn([("w", (3, 4), "fan_in")], big)()
    w3 = seeded.make_weights_fn([("w", (3, 4), "fan_in")], big - 2 ** 31)()
    assert np.array_equal(w1["w"], w2["w"])
    assert not np.array_equal(w1["w"], w3["w"])


class _FakeRun:
    traffic = {"in_flight": 2}


class _FakeTrainer:
    """Steps whose losses follow a script: a number, NaN, or an exception."""

    run = _FakeRun()

    def __init__(self, script):
        self.script, self.calls = script, 0

    def step(self):
        value = self.script[min(self.calls, len(self.script) - 1)]
        self.calls += 1
        if isinstance(value, Exception):
            raise value
        return np.float32(value)


def test_window_counts_only_completed_finite_steps():
    train = harness.load_module(os.path.join(ROOT, "benchmark", "jobs",
                                             "train.py"))
    trainer = _FakeTrainer([1.0, float("nan"), RuntimeError("x"), 1.0])
    spans = harness.Spans()
    done, failed, elapsed = train.window(trainer, 0.05, spans)
    assert failed == 2
    assert done + failed == trainer.calls
    assert len(spans.durations("exe_run")) == trainer.calls
    assert elapsed >= 0.05


# -- the comparison ----------------------------------------------------------

def test_norm_gaps_by_the_worst_leaf_and_over_all_leaves():
    from benchmark.jobs import train_check

    expected = {"a": 10.0, "b": 1.0, "c": 1e-9}       # median 1.0
    observed = {"a": 10.5, "b": 1.02, "c": 3e-9}
    gap, leaf = train_check.worst_leaf_gap(observed, expected)
    # a: 0.5/10; b: 0.02/1; c: 2e-9 against the median leaf, not itself
    assert leaf == "a" and gap == pytest.approx(0.05)
    sizes = {"a": 5000, "b": 4096, "c": 64}
    mean = train_check.large_leaf_mean_gap(observed, expected, sizes)
    assert mean == pytest.approx((0.05 + 0.02) / 2)   # c is a small leaf
    observed["b"] = float("nan")
    assert train_check.worst_leaf_gap(observed, expected)[0] == float("inf")
    assert train_check.large_leaf_mean_gap(observed, expected,
                                           sizes) == float("inf")
    limits = {"loss_rel_gap": 1e-3, "grad_norm_gap": 0.1,
              "grad_large_leaf_mean_gap": 0.1, "change_norm_gap": 0.01}
    same = {"losses": [2.0, 1.9, 1.8], "grad_norms": expected,
            "change_norms": expected}
    unchanged = dict(same, change_norms={k: 0.0 for k in expected})
    rows = {r["name"]: r for r in train_check.compare(unchanged, same, limits,
                                                       sizes)}
    assert not rows["param_change_norm_worst_leaf_gap"]["ok"]
    assert rows["param_change_norm_worst_leaf_gap"]["value"] == 1.0
    assert all(r["ok"] for n, r in rows.items()
               if n != "param_change_norm_worst_leaf_gap")


# -- the manifest ------------------------------------------------------------

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


@pytest.mark.parametrize("case", appended.CASES)
def test_manifest_names_only_files_that_exist_and_keeps_the_contract(
        case, tmp_path):
    root = appended.root(case, tmp_path)
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    bench = os.path.join(root, "benchmark")
    assert os.path.exists(os.path.join(root, m["command"][1]))
    configs = {}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        body = harness.load_json(os.path.join(root, c["file"]))
        assert body["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(root, body["reference"]))
        if "optimizer" in body:  # a served configuration trains nothing
            assert os.path.exists(os.path.join(
                bench, "optimizers", body["optimizer"]["name"] + ".py"))
        file, function = body["ops_count"].split(":")
        assert hasattr(harness.load_module(os.path.join(bench, file)),
                       function)
        configs[c["name"]] = body
    cells = {}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and len(w["why"]) <= 200
        assert w["config"] in configs and w["chips"] in (1, 4)
        mix = harness.load_json(os.path.join(bench, "traffic",
                                             w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(bench, "jobs",
                                           mix["job"] + ".py"))
        cells[w["name"]] = w
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and all(0 < x["bound"] <= 0.1
                                    for x in e2e.values())
    for x in m["per_layer"]:
        assert NAME.fullmatch(x["name"]) and x["moves"] in e2e
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", x["unit"])
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.exists(os.path.join(bench, "layer_metrics",
                                           x["name"] + ".py"))
        assert set(x.get("workloads", [])) <= set(cells)
    for name in cells:  # every cell reports a per-layer metric
        assert any("workloads" not in x or name in x["workloads"]
                   for x in m["per_layer"])


def test_every_parameter_of_each_config_has_an_init_rule():
    body = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                          "resnet50.json"))
    assert seeded.init_kind("batch_norm_4.w_0_0", body["init"]) == \
        "small_scale"
    assert seeded.init_kind("batch_norm_5.w_0_0", body["init"]) == "scale"
    assert seeded.init_kind("conv2d_52.w_0_0", body["init"]) == "he_fan_in"
    with pytest.raises(KeyError):
        seeded.init_kind("mystery", body["init"])
