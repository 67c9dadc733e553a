"""The two per-layer metrics of the ``grouped_experts`` kernels, on a
hand-made trace and scope: ``moe_kernel_ms`` reads the kernels' own events
by name, ``moe_row_fill_pct`` the ``*.moe.rows`` counters; each returns None
where there is nothing to read (the parent, the CPU). And what the manifest
says of them."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import appended  # noqa: E402
from benchmark import harness, trace_reduce  # noqa: E402

CELLS = ["qwen3next.train.s8192", "nemotron3super.train.s8192"]
NAMES = ("moe_kernel_ms", "moe_row_fill_pct")


def _reader(name):
    return harness.load_module(os.path.join(ROOT, "benchmark",
                                            "layer_metrics", name + ".py"))


class _Scope:
    def __init__(self, values):
        self.values = values

    def var_names(self):
        return list(self.values)

    def get(self, name):
        return self.values[name]


class _Trainer:
    def __init__(self, values):
        self.scope = _Scope(values)


def _trace(with_kernels):
    """Two steps on one device: an expert layer's router, its gather (a
    while that holds one op of its body), the two kernels (named by their
    instruction; the second also by its scope) and the shared expert."""
    under = "jit(step)/autodiff/jvp(routed_experts)/"
    back = "jit(step)/autodiff/transpose(jvp(routed_experts))/"
    rows = [
        ("fusion.1", 0, 100, under + "moe.router/dot_general"),
        ("while.2", 100, 60, under + "moe.gather/while"),
        ("fusion.3", 110, 40, under + "moe.gather/while/body/gather"),
        ("fusion.6", 700, 90, under + "moe.shared/dot_general"),
        # a name that only begins like the family's is not the family
        ("grouped_experts_like.fwd.9", 800, 70, under + "custom-call"),
    ]
    if with_kernels:
        rows += [
            ("grouped_experts.fwd.4", 200, 120, under + "pallas_call"),
            ("custom-call.5", 400, 260,
             back + "grouped_experts.bwd/pallas_call"),
        ]
    return trace_reduce.Trace([rows], steps=2)


def test_moe_kernel_ms_reads_the_kernels_own_events_only():
    read = _reader("moe_kernel_ms").read
    assert read({"trace": _trace(True)}) == pytest.approx(
        (120 + 260) / 2 / 1e6)
    # the op's scope holds more than its kernels
    moe_ms = _reader("moe_ms").read({"trace": _trace(True)})
    assert moe_ms == pytest.approx(
        (100 + 60 + 90 + 70 + 120 + 260) / 2 / 1e6)
    # a program without the kernels (the parent), and no device trace
    assert read({"trace": _trace(False)}) is None
    assert read({"trace": trace_reduce.NoDeviceTrace()}) is None


def test_moe_row_fill_pct_reads_the_rows_counters_of_every_layer():
    read = _reader("moe_row_fill_pct").read
    trainer = _Trainer({
        "l0.moe.rows": np.array([300, 512], np.int32),
        "l1.moe.rows": np.array([900, 1024], np.int32),
        "l0.moe.load": np.array([100, 200], np.int32),
        "l0.moe.router": np.zeros((2, 2))})
    assert read({"trainer": trainer}) == pytest.approx(
        100.0 * (300 + 900) / (512 + 1024))
    # no such counter (the parent), and counters no step has written yet
    assert read({"trainer": _Trainer({"fc.w": np.zeros(3)})}) is None
    assert read({"trainer": _Trainer(
        {"l0.moe.rows": np.zeros(2, np.int32)})}) is None


@pytest.mark.parametrize("case", appended.CASES)
@pytest.mark.parametrize("name", NAMES)
def test_manifest_lists_both_hybrid_cells_for(name, case, tmp_path):
    root = appended.root(case, tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    metric, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert set(CELLS) <= set(metric["workloads"])
    assert (metric["layer"], metric["moves"]) == (
        "kernels", "train_samples_per_s")
    assert (metric["unit"], metric["better"], metric["source"]) == {
        "moe_kernel_ms": ("ms", "lower", "device_trace"),
        "moe_row_fill_pct": ("%", "higher", "program_counter")}[name]
    # each cell reports the end-to-end metric the two should move
    moved, = [m for m in manifest["end_to_end"]
              if m["name"] == metric["moves"]]
    assert all("workloads" not in moved or c in moved["workloads"]
               for c in CELLS)
    assert os.path.exists(os.path.join(
        root, "benchmark", "layer_metrics", name + ".py"))
