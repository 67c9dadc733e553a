"""Whole runs of the benchmark on the CPU at tiny size (rehearsals: the
numbers mean nothing, the control flow and the checks are the real ones):
the result line's keys, the references against the system, the fp8 control
and a broken step coming out not correct, the mesh path on four virtual
devices, and a configuration, a traffic mix, a job kind and a layer metric
added as files."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from benchmark import control, harness  # noqa: E402

train = harness.load_module(os.path.join(ROOT, "benchmark", "jobs",
                                         "train.py"))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(tmp_path_factory.mktemp("checkout"))


def in_process(manifest, workload, seed, trace=0, seconds=0.5):
    run = harness.Run(manifest, workload, seed, seconds, trace, True,
                      time.time())
    return run, train.run(run)


def by_name(rows):
    return {r["name"]: r for r in rows}


@pytest.mark.parametrize("workload", ["tiny.tbase", "tiny.resnet"])
def test_reference_agrees_with_the_system(checkout, workload):
    _, result = in_process(checkout[1], workload, seed=2 ** 31 + 7)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    rows = by_name(result["compared"])
    assert set(rows) == {"loss_step1_rel_gap", "loss_step2_rel_gap",
                         "loss_step3_rel_gap",
                         "first_grad_norm_worst_leaf_gap",
                         "first_grad_norm_large_leaf_mean_gap",
                         "param_change_norm_worst_leaf_gap"}
    # the numbers are real comparisons, not zeros
    assert 0 < rows["first_grad_norm_worst_leaf_gap"]["value"]


def test_mesh_path_on_four_virtual_devices(checkout):
    run, result = in_process(checkout[1], "tiny.tbase.dp4", seed=11)
    assert len(run.devices) == 4
    assert result["correct"], result["compared"]


@pytest.mark.parametrize("workload", ["tiny.tbase", "tiny.resnet"])
def test_fp8_control_comes_out_not_correct(checkout, workload):
    rows = control.control(checkout[1], workload, seed=5, rehearse=True)
    assert not all(r["ok"] for r in rows), rows
    # and the same reference against itself is exact
    same = control.control(checkout[1], workload, seed=5, rehearse=True,
                           precision="exact")
    assert all(r["value"] == 0 for r in same)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_step_comes_out_not_correct(checkout, fault, monkeypatch):
    """The rest of a run, with the timed path broken underneath."""
    real_step = train.Trainer.step

    def broken(self):
        if fault == "state_unchanged":
            # a step that computes its loss and returns its state unchanged
            before = {n: self.scope.get(n) for n in self.param_names}
            kept = {n: np.asarray(v) for n, v in before.items()}
            loss = real_step(self)
            for n, v in kept.items():
                self.scope.set(n, v)
            return loss
        # a step that leaves out a part of the batch: the second half of
        # the rows repeats the first
        k = self.steps_done % len(self.batches)
        feed = self.batches[k]
        half = {n: np.concatenate([v[:len(v) // 2]] * 2) for n, v in
                feed.items()}
        self.batches[k] = half
        try:
            return real_step(self)
        finally:
            self.batches[k] = feed

    monkeypatch.setattr(train.Trainer, "step", broken)
    _, result = in_process(checkout[1], "tiny.tbase", seed=3)
    assert result["correct"] is False
    bad = [r["name"] for r in result["compared"] if not r["ok"]]
    if fault == "state_unchanged":
        assert "param_change_norm_worst_leaf_gap" in bad
    else:
        assert bad


def _run_cli(root, *args, rehearse=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               BENCH_RUN="ignored")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"),
           "--manifest", os.path.join(root, "BENCHMARK.json"), *args]
    if rehearse:
        cmd.append("--rehearse")
    return subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env=env, cwd=root)


def test_whole_run_prints_the_contracts_keys_last(checkout):
    root = checkout[0]
    done = _run_cli(root, "--workload", "tiny.tbase", "--seed", "9",
                    "--seconds", "1", "--trace", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # a rehearsal carries no device metric
    assert set(line["metrics"]) == {"import_s", "first_step_s",
                                    "pallas_calls"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert sum(l.startswith("compared ") for l in lines) == 6


def test_without_a_tpu_there_is_no_result(checkout):
    done = _run_cli(checkout[0], "--workload", "tiny.tbase", "--seed", "9",
                    "--seconds", "1", "--trace", "0", rehearse=False)
    assert done.returncode == 3
    assert done.stdout.strip() == ""


def test_a_config_a_mix_a_job_kind_and_a_layer_metric_are_new_files(tmp_path):
    """Adds a dummy of each to a temporary copy: new files and one manifest
    entry each, no file that was there edited."""
    root, manifest_path = tiny.make_checkout(tmp_path)
    bench = os.path.join(root, "benchmark")
    before = {}
    for folder, _, files in os.walk(bench):
        for f in files:
            p = os.path.join(folder, f)
            before[p] = os.path.getmtime(p), os.path.getsize(p)

    with open(os.path.join(bench, "jobs", "echo.py"), "w") as f:
        f.write('''
def run(run):
    devices = run.claim_devices()
    ctx = {"answer": run.config["answer"] * run.traffic["times"]}
    metrics = (run.read_layer_metrics(ctx) if run.trace
               else {"setup_s": 0.5})
    return {"correct": True, "attempted": 1, "failed": 0,
            "metrics": metrics, "memory_peak_bytes": 0}
''')
    with open(os.path.join(bench, "layer_metrics", "echo_count.py"),
              "w") as f:
        f.write('def read(ctx):\n    return ctx.get("answer")\n')
    with open(os.path.join(bench, "configs", "echo.json"), "w") as f:
        json.dump({"name": "echo", "answer": 7}, f)
    with open(os.path.join(bench, "traffic", "echo.x3.json"), "w") as f:
        json.dump({"job": "echo", "times": 3}, f)
    manifest = harness.load_json(manifest_path)
    manifest["configs"].append({"name": "echo", "source": "tests",
                                "file": "benchmark/configs/echo.json",
                                "reduced": [], "why": "tests"})
    manifest["workloads"].append({"name": "echo.x3", "config": "echo",
                                  "traffic": "echo.x3", "chips": 1,
                                  "why": "tests"})
    manifest["per_layer"].append({
        "name": "echo_count", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "entry points",
        "moves": "setup_s", "workloads": ["echo.x3"]})
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)

    done = _run_cli(root, "--workload", "echo.x3", "--seed", "1",
                    "--seconds", "1", "--trace", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["metrics"]["echo_count"] == {"value": 21.0, "unit": "calls"}
    for p, stamp in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp, p
