"""What PR 24 added to the benchmark: the program's spans read out of a
profiler trace (``program_spans``: medians, idle gaps put down to the
innermost span) on a small recorded trace, the kernels told apart by the
names the program gives them (``named_kernels``), and every new reader in a
rehearsal on the CPU, where the program's spans are real and the device
trace is not there."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tiny  # noqa: E402
from benchmark import (harness, named_kernels, program_spans,  # noqa: E402
                       trace_reduce)


def recorded():
    with open(os.path.join(HERE, "recorded_program_spans.json")) as f:
        rec = json.load(f)
    rows = [tuple(r) for r in rec["host"]]
    return program_spans.ProgramSpans(rows, rec["busy"], rec["steps"]), rec


# -- program spans on the recorded trace --------------------------------------

def test_idle_gaps_go_to_the_innermost_span_that_covers_them():
    spans, rec = recorded()
    assert spans.idle_gaps() == rec["expect"]["idle_gaps"]
    got = spans.idle_by_span()
    want = rec["expect"]["idle_ns_by_span"]
    assert set(got) == set(want)
    for name, ns in want.items():
        assert got[name] == pytest.approx(ns / 1e9), name
    # every idle nanosecond is put down exactly once
    idle = sum(b - a for a, b in spans.idle_gaps())
    assert sum(got.values()) == pytest.approx(idle / 1e9)
    assert spans.idle_ms_a_step_under("executor.") == pytest.approx(
        rec["expect"]["idle_ms_a_step_under_executor"])
    assert spans.idle_ms_a_step_under("serving.") == pytest.approx(
        100 / 1e6 / rec["steps"])


def test_phases_are_read_in_the_median_call_and_add_up_to_it():
    spans, rec = recorded()
    for span, want in rec["expect"]["median_ms"].items():
        assert spans.median_ms(span) == pytest.approx(want), span
    assert list(spans.median_run()) == rec["expect"]["median_run"]
    phases = rec["expect"]["phase_ms_of_median_run"]
    for span, want in phases.items():
        assert spans.phase_ms(span) == pytest.approx(want), span
    # the four phases leave that call's run span the 10 ns between them
    start, end = spans.median_run()
    assert (end - start) / 1e6 - sum(
        spans.phase_ms(p) for p in phases) == pytest.approx(10e-6)
    # medians taken phase by phase come from different calls
    assert spans.median_ms("executor.prepare") != spans.phase_ms(
        "executor.prepare")
    assert spans.phase_ms("executor.trace") is None  # staged outside it


def test_a_trace_without_program_spans_gives_none():
    """What the parent of PR 24 gives: device events, no ``paddle_tpu.*``
    host event. And what a rehearsal gives: host events, no device."""
    _, rec = recorded()
    parent = program_spans.ProgramSpans([], rec["busy"], rec["steps"])
    assert parent.median_ms("executor.prepare") is None
    assert parent.median_run() is None
    assert parent.phase_ms("executor.prepare") is None
    assert parent.idle_ms_a_step_under("executor.") is None
    assert parent.idle_by_span() == {program_spans.NO_SPAN: 500 / 1e9}
    rehearsal = program_spans.ProgramSpans(
        [tuple(r) for r in rec["host"]], None, rec["steps"])
    assert rehearsal.phase_ms("executor.prepare") is not None
    assert rehearsal.idle_by_span() is None
    assert rehearsal.idle_ms_a_step_under("executor.") is None


# -- kernels by name -----------------------------------------------------------

def _kernel_trace(named):
    """One device, two steps; the attention core forward and backward with
    a layout copy under the same op's scope, a fused conv pair."""
    fwd = "dense_vmem.fwd" if named else "jvp_flash_attention_"
    bwd = "dense_vmem.bwd" if named else "jvp_flash_attention_"
    scope = "jit(step)/autodiff/%s/%spallas_call"
    rows = []
    for at in (0.0, 1000.0):
        rows += [
            (fwd + ".56", at, 100.0, scope % (
                "jvp(flash_attention)", fwd + "/" if named else "")),
            ("copy.9", at + 100, 30.0,
             "jit(step)/autodiff/jvp(flash_attention)/transpose"),
            (bwd + ".62", at + 130, 250.0, scope % (
                "transpose(jvp(flash_attention))",
                bwd + "/" if named else "")),
            ("fused_conv.fwd.3" if named else "jvp_fused_conv2d_.3",
             at + 400, 60.0, "jit(step)/autodiff/jvp(fused_conv2d)/"
             + ("fused_conv.fwd/" if named else "") + "pallas_call"),
            ("fused_conv.apply.4" if named else "jvp_fused_conv2d_.4",
             at + 460, 40.0, "jit(step)/autodiff/jvp(fused_conv2d)/"
             + ("fused_conv.apply/" if named else "") + "pallas_call"),
            ("fusion.7", at + 500, 90.0,
             "jit(step)/autodiff/transpose(jvp(fused_conv2d))/add_any"),
        ]
    return trace_reduce.Trace([rows], steps=2)


def test_kernels_are_told_apart_by_direction_and_from_the_ops_round_them():
    trace = _kernel_trace(named=True)
    attention = ("dense_vmem", "packed_stream", "head_split_stream")
    fwd = named_kernels.ms_a_step(trace, attention, ("fwd",))
    bwd = named_kernels.ms_a_step(trace, attention, ("bwd",))
    assert fwd == pytest.approx(100e-6) and bwd == pytest.approx(250e-6)
    # the op's scope also holds the layout copy: attn_ms is the larger
    assert trace.ms_a_step_under(("flash_attention",)) == pytest.approx(
        380e-6)
    conv = named_kernels.ms_a_step(trace, ("fused_conv",),
                                   ("fwd", "apply", "infer"))
    assert conv == pytest.approx(100e-6)
    assert trace.ms_a_step_under(("conv2d", "fused_conv2d")) == \
        pytest.approx(190e-6)


def test_unnamed_kernels_and_no_device_trace_give_none():
    attention = ("dense_vmem", "packed_stream", "head_split_stream")
    parent = _kernel_trace(named=False)
    assert named_kernels.ms_a_step(parent, attention, ("fwd",)) is None
    assert named_kernels.ms_a_step(parent, ("fused_conv",),
                                   ("fwd", "apply")) is None
    assert named_kernels.ms_a_step(trace_reduce.NoDeviceTrace(), attention,
                                   ("fwd", "bwd")) is None


@pytest.mark.parametrize("text,hit", [
    ("dense_vmem.fwd.56", True),
    ("jit(step)/autodiff/jvp(flash_attention)/dense_vmem.fwd/pallas_call",
     True),
    ("dense_vmem.fwd", True),
    ("dense_vmem.bwd.3", False),
    ("my_dense_vmem.fwd.3", False),
    ("jit(step)/dense_vmem.fwdx/pallas_call", False),
    ("", False),
])
def test_a_kernel_name_matches_whole_components_only(text, hit):
    wanted = named_kernels.pattern(("dense_vmem",), ("fwd",))
    assert bool(wanted.search(text)) is hit


# -- every new reader in a rehearsal -------------------------------------------

NEW_READERS = ["trace_s", "lower_s", "backend_compile_s", "prepare_ms",
               "feed_put_ms", "jit_call_ms", "writeback_ms",
               "device_wait_host_ms", "attn_fwd_ms", "attn_bwd_ms",
               "conv_pallas_ms"]


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced rehearsal of the tiny transformer cell; what its readers
    were given (``ctx``) is kept beside the result."""
    root, manifest = tiny.make_checkout(tmp_path_factory.mktemp("checkout"))
    train = harness.load_module(os.path.join(ROOT, "benchmark", "jobs",
                                             "train.py"))
    run = harness.Run(manifest, "tiny.tbase", 2 ** 31 + 24, 0.5, 1, True,
                      time.time())
    kept = {}
    read = run.read_layer_metrics

    def keeping(ctx):
        kept["ctx"] = dict(ctx, run=run)
        return read(ctx)

    run.read_layer_metrics = keeping
    result = train.run(run)
    assert result["correct"], result["compared"]
    return run, result, kept["ctx"]


def test_the_manifest_lists_every_new_reader_for_the_tiny_cell(rehearsal):
    run, _, _ = rehearsal
    assert set(NEW_READERS) <= set(run.metric_names())
    for name in NEW_READERS + ["allreduce_ms", "allreduce_exposed_ms"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py")), name


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_in_a_rehearsal(rehearsal, name):
    run, result, ctx = rehearsal
    value = result["metrics"].get(name)
    if name in ("prepare_ms", "feed_put_ms", "jit_call_ms", "writeback_ms"):
        # the program's spans are real on the CPU too: the phases of the
        # median one of the five profiled calls, which add up to its span
        assert value is not None and 0 < value < 1000
        if name == "jit_call_ms":
            phases = sum(result["metrics"][n] for n in (
                "prepare_ms", "feed_put_ms", "jit_call_ms", "writeback_ms"))
            whole = program_spans.of(ctx).median_ms("executor.run")
            assert 0.95 * whole < phases <= whole
    elif name.endswith("_s"):
        # a rehearsal's compile phases are the CPU backend's: not reported,
        # but the record the reader reads is there, for the training step
        assert value is None
        record = program_spans.compile_record(ctx)
        assert ctx["trainer"].loss.name in record["fetch_names"]
        assert record[name] > 0 and record["persistent_cache"] in (
            "hit", "miss", "off")
        assert record["memory"]["temp_bytes"] >= 0
    else:
        # no device plane in a CPU trace: nothing to read, nothing raised
        assert value is None


def test_readers_find_nothing_in_a_program_without_spans_or_records():
    """The parent of PR 24: an executor without ``compile_records``, a
    trace directory without ``paddle_tpu.*`` events. Every new reader
    returns None and raises nothing."""
    class Exe:
        pass

    class Trainer:
        exe = Exe()

        class loss:
            name = "loss"

    class Device:
        platform = "tpu"

    class Run:
        devices = [Device()]

        def path(self, *parts):
            return os.path.join(HERE, "no_such_directory", *parts)

    for name in NEW_READERS:
        reader = harness.load_module(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
        ctx = {"run": Run(), "trainer": Trainer(),
               "trace": _kernel_trace(named=False)}
        assert reader.read(ctx) is None, name
