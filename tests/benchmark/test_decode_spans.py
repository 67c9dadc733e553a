"""What PR 37 added to the benchmark: the decode loop's own spans and
counters read out of a serving run (``decode_spans``: the parts of the
median step, the loop's work between two quanta, the part of a step's wait
that is a chunk's run, the chip's idle time split three ways) on a small
recorded trace; what each reader gives on the parent's rows (nothing); and
the three counter readers and the compile records' in traced rehearsals of
the tiny serving cells on the CPU."""

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

import appended  # noqa: E402
import tiny_serve  # noqa: E402
from benchmark import (decode_spans, harness, program_spans,  # noqa: E402
                       serve_trace)

P = serve_trace.PREFIX
SPAN_READERS = ["fetch_ms", "sample_ms", "admit_plan_ms", "chunk_wait_ms",
                "idle_host_ms", "idle_unspanned_pct"]
COUNTER_READERS = ["queue_wait_mean_ms.chat", "chunk_lane_fill_pct",
                   "idle_no_request_pct.chat"]
TWINS = ["fetch_ms", "sample_ms", "admit_plan_ms", "idle_host_ms",
         "idle_unspanned_pct"]
NEW = (SPAN_READERS + [n + ".chat" for n in TWINS] + COUNTER_READERS
       + ["cache_alias_pct"])
CHAT, SAT, GLM = ("opt1p3b.serve.chat", "opt1p3b.serve.chat.sat",
                  "glm52.serve.longdoc.sat")


def reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py")).read


def recorded(keep=None, since=0):
    """(ServeTrace of the recorded rows, the record). ``keep``: the span
    names the host rows are cut down to (the parent's: what PR 36 opened).
    ``since``: the instant the profiler started, so that a span opened
    before it is not on record and a device run is seen from there on."""
    with open(os.path.join(HERE, "recorded_decode_spans.json")) as f:
        rec = json.load(f)
    host = [tuple(r) for r in rec["host"]
            if (keep is None or r[0][len(P):] in keep) and r[1] >= since]
    modules = [(max(s, since), e) for s, e in rec["modules"] if e > since]
    device = [("fusion.%d" % i, float(s), float(e - s))
              for i, (s, e) in enumerate(modules)]
    return serve_trace.ServeTrace([device], host, {}, [modules]), rec


PARENT = ("decode.step", "prefill.chunk", "executor.run")


# -- the parts of the median step ---------------------------------------------

def test_the_parts_add_up_to_the_median_step():
    trace, rec = recorded()
    want = rec["expect"]
    assert list(trace.median_span("decode.step")) == want["median_step"]
    ctx = {"trace": trace}
    parts = {"decode.feed": trace.child_ms("decode.step", "decode.feed"),
             "executor.run": reader("predict_ms")(ctx),
             "decode.fetch": reader("fetch_ms")(ctx),
             "decode.sample": reader("sample_ms")(ctx)}
    for span, ns in want["parts_ns"].items():
        assert parts[span] == pytest.approx(ns / 1e6), span
    # the four leave the step's span the 40 ns between them
    assert reader("decode_step_ms")(ctx) - sum(parts.values()) == \
        pytest.approx(40e-6)
    # and the old remainder is the three of them that are no predictor call
    assert reader("sample_deliver_ms")(ctx) == pytest.approx(
        (760 - 40) / 1e6)


def test_admission_and_the_plan_are_read_between_the_step_and_the_quantum_before():
    trace, rec = recorded()
    # the chunk before the median step ended at 1200: the admission and the
    # plan of 1210-1230 count, those before the chunk and after the step not
    assert decode_spans.admit_plan_ms(trace) == pytest.approx(
        rec["expect"]["admit_plan_ns"] / 1e6)
    assert reader("admit_plan_ms")({"trace": trace}) == \
        decode_spans.admit_plan_ms(trace)
    # a median step that is the trace's first quantum takes what lies
    # before it
    first = serve_trace.SpanReadings(
        [(P + "decode.admit", 0, 7), (P + "decode.plan", 8, 2),
         (P + "decode.step", 20, 100)])
    assert decode_spans.admit_plan_ms(first) == pytest.approx(9e-6)


def test_chunk_wait_takes_only_the_chunks_run_out_of_the_fetch():
    trace, rec = recorded()
    # the median step's fetch (1310-1900) spans the chunk's run, to 1700,
    # and the step's own, 1700-1880: only the first is another quantum's
    assert trace.quanta["prefill.chunk"] == [(1180, 1700)]
    assert trace.quanta["decode.step"] == [(1700, 1880), (2100, 2310),
                                           (2485, 3190)]
    got = reader("chunk_wait_ms")({"trace": trace})
    assert got == pytest.approx(rec["expect"]["chunk_wait_ns"] / 1e6)
    assert reader("fetch_ms")({"trace": trace}) - got == pytest.approx(
        200e-6)
    # a median step that follows no chunk waited for none: 0, not None
    alone, _ = recorded()
    alone.quanta = dict(alone.quanta, **{"prefill.chunk": []})
    assert decode_spans.chunk_wait_ms(alone) == 0.0


# -- the chip's idle time, three ways -----------------------------------------

def test_idle_time_is_split_between_no_request_the_host_and_no_span():
    trace, rec = recorded()
    want = rec["expect"]
    got = program_spans.idle_by_span(trace.host, trace.busy[0])
    assert set(got) == set(want["idle_ns_by_span"])
    for name, ns in want["idle_ns_by_span"].items():
        assert got[name] == pytest.approx(ns / 1e9), name
    assert sum(got.values()) == pytest.approx(want["idle_ns"] / 1e9)
    assert trace.window_s == pytest.approx(want["window_ns"] / 1e9)
    waiting, host, unspanned = decode_spans.idle_split(trace)
    assert (waiting, host, unspanned) == pytest.approx(
        (800e-9, 495e-9, 80e-9))
    ctx = {"trace": trace}
    assert reader("idle_host_ms")(ctx) == pytest.approx(
        495e-6 / want["quanta"])
    assert reader("idle_unspanned_pct")(ctx) == pytest.approx(
        100.0 * 80 / want["idle_ns"])


def test_the_quantum_under_way_when_the_profiler_started_is_left_out():
    """A trace that starts inside the first step: the step's span and its
    ``decode.feed`` were open and are not on record, its later children
    are. The idle time of that quantum would read as under no span; the
    split starts at the first admission on record."""
    cut, _ = recorded(since=1250)
    names = [name[len(P):] for name, _, _ in sorted(
        cut.host, key=lambda r: r[1])]
    assert names[:4] == ["executor.run", "decode.fetch", "decode.sample",
                         "decode.admit"]
    whole = program_spans.idle_by_span(cut.host, cut.busy[0])
    # 1900-1910 and 1990-2000 lay under the step that is not on record
    assert whole[program_spans.NO_SPAN] == pytest.approx(80e-9)
    assert decode_spans.accounted(cut.host, cut.busy[0]) == [
        (2010, 2010), (2100, 2310), (2485, 3190), (3190, 3190)]
    # from 2010 on: the gaps 2010-2100 and 2310-2485, 50 ns of them between
    # two spans of the loop
    assert decode_spans.idle_split(cut) == pytest.approx(
        (0.0, 215e-9, 50e-9))


def test_innermost_is_the_shortest_span_that_covers_an_instant():
    rows = [("a", 0, 100), ("b", 10, 50), ("c", 20, 10), ("d", 55, 100),
            ("e", 300, 0)]
    assert program_spans.innermost(rows) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 55, "b"),
        (55, 60, "b"), (60, 100, "a"), (100, 155, "d")]


def test_idle_time_goes_to_the_innermost_of_nested_and_overlapping_spans():
    """The one reader of the rule (``program_spans.idle_by_span``, which
    ``ProgramSpans.idle_by_span`` calls) against a trace made by hand:
    ``run`` 0-1000 holds ``prepare`` 100-400, which holds ``lookup``
    150-250; ``dispatch`` 350-700 overlaps ``prepare`` and is the longer
    of the two; ``other`` 900-1300 overlaps the end of ``run`` and is the
    shorter; a span of another prefix is no span of the program. The
    device is busy 0-120, 200-380, 650-950, 1250-1260, 1500-1510."""
    rows = [(P + "run", 0, 1000), (P + "prepare", 100, 300),
            (P + "lookup", 150, 100), (P + "dispatch", 350, 350),
            (P + "other", 900, 400), ("bench.step", 0, 2000)]
    busy = [(0, 120), (200, 380), (650, 950), (1250, 1260), (1500, 1510)]
    want = {
        # 120-200: prepare 120-150, lookup 150-200
        # 380-650: prepare 380-400 (300 long, under dispatch's 350), then
        #          dispatch 400-650
        # 950-1250: other 950-1250 (400 long, under run's 1000 to 1000)
        # 1260-1500: other 1260-1300, then no span of the program
        P + "prepare": 30 + 20, P + "lookup": 50, P + "dispatch": 250,
        P + "other": 300 + 40, program_spans.NO_SPAN: 200}
    got = program_spans.idle_by_span(rows, busy)
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})
    spans = program_spans.ProgramSpans(
        [r for r in rows if r[0].startswith(P)], busy, steps=2)
    assert spans.idle_by_span() == got
    assert sum(got.values()) == pytest.approx(
        sum(b - a for a, b in spans.idle_gaps()) / 1e9)
    # per step, under the spans of one prefix
    assert spans.idle_ms_a_step_under("p") == pytest.approx(50e-6 / 2)


# -- what the parent gives ----------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_the_parents_rows(name):
    """The parent of PR 37: ``decode.step``, ``prefill.chunk`` and the
    executor's spans, counters without the four new ones, and (for the
    compile records) an executor that hands nothing over or keeps none.
    Every new reader returns None and raises nothing, with and without a
    device plane."""
    parent, _ = recorded(keep=PARENT)
    counters = ({"prefill_tokens": 10.0, "decode_steps": 3.0},
                {"prefill_tokens": 90.0, "decode_steps": 9.0})
    for trace in (parent, serve_trace.NoDeviceServeTrace(parent.host)):
        for records in ([], [{"trace_s": 0.1}],
                        [{"donated_feed_bytes": 0,
                          "memory": {"alias_bytes": 64}}]):
            ctx = {"trace": trace, "window_counters": counters,
                   "window": (5.0, 15.0), "compile_records": records}
            assert reader(name)(ctx) is None
    assert reader(name)({"trace": parent, "window": (5.0, 15.0),
                         "window_counters": counters}) is None
    # and the parent's own readings are what they were
    assert reader("decode_step_ms")({"trace": parent}) == pytest.approx(
        760e-6)
    assert reader("predict_ms")({"trace": parent}) == pytest.approx(40e-6)


def test_a_rehearsals_trace_has_the_spans_and_no_device_number():
    """Host rows alone (a run on the CPU): the parts of a step are read,
    nothing that needs the device plane is made up."""
    full, _ = recorded()
    trace = serve_trace.NoDeviceServeTrace(full.host)
    ctx = {"trace": trace}
    assert reader("fetch_ms")(ctx) == pytest.approx(590e-6)
    assert reader("sample_ms")(ctx) == pytest.approx(80e-6)
    assert reader("admit_plan_ms")(ctx) == pytest.approx(15e-6)
    for name in ("chunk_wait_ms", "idle_host_ms", "idle_unspanned_pct"):
        assert reader(name)(ctx) is None, name


# -- the counters' readers ------------------------------------------------------

def test_counter_readers_take_the_windows_difference():
    before = {"queue_wait_seconds": 1.5, "admitted": 10.0,
              "prefill_tokens": 1000.0, "prefill_lanes": 16384.0,
              "idle_seconds": 2.0}
    after = {"queue_wait_seconds": 1.74, "admitted": 22.0,
             "prefill_tokens": 4072.0, "prefill_lanes": 40960.0,
             "idle_seconds": 2.75}
    ctx = {"window_counters": (before, after), "window": (100.0, 110.0)}
    assert reader("queue_wait_mean_ms.chat")(ctx) == pytest.approx(20.0)
    assert reader("chunk_lane_fill_pct")(ctx) == pytest.approx(12.5)
    # 0.75 s of the window's ten in the loop's wait for a request
    assert reader("idle_no_request_pct.chat")(ctx) == pytest.approx(7.5)
    stood = {"window_counters": (after, after), "window": (100.0, 110.0)}
    assert reader("queue_wait_mean_ms.chat")(stood) is None
    assert reader("chunk_lane_fill_pct")(stood) is None
    # a loop that never waited is a reading: none of the window was idle
    assert reader("idle_no_request_pct.chat")(stood) == 0.0


@pytest.mark.parametrize("records,want", [
    ([{"donated_feed_bytes": 4096, "memory": {"alias_bytes": 4096}},
      {"donated_feed_bytes": 0, "memory": {"alias_bytes": 64}}], 100.0),
    # an executable that aliases state of its own beside the caches counts
    # more than it was handed: the reading shows it, nothing cuts it off
    ([{"donated_feed_bytes": 4096, "memory": {"alias_bytes": 4096}},
      {"donated_feed_bytes": 4096, "memory": {"alias_bytes": 4160}}],
     100.0 * 8256 / 8192),
    ([{"donated_feed_bytes": 4096, "memory": {"alias_bytes": 4096}},
      {"donated_feed_bytes": 4096, "memory": {"alias_bytes": 1024}}],
     62.5),
])
def test_cache_alias_pct_is_what_was_aliased_of_what_was_handed_over(
        records, want):
    assert reader("cache_alias_pct")({"compile_records": records}) == \
        pytest.approx(want)


# -- traced rehearsals of the tiny cells ---------------------------------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The tiny serving checkout: its manifest carries the decode loop's
    entries over from ``BENCHMARK.json``, each under the tiny cell of its
    traffic, as ``tiny_serve`` carries every accepted entry."""
    return tiny_serve.make_checkout(tmp_path_factory.mktemp("decode_spans"))


def rehearse(manifest, cell, seconds):
    serve = harness.load_module(os.path.join(ROOT, "benchmark", "jobs",
                                             "serve.py"))
    run = harness.Run(manifest, cell, 2 ** 31 + 37, seconds, 1, True,
                      time.time())
    return run, serve.run(run)


@pytest.mark.parametrize("cell,seconds,wanted", [
    ("tiny.serve.chat.sat", 1.0,
     ["fetch_ms", "sample_ms", "admit_plan_ms", "chunk_lane_fill_pct",
      "cache_alias_pct"]),
    ("tiny.serve.chat", 1.5,
     ["fetch_ms.chat", "sample_ms.chat", "admit_plan_ms.chat",
      "queue_wait_mean_ms.chat", "idle_no_request_pct.chat"]),
])
def test_traced_rehearsal_reads_the_loops_spans_and_counters(
        checkout, cell, seconds, wanted):
    run, result = rehearse(checkout[1], cell, seconds)
    assert result["correct"], result["compared"]
    m = result["metrics"]
    assert set(wanted) <= set(run.metric_names())
    assert set(wanted) <= set(m), set(wanted) - set(m)
    twin = ".chat" if cell.endswith("chat") else ""
    step, predict, fetch, sample = (m[n + twin] for n in (
        "decode_step_ms", "predict_ms", "fetch_ms", "sample_ms"))
    # the step is its parts: the call, the wait, the sampling, and a feed
    # of microseconds (on the chip to 2%: PERF.md; here the client threads
    # and the collector take the interpreter between two spans of a 7 ms
    # step, 18% of it in one run of this test)
    assert 0.75 * step < predict + fetch + sample <= step
    assert m["sample_deliver_ms" + twin] == pytest.approx(
        step - predict)
    assert 0 <= m["admit_plan_ms" + twin] < step
    if twin:
        # the open loop at this rate finds a free slot: a request waits
        # for the quantum under way and no longer
        assert 0 <= m["queue_wait_mean_ms.chat"] < m[
            "server_ttft_mean_ms.chat"]
        # and between two arrivals the loop waits with nothing to serve (a
        # wait that began before the window opened is counted whole as it
        # ends: on a loaded machine that read 103.9 of this 1.5 s window)
        assert 0 < m["idle_no_request_pct.chat"] < 150
    else:
        # 4 slot rows padded to a rung of 8 or 16: one or two ingest
        assert 0 < m["chunk_lane_fill_pct"] <= 100
        assert m["cache_alias_pct"] == pytest.approx(100.0)
    # no device plane on the CPU: no device number is made up
    assert not {"chunk_wait_ms", "idle_host_ms", "idle_unspanned_pct",
                "idle_host_ms.chat", "idle_unspanned_pct.chat"} & set(m)


# -- the entries in the manifest --------------------------------------------------

@pytest.mark.parametrize("case", appended.CASES)
def test_the_manifest_holds_the_loops_entries_and_names_their_readers(
        case, tmp_path):
    """``BENCHMARK.json`` lists the fifteen readers since PR 39 (PR 37
    wrote them and could register none: ``PERF.md``, Findings). Each entry
    is found by name, wherever it stands and whoever else is on its list."""
    root = appended.root(case, tmp_path)
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    names = [x["name"] for x in m["per_layer"]]
    assert len(set(names)) == len(names)
    layers = dict(zip(names, m["per_layer"]))
    assert set(NEW) <= set(layers)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    others = {x["layer"] for x in m["per_layer"] if x["name"] not in NEW}
    for name in NEW:
        x = layers[name]
        assert set(x) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            root, "benchmark", "layer_metrics", name + ".py")), name
        if name.endswith(".chat"):
            assert CHAT in x["workloads"] and x["moves"] == "token_ms_mean"
            assert not {SAT, GLM} & set(x["workloads"])
        else:
            assert {SAT, GLM} <= set(x["workloads"])
            assert CHAT not in x["workloads"]
            assert x["moves"] == "serve_tokens_per_s"
        # every cell it lists reports the end-to-end metric it moves
        assert set(x["workloads"]) <= set(e2e[x["moves"]]["workloads"])
        assert x["unit"] == ("%" if "pct" in name else "ms")
        assert x["better"] in ("lower", "higher")
        assert x["layer"] in others
        assert x["source"] == ("program_counter" if name in COUNTER_READERS
                               + ["cache_alias_pct"] else "program_span")
    for name in TWINS:  # a twin is its sibling's reader, not a copy
        assert reader(name + ".chat") is reader(name)
