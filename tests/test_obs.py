"""Telemetry plane (ISSUE 17): tracing, metrics registry, flight
recorder; and (ISSUE 24) the Executor's own spans through the one span
primitive, on the obs tracer and on a ``jax.profiler`` trace, with the
compile record beside them.

The expensive acceptance drills live here too: one ``RouterClient.
predict`` against a REAL router subprocess must produce ONE stitched
trace across client, router, and worker processes; and a SIGKILL chaos
burst must leave a flight-recorder dump that accounts for every
accepted request.
"""

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import tracemalloc

import numpy as np
import pytest

from paddle_tpu.obs import flight, trace
from paddle_tpu.obs.registry import Registry
from paddle_tpu.serving import (DeadlineExceededError, Router, RouterClient,
                                ServerOverloadedError, WorkerFailedError)
from paddle_tpu.serving import rpc
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.serving.router import ROUTER_READY_PREFIX

FC_FEED = {"x": np.full((1, 8), 0.5, "float32")}


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends with tracing disabled — the module
    global must never leak between tests (or into other test files)."""
    trace.stop()
    yield
    trace.stop()


def _wait_for(cond, timeout=60.0, what="condition"):
    t0 = time.time()
    while not cond():
        assert time.time() - t0 < timeout, "timed out waiting for " + what
        time.sleep(0.05)


# -- spans under a fake clock ----------------------------------------------

def test_fake_clock_span_nesting_and_determinism():
    clk = {"t": 100.0}
    tracer = trace.Tracer(clock=lambda: clk["t"])
    with tracer.span("outer") as outer:
        clk["t"] += 1.0
        with tracer.span("inner") as inner:
            clk["t"] += 0.5
        clk["t"] += 0.25
    spans = {s["name"]: s for s in tracer.drain()}
    assert spans["inner"]["parent_id"] == outer.span_id
    assert spans["outer"]["parent_id"] is None
    assert spans["inner"]["trace_id"] == outer.trace_id
    assert inner.trace_id == outer.trace_id
    # injected clock => wall offset is forced to zero, times are EXACT
    assert spans["outer"]["t0"] == 100.0
    assert spans["outer"]["dur"] == 1.75
    assert spans["inner"]["t0"] == 101.0
    assert spans["inner"]["dur"] == 0.5
    # context popped cleanly: a new span is a fresh root
    with tracer.span("later"):
        pass
    later = tracer.drain()[0]
    assert later["parent_id"] is None
    assert later["trace_id"] != outer.trace_id


def test_span_records_error_tag_and_sets_tags():
    tracer = trace.Tracer(clock=lambda: 0.0)
    with pytest.raises(ValueError):
        with tracer.span("boom") as sp:
            sp.set(n=4)
            raise ValueError("x")
    rec = tracer.drain()[0]
    assert rec["tags"] == {"n": 4, "error": "ValueError"}


def test_explicit_parent_crosses_threads():
    tracer = trace.Tracer(clock=lambda: 0.0)
    with tracer.span("submit") as sub:
        ctx = tracer.current()
    done = threading.Event()

    def worker():
        with tracer.span("batch", parent=ctx):
            pass
        done.set()

    threading.Thread(target=worker).start()
    assert done.wait(10.0)
    spans = {s["name"]: s for s in tracer.drain()}
    assert spans["batch"]["trace_id"] == sub.trace_id
    assert spans["batch"]["parent_id"] == sub.span_id


# -- propagation over the rpc header ---------------------------------------

def test_inject_extract_roundtrip_through_rpc_frame():
    header = {"type": "infer", "deadline_s": 1.5}
    ctx = ("00ab" * 4, "11cd" * 4)
    trace.inject(header, ctx=ctx)
    # the trace key must survive real wire framing beside deadline_s
    payload = rpc.encode_msg(header, {"x": np.ones(3, "f4")})
    got_header, _ = rpc.decode_msg(payload)
    assert got_header["deadline_s"] == 1.5
    assert trace.extract(got_header) == ctx
    # extract works with NO tracer installed, and tolerates absence/junk
    assert trace.extract({"type": "infer"}) is None
    assert trace.extract({"trace": "garbage"}) is None
    # inject with no tracer and no explicit ctx is a no-op
    h = {"type": "infer"}
    assert trace.inject(h) == {"type": "infer"}


def test_each_hop_reparents_but_trace_id_propagates_verbatim():
    tracer = trace.Tracer(clock=lambda: 0.0)
    with tracer.span("client") as c:
        header = {}
        trace.inject(header, ctx=c.context())
    # router adopts, opens its own span, re-injects
    ctx = trace.extract(header)
    token = tracer.activate(ctx)
    try:
        with tracer.span("router") as r:
            fwd = dict(header)
            trace.inject(fwd, ctx=tracer.current())
    finally:
        tracer.deactivate(token)
    tid, sid = trace.extract(fwd)
    assert tid == c.trace_id  # verbatim across both hops
    assert sid == r.span_id  # re-parented onto the router's span
    assert r.parent_id == c.span_id


# -- disabled hot path: the zero-allocation contract ------------------------

def test_disabled_span_is_falsy_singleton():
    assert trace.active() is None
    sp = trace.span("x")
    assert sp is trace.span("y")
    assert not sp
    assert sp.set(a=1) is sp
    assert sp.context() is None
    with sp:
        pass
    assert trace.current() is None
    assert trace.flush() is None


def test_disabled_hot_path_zero_allocations():
    def hot():
        for _ in range(200):
            sp = trace.span("x")
            if sp:
                sp.set(a=1)  # guarded call sites never allocate the dict
            trace.current()
            trace.flush()

    hot()  # warm any lazy caches before measuring
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        hot()
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    leaks = [s for s in after.compare_to(before, "lineno")
             if s.traceback[0].filename == trace.__file__
             and s.size_diff > 0]
    assert not leaks, "disabled tracing allocated: %s" % leaks


# -- tracing overhead <5% on the serving smoke path -------------------------

def test_tracing_overhead_under_5_percent_of_a_serving_request():
    """Per-span cost (enabled minus disabled) times the spans a routed
    request emits must stay under 5% of a real fc-engine request."""
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.worker import build_model

    # many short loops and the fastest of them: a loop of 3 ms is either
    # undisturbed or not, where three of 15 ms all lost their cores to the
    # other xdist workers' compiles once (65 us a span against 4.5 alone:
    # the one failure of the driver's run of PR 37's first tree)
    n, loops = 500, 20

    def span_loop():
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("bench") as sp:
                if sp:
                    sp.set(k=1)
        return (time.perf_counter() - t0) / n

    disabled = min(span_loop() for _ in range(loops))
    tracer = trace.start(max_spans=2 * n * loops)
    try:
        enabled = min(span_loop() for _ in range(loops))
        assert len(tracer.spans) >= n * loops
    finally:
        trace.stop()
    per_span = max(0.0, enabled - disabled)

    engine = ServingEngine(build_model("builtin:fc"), num_replicas=1,
                           ladder=(1, 2, 4, 8))
    try:
        engine.warmup()
        lat = []
        for _ in range(10):
            t0 = time.perf_counter()
            engine.predict(FC_FEED, timeout_s=30.0)
            lat.append(time.perf_counter() - t0)
        request_s = sorted(lat)[len(lat) // 2]
    finally:
        engine.shutdown()
    # a routed predict opens ~7 spans end to end (client, door, queue,
    # dispatch, worker queue, engine batch, executor run); budget 8
    overhead = 8 * per_span
    assert overhead < 0.05 * request_s, (
        "tracing overhead %.1fus vs request %.1fus (%.2f%%)"
        % (overhead * 1e6, request_s * 1e6,
           100.0 * overhead / request_s))


# -- metrics registry + Prometheus exposition -------------------------------

_PROM_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_PROM_VALUE = r"(?:[+-]?[0-9.eE+-]+|NaN|\+Inf|-Inf)"
_PROM_LINE = re.compile(
    r"^(?:# HELP %(n)s .*"
    r"|# TYPE %(n)s (?:counter|gauge|summary|histogram)"
    r"|%(n)s(?:\{[^}]*\})? %(v)s)$"
    % {"n": _PROM_NAME, "v": _PROM_VALUE})


def test_prometheus_exposition_grammar():
    m = ServingMetrics()
    m.observe_completed(0.010)
    m.observe_completed(0.020)
    m.observe_batch(actual=4, bucket=8, cache_hit=False)
    m.observe_decode_step(live=3, bucket=4, generated=3)
    m.bind_gauges(lambda: 2, lambda: 5)
    text = m.prometheus_text()
    assert text.endswith("\n")
    for line in text.rstrip("\n").split("\n"):
        assert _PROM_LINE.match(line), "bad exposition line: %r" % line
    assert "paddle_tpu_serving_requests_completed 2" in text
    assert "paddle_tpu_serving_queue_depth 2" in text
    assert "paddle_tpu_serving_in_flight 5" in text
    assert 'paddle_tpu_serving_latency_seconds{quantile="0.5"}' in text
    assert "paddle_tpu_serving_latency_seconds_count 2" in text
    assert "paddle_tpu_mfu" not in text  # the gauge is gone (ISSUE 24)
    # a TYPE line precedes every sample family
    assert text.index("# TYPE paddle_tpu_serving_requests_completed "
                      "counter") < text.index(
        "paddle_tpu_serving_requests_completed 2")


def test_registry_rejects_bad_names_and_kind_conflicts():
    r = Registry()
    with pytest.raises(ValueError):
        r.counter("0bad")
    with pytest.raises(ValueError):
        r.counter("has space")
    r.counter("ok_total")
    with pytest.raises(TypeError):
        r.gauge("ok_total")
    assert r.counter("ok_total") is r.get("ok_total")  # idempotent


def test_registry_snapshot_consistency():
    """Satellite 2: the registry IS the storage — every pinned snapshot
    counter field must equal its registry metric, always."""
    m = ServingMetrics()
    m.observe_completed(0.01)
    m.observe_failed(2)
    m.observe_rejected()
    m.observe_expired(3)
    m.observe_shed()
    m.observe_retried()
    m.observe_evicted()
    m.observe_respawned()
    m.observe_door_shed()
    m.observe_rerouted(2)
    m.observe_respawn()
    m.observe_heartbeat_miss(4)
    m.observe_deadline_refused()
    m.observe_batch(actual=3, bucket=4, cache_hit=True)
    m.observe_decode_step(live=2, bucket=4, generated=1, ahead=True)
    m.observe_prefix_hit(5)
    m.observe_prefix_eviction()
    m.observe_prefill_chunk(2, 9, 32, deferred=3)
    m.observe_prefill_chunk(1, 4, 8)
    m.observe_admitted(2, 0.75)
    m.observe_idle(0.5)
    m.observe_idle(1.25)
    m.observe_spec(accepted=3, rejected=1)
    m.bind_gauges(lambda: 7, lambda: 1)
    m.bind_prefix_bytes(lambda: 4096)
    m.observe_cache_donated(1 << 20)
    snap = m.snapshot()
    vals = m.registry.values()
    for field in ("requests_completed", "requests_failed",
                  "requests_rejected", "requests_expired", "requests_shed",
                  "requests_retried", "replicas_evicted",
                  "workers_respawned", "door_shed", "rerouted", "respawns",
                  "heartbeat_misses", "deadline_refused", "batches",
                  "compile_cache_hits", "compile_cache_misses",
                  "decode_steps", "decode_steps_ahead_total",
                  "decode_tokens", "queue_depth",
                  "in_flight", "prefix_hits", "prefix_tokens_reused",
                  "prefix_evictions", "prefix_bytes", "cache_donated_bytes",
                  "prefill_chunks", "prefill_tokens", "prefill_lanes",
                  "prefill_deferred_rows", "admitted", "queue_wait_seconds",
                  "idle_seconds", "spec_steps", "spec_drafted",
                  "spec_accepted", "spec_rejected"):
        assert vals["paddle_tpu_serving_" + field] == snap[field], field
    # one verifying run that judged four drafts
    assert (snap["spec_steps"], snap["spec_drafted"]) == (1, 4)
    # derived fields still derive from registry counters
    assert snap["batch_occupancy"] == 3 / 4
    assert snap["slot_occupancy"] == 2 / 4
    assert snap["compile_cache_hit_rate"] == 1.0
    assert snap["spec_accept_rate"] == 3 / 4
    assert snap["prefix_bytes"] == 4096
    assert snap["cache_donated_bytes"] == 1 << 20
    assert (snap["prefill_lanes"], snap["admitted"],
            snap["queue_wait_seconds"], snap["idle_seconds"]) == (
        40, 2, 0.75, 1.75)
    # rows a sub-batched chunk had no room for; a dispatch that names none
    # adds none
    assert (snap["prefill_chunks"], snap["prefill_deferred_rows"]) == (2, 3)
    assert "paddle_tpu_serving_prefill_deferred_rows 3" in \
        m.prometheus_text()
    # the pinned snapshot field list itself is unchanged
    assert set(snap) == {
        "requests_completed", "requests_failed", "requests_rejected",
        "requests_expired", "requests_shed", "requests_retried",
        "replicas_evicted", "workers_respawned", "door_shed", "rerouted",
        "respawns", "heartbeat_misses", "deadline_refused", "queue_depth",
        "in_flight", "batches", "batch_occupancy", "avg_batch_size",
        "compile_cache_hits", "compile_cache_misses",
        "compile_cache_hit_rate", "decode_steps",
        "decode_steps_ahead_total", "decode_tokens",
        "slot_occupancy", "latency_s", "ttft_s", "tpot_s",
        "prefix_hits", "prefix_tokens_reused", "prefix_evictions",
        "prefix_bytes", "cache_donated_bytes", "prefill_chunks",
        "prefill_tokens", "prefill_lanes", "prefill_deferred_rows",
        "admitted", "queue_wait_seconds", "idle_seconds", "spec_steps",
        "spec_drafted", "spec_accepted", "spec_rejected",
        "spec_accept_rate"}
    # and the report names every scalar of the snapshot, these four too
    rows = {line.split()[0] for line in m.report().splitlines()[1:]}
    assert {k for k in snap if not k.endswith("_s")} <= rows


def test_a_sub_batched_chunk_says_its_height_and_counts_its_own_lanes(
        monkeypatch):
    """ISSUE 38: ``prefill_lanes`` adds the rows a chunk run COMPUTED times
    the rung, the ``prefill.chunk`` span carries them as ``sub_rows`` beside
    ``rows``, ``tokens`` and ``lanes``, and ``prefill_deferred_rows`` counts
    the ingesting rows a dispatch left for the next."""
    import paddle_tpu as fluid
    from paddle_tpu.serving import DecodeBatcher, decode_batcher
    from test_serving import _build_lm_family

    monkeypatch.setattr(decode_batcher, "CHUNK_TOKEN_BUDGET", 8)
    pred, dspec, prefill, _ = _build_lm_family(fluid.Scope())
    bat = DecodeBatcher(pred, dspec, ladder=(4,), ctx_ladder=(32,),
                        prefill=prefill, start=False)
    tracer = trace.start()
    try:
        for first in (1, 2, 3):             # rung 4: two rows a chunk
            bat.submit([first, 7, 11, 2, 5], max_new_tokens=2)
        bat.drive()
        spans = tracer.drain()
    finally:
        trace.stop()
    chunks = [s["tags"] for s in spans if s["name"] == "prefill.chunk"]
    assert [(t["rows"], t["sub_rows"], t["chunk"], t["lanes"], t["bucket"])
            for t in chunks] == [(2, 2, 4, 8, 4), (1, 2, 4, 8, 4)]
    m = bat.metrics()
    assert m["prefill_lanes"] == sum(t["lanes"] for t in chunks) == 16
    assert m["prefill_tokens"] == sum(t["tokens"] for t in chunks)
    assert m["prefill_deferred_rows"] == 1
    assert "\npaddle_tpu_serving_prefill_deferred_rows 1" in \
        bat.metrics_.prometheus_text()


# -- the Executor's own spans, through the one primitive (ISSUE 24) ----------

PHASES = ["executor.prepare", "executor.feed_put", "executor.dispatch",
          "executor.writeback"]
STAGES = ["executor.trace", "executor.lower", "executor.backend_compile"]


def _fc_executor(width=8):
    """An executor that has run the startup program of a tiny fc model
    (conftest gives every test fresh default programs and scope)."""
    import paddle_tpu as fluid

    x = fluid.layers.data("x", shape=[width])
    out = fluid.layers.fc(x, size=4)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.full((2, width), 0.5, "float32")}
    return exe, feed, out


def _ticking_clock():
    """A fake clock that moves by one second at every reading: spans that
    follow each other read consecutive numbers."""
    import itertools

    ticks = itertools.count()
    return lambda: float(next(ticks))


def test_executor_phases_nest_under_run_and_cover_it():
    exe, feed, out = _fc_executor()
    tracer = trace.start(clock=_ticking_clock())
    try:
        exe.run(feed=feed, fetch_list=[out])  # stages the variant
        exe.run(feed=feed, fetch_list=[out])  # finds it compiled
    finally:
        trace.stop()
    runs = [s for s in tracer.spans if s["name"] == "executor.run"]
    assert [r["tags"]["variant_hit"] for r in runs] == [False, True]
    assert runs[1]["tags"]["ordinal"] == runs[0]["tags"]["ordinal"] + 1
    expected = [PHASES[:2] + STAGES + PHASES[2:], PHASES]
    for run, names in zip(runs, expected):
        kids = sorted((s for s in tracer.spans
                       if s["parent_id"] == run["span_id"]),
                      key=lambda s: s["t0"])
        assert [k["name"] for k in kids] == names
        # the clock is read by the spans alone, one tick a reading: the
        # children follow each other without a gap and leave the run span
        # nothing but its own two readings
        assert kids[0]["t0"] == run["t0"] + 1
        for before, after in zip(kids, kids[1:]):
            assert after["t0"] == before["t0"] + before["dur"] + 1
        assert kids[-1]["t0"] + kids[-1]["dur"] + 1 == run["t0"] + run["dur"]
        assert all(k["trace_id"] == run["trace_id"] for k in kids)
    by_name = {s["name"]: s for s in tracer.spans
               if s["parent_id"] == runs[1]["span_id"]}
    assert by_name["executor.feed_put"]["tags"] == {
        "bytes": feed["x"].nbytes, "state_relayouts": 0}
    assert by_name["executor.writeback"]["tags"] == {"fetch": "numpy"}


def _host_events(trace_dir, prefix="paddle_tpu."):
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


def test_executor_spans_reach_a_jax_profiler_trace(tmp_path):
    """With no tracer at all: whoever takes a jax.profiler trace finds the
    program's spans on its host plane, children inside their run."""
    import jax

    exe, feed, out = _fc_executor()
    exe.run(feed=feed, fetch_list=[out])
    assert trace.active() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            exe.run(feed=feed, fetch_list=[out], return_numpy=False)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    runs = [e for e in events if e[0] == "paddle_tpu.executor.run"]
    assert len(runs) == 3
    for phase in PHASES:
        found = [e for e in events if e[0] == "paddle_tpu." + phase]
        assert len(found) == 3, phase
        for (_, start, dur), (_, r0, rdur) in zip(sorted(found, key=lambda
                                                          e: e[1]),
                                                  sorted(runs, key=lambda
                                                         e: e[1])):
            assert r0 <= start and start + dur <= r0 + rdur, phase
    # and once the trace is stopped a span is the no-op again
    assert not trace.span("executor.run")


def test_no_span_path_waits_for_the_device(tmp_path, monkeypatch):
    """Tracing must not change the schedule it observes: with a tracer and
    a profiler trace both live, a run that fetches device arrays never
    blocks."""
    import inspect

    import jax

    from paddle_tpu import profiler
    from paddle_tpu.core import executor as executor_mod

    exe, feed, out = _fc_executor()

    def refuse(*a, **k):
        raise AssertionError("a span path waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    trace.start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            got, = exe.run(feed=feed, fetch_list=[out], return_numpy=False)
        with profiler.record_event("stage"):
            pass
    finally:
        jax.profiler.stop_trace()
        trace.stop()
    assert isinstance(got, jax.Array)
    for module in (executor_mod, trace, profiler):
        assert "block_until_ready" not in inspect.getsource(module)


def test_spans_of_one_run_cost_nothing_retained_and_under_50us_when_off():
    """No tracer, no profiler trace: the spans one exe.run opens (the run,
    its four phases, tags guarded as the executor guards them) allocate
    nothing that stays and take well under 50 microseconds together."""
    from paddle_tpu import profiler

    assert trace.active() is None

    def one_run():
        with trace.span("executor.run") as run_sp:
            with trace.span("executor.prepare"):
                pass
            if run_sp:
                run_sp.set(ordinal=1, variant_hit=True)
            with trace.span("executor.feed_put") as sp:
                if sp:
                    sp.set(bytes=1)
            with trace.span("executor.dispatch"):
                pass
            with trace.span("executor.writeback") as sp:
                if sp:
                    sp.set(fetch="device")

    def loop(n=2000):
        t0 = time.perf_counter()
        for _ in range(n):
            one_run()
        return (time.perf_counter() - t0) / n

    loop(200)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        loop(200)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    leaks = [s for s in after.compare_to(before, "lineno")
             if s.traceback[0].filename in (trace.__file__,
                                            profiler.__file__)
             and s.size_diff > 0]
    assert not leaks, "spans that are off allocated: %s" % leaks
    per_run = min(loop() for _ in range(3))
    assert per_run < 50e-6, "%.1f us a run" % (per_run * 1e6)


def test_lowered_hlo_text_after_run_traces_lowers_and_compiles_nothing():
    import jax.monitoring

    exe, feed, out = _fc_executor()
    exe.run(feed=feed, fetch_list=[out])
    seen = []
    listening = [True]

    def on(event, duration, **kwargs):
        if listening[0]:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        compiled = exe.lowered_hlo_text(optimized=True)
        stablehlo = exe.lowered_hlo_text(optimized=False)
    finally:
        listening[0] = False
    assert "HloModule" in compiled and "stablehlo" in stablehlo
    assert not [e for e in seen if "jaxpr_trace_duration" in e
                or "jaxpr_to_mlir" in e or "backend_compile" in e], seen


def test_compile_record_fields_and_counters_hit_and_miss():
    exe, feed, out = _fc_executor()
    assert (exe.runs, exe.variant_hits, exe.variant_misses) == (1, 0, 1)
    for _ in range(3):
        exe.run(feed=feed, fetch_list=[out])
    assert (exe.runs, exe.variant_hits, exe.variant_misses) == (4, 2, 2)
    other = {"x": np.ones((5, 8), "float32")}  # another batch: a variant
    exe.run(feed=other, fetch_list=[out])
    assert (exe.runs, exe.variant_hits, exe.variant_misses) == (5, 2, 3)
    assert exe.state_relayouts == 0
    startup, first, second = exe.compile_records
    assert startup["fetch_names"] == [] and startup["ordinal"] == 0
    for record, ordinal in ((first, 1), (second, 4)):
        assert record["fetch_names"] == [out.name]
        assert record["feed_names"] == ["x"]
        assert record["ordinal"] == ordinal and not record["restaged"]
        assert record["ops"] > 0 and record["meshed"] is False
        for phase in ("trace_s", "lower_s", "backend_compile_s"):
            assert record[phase] > 0
        # conftest turns the persistent cache off
        assert record["persistent_cache"] == "off"
        assert set(record["memory"]) == {"temp_bytes", "argument_bytes",
                                         "output_bytes", "alias_bytes"}
        assert record["memory"]["argument_bytes"] > 0
        assert record["gates"] == {}
        assert record["kernel_traces"] == {}  # no Pallas kernel in the step
    # the records outlive close(); the kept executable does not
    exe.close()
    assert len(exe.compile_records) == 3
    with pytest.raises(RuntimeError):
        exe.lowered_hlo_text()


def test_compile_record_says_whether_the_persistent_cache_served(tmp_path):
    import jax
    import paddle_tpu as fluid
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        x = fluid.layers.data("x", shape=[6])
        out = fluid.layers.fc(x, size=3)
        feed = {"x": np.ones((2, 6), "float32")}
        served = []
        for _ in range(2):  # a second executor stages the same step anew
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            exe.run(feed=feed, fetch_list=[out])
            served.append(exe.compile_records[-1]["persistent_cache"])
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert served == ["miss", "hit"]


def test_record_event_is_a_span_of_the_same_primitive():
    from paddle_tpu import profiler

    profiler.reset_profiler()
    tracer = trace.start(clock=_ticking_clock())
    try:
        with trace.span("outer"):
            with profiler.record_event("stage/a"):
                pass
    finally:
        trace.stop()
    inner, outer = tracer.spans
    assert inner["name"] == "stage/a" and outer["name"] == "outer"
    assert inner["parent_id"] == outer["span_id"]
    assert "stage/a" not in profiler._report()  # the profiler is off
    # the table is fed from the span's own duration, tracer or not
    profiler.start_profiler()
    try:
        with profiler.record_event("stage/b"):
            time.sleep(0.002)
    finally:
        report = profiler.stop_profiler(silent=True)
    row, = [line.split() for line in report.splitlines()
            if line.startswith("stage/b")]
    assert row[1] == "1" and float(row[2]) >= 2.0
    profiler.reset_profiler()


def test_fused_ce_gate_answers_with_its_reason():
    import jax.numpy as jnp
    from paddle_tpu.ops import fused_ce
    from paddle_tpu.ops.gates import GateDecision, placed

    w = jnp.zeros((512, 30000), jnp.bfloat16)
    small = jnp.zeros((128 * 256, 512), jnp.bfloat16)  # cell 1's head
    large = jnp.zeros((256 * 256, 512), jnp.bfloat16)
    here = fused_ce._use_fused(small, w)
    assert isinstance(here, GateDecision) and not here
    assert here.blocked_only_by("platform")
    with placed("tpu"):
        refused = fused_ce._use_fused(small, w)
        admitted = fused_ce._use_fused(large, w)
    assert not refused and refused.kernel == "xla_projection_ce"
    assert refused.blocked_only_by("size")
    assert "9.83e+08 logits" in refused.describe()
    assert admitted and admitted.kernel == "fused_ce"


def test_gate_decisions_ride_the_trace_span_and_the_compile_record():
    import paddle_tpu as fluid

    x = fluid.layers.data("x", shape=[4, 16])
    out = fluid.layers.multi_head_attention(x, x, x, d_model=16, n_head=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    tracer = trace.start()
    try:
        exe.run(feed={"x": np.ones((2, 4, 16), "float32")},
                fetch_list=[out])
    finally:
        trace.stop()
    staged, = [s for s in tracer.spans if s["name"] == "executor.trace"]
    gates = staged["tags"]["gates"]
    assert list(gates) == ["flash_attention"]
    (line, times), = gates["flash_attention"].items()
    assert line.startswith("fell back to reference") and times == 1
    assert exe.compile_records[-1]["gates"] == gates
    compiled, = [s for s in tracer.spans
                 if s["name"] == "executor.backend_compile"]
    assert compiled["tags"] == {"persistent_cache": "off"}


def test_kernel_traces_ride_the_trace_span_and_the_compile_record():
    """Two attention layers of one signature, trained: the forward body is
    traced once for four sites (each layer in the forward pass and in the
    autodiff replay) and the backward once for two. The same program on
    the reference path (no kernel) reports nothing."""
    import jax
    import paddle_tpu as fluid
    import paddle_tpu.ops.flash_attention as fa

    x = fluid.layers.data("x", shape=[8, 16])
    h = x
    for _ in range(2):
        h = fluid.layers.multi_head_attention(h, h, h, d_model=16, n_head=2)
    loss = fluid.layers.mean(h)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    feed = {"x": np.ones((2, 8, 16), "float32")}

    def staged():
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        tracer = trace.start()
        try:
            exe.run(feed=feed, fetch_list=[loss])
        finally:
            trace.stop()
        span, = [s for s in tracer.spans if s["name"] == "executor.trace"]
        assert exe.compile_records[-1]["kernel_traces"] == \
            span["tags"]["kernel_traces"]
        return span["tags"]["kernel_traces"]

    assert staged() == {}  # CPU-placed: the gate takes the reference
    jax.clear_caches()  # "once per process": not what a test before traced
    fa._INTERPRET = True
    try:
        assert staged() == {
            "dense_vmem.bwd": {"traced": 1, "reused": 1},
            "dense_vmem.fwd": {"traced": 1, "reused": 3}}
        # a second executor stages the same step anew: every site reuses
        assert staged() == {
            "dense_vmem.bwd": {"traced": 0, "reused": 2},
            "dense_vmem.fwd": {"traced": 0, "reused": 4}}
    finally:
        fa._INTERPRET = False


def test_replaced_state_array_is_staged_again_not_refused():
    """A state array that no longer has the type the variant was staged
    for: jit would retrace; the kept executable cannot, so the executor
    stages the variant again and says so in its record."""
    import jax.numpy as jnp
    import paddle_tpu as fluid

    exe, feed, out = _fc_executor()
    want, = exe.run(feed=feed, fetch_list=[out])
    scope = fluid.global_scope()
    weight = fluid.default_main_program().global_block() \
        .all_parameters()[0].name
    scope.set(weight, jnp.asarray(scope.get(weight), jnp.bfloat16))
    got, = exe.run(feed=feed, fetch_list=[out])
    # atol: an output that happens to lie near 0 moves by the weight's
    # bfloat16 rounding, which no relative bound covers
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3)
    assert [r["restaged"] for r in exe.compile_records] == [
        False, False, True]


def test_state_relayouts_counts_the_arrays_a_call_had_to_move():
    """A scope filled by a one-device startup run, then a step over a
    four-device mesh: the first call lays every state array out over the
    mesh, a steady loop moves none."""
    import jax
    import paddle_tpu as fluid

    x = fluid.layers.data("x", shape=[8])
    loss = fluid.layers.mean(fluid.layers.fc(x, size=4))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    meshed = fluid.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(
        loss_name=loss.name, places=jax.devices("cpu")[:4])
    feed = {"x": np.ones((8, 8), "float32")}
    exe.run(meshed, feed=feed, fetch_list=[loss])
    moved = exe.state_relayouts
    assert moved >= 2  # the fc's weight and bias at the least
    for _ in range(2):
        exe.run(meshed, feed=feed, fetch_list=[loss])
    assert exe.state_relayouts == moved
    assert exe.compile_records[-1]["meshed"] is True


# -- flight recorder --------------------------------------------------------

def test_flight_recorder_ring_bounds_and_dump(tmp_path, monkeypatch):
    rec = flight.FlightRecorder(capacity=4, clock=lambda: 1.0)
    for i in range(10):
        rec.record("edf.shed", n=i)
    assert len(rec.events()) == 4  # ring is bounded
    assert rec.counts() == {"edf.shed": 10}  # counts are not
    assert [e["n"] for e in rec.events()] == [6, 7, 8, 9]
    path = rec.dump(str(tmp_path / "f.json"), reason="test")
    dump = flight.load(path)
    assert dump["reason"] == "test"
    assert dump["counts"] == {"edf.shed": 10}
    assert len(dump["events"]) == 4
    # maybe_dump is a no-op without the env, dumps with it
    monkeypatch.delenv(flight.ENV_FLIGHT_DIR, raising=False)
    assert flight.maybe_dump() is None
    monkeypatch.setenv(flight.ENV_FLIGHT_DIR, str(tmp_path))
    flight.record("test.event")
    out = flight.maybe_dump(reason="unit")
    assert out == flight.dump_path()
    assert any(e["kind"] == "test.event"
               for e in flight.load(out)["events"])


def test_flight_dump_accounts_for_every_request_after_sigkill(
        tmp_path, monkeypatch):
    """The acceptance drill: SIGKILL a worker mid-burst; the shutdown
    dump must hold one request.outcome per accepted request (zero silent
    telemetry losses) plus the respawn evidence."""
    monkeypatch.setenv(flight.ENV_FLIGHT_DIR, str(tmp_path))
    router = Router("builtin:fc", num_workers=2, heartbeat_interval_s=0.2)
    try:
        router.start()
        client = RouterClient(router.address, pool_size=8)
        for _ in range(2):
            client.predict(FC_FEED, timeout_s=60.0)
        flight.RECORDER.clear()  # the audited ledger starts here
        futs = [client.submit(FC_FEED, timeout_s=60.0) for _ in range(8)]
        os.kill(router._workers[0].pid, signal.SIGKILL)
        resolved = typed = 0
        for f in futs:
            try:
                f.result(60.0)
                resolved += 1
            except (WorkerFailedError, ServerOverloadedError,
                    DeadlineExceededError):
                typed += 1
        assert resolved + typed == 8
        _wait_for(lambda: router.metrics_.snapshot()["respawns"] >= 1,
                  what="respawn")
        client.close()
    finally:
        router.shutdown()
    dump = flight.load(flight.dump_path())
    assert dump["reason"] == "router-shutdown"
    outcomes = [e for e in dump["events"]
                if e["kind"] == "request.outcome"]
    assert len(outcomes) == 8, dump["counts"]
    assert sum(1 for e in outcomes if e["outcome"] == "completed") \
        == resolved
    assert dump["counts"].get("worker.respawn", 0) >= 1


# -- the stitched cross-process trace ---------------------------------------

def _read_ready_line(proc, timeout=120.0):
    out = {}

    def reader():
        for line in proc.stdout:
            if line.startswith(ROUTER_READY_PREFIX):
                out["info"] = json.loads(line[len(ROUTER_READY_PREFIX):])
                return

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    t.join(timeout)
    return out.get("info")


def test_one_predict_one_trace_across_three_processes(tmp_path):
    """ISSUE 17 acceptance: ONE RouterClient.predict against a 2-worker
    router subprocess yields ONE trace, stitched by the propagated trace
    id across client, router, and worker processes."""
    trace_dir = str(tmp_path / "traces")
    env = dict(os.environ)
    env["PADDLE_TPU_TRACE"] = trace_dir
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.serving.router",
         "--model", "builtin:fc", "--workers", "2"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env)
    try:
        info = _read_ready_line(proc)
        assert info, "router never announced READY"
        trace.start(trace_dir=trace_dir)
        try:
            client = RouterClient(("127.0.0.1", info["port"]))
            (o,) = client.predict(FC_FEED, timeout_s=60.0)
            assert o.shape == (1, 4)
            client.close()
        finally:
            trace.stop()  # flushes the client's shard
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(30)

    spans = trace.load_dir(trace_dir)
    roots = [s for s in spans if s["name"] == "client.predict"]
    assert len(roots) == 1  # ONE predict -> ONE root
    tid = roots[0]["trace_id"]
    tspans = [s for s in spans if s["trace_id"] == tid]
    names = {s["name"] for s in tspans}
    # the acceptance set: door, dispatch, worker queue, engine run —
    # all on the ONE propagated trace id
    assert {"client.predict", "router.door", "router.dispatch",
            "worker.queue", "engine.batch", "executor.run"} <= names
    assert len(tspans) >= 4
    pids = {s["pid"] for s in tspans}
    assert len(pids) >= 3, "trace did not span 3 processes: %s" % pids
    # fully stitched: every non-root parent resolves inside the trace
    ids = {s["span_id"] for s in tspans}
    for s in tspans:
        if s["parent_id"] is not None:
            assert s["parent_id"] in ids, s
    # and the stray-span check: nothing from this drill landed on a
    # DIFFERENT trace id with these names (a broken re-parent would)
    for s in spans:
        if s["name"] in ("router.door", "worker.queue", "engine.batch"):
            assert s["trace_id"] == tid
