"""The sampled token stays on the device (ISSUE 41): the step executable
returns its greedy ids as one more fetch, and the decode loop dispatches
step n+1, that id array its token feed, before it reads step n.

On the CPU, deterministic (``start=False`` and ``drive``; one case runs the
loop's own thread): the ids fetch against ``np.argmax`` on exact ties; the
same answers bit for bit with the loop running ahead as from a predictor
that cannot be asked for ids (the host path), for ``max_new`` ends, an
end-of-sequence end in the middle of a run of steps and a slot recycled
right after it, over a plain cache and over MiMo-V2's ring; the states in
which the loop does NOT run ahead, with the counter counting exactly the
steps that did; no step left unread by ``drive(max_steps=n)``, ``shutdown``
or a failing step; and one step executable a geometry still."""

import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.inference import ProgramPredictor
from paddle_tpu.obs import trace
from paddle_tpu.serving import DecodeBatcher, EngineShutdownError

from test_mimo_v2 import NEW, PROMPTS, VOCAB, _programs
from test_serving import FakeStepModel, _build_lm_family, _counting_seq


class Unasked:
    """A predictor that runs, stages and takes hand-overs like the one it
    wraps and cannot be asked for ids: the loop serves it from its logits,
    on the host, as it served every predictor before."""

    def __init__(self, predictor):
        self._predictor = predictor
        self.fetch_names = predictor.fetch_names
        self.stage = predictor.stage

    def run(self, feed, return_numpy=True, donate_feeds=()):
        return self._predictor.run(feed, return_numpy=return_numpy,
                                   donate_feeds=donate_feeds)


class AskedStepModel(FakeStepModel):
    """The fake step program (next token = token + 1) that can be asked
    for its ids, and says so in a log: ``run`` a step run, ``read`` the
    moment somebody converts a run's ids to host integers."""

    def __init__(self, fail_at=None):
        super().__init__()
        self.fetch_names = list(FakeStepModel.fetch_names)
        self.fail_at = fail_at
        self.log = []

    def fetch_argmax(self, fetch_name):
        name = fetch_name + "@argmax"
        if name not in self.fetch_names:
            self.fetch_names = self.fetch_names + [name]
        return name

    def run(self, feed, return_numpy=True, donate_feeds=()):
        if self.fail_at is not None and len(self.calls) == self.fail_at:
            self.calls.append(None)
            raise RuntimeError("step %d failed" % self.fail_at)
        n = len(self.calls)
        self.log.append(("run", n))
        if isinstance(feed["tok"], _Ids):   # the device's own array: the
            feed = dict(feed, tok=feed["tok"]._ids)     # host reads nothing
        logits, cache = super().run(feed, return_numpy)
        return [logits, cache, _Ids(np.argmax(logits, -1).astype(np.int32),
                                    self.log, n)]


class _Ids:
    """An id array that notes when it is read on the host."""

    def __init__(self, ids, log, n):
        self._ids, self._log, self._n = ids, log, n

    def __array__(self, dtype=None, copy=None):
        self._log.append(("read", self._n))
        return self._ids if dtype is None else self._ids.astype(dtype)


def _asked(**kw):
    model = AskedStepModel(kw.pop("fail_at", None))
    kw.setdefault("ladder", (1, 2, 4))
    kw.setdefault("ctx_ladder", (8, 16))
    kw.setdefault("start", False)
    return model, DecodeBatcher(model, FakeStepModel.spec, **kw)


def _tokens(future):
    return tuple(int(t) for t in np.asarray(future.result(0)).ravel())


# -- (a) the ids fetch is np.argmax, ties and all ----------------------------

TIES = {
    "two_maxima": [0.0, 3.0, 1.0, 3.0, 2.0, 3.0],
    "all_equal": [1.5] * 6,
    "tie_at_the_ends": [7.0, 0.0, 0.0, 0.0, 0.0, 7.0],
    "negative_zero_and_zero": [-1.0, -0.0, 0.0, -2.0, 0.0, -0.0],
    "infinities": [0.0, np.inf, 1.0, np.inf, -np.inf, 2.0],
    "no_tie": [0.1, 0.2, 0.9, 0.3, 0.4, 0.5],
}


@pytest.mark.parametrize("case", sorted(TIES))
def test_the_ids_fetch_is_argmax_of_the_logits_fetch(case):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[6], dtype="float32")
        logits = layers.scale(x, scale=1.0)
    predictor = ProgramPredictor(main, ["x"], [logits], scope=fluid.Scope())
    name = predictor.fetch_argmax(logits.name)
    # asked again it is the same fetch, and a clone shares it
    assert predictor.fetch_argmax(logits.name) == name
    assert predictor.fetch_names == [logits.name, name]
    assert predictor.clone().fetch_argmax(logits.name) == name
    assert [op.type for op in main.global_block().ops].count("argmax") == 1
    row = np.asarray(TIES[case], np.float32)
    rows = np.stack([row, row[::-1], np.roll(row, 2), -row])
    got_logits, got_ids = predictor.run({"x": rows})
    assert got_ids.dtype == np.int32 and got_ids.shape == (4,)
    np.testing.assert_array_equal(got_ids, np.argmax(got_logits, -1))
    np.testing.assert_array_equal(got_ids, np.argmax(rows, -1))


# -- (b) the same answers, ahead or not --------------------------------------

REQUESTS = [([3, 7, 11, 2, 5, 9, 4, 6, 1, 8, 2, 3], 6), ([1, 2], 9),
            ([5], 3), ([8, 9, 10, 11], 4), ([4, 4], 7), ([2], 8)]


@pytest.fixture(scope="module")
def families():
    """Two families over equal weights: one is asked for ids, one is
    wrapped so that it cannot be."""
    asked = _build_lm_family(fluid.Scope())
    pred, dspec, prefill, draft = _build_lm_family(fluid.Scope())
    return asked, (Unasked(pred), dspec, prefill, draft)


def _lm_run(family, slots, chunked, eos=None, requests=REQUESTS):
    """(answers, metrics) of one drive; ``eos``: {request index: id}."""
    pred, dspec, prefill, _draft = family
    bat = DecodeBatcher(pred, dspec, ladder=(slots,), ctx_ladder=(32,),
                        prefill=prefill if chunked else None, start=False)
    futures = [bat.submit(p, max_new_tokens=n, eos_id=(eos or {}).get(i))
               for i, (p, n) in enumerate(requests)]
    bat.drive()
    assert bat._flight is None
    return [_tokens(f) for f in futures], bat.metrics()


@pytest.mark.parametrize("chunked", [False, True], ids=["steps", "chunks"])
@pytest.mark.parametrize("slots", [2, 4])
@pytest.mark.parametrize("ends", ["max_new", "eos_mid_run", "eos_recycled"])
def test_answers_are_the_host_paths_bit_for_bit(families, ends, slots,
                                                chunked):
    asked, unasked = families
    want, host = _lm_run(unasked, slots, chunked)
    eos = None
    if ends != "max_new":
        # an id a request samples in the middle of its run of steps, and
        # not before: the request ends there, which the host learns late.
        # "recycled": every long request ends so, with requests queued
        # behind it that take the slot the moment it is free
        picks = {}
        for i, answer in enumerate(want):
            mid = [k for k in range(2, len(answer) - 1)
                   if answer[k] not in answer[:k]]
            if mid:
                picks[i] = answer[mid[0]]
        assert picks
        eos = picks if ends == "eos_recycled" else dict([min(picks.items())])
        want, host = _lm_run(unasked, slots, chunked, eos)
        assert any(len(want[i]) < REQUESTS[i][1] for i in eos)
    got, ran = _lm_run(asked, slots, chunked, eos)
    assert got == want
    assert host["decode_steps_ahead_total"] == 0
    assert ran["decode_steps_ahead_total"] > 0
    assert ran["decode_tokens"] == host["decode_tokens"] == sum(
        len(a) for a in want)


# -- (c) the same over a ring ------------------------------------------------

@pytest.fixture(scope="module")
def ring_programs():
    return _programs("ring"), _programs("ring")


def _ring_run(programs, wrap, eos=None, slots=2):
    predictors, specs, _weights = programs
    step = wrap(predictors["step"])
    bat = DecodeBatcher(
        step, specs["step"], ladder=(slots,), ctx_ladder=(64,), start=False,
        prefill={"predictor": predictors["chunk"], "spec": specs["chunk"],
                 "ladder": (4, 16)})
    prompts = [np.random.default_rng(10 + i).integers(0, VOCAB, size=n)
               for i, n in enumerate(PROMPTS + (9, 12))]
    futures = [bat.submit(p, max_new_tokens=NEW + 4,
                          eos_id=(eos or {}).get(i))
               for i, p in enumerate(prompts)]
    bat.drive()
    assert bat._flight is None
    return [_tokens(f) for f in futures], bat.metrics()


@pytest.mark.parametrize("ends", ["max_new", "eos_recycled"])
def test_a_ring_cache_gives_the_host_paths_answers(ring_programs, ends):
    """MiMo-V2's served program, a ring of 8 positions a window layer and
    prompts that wrap it: the row that ended on its eos id rode one more
    step, wrote its own row's ring, and the request that takes the slot
    next starts at position 0 and reads nothing of it."""
    ours, theirs = ring_programs
    want, host = _ring_run(theirs, Unasked)
    eos = None
    if ends == "eos_recycled":
        eos = {i: answer[next(k for k in range(2, len(answer))
                              if answer[k] not in answer[:k])]
               for i, answer in enumerate(want)}
        want, host = _ring_run(theirs, Unasked, eos)
        assert all(len(a) < NEW + 4 for a in want)
    got, ran = _ring_run(ours, lambda predictor: predictor, eos)
    assert got == want
    assert host["decode_steps_ahead_total"] == 0
    assert ran["decode_steps_ahead_total"] > 0


# -- (d) where the loop does not run ahead -----------------------------------

def _step_spans(bat, submit):
    """The ``ahead`` tag of each ``decode.step`` span of one drive, in the
    order the steps were read (a span ends with its step's read), and the
    names of each span's children."""
    trace.stop()
    tracer = trace.start()
    try:
        submit()
        bat.drive()
        spans = sorted(tracer.drain(), key=lambda s: s["t0"])
    finally:
        trace.stop()
    steps = sorted((s for s in spans if s["name"] == "decode.step"),
                   key=lambda s: s["t0"] + s["dur"])
    kids = [[k["name"] for k in spans if k["parent_id"] == s["span_id"]]
            for s in steps]
    return [s["tags"]["ahead"] for s in steps], kids


RUN = ["decode.feed", "executor.run"]
READ = ["decode.fetch", "decode.sample"]


def test_a_run_of_steps_is_dispatched_one_ahead_and_read_one_behind():
    model, bat = _asked()
    ahead, kids = _step_spans(
        bat, lambda: bat.submit([4], max_new_tokens=4))
    # four steps in three quanta: the first dispatched and followed at
    # once; the last, known to be the run's last when it is dispatched,
    # read in the quantum that dispatched it, under a span of its own that
    # holds that quantum's
    assert ahead == [0, 1, 1, 1]
    assert kids == [["decode.feed"] * 2 + READ, ["decode.feed"] + READ,
                    ["decode.feed"] + READ, ["decode.step"] + READ]
    assert model.log == [("run", 0), ("run", 1), ("read", 0), ("run", 2),
                         ("read", 1), ("run", 3), ("read", 2), ("read", 3)]
    # the device's ids are the next step's tokens; positions move by one
    assert [(int(c[0][0]), int(c[1][0])) for c in model.calls] == [
        (4, 0), (5, 1), (6, 2), (7, 3)]
    m = bat.metrics()
    assert (m["decode_steps"], m["decode_steps_ahead_total"]) == (4, 3)
    assert "decode_steps_ahead_total" in bat.metrics_report()
    assert "paddle_tpu_serving_decode_steps_ahead_total 3" in \
        bat.metrics_.prometheus_text()


def _forcing():
    # a prompt of three tokens and no chunk program: two steps are fed the
    # prompt's tokens, and the step after a forcing step is the host's
    model, bat = _asked()
    return bat, lambda: bat.submit([1, 2, 3], max_new_tokens=3), \
        [0, 0, 0, 1, 1]


def _unharvested():
    # under a prefix cache the step that ends a prompt's ingestion is
    # followed by a harvest of the rows it wrote: nothing rides ahead of it
    model, bat = _asked(prefix_cache=True)
    return bat, lambda: bat.submit([1, 2], max_new_tokens=4), \
        [0, 0, 0, 1, 1]


def _pending_and_a_free_slot():
    # a second request arrives while a step of the first one's is in
    # flight, and there is a slot for it: the quantum that finds it waiting
    # reads that step and dispatches nothing (the newcomer waits for the
    # step running and the one queued, no more), and after the admission
    # the two rows run ahead together
    model, bat = _asked(ladder=(2,))

    def submit():
        bat.submit([4], max_new_tokens=6)
        bat._admit()
        bat._tick()                     # step 0 read, step 1 in flight
        assert bat._flight is not None
        bat.submit([9], max_new_tokens=3)
        bat._admit()                    # not while a step is in flight
        assert len(bat._pending) == 1
        bat._tick()                     # step 1 read, none dispatched
        assert bat._flight is None and len(model.calls) == 2
        assert bat.metrics()["decode_steps"] == 2

    return bat, submit, [0, 1, 0, 1, 1, 1]


def _rebucket_due():
    # two rows in a bucket of two, one ends by max_new at the third step:
    # the table shrinks to one row after it, so that step is not followed
    model, bat = _asked(ladder=(1, 2))

    def submit():
        bat.submit([4], max_new_tokens=6)
        bat.submit([9], max_new_tokens=3)

    return bat, submit, [0, 1, 1, 0, 1, 1]


def _everything_ends():
    # every row ends by max_new at this step: there is no step after it
    model, bat = _asked()
    return bat, lambda: [bat.submit([s], max_new_tokens=1)
                         for s in (1, 2, 3)], [0]


HELD_BACK = {"forcing_row": _forcing, "unharvested_row": _unharvested,
             "pending_and_a_free_slot": _pending_and_a_free_slot,
             "rebucket_due": _rebucket_due, "everything_ends": _everything_ends}


@pytest.mark.parametrize("case", sorted(HELD_BACK))
def test_the_loop_holds_back_where_the_next_quantum_is_not_a_plain_step(case):
    bat, submit, want = HELD_BACK[case]()
    ahead, kids = _step_spans(bat, submit)
    assert ahead[-len(want):] == want
    # every span lies over a dispatch but the one that reads a step whose
    # follower an arrival called off
    assert [k for k in kids if "decode.feed" not in k
            and "decode.step" not in k] == (
        [READ] if case == "pending_and_a_free_slot" else [])
    m = bat.metrics()
    assert m["decode_steps_ahead_total"] == sum(ahead)
    assert m["requests_failed"] == 0 and bat._flight is None


def test_a_full_table_runs_ahead_past_a_queue():
    """A request waits and no slot comes free at this step: the loop runs
    ahead; the step at which a slot does come free by ``max_new`` is not
    followed, and the one who waits rides the very next step."""
    model, bat = _asked(ladder=(2,))
    asked = ((1, 2), (5, 4), (9, 3))
    futures = [bat.submit([s], max_new_tokens=n) for s, n in asked]
    ahead, _kids = _step_spans(bat, lambda: None)
    assert ahead == [0, 1, 0, 1, 1]
    assert [_tokens(f) for f in futures] == [
        tuple(_counting_seq(s, n)) for s, n in asked]
    # the third step was the host's: the newcomer's first token beside the
    # survivor's next one
    assert sorted(int(t) for t in model.calls[2][0]) == [7, 9]


@pytest.mark.parametrize("speculate", [False, True],
                         ids=["chunks", "speculation"])
def test_chunk_quanta_and_speculation_are_never_run_ahead_of(families,
                                                             speculate):
    pred, dspec, prefill, draft = families[0]
    bat = DecodeBatcher(
        pred, dspec, ladder=(4,), ctx_ladder=(32,), prefill=prefill,
        speculative={"draft": draft, "k": 4} if speculate else None,
        start=False)
    for prompt, n in REQUESTS[:3]:
        bat.submit(prompt, max_new_tokens=n)
    trace.stop()
    tracer = trace.start()
    try:
        bat.drive()
        spans = sorted(tracer.drain(), key=lambda s: s["t0"])
    finally:
        trace.stop()
    top = [s for s in spans if s["parent_id"] is None
           and s["name"] in ("decode.step", "prefill.chunk", "spec.verify")]
    m = bat.metrics()
    if speculate:
        assert m["decode_steps_ahead_total"] == 0
        assert all(s["name"] != "decode.step" for s in top)
        return
    assert m["decode_steps_ahead_total"] > 0
    for before, after in zip(top, top[1:]):
        if after["name"] == "prefill.chunk" and before["name"] == "decode.step":
            # whatever ran before a chunk was read before the chunk ran
            assert "decode.fetch" in [
                k["name"] for k in spans
                if k["parent_id"] == before["span_id"]]
        if after["name"] == "decode.step" and after["tags"]["ahead"]:
            assert before["name"] == "decode.step"


def _inside(spans, name, outer):
    lo, hi = outer["t0"], outer["t0"] + outer["dur"]
    return [s for s in spans if s["name"] == name
            and lo <= s["t0"] and s["t0"] + s["dur"] <= hi]


def _one_dispatch_one_read_a_span(spans, dispatch, cut_short=0):
    """What the benchmark's readers lean on, read as they read it (by
    time): every ``decode.step`` span holds a dispatch (but ``cut_short``
    of them at most: runs whose last step's follower an arrival called
    off), and the read that ends last inside it is a different step's for
    every span."""
    steps = [s for s in spans if s["name"] == "decode.step"]
    ends_with = []
    for step in steps:
        if not _inside(spans, dispatch, step):
            cut_short -= 1
            assert cut_short >= 0, step
        reads = _inside(spans, "decode.fetch", step)
        assert reads and _inside(spans, "decode.sample", step)
        ends_with.append(max(r["t0"] + r["dur"] for r in reads))
    assert len(set(ends_with)) == len(steps)
    return steps


@pytest.mark.parametrize("chunked", [False, True], ids=["steps", "chunks"])
@pytest.mark.parametrize("slots", [2, 4])
def test_every_step_span_holds_a_dispatch_and_ends_with_one_read(
        families, slots, chunked):
    pred, dspec, prefill, _draft = families[0]
    bat = DecodeBatcher(pred, dspec, ladder=(slots,), ctx_ladder=(32,),
                        prefill=prefill if chunked else None, start=False)
    for prompt, n in REQUESTS:
        bat.submit(prompt, max_new_tokens=n)
    trace.stop()
    tracer = trace.start()
    try:
        bat.drive()
        spans = tracer.drain()
    finally:
        trace.stop()
    steps = _one_dispatch_one_read_a_span(spans, "executor.run")
    m = bat.metrics()
    assert len(steps) == m["decode_steps"] - m["prefill_chunks"]
    assert sum(s["tags"]["ahead"] for s in steps) == \
        m["decode_steps_ahead_total"] > 0
    # a run's last step is read under a span of its own round the quantum
    # that dispatched it
    nested = [s for s in steps if any(
        k["parent_id"] == s["span_id"] and k["name"] == "decode.step"
        for k in spans)]
    assert nested and all(s["tags"]["ahead"] for s in nested)


# -- (e) nothing is left unread ----------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_drive_with_a_budget_reads_every_step_it_dispatched(n):
    model, bat = _asked()
    future = bat.submit([4], max_new_tokens=8)
    assert bat.drive(max_steps=n) == n
    assert bat._flight is None
    assert len(model.calls) == n == bat.metrics()["decode_steps"]
    assert [kind for kind, _ in model.log].count("read") == n
    slot, = [s for s in bat._slots if s is not None]
    assert (slot.pos, len(slot.out)) == (n, n)
    bat.drive()
    assert _tokens(future) == tuple(_counting_seq(4, 8))
    assert len(model.calls) == 8


@pytest.mark.parametrize("threaded", [False, True],
                         ids=["driven", "loop_thread"])
def test_a_draining_shutdown_leaves_no_step_unread(threaded):
    model, bat = _asked(start=threaded, ladder=(1, 2, 4), ctx_ladder=(32,))
    futures = [bat.submit([s], max_new_tokens=n, eos_id=eos)
               for s, n, eos in ((3, 9, None), (7, 12, 11), (1, 5, None),
                                 (9, 20, 14), (5, 7, None))]
    bat.shutdown(drain=True, timeout_s=60.0)
    assert bat._flight is None
    assert [_tokens(f) for f in futures] == [
        tuple(_counting_seq(3, 9)), (8, 9, 10, 11),
        tuple(_counting_seq(1, 5)), (10, 11, 12, 13, 14),
        tuple(_counting_seq(5, 7))]
    runs = [n for kind, n in model.log if kind == "run"]
    reads = [n for kind, n in model.log if kind == "read"]
    # a step nobody read is one whose every row had ended on its eos id
    assert set(reads) <= set(runs) and len(runs) - len(reads) <= 2
    m = bat.metrics()
    assert m["decode_steps"] == len(reads)
    assert m["decode_tokens"] == 9 + 4 + 5 + 5 + 7


def test_an_abort_settles_the_step_in_flight():
    model, bat = _asked()
    future = bat.submit([4], max_new_tokens=8)
    bat._admit()
    bat._tick()                         # step 0 read, step 1 in flight
    assert bat._flight is not None
    bat.shutdown(drain=False)
    assert bat._flight is None and model.log[-1] == ("read", 1)
    with pytest.raises(EngineShutdownError):
        future.result(0)


@pytest.mark.parametrize("fail_at", [1, 2, 4])
def test_a_step_that_raises_with_one_in_flight_poisons_the_loop_once(fail_at):
    """Run ``fail_at`` is a step dispatched ahead (or, at 1, the first
    one's follower): the step before it is in the air, unread. Every live
    request fails with the step's error, once; the table is dropped; the
    next request is served from fresh caches."""
    model, bat = _asked(fail_at=fail_at)
    futures = [bat.submit([s], max_new_tokens=8) for s in (4, 9)]
    with pytest.raises(RuntimeError, match="step %d failed" % fail_at):
        bat.drive()
    for f in futures:
        with pytest.raises(RuntimeError, match="step %d failed" % fail_at):
            f.result(0)
    assert bat._flight is None and bat._slots == [] and not bat._caches
    assert bat.metrics()["decode_steps"] == fail_at - 1
    assert bat._admission.in_flight == 0
    model.fail_at = None
    again = bat.submit([2], max_new_tokens=5)
    bat.drive()
    assert _tokens(again) == tuple(_counting_seq(2, 5))


# -- (f) one step executable a geometry --------------------------------------

@pytest.mark.parametrize("chunked", [False, True], ids=["steps", "chunks"])
def test_the_id_array_as_a_feed_finds_the_step_executable(chunked):
    pred, dspec, prefill, _draft = _build_lm_family(fluid.Scope())
    bat = DecodeBatcher(pred, dspec, ladder=(2, 4), ctx_ladder=(32,),
                        prefill=prefill if chunked else None, start=False)
    for prompt, n in REQUESTS:
        bat.submit(prompt, max_new_tokens=n)
    bat.drive()
    assert bat.metrics()["decode_steps_ahead_total"] > 0
    steps = {sig for sig in bat.seen_signatures if len(sig) == 2}
    assert steps <= {(2, 32), (4, 32)}
    assert bat.compiled_shape_counts() == [len(bat.seen_signatures)]
    assert bat.compiled_shape_counts()[0] <= bat.compile_cache_bound()
    # the executor made ONE variant a step geometry: the device's int32 ids
    # and the host's int64 tokens are one feed signature
    assert len(pred._exe._cache) == len(steps)
    assert len(pred._exe.compile_records) == len(steps)
    assert all(dspec["logits_fetch"] + "@argmax" in r["fetch_names"]
               for r in pred._exe.compile_records)


def test_the_loop_thread_serves_while_it_runs_ahead():
    """The loop's own thread, requests arriving while steps are in flight:
    every answer whole, the counter moved, nothing left in the air."""
    trace.stop()
    tracer = trace.start()
    model, bat = _asked(start=True, ladder=(1, 2, 4), ctx_ladder=(64,))
    futures = []
    gate = threading.Event()

    def client():
        for s in range(12):
            futures.append((s, bat.submit([s], max_new_tokens=5 + s % 4)))
        gate.set()

    thread = threading.Thread(target=client)
    thread.start()
    gate.wait(30.0)
    thread.join(30.0)
    for s, f in futures:
        assert tuple(int(t) for t in f.result(30.0)) == tuple(
            _counting_seq(s, 5 + s % 4))
    bat.shutdown(drain=True, timeout_s=30.0)
    spans = tracer.drain()
    trace.stop()
    assert bat._flight is None
    assert bat.metrics()["decode_steps_ahead_total"] > 0
    # whoever arrived while a step was in flight waited for that step to
    # be read, by a quantum that dispatched nothing: one a client at most
    steps = _one_dispatch_one_read_a_span(spans, "decode.feed",
                                          cut_short=len(futures))
    assert len(steps) == bat.metrics()["decode_steps"]
