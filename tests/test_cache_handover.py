"""The decode tier hands its caches over (ISSUE 36): ``Executor.run(
donate_feeds=...)`` gives a step the named feeds as a donated argument of
their own, ``ProgramPredictor.run`` passes the names on, and
``DecodeBatcher`` names its carried caches in every step and chunk run, so
that ``kv_cache_write`` updates in place and no whole cache is copied.

Everything here runs on the CPU at tiny size: what is deleted, what the
lowered text aliases, which tokens come out. That a copy really goes on the
chip is the benchmark's to show (``cache_write_ms``, ``decode_device_ms``)."""

import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu.obs import trace
from paddle_tpu.serving.decode_batcher import DecodeBatcher

from test_serving import _build_lm_family


@pytest.fixture(scope="module")
def family():
    """(step predictor, step spec, prefill dict, draft) on one scope."""
    return _build_lm_family(fluid.Scope())


class NoHandOver:
    """A predictor as the parent of ISSUE 36 had it: ``run`` takes no
    ``donate_feeds``, so the batcher feeds its caches as ever."""

    def __init__(self, predictor):
        self._predictor = predictor
        self.fetch_names = predictor.fetch_names

    def run(self, feed, return_numpy=True):
        return self._predictor.run(feed, return_numpy=return_numpy)


def _batcher(family, hand_over=True, **kw):
    pred, dspec, prefill, draft = family
    chunk = dict(prefill)
    if not hand_over:
        pred = NoHandOver(pred)
        chunk["predictor"] = NoHandOver(chunk["predictor"])
    kw.setdefault("ladder", (4,))
    kw.setdefault("ctx_ladder", (32,))
    if kw.pop("chunked", True):
        kw["prefill"] = chunk
    if kw.pop("speculate", False):
        kw["speculative"] = {"draft": draft, "k": 4}
    return DecodeBatcher(pred, dspec, start=False, **kw)


def _tokens(future):
    return tuple(int(t) for t in np.asarray(future.result(0)).ravel())


def _cache_bytes(bat):
    return sum(int(a.nbytes) for a in bat._caches.values())


# -- (i) what is handed over is gone, what comes back is kept ----------------

LONG = [3, 7, 11, 2, 5, 9, 4, 6, 1, 8, 2, 3]


@pytest.mark.parametrize("quantum", ["step", "chunk"])
def test_arrays_handed_over_are_deleted_and_the_table_keeps_live_ones(
        family, quantum):
    bat = _batcher(family)
    bat.submit(LONG if quantum == "chunk" else [5], max_new_tokens=4)
    bat._admit()
    bat._tick()                     # host zeros go in, device arrays back
    before = dict(bat._caches)
    steps = bat.metrics()["decode_steps"]
    chunks = bat.metrics()["prefill_chunks"]
    bat.submit(LONG, max_new_tokens=2)
    bat._admit()
    while True:                     # until a quantum of the asked kind ran
        held = dict(bat._caches)
        bat._admit()
        bat._tick()
        m = bat.metrics()
        ran_chunk = m["prefill_chunks"] > chunks
        chunks = m["prefill_chunks"]
        # (a step quantum that only reads the step dispatched ahead of it
        # hands nothing over: the table's arrays are the ones it held)
        dispatched = any(bat._caches[n] is not a for n, a in held.items())
        if ran_chunk == (quantum == "chunk") and dispatched:
            break
    assert m["decode_steps"] > steps
    assert set(bat._caches) == set(held) == set(before)
    for name, old in held.items():
        assert old.is_deleted(), name
        assert not bat._caches[name].is_deleted(), name
        assert bat._caches[name].shape == old.shape
    assert m["cache_donated_bytes"] == _cache_bytes(bat) > 0


def test_a_predictor_without_the_argument_is_fed_as_ever(family):
    bat = _batcher(family, hand_over=False)
    bat.submit(LONG, max_new_tokens=3)
    bat._admit()
    bat._tick()
    held = dict(bat._caches)
    bat._tick()
    assert not any(a.is_deleted() for a in held.values())
    assert bat.metrics()["cache_donated_bytes"] == 0
    bat.drive()


# -- (ii) the lowered text and the compile record ----------------------------

def _handed_arguments(text):
    """[(argument number, aliased output or None)] of the ``main``
    function's tensor arguments, in order."""
    head = next(line for line in text.splitlines()
                if "func.func public @main" in line)
    args = head[:head.index(") -> ")]
    out = []
    for number, attrs in re.findall(
            r"%arg(\d+): tensor<[^>]*>( \{[^%]*\})?", args):
        alias = re.search(r"tf\.aliasing_output = (\d+)", attrs or "")
        out.append((int(number), int(alias.group(1)) if alias else None))
    return out


def test_lowered_text_aliases_every_carried_cache_to_its_own_fetch():
    family = _build_lm_family(fluid.Scope())  # its executors' records alone
    pred, dspec, prefill, _ = family
    bat = _batcher(family)
    assert bat.warmup() == 1 + len(bat.prefill_ladder)
    cache_bytes = sum(
        4 * 32 * int(np.prod(cf["tail"])) * np.dtype(
            cf.get("dtype", "float32")).itemsize
        for cf in dspec["cache_feeds"])
    for predictor, spec in ((pred, dspec),
                            (prefill["predictor"], prefill["spec"])):
        exe = predictor._exe
        fetches = list(predictor.fetch_names)
        # the caches by the place of the fetch that carries each on
        want = sorted(fetches.index(cf["fetch"])
                      for cf in spec["cache_feeds"])
        records = [r for r in exe.compile_records
                   if r["donated_feed_bytes"]]
        assert records
        for entry in exe._cache.values():
            if not entry.handed:
                continue
            handed = _handed_arguments(entry.lowered.as_text())[
                -len(entry.handed):]
            # handed-over argument i aliases the fetch of ITS cache: the
            # i-th of them in fetch order, not just any output of its shape
            assert [alias for _, alias in handed] == want
            assert [fetches.index(cf["fetch"])
                    for name in entry.handed
                    for cf in spec["cache_feeds"]
                    if cf["feed"] == name] == want
        for record in records:
            assert record["donated_feed_bytes"] == cache_bytes
            assert record["memory"]["alias_bytes"] >= cache_bytes
    # the batcher's own accessor: the step's record, then the rungs'
    records = bat.compile_records()
    assert len(records) == 1 + len(bat.prefill_ladder)
    assert all(r["donated_feed_bytes"] == cache_bytes
               <= r["memory"]["alias_bytes"] for r in records)
    # the warm-up made the executables the schedule runs: no new variant
    before = [len(p._exe.compile_records)
              for p in (pred, prefill["predictor"])]
    futs = [bat.submit(LONG, max_new_tokens=3),
            bat.submit([1, 2], max_new_tokens=2)]
    bat.drive()
    assert all(f.done() for f in futs)
    assert before == [len(p._exe.compile_records)
                      for p in (pred, prefill["predictor"])]
    assert len(bat.seen_signatures) == 1 + len(bat.prefill_ladder)


def test_a_sub_batched_chunk_hands_over_its_rows_and_the_scatter_the_table(
        monkeypatch):
    """ISSUE 38: a chunk rung whose height is under the bucket is handed
    the SUB-BATCH's caches (its compile record says so, and that the
    hand-over engages at that size), and the scatter that writes its lanes
    back is handed the table and aliases every array of it: no whole cache
    is copied in a chunk quantum, as PR 36 left it."""
    from paddle_tpu.serving import decode_batcher

    monkeypatch.setattr(decode_batcher, "CHUNK_TOKEN_BUDGET", 16)
    family = _build_lm_family(fluid.Scope())  # its executors' records alone
    pred, dspec, prefill, _ = family
    bat = _batcher(family)
    heights = [decode_batcher.chunk_rows(k, 4) for k in bat.prefill_ladder]
    assert bat.prefill_ladder == (4, 8, 16) and heights == [4, 2, 1]
    assert bat.warmup() == 1 + len(bat.prefill_ladder)
    row_bytes = sum(
        32 * int(np.prod(cf["tail"])) * np.dtype(
            cf.get("dtype", "float32")).itemsize
        for cf in dspec["cache_feeds"])
    records = bat.compile_records()
    assert [r["donated_feed_bytes"] for r in records] == [
        rows * row_bytes for rows in [4] + heights]
    assert all(r["memory"]["alias_bytes"] >= r["donated_feed_bytes"] > 0
               for r in records)
    # the copies: one pair a sub-batched rung, the scatter's table donated
    assert sorted(bat._rows_staged) == [(4, 32, 8), (4, 32, 16)]
    n_caches = len(dspec["cache_feeds"])
    for (b, c, k), (gather, scatter) in bat._rows_staged.items():
        rows = decode_batcher.chunk_rows(k, b)
        assert scatter.memory_analysis().alias_size_in_bytes == 4 * row_bytes
        assert gather.memory_analysis().alias_size_in_bytes == 0
        assert gather.memory_analysis().output_size_in_bytes >= \
            rows * row_bytes
        # every table array is an argument the lowering aliases to its output
        table, sub = bat._cache_shapes(b, c), bat._cache_shapes(rows, c)
        import jax

        idx = jax.ShapeDtypeStruct((rows,), np.dtype("int32"))
        n = jax.ShapeDtypeStruct((), np.dtype("int32"))
        lowered = decode_batcher._rows_helpers(k)[1].lower(
            table, sub, idx, idx, n)
        aliased = [alias for _, alias in _handed_arguments(
            lowered.as_text()) if alias is not None]
        assert sorted(aliased) == list(range(n_caches))
        assert "jit_serve_rows_scatter" in scatter.as_text()
        assert "jit_serve_rows_gather" in gather.as_text()
    # a live sub-batched quantum: the table handed to the scatter is gone,
    # what the table keeps is live, and the gauge reads the sub-batch
    bat.submit([5, 9], max_new_tokens=8)
    bat.submit(list(range(1, 15)), max_new_tokens=2)
    bat._admit()
    bat._tick()             # rung 4 over the whole table: device arrays back
    bat._tick()             # a step
    assert bat.metrics()["cache_donated_bytes"] == 4 * row_bytes
    held = dict(bat._caches)
    bat._tick()             # 8 of the 9 left: rung 8, a sub-batch of two
    assert bat.metrics()["prefill_lanes"] == 4 * 4 + 2 * 8
    assert all(a.is_deleted() for a in held.values())
    assert not any(a.is_deleted() for a in bat._caches.values())
    assert bat.metrics()["cache_donated_bytes"] == 2 * row_bytes
    bat.drive()
    assert len(bat.compile_records()) == 1 + len(bat.prefill_ladder)


def test_a_geometrys_executables_are_staged_while_its_first_chunk_runs():
    """The first chunk of a geometry: helper threads make the executables
    of the step and of the other chunk rungs meanwhile (``Executor.stage``,
    from shapes), one each, and the quanta that follow find their variants
    staged and stage nothing again."""
    family = _build_lm_family(fluid.Scope())
    pred, chunk_pred = family[0], family[2]["predictor"]
    bat = _batcher(family)
    assert len(bat.prefill_ladder) > 1
    bat.submit(LONG, max_new_tokens=3)
    bat._admit()
    bat._tick()                                  # the first chunk
    assert bat.metrics()["prefill_chunks"] == 1
    (ran,) = bat.seen_signatures
    others = [(4, 32, k) for k in bat.prefill_ladder if (4, 32, k) != ran]
    assert sorted(bat._ahead) == sorted([(4, 32)] + others)
    for staging in bat._ahead.values():
        staging.join(120)
        assert not staging.is_alive()
    exe, chunk_exe = pred._exe, chunk_pred._exe
    assert exe.runs == 0 and len(exe.compile_records) == 1
    assert chunk_exe.runs == 1
    assert len(chunk_exe.compile_records) == len(bat.prefill_ladder)
    assert all(r["donated_feed_bytes"] == _cache_bytes(bat)
               for r in exe.compile_records + chunk_exe.compile_records)
    bat.drive()
    for k in bat.prefill_ladder:                 # every rung, then drained
        bat.submit(list(range(1, k + 2)), max_new_tokens=2)
        bat.drive()
    assert len(bat.seen_signatures) == 1 + len(bat.prefill_ladder)
    assert bat._ahead == {}
    # the runs found the staged variants: no record beyond the stagings',
    # and the step's executor missed once (the staging) and hit ever after
    assert exe.runs > 0 and len(exe.compile_records) == 1
    assert (exe.variant_misses, exe.variant_hits) == (1, exe.runs)
    assert len(chunk_exe.compile_records) == len(bat.prefill_ladder)
    # speculation samples from the chunk's own logits: no step is staged
    spec = _batcher(_build_lm_family(fluid.Scope()), speculate=True)
    spec.submit(LONG, max_new_tokens=3)
    spec._admit()
    spec._tick()
    assert spec._ahead and all(len(sig) == 3 for sig in spec._ahead)
    spec.drive()
    spec.shutdown()


def test_executor_stage_makes_the_variant_a_run_then_finds():
    import jax

    main, startup, out = _fc_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    shapes = {"x": jax.ShapeDtypeStruct((4, 8), np.dtype("float32")),
              "y": jax.ShapeDtypeStruct((4, 8), np.dtype("float32"))}
    runs = exe.runs
    exe.stage(main, feed=shapes, fetch_list=[out], scope=scope,
              donate_state=False, donate_feeds=("y",))
    assert exe.runs == runs                      # nothing ran
    assert exe.compile_records[-1]["donated_feed_bytes"] == 4 * 8 * 4
    records = len(exe.compile_records)
    import jax.numpy as jnp

    y = jnp.ones((4, 8), "float32")
    got, = exe.run(main, feed={"x": np.ones((4, 8), "float32"), "y": y},
                   fetch_list=[out], scope=scope, donate_state=False,
                   donate_feeds=("y",))
    assert len(exe.compile_records) == records and y.is_deleted()
    plain, = exe.run(main, feed={"x": np.ones((4, 8), "float32"),
                                 "y": np.ones((4, 8), "float32")},
                     fetch_list=[out], scope=scope, donate_state=False)
    np.testing.assert_array_equal(got, plain)
    meshed = fluid.CompiledProgram(main).with_data_parallel()
    with pytest.raises(NotImplementedError, match="mesh"):
        exe.stage(meshed, feed=shapes, fetch_list=[out], scope=scope)


def test_variants_staged_from_many_threads_are_each_made_once():
    """More staging threads than cores on ONE executor, beside runs of a
    variant already made: every variant is recorded once, no count is lost,
    and the run that follows each finds it staged."""
    import sys
    import threading

    import jax

    main, startup, out = _fc_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)

    def feed(rows, shapes=False):
        make = ((lambda: jax.ShapeDtypeStruct((rows, 8), np.dtype("float32")))
                if shapes else (lambda: np.ones((rows, 8), "float32")))
        return {"x": make(), "y": make()}

    exe.run(main, feed=feed(1), fetch_list=[out], scope=scope,
            donate_state=False)
    runs, misses, records = exe.runs, exe.variant_misses, len(
        exe.compile_records)
    rows = list(range(2, 18))                    # 16 threads, 8 cores
    threads = [threading.Thread(
        target=exe.stage, args=(main,), kwargs=dict(
            feed=feed(r, shapes=True), fetch_list=[out], scope=scope,
            donate_state=False, donate_feeds=("y",))) for r in rows]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for _ in range(20):                      # the running thread's part
            exe.run(main, feed=feed(1), fetch_list=[out], scope=scope,
                    donate_state=False)
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert exe.variant_misses == misses + len(rows)
    assert exe.variant_hits + exe.variant_misses == exe.runs + len(rows)
    assert len(exe.compile_records) == records + len(rows)
    for r in rows:
        got, = exe.run(main, feed=feed(r), fetch_list=[out], scope=scope,
                       donate_state=False, donate_feeds=("y",))
        assert got.shape == (r, 8)
    assert len(exe.compile_records) == records + len(rows)
    assert exe.runs == runs + 20 + len(rows)


def test_the_hand_over_is_told_by_the_gauge_and_the_records_not_a_tag(
        family):
    """The ``donated=`` tag PR 36 put on the quantum spans said what the
    gauge and the compile records say, and nothing read it (ISSUE 37): the
    spans carry what the quantum ran over, the gauge what was handed to
    it, and each executable's record whether that engaged."""
    bat = _batcher(family)
    tracer = trace.start()
    try:
        bat.submit(LONG, max_new_tokens=3)
        bat._admit()
        handed = []
        while any(s is not None for s in bat._slots):
            bat._tick()
            handed.append((bat.metrics()["cache_donated_bytes"],
                           _cache_bytes(bat)))
        spans = tracer.drain()
    finally:
        trace.stop()
    assert handed and all(gauge == held > 0 for gauge, held in handed)
    for name in ("decode.step", "prefill.chunk"):
        found = [s for s in spans if s["name"] == name]
        assert found, name
        assert all("donated" not in s["tags"] and s["tags"]["bucket"] == 4
                   for s in found), name
    ran = [r for r in bat.compile_records() if r["donated_feed_bytes"]]
    assert len(ran) >= 2            # the step's and a chunk rung's
    assert all(r["memory"]["alias_bytes"] >= r["donated_feed_bytes"]
               == handed[0][1] for r in ran)


# -- (iii) the same requests give the same tokens ----------------------------

def _solo_vs_batched(family, hand_over):
    prompt = [3, 7, 11]
    solo_b = _batcher(family, hand_over, chunked=False, ctx_ladder=(16,))
    solo = solo_b.submit(prompt, max_new_tokens=6)
    solo_b.drive()
    bat = _batcher(family, hand_over, chunked=False, ctx_ladder=(16,))
    futs = [bat.submit(prompt, max_new_tokens=6),
            bat.submit([1, 2], max_new_tokens=9),
            bat.submit([5], max_new_tokens=3),
            bat.submit([8, 9, 10, 11], max_new_tokens=4)]
    bat.drive()
    assert _tokens(futs[0]) == _tokens(solo)     # strangers change nothing
    return [_tokens(f) for f in futs]


def _prefix_hit(family, hand_over):
    shared = [3, 7, 11, 2, 5, 9, 4, 6]
    bat = _batcher(family, hand_over, ladder=(2,), prefix_cache=True)
    out = []
    for last in (1, 8, 1, 13):
        f = bat.submit(shared + [last], max_new_tokens=4)
        bat.drive()
        out.append(_tokens(f))
    assert bat.metrics()["prefix_hits"] > 0
    return out


def _speculative_rewind(family, hand_over):
    class GarbageDraft:
        def propose(self, histories, n):
            return [[1] * n for _ in histories]

    pred, dspec, prefill, _ = family
    out = []
    for draft in (None, GarbageDraft()):
        bat = _batcher((pred, dspec, prefill, draft or family[3]),
                       hand_over, speculate=True)
        futs = [bat.submit([3, 7, 11], max_new_tokens=8),
                bat.submit([1, 2], max_new_tokens=9),
                bat.submit([5], max_new_tokens=3)]
        bat.drive()
        out.append([_tokens(f) for f in futs])
        m = bat.metrics()
        assert m["spec_accepted"] + m["spec_rejected"] > 0
    assert m["spec_rejected"] > 0       # the garbage draft was rewound
    assert out[0] == out[1]
    return out[0]


def _rebucketing(family, hand_over):
    bat = _batcher(family, hand_over, ladder=(1, 2, 4), ctx_ladder=(16, 32))
    first = bat.submit([3, 7, 11], max_new_tokens=12)
    bat.drive(max_steps=3)                     # one slot, 16 positions
    assert bat._bucket == (1, 16)
    more = [bat.submit([1, 2], max_new_tokens=5),
            bat.submit(LONG, max_new_tokens=9)]  # 4 slots, 32 positions
    bat.drive(max_steps=2)
    assert bat._bucket == (4, 32)
    bat.drive()
    return [_tokens(f) for f in [first] + more]


@pytest.mark.parametrize("scenario", [_solo_vs_batched, _prefix_hit,
                                      _speculative_rewind, _rebucketing])
def test_tokens_are_those_of_a_batcher_that_hands_nothing_over(
        family, scenario):
    assert scenario(family, True) == scenario(family, False)


# -- (iv) a run that fails after the hand-over -------------------------------

class FailsOnce:
    """Hands the caches over to the real run, then raises: the state the
    batcher has to come back from."""

    def __init__(self, predictor):
        self._predictor = predictor
        self.fetch_names = predictor.fetch_names
        self.fail_next = False

    def run(self, feed, return_numpy=True, donate_feeds=()):
        outs = self._predictor.run(feed, return_numpy=return_numpy,
                                   donate_feeds=donate_feeds)
        if self.fail_next:
            self.fail_next = False
            raise RuntimeError("replica fault after the hand-over")
        return outs


@pytest.mark.parametrize("threaded", [False, True])
def test_a_failed_run_fails_the_live_requests_and_the_next_starts_afresh(
        family, threaded):
    pred, dspec, _prefill, _ = family
    prompt = [3, 7, 11]
    solo_b = _batcher(family, chunked=False, ctx_ladder=(16,))
    solo = solo_b.submit(prompt, max_new_tokens=6)
    solo_b.drive()

    faulty = FailsOnce(pred)
    bat = DecodeBatcher(faulty, dspec, ladder=(4,), ctx_ladder=(16,),
                        start=threaded)
    try:
        if threaded:
            warm = bat.submit([5], max_new_tokens=2)
            assert len(warm.result(60)) == 2
            faulty.fail_next = True
            doomed = bat.submit([1, 2, 4], max_new_tokens=5)
            with pytest.raises(RuntimeError, match="after the hand-over"):
                doomed.result(60)
            after = bat.submit(prompt, max_new_tokens=6)
            assert tuple(int(t) for t in after.result(60)) == _tokens(solo)
        else:
            doomed = bat.submit([1, 2, 4], max_new_tokens=5)
            bat.drive(max_steps=2)
            held = dict(bat._caches)
            faulty.fail_next = True
            with pytest.raises(RuntimeError, match="after the hand-over"):
                bat.drive()
            assert all(a.is_deleted() for a in held.values())
            with pytest.raises(RuntimeError, match="after the hand-over"):
                doomed.result(0)
            # nothing deleted is left to feed: the table is gone
            assert bat._caches == {} and bat._bucket == (0, 0)
            after = bat.submit(prompt, max_new_tokens=6)
            bat.drive()
            assert _tokens(after) == _tokens(solo)
    finally:
        bat.shutdown(drain=False)


# -- (v) an Executor.run that hands nothing over is the one it was -----------

def _fc_program():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=[8])
        y = fluid.layers.data("y", shape=[8])
        h = fluid.layers.fc(x, size=8)
        out = fluid.layers.elementwise_add(h, y)
    return main, startup, out


@pytest.mark.parametrize("donate_state", [True, False])
def test_without_donate_feeds_the_variant_is_built_as_before(donate_state):
    main, startup, out = _fc_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((4, 8), "float32"), "y": np.ones((4, 8), "float32")}
    exe.run(main, feed=feed, fetch_list=[out], scope=scope,
            donate_state=donate_state)
    entry = exe._last
    assert entry.handed == ()
    args, kwargs = entry.lowered.args_info
    assert len(args) == 3 and not kwargs          # (state, feed, rng)
    state, feeds, rng = args
    assert all(leaf.donated == donate_state
               for leaf in state.values()) and state
    assert not any(leaf.donated for leaf in feeds.values())
    assert not rng.donated
    text = entry.lowered.as_text()
    aliased = [n for n, alias in _handed_arguments(text) if alias is not None]
    # only state is ever aliased: the feeds are the last arguments but the
    # rng key, and none of them carries an aliasing attribute
    n_feeds = len(feed)
    assert all(n < len(_handed_arguments(text)) - 1 - n_feeds
               for n in aliased)
    if not donate_state:
        assert "tf.aliasing_output" not in text
        assert "jax.buffer_donor" not in text
    # the key is the fifteen-part one, and naming no feed finds it again
    (key,) = [k for k in exe._cache if k[0] == id(main)]
    assert len(key) == 15
    hits = exe.variant_hits
    exe.run(main, feed=feed, fetch_list=[out], scope=scope,
            donate_state=donate_state, donate_feeds=())
    assert exe.variant_hits == hits + 1 and exe._last is entry
    assert exe.compile_records[-1]["donated_feed_bytes"] == 0


def test_a_named_feed_is_a_fourth_donated_argument_and_a_variant_of_its_own():
    import jax.numpy as jnp

    main, startup, out = _fc_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    x = np.ones((4, 8), "float32")
    plain, = exe.run(main, feed={"x": x, "y": jnp.ones((4, 8), "float32")},
                     fetch_list=[out], scope=scope, donate_state=False)
    y = jnp.ones((4, 8), "float32")
    misses = exe.variant_misses
    got, = exe.run(main, feed={"x": x, "y": y}, fetch_list=[out],
                   scope=scope, donate_state=False, donate_feeds=("y",),
                   return_numpy=False)
    assert exe.variant_misses == misses + 1       # a variant of its own
    assert y.is_deleted()
    np.testing.assert_array_equal(np.asarray(got), plain)
    entry = exe._last
    assert entry.handed == ("y",)
    args, _ = entry.lowered.args_info
    assert len(args) == 4 and [leaf.donated for leaf in args[3]] == [True]
    assert "y" not in args[1] and "x" in args[1]
    handed = _handed_arguments(entry.lowered.as_text())[-1]
    assert handed[1] == 0                         # aliased to the one fetch
    record = exe.compile_records[-1]
    assert record["donated_feed_bytes"] == 4 * 8 * 4
    assert record["memory"]["alias_bytes"] >= record["donated_feed_bytes"]
    keys = [k for k in exe._cache if k[0] == id(main)]
    assert sorted(len(k) for k in keys) == [15, 16]


def test_a_name_that_is_not_fed_raises():
    main, startup, out = _fc_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((4, 8), "float32"), "y": np.ones((4, 8), "float32")}
    with pytest.raises(KeyError, match="cache_k_0"):
        exe.run(main, feed=feed, fetch_list=[out], scope=scope,
                donate_feeds=("cache_k_0",))


def test_a_meshed_step_refuses_a_hand_over():
    main, startup, out = _fc_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((8, 8), "float32"), "y": np.ones((8, 8), "float32")}
    meshed = fluid.CompiledProgram(main).with_data_parallel()
    with pytest.raises(NotImplementedError, match="mesh"):
        exe.run(meshed, feed=feed, fetch_list=[out], scope=scope,
                donate_feeds=("y",))
    got, = exe.run(meshed, feed=feed, fetch_list=[out], scope=scope)
    assert got.shape == (8, 8)                    # and runs without one


# -- the donation-alias check tells state from a handed-over feed ------------

def _write_program():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        cache = fluid.layers.data("cache", shape=[16, 8])
        x = fluid.layers.data("x", shape=[8])
        pos = fluid.layers.data("pos", shape=[], dtype="int32")
        new = fluid.layers.kv_cache_write(cache, x, pos)
        total = fluid.layers.reduce_sum(new)
    return main, new, total


def test_a_fetch_that_takes_a_handed_over_feeds_buffer_is_no_finding():
    main, new, _ = _write_program()
    result = analysis.analyze_program(
        main, fetch_names=[new.name], donate_feeds=("cache",))
    assert not [d for d in result.diagnostics
                if d.check == "donation-alias"], result.diagnostics
    # nor is the feed itself fetched back through a view
    with fluid.program_guard(main, fluid.Program()):
        flat = fluid.layers.tensor.reshape(
            main.global_block().var("cache"), shape=[-1, 128])
    result = analysis.analyze_program(
        main, fetch_names=[new.name, flat.name], donate_feeds=("cache",))
    assert not [d for d in result.errors if d.check == "donation-alias"]


def test_a_hand_over_that_nothing_can_take_is_reported():
    main, _, total = _write_program()
    result = analysis.analyze_program(
        main, fetch_names=[total.name], donate_feeds=("cache",))
    (found,) = [d for d in result.warnings if d.check == "donation-alias"]
    assert "cache" in found.message and "handed over" in found.message
    assert result.ok                              # a warning, not an error


def test_donated_state_is_still_an_error_beside_a_handed_over_feed():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=[4])
        h = fluid.layers.fc(x, size=4)
        w = main.all_parameters()[0]
    result = analysis.analyze_program(
        main, fetch_names=[h.name, w.name], donate_state=True,
        donate_feeds=("x",))
    (error,) = [d for d in result.errors if d.check == "donation-alias"]
    assert w.name in error.message
    # the same fetch with only the feed handed over: the state is not
    # donated, so nothing is invalidated
    result = analysis.analyze_program(
        main, fetch_names=[h.name, w.name], donate_state=False,
        donate_feeds=("x",))
    assert not result.errors
