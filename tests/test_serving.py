"""paddle_tpu.serving: bucket ladder, dynamic batcher (fake clock — no
sleeps), ServingEngine end-to-end (ISSUE acceptance: 100 mixed-size
requests, bounded compiles, metrics), overload fast-fail, worker-crash
containment, deadlines, and a slow-marked soak."""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.serving import (BucketError, DeadlineExceededError,
                                DynamicBatcher, Request, ServingEngine,
                                ServerOverloadedError, bucket_for,
                                pad_to_bucket, pow2_ladder, unpad_fetch)

from test_inference import _train_and_save


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------

def test_pow2_ladder():
    assert pow2_ladder(8) == (1, 2, 4, 8)
    assert pow2_ladder(6) == (1, 2, 4, 6)
    assert pow2_ladder(1) == (1,)
    with pytest.raises(ValueError):
        pow2_ladder(0)


def test_bucket_for():
    ladder = (1, 2, 4, 8)
    assert bucket_for(1, ladder) == 1
    assert bucket_for(3, ladder) == 4
    assert bucket_for(8, ladder) == 8
    with pytest.raises(BucketError):
        bucket_for(9, ladder)


def test_pad_to_bucket_edge_padding():
    feed = {"x": np.arange(6, dtype="f4").reshape(3, 2),
            "ids": np.array([[5], [6], [7]], dtype="i8")}
    padded, n = pad_to_bucket(feed, (1, 2, 4, 8))
    assert n == 3
    assert padded["x"].shape == (4, 2)
    # edge padding replicates the last real row — ids stay in-vocabulary
    np.testing.assert_array_equal(padded["x"][3], feed["x"][2])
    np.testing.assert_array_equal(padded["ids"][3], [7])
    outs = unpad_fetch([padded["x"] * 2], n)
    assert outs[0].shape == (3, 2)
    # padded_to pins slicing to the padded batch: a non-batch output that
    # is merely longer than n passes through untouched
    keep, = unpad_fetch([np.arange(16)], 3, padded_to=4)
    assert keep.shape == (16,)
    cut, = unpad_fetch([np.zeros((4, 2))], 3, padded_to=4)
    assert cut.shape == (3, 2)
    # scalar feeds carry no batch dim: excluded from consensus, unpadded
    padded, n = pad_to_bucket({"x": np.ones((3, 2), "f4"),
                               "temp": np.float32(2.0)}, (4,))
    assert padded["temp"].shape == () and padded["x"].shape == (4, 2)


def test_pad_to_bucket_seq_ladder():
    feed = {"tok": np.ones((3, 5), dtype="i8")}
    padded, n = pad_to_bucket(feed, (4,), seq_ladder=(8, 16))
    assert padded["tok"].shape == (4, 8) and n == 3


def test_pad_to_bucket_rejects_mismatch_and_empty():
    with pytest.raises(ValueError, match="disagree"):
        pad_to_bucket({"a": np.ones((2, 1)), "b": np.ones((3, 1))}, (4,))
    with pytest.raises(BucketError):
        pad_to_bucket({"a": np.ones((9, 1))}, (1, 2, 4, 8))


# ---------------------------------------------------------------------------
# batcher — fake clock, fully deterministic, zero sleeps (tier-1)
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _req(clock, n=1, deadline=None):
    from concurrent.futures import Future
    return Request({"x": np.zeros((n, 2), "f4")}, n, Future(), clock(),
                   deadline=deadline)


def test_batcher_full_cut_no_wait():
    clock = FakeClock()
    b = DynamicBatcher(max_batch_size=4, max_wait_ms=50, clock=clock)
    for _ in range(4):
        b.put(_req(clock))
    batch = b.get_batch()  # full: returns without consulting the deadline
    assert [r.n for r in batch] == [1, 1, 1, 1]
    assert b.depth() == 0


def test_batcher_deadline_cut_via_fake_clock():
    clock = FakeClock()
    b = DynamicBatcher(max_batch_size=8, max_wait_ms=5, clock=clock)
    b.put(_req(clock))
    b.put(_req(clock))
    clock.advance(0.006)  # oldest request is now past max_wait
    batch = b.get_batch()
    assert len(batch) == 2
    assert b.depth() == 0


def test_batcher_greedy_cut_respects_max_batch():
    clock = FakeClock()
    b = DynamicBatcher(max_batch_size=4, max_wait_ms=0, clock=clock)
    b.put(_req(clock, n=3))
    b.put(_req(clock, n=2))  # 3 + 2 > 4: stays queued for the next cut
    batch = b.get_batch()
    assert [r.n for r in batch] == [3]
    assert b.depth() == 2
    batch = b.get_batch()
    assert [r.n for r in batch] == [2]


def test_batcher_oversize_head_served_solo():
    clock = FakeClock()
    b = DynamicBatcher(max_batch_size=4, max_wait_ms=0, clock=clock)
    b.put(_req(clock, n=6))  # engine validates earlier; batcher must not hang
    assert [r.n for r in b.get_batch()] == [6]


def test_batcher_close_drains_then_none():
    clock = FakeClock()
    b = DynamicBatcher(max_batch_size=4, max_wait_ms=1000, clock=clock)
    b.put(_req(clock))
    b.close()
    assert len(b.get_batch()) == 1  # closed: cut immediately, no deadline
    assert b.get_batch() is None
    with pytest.raises(RuntimeError):
        b.put(_req(clock))


# ---------------------------------------------------------------------------
# engine — fake predictor (deterministic, no XLA in the control-flow tests)
# ---------------------------------------------------------------------------

class FakePredictor:
    """Doubles its input; optional gate to hold the worker mid-run and a
    poison value that raises (worker-crash path)."""
    feed_names = ["x"]
    fetch_names = ["y"]

    def __init__(self, gate=None):
        self.gate = gate

    def run(self, feed, return_numpy=True):
        if self.gate is not None:
            assert self.gate.wait(5.0), "test gate never opened"
        x = np.asarray(feed["x"])
        if np.any(x == -777):
            raise RuntimeError("poisoned batch")
        return [x * 2.0]

    def clone(self):
        return FakePredictor(self.gate)


def _drain_queue(eng, timeout=5.0):
    t0 = time.time()
    while eng._batcher.depth() > 0:
        assert time.time() - t0 < timeout, "queue never drained"
        time.sleep(0.001)


def test_engine_overload_fast_fails_while_in_flight_completes():
    gate = threading.Event()
    eng = ServingEngine(FakePredictor(gate), num_replicas=1,
                        ladder=(1, 2, 4), max_wait_ms=0, max_queue_depth=4)
    try:
        first = eng.submit({"x": np.full((1, 2), 3.0, "f4")})
        _drain_queue(eng)  # worker holds `first` at the gate
        backlog = [eng.submit({"x": np.full((1, 2), float(i), "f4")})
                   for i in range(3)]  # in_flight now at the depth limit
        with pytest.raises(ServerOverloadedError):
            eng.submit({"x": np.zeros((1, 2), "f4")})
        m = eng.metrics()
        assert m["requests_rejected"] == 1
        gate.set()  # overload must not have hurt admitted requests
        np.testing.assert_array_equal(first.result(5.0)[0],
                                      np.full((1, 2), 6.0))
        for i, f in enumerate(backlog):
            np.testing.assert_array_equal(f.result(5.0)[0],
                                          np.full((1, 2), 2.0 * i))
    finally:
        gate.set()
        eng.shutdown()
    m = eng.metrics()
    assert m["requests_completed"] == 4
    assert eng._admission.in_flight == 0


def test_engine_worker_crash_fails_batch_only():
    eng = ServingEngine(FakePredictor(), num_replicas=1,
                        ladder=(1, 2), max_wait_ms=0, max_queue_depth=16)
    try:
        bad = eng.submit({"x": np.full((1, 2), -777.0, "f4")})
        with pytest.raises(RuntimeError, match="poisoned"):
            bad.result(5.0)
        good = eng.submit({"x": np.ones((1, 2), "f4")})
        np.testing.assert_array_equal(good.result(5.0)[0],
                                      np.full((1, 2), 2.0))
        m = eng.metrics()
        assert m["requests_failed"] == 1 and m["requests_completed"] == 1
    finally:
        eng.shutdown()
    assert eng._admission.in_flight == 0


def test_engine_deadline_expires_queued_request():
    clock = FakeClock()
    gate = threading.Event()
    eng = ServingEngine(FakePredictor(gate), num_replicas=1,
                        ladder=(1, 2), max_wait_ms=0, max_queue_depth=8,
                        clock=clock)
    try:
        blocker = eng.submit({"x": np.ones((1, 2), "f4")})
        _drain_queue(eng)
        doomed = eng.submit({"x": np.ones((1, 2), "f4")}, timeout_s=5.0)
        clock.advance(10.0)  # past the deadline while still queued
        gate.set()
        with pytest.raises(DeadlineExceededError):
            doomed.result(5.0)
        assert blocker.result(5.0)
        assert eng.metrics()["requests_expired"] == 1
    finally:
        gate.set()
        eng.shutdown()


def test_engine_rejects_oversize_and_shutdown_submit():
    eng = ServingEngine(FakePredictor(), ladder=(1, 2, 4), max_wait_ms=0)
    with pytest.raises(BucketError):
        eng.submit({"x": np.ones((5, 2), "f4")})
    eng.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit({"x": np.ones((1, 2), "f4")})


def test_engine_shutdown_no_drain_cancels_queued():
    gate = threading.Event()
    eng = ServingEngine(FakePredictor(gate), num_replicas=1,
                        ladder=(1,), max_wait_ms=0, max_queue_depth=8)
    running = eng.submit({"x": np.ones((1, 2), "f4")})
    _drain_queue(eng)  # worker holds `running` at the gate
    queued = eng.submit({"x": np.ones((1, 2), "f4")})
    # drain=False while the worker is still gated: `queued` must be
    # cancelled, the in-flight request must still complete
    eng.shutdown(drain=False, timeout_s=0.2)
    assert queued.cancelled()
    gate.set()
    assert running.result(5.0)
    for w in eng._workers:
        w.thread.join(5.0)
    assert eng._admission.in_flight == 0


def test_engine_scalar_feed_coalescing():
    """0-d feeds can't concatenate: equal scalars share the batch, a
    disagreeing scalar fails only that batch (not the worker)."""
    gate = threading.Event()
    eng = ServingEngine(FakePredictor(gate), num_replicas=1,
                        ladder=(1, 2, 4), max_wait_ms=0, max_queue_depth=16)
    try:
        blocker = eng.submit({"x": np.ones((1, 2), "f4")})
        _drain_queue(eng)
        same = [eng.submit({"x": np.full((1, 2), float(i), "f4"),
                            "temp": np.float32(2.0)}) for i in range(2)]
        gate.set()
        assert blocker.result(5.0)
        for i, f in enumerate(same):
            np.testing.assert_array_equal(f.result(5.0)[0],
                                          np.full((1, 2), 2.0 * i))
        gate.clear()
        blocker2 = eng.submit({"x": np.ones((1, 2), "f4")})
        _drain_queue(eng)
        differ = [eng.submit({"x": np.ones((1, 2), "f4"),
                              "temp": np.float32(t)}) for t in (1.0, 3.0)]
        gate.set()
        assert blocker2.result(5.0)
        for f in differ:
            with pytest.raises(ValueError, match="scalar feed"):
                f.result(5.0)
        # the replica survives the failed batch
        after = eng.submit({"x": np.ones((1, 2), "f4")})
        assert after.result(5.0)
    finally:
        gate.set()
        eng.shutdown()
    assert eng._admission.in_flight == 0


def test_engine_coalesces_mixed_seq_lengths():
    """Two riders with different sequence lengths in ONE micro-batch:
    each is edge-padded to the rung covering the longest before the rows
    concatenate (the variable-length text case seq_ladder exists for)."""
    gate = threading.Event()
    eng = ServingEngine(FakePredictor(gate), num_replicas=1,
                        ladder=(1, 2, 4), seq_ladder=(8, 16),
                        max_wait_ms=0, max_queue_depth=16)
    try:
        blocker = eng.submit({"x": np.ones((1, 5), "f4")})
        _drain_queue(eng)
        a = eng.submit({"x": np.full((1, 5), 2.0, "f4")})
        b = eng.submit({"x": np.full((1, 7), 3.0, "f4")})
        gate.set()
        assert blocker.result(5.0)
        ra, = a.result(5.0)
        rb, = b.result(5.0)
        assert ra.shape == (1, 8) and rb.shape == (1, 8)
        np.testing.assert_array_equal(ra, np.full((1, 8), 4.0))
        np.testing.assert_array_equal(rb, np.full((1, 8), 6.0))
        # an over-long sequence is rejected at the door, not in-batch
        with pytest.raises(BucketError):
            eng.submit({"x": np.ones((1, 17), "f4")})
        assert eng.metrics()["requests_failed"] == 0
    finally:
        gate.set()
        eng.shutdown()


def test_engine_warmup_covers_seq_ladder():
    eng = ServingEngine(FakePredictor(), num_replicas=1, ladder=(1, 2),
                        seq_ladder=(4, 8), max_wait_ms=0)
    try:
        # example seq len 3 pads up to both rungs: 2 batch x 2 seq buckets
        assert eng.warmup({"x": np.ones((1, 3), "f4")}) == 4
        assert eng.compiled_shape_counts() == [4]
        got = eng.submit({"x": np.ones((1, 3), "f4")}).result(5.0)
        # batch dim is unpadded; the seq dim stays at its rung (which
        # outputs carry a seq dim is model-dependent — callers slice)
        assert got[0].shape == (1, 4)
        assert eng.metrics()["compile_cache_hit_rate"] == 1.0
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# engine — end-to-end over the real Predictor (ISSUE acceptance criteria)
# ---------------------------------------------------------------------------

def test_serving_engine_end_to_end(tmp_path):
    """Ladder {1,2,4,8}, 100 mixed-size requests: correct outputs, at most
    len(ladder) compiled shapes per replica, metrics report queue depth /
    batch occupancy / p50-p95-p99 latency."""
    xs, want = _train_and_save(tmp_path)
    from paddle_tpu.inference import Predictor

    oracle = Predictor(str(tmp_path / "model"))
    ladder = (1, 2, 4, 8)
    eng = ServingEngine(str(tmp_path / "model"), num_replicas=2,
                        ladder=ladder, max_wait_ms=2, max_queue_depth=1000)
    try:
        assert eng.warmup() == len(ladder) * 2

        rng = np.random.RandomState(7)
        sizes = [int(rng.choice([1, 2, 3, 5, 8])) for _ in range(100)]
        feeds = [rng.randn(n, 8).astype("f4") for n in sizes]
        futures = [eng.submit({"x": f}) for f in feeds]
        for f, x in zip(futures, feeds):
            got, = f.result(30.0)
            ref, = oracle.run({"x": x})
            assert got.shape == ref.shape
            np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)

        # bounded compiles: every replica dispatched at most len(ladder)
        # distinct padded shapes, and the program-path Executor cache agrees
        assert all(c <= len(ladder) for c in eng.compiled_shape_counts())
        for w in eng._workers:
            assert len(w.predictor._exe._cache) <= len(ladder)

        m = eng.metrics()
        assert m["requests_completed"] == 100
        assert m["requests_failed"] == 0
        assert m["queue_depth"] == 0
        assert 0 < m["batch_occupancy"] <= 1.0
        for p in ("p50", "p95", "p99"):
            assert m["latency_s"][p] is not None and m["latency_s"][p] > 0
        # warmed every rung up front: live traffic never compiled
        assert m["compile_cache_hit_rate"] == 1.0
        report = eng.metrics_report()
        for token in ("queue_depth", "batch_occupancy", "latency_p99_ms"):
            assert token in report
    finally:
        eng.shutdown(drain=True)


def test_serving_engine_stablehlo_predictor(tmp_path):
    """The engine accepts either predictor type (clone parity satellite)."""
    xs, want = _train_and_save(tmp_path)
    from paddle_tpu.inference import load_stablehlo_predictor

    base = load_stablehlo_predictor(str(tmp_path / "model"))
    twin = base.clone()
    a, = base.run({"x": xs})
    b, = twin.run({"x": xs})
    np.testing.assert_array_equal(a, b)
    if base.batch_mode != "symbolic":
        pytest.skip("pinned-batch export can't bucket")
    eng = ServingEngine(base, num_replicas=2, ladder=(1, 2, 4),
                        max_wait_ms=1, max_queue_depth=100)
    try:
        futs = [eng.submit({"x": xs[i % 2:i % 2 + 1]}) for i in range(10)]
        for i, f in enumerate(futs):
            got, = f.result(30.0)
            np.testing.assert_allclose(got, want[i % 2:i % 2 + 1],
                                       rtol=1e-4, atol=1e-5)
    finally:
        eng.shutdown()


@pytest.mark.slow
def test_serving_soak_sustained_load(tmp_path):
    """Soak: multi-threaded clients sustain load >= 2s; nothing fails,
    nothing leaks, the tail stays finite."""
    _train_and_save(tmp_path)
    eng = ServingEngine(str(tmp_path / "model"), num_replicas=2,
                        ladder=(1, 2, 4, 8), max_wait_ms=2,
                        max_queue_depth=64)
    stop = time.time() + 2.5
    errors = []
    rejected = [0]
    lock = threading.Lock()

    def client(seed):
        rng = np.random.RandomState(seed)
        while time.time() < stop:
            x = rng.randn(int(rng.randint(1, 4)), 8).astype("f4")
            try:
                out, = eng.submit({"x": x}).result(10.0)
                if out.shape[0] != x.shape[0]:
                    raise AssertionError("shape mismatch")
            except ServerOverloadedError:
                with lock:
                    rejected[0] += 1
                time.sleep(0.002)  # backoff, as the error contract asks
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    try:
        eng.warmup()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        m = eng.metrics()
        assert m["requests_completed"] > 50
        assert m["requests_failed"] == 0
        assert m["latency_s"]["p99"] is not None
        assert all(c <= 4 for c in eng.compiled_shape_counts())
    finally:
        eng.shutdown(drain=True)
    assert eng._admission.in_flight == 0


# ---------------------------------------------------------------------------
# profiler satellites
# ---------------------------------------------------------------------------

def test_stop_profiler_silent(capsys):
    from paddle_tpu import profiler

    profiler.reset_profiler()
    profiler.start_profiler()
    with profiler.record_event("serve"):
        pass
    report = profiler.stop_profiler(silent=True)
    assert "serve" in report
    assert capsys.readouterr().out == ""
    profiler.start_profiler()  # default path still prints
    profiler.stop_profiler()
    assert "Event" in capsys.readouterr().out


def test_profiler_histogram_percentiles():
    from paddle_tpu.profiler import Histogram

    h = Histogram(max_samples=100)
    assert h.percentile(99) is None
    for v in range(1, 101):
        h.add(v / 1000.0)
    ps = h.percentiles((50, 95, 99))
    assert ps["p50"] == pytest.approx(0.050, abs=0.002)
    assert ps["p99"] == pytest.approx(0.099, abs=0.002)
    assert h.count == 100
    assert h.cdf(0.050) == pytest.approx(0.5, abs=0.02)
    # sliding window: old samples age out
    for _ in range(100):
        h.add(1.0)
    assert h.percentile(50) == 1.0 and h.count == 200


# ---------------------------------------------------------------------------
# continuous-batching decode tier (ISSUE 14): slot recycling, re-bucketing,
# bitwise parity, skew, determinism — scheduler logic on a fake step model
# (zero XLA), end-to-end on the real KV-cached transformer step program
# ---------------------------------------------------------------------------

from paddle_tpu.serving import DecodeBatcher, EngineShutdownError


class FakeStepModel:
    """Deterministic step 'program': next token = (tok + 1) % vocab, via
    one-hot logits. One fake cache layer verifies the carried-state
    plumbing (the batcher must feed fetched caches back untouched)."""

    vocab = 16
    fetch_names = ["logits", "c0_out"]
    spec = {"token_feed": "tok", "pos_feed": "pos",
            "logits_fetch": "logits",
            "cache_feeds": [{"feed": "c0", "fetch": "c0_out",
                             "tail": [2], "dtype": "float32"}],
            "vocab": 16, "ctx_cap": 64}

    def __init__(self):
        self.calls = []

    def run(self, feed, return_numpy=True):
        tok = np.asarray(feed["tok"])
        pos = np.asarray(feed["pos"])
        cache = np.array(feed["c0"], dtype="f4")
        self.calls.append((tok.copy(), pos.copy(), cache.shape))
        b = tok.shape[0]
        logits = np.zeros((b, self.vocab), "f4")
        logits[np.arange(b), (tok + 1) % self.vocab] = 1.0
        cache[np.arange(b), np.minimum(pos, cache.shape[1] - 1), 0] = \
            tok.astype("f4")
        return [logits, cache]


def _fake_batcher(**kw):
    m = FakeStepModel()
    kw.setdefault("ladder", (1, 2, 4))
    kw.setdefault("ctx_ladder", (8, 16))
    kw.setdefault("start", False)
    return m, DecodeBatcher(m, FakeStepModel.spec, **kw)


def _counting_seq(start, n, vocab=16):
    return [(start + 1 + i) % vocab for i in range(n)]


def test_decode_batcher_generates_and_recycles():
    """Mixed lengths complete correctly; finished slots recycle so the
    compile-geometry set stays on the ladder product."""
    m, bat = _fake_batcher()
    futs = [bat.submit([s], max_new_tokens=n)
            for s, n in ((3, 4), (7, 2), (1, 6), (9, 3), (5, 5))]
    bat.drive()
    for f, (s, n) in zip(futs, ((3, 4), (7, 2), (1, 6), (9, 3), (5, 5))):
        np.testing.assert_array_equal(f.result(0), _counting_seq(s, n))
    assert len(bat.seen_signatures) <= 2 * 3
    meters = bat.metrics()
    assert meters["requests_completed"] == 5
    assert 0 < meters["slot_occupancy"] <= 1.0
    assert meters["decode_tokens"] == 4 + 2 + 6 + 3 + 5
    assert bat._admission.in_flight == 0


def test_decode_batcher_eos_stops_early():
    m, bat = _fake_batcher()
    # from token 4, generation counts 5,6,7,...; eos=7 stops after 3
    f = bat.submit([4], max_new_tokens=10, eos_id=7)
    bat.drive()
    np.testing.assert_array_equal(f.result(0), [5, 6, 7])


def test_decode_batcher_skew_no_starvation():
    """One long request admitted alongside a stream of shorts: the
    shorts flow through recycled slots while the long one keeps exactly
    one slot — nobody stalls, nobody starves."""
    m, bat = _fake_batcher(ladder=(1, 2, 4), ctx_ladder=(8, 64),
                           max_queue_depth=256)
    long_f = bat.submit([1], max_new_tokens=50)   # ctx rung 64
    shorts = [bat.submit([2], max_new_tokens=4) for _ in range(12)]
    steps = bat.drive()
    assert long_f.done() and all(s.done() for s in shorts)
    np.testing.assert_array_equal(long_f.result(0), _counting_seq(1, 50))
    # the long request is never preempted: total steps stay within a
    # couple of admission waves of its own length (51 ingests), instead
    # of shorts being serialized behind it (~13 * 5 extra steps)
    assert steps <= 51 + 16, steps
    # and the shorts were NOT starved behind the long one: all of them
    # finished strictly before the loop's final step
    m2 = bat.metrics()
    assert m2["requests_completed"] == 13
    assert m2["requests_failed"] == 0


def test_decode_batcher_rebucket_and_compile_bound():
    """Occupancy crossing ladder rungs re-buckets (grow AND shrink) and
    the distinct compiled geometries stay <= len(ladder)*len(ctx_ladder);
    generation survives the moves bit-exactly."""
    m, bat = _fake_batcher(ladder=(1, 2, 4), ctx_ladder=(8, 16))
    f1 = bat.submit([3], max_new_tokens=12)       # rung (1, 16)
    bat.drive(max_steps=3)
    assert bat._bucket == (1, 16)
    more = [bat.submit([5], max_new_tokens=3) for _ in range(3)]
    bat.drive(max_steps=2)
    assert bat._bucket == (4, 16)                 # grew mid-flight
    bat.drive()
    assert bat._bucket[0] <= 2                    # shrank after retires
    np.testing.assert_array_equal(f1.result(0), _counting_seq(3, 12))
    for f in more:
        np.testing.assert_array_equal(f.result(0), _counting_seq(5, 3))
    assert len(bat.seen_signatures) <= 3 * 2


def test_decode_batcher_deterministic_under_fake_clock():
    """Same submissions + injectable clock -> identical outputs, step
    count, and metric counters (the reliability-harness determinism
    contract)."""
    def run_once():
        clock = FakeClock()
        m, bat = _fake_batcher(clock=clock)
        futs = [bat.submit([s], max_new_tokens=3 + s % 3)
                for s in (2, 9, 4, 11, 6)]
        steps = bat.drive()
        out = [tuple(f.result(0)) for f in futs]
        met = bat.metrics()
        return out, steps, met["decode_steps"], met["decode_tokens"], \
            met["slot_occupancy"]

    assert run_once() == run_once()


def test_decode_batcher_overload_deadline_shutdown():
    m, bat = _fake_batcher(max_queue_depth=2)
    f1 = bat.submit([1], max_new_tokens=2)
    f2 = bat.submit([2], max_new_tokens=2)
    with pytest.raises(ServerOverloadedError):
        bat.submit([3], max_new_tokens=2)
    clock = FakeClock()
    m2, bat2 = _fake_batcher(clock=clock)
    doomed = bat2.submit([1], max_new_tokens=2, timeout_s=5.0)
    clock.advance(10.0)                            # expires while queued
    bat2.drive()
    with pytest.raises(DeadlineExceededError):
        doomed.result(0)
    assert bat2.metrics()["requests_expired"] == 1
    # drain shutdown serves what's pending; post-shutdown submit raises
    bat.shutdown(drain=True)
    assert f1.result(0) is not None and f2.result(0) is not None
    with pytest.raises(RuntimeError):
        bat.submit([1])
    # abort shutdown fails never-started work with the typed error
    m3, bat3 = _fake_batcher()
    f3 = bat3.submit([1], max_new_tokens=2)
    bat3.shutdown(drain=False)
    with pytest.raises(EngineShutdownError):
        f3.result(0)
    assert bat3._admission.in_flight == 0


def test_decode_batcher_rejects_over_capacity_prompt():
    m, bat = _fake_batcher(ctx_ladder=(8,))
    with pytest.raises(BucketError):
        bat.submit([1, 2, 3], max_new_tokens=32)   # needs ctx 34 > 8
    with pytest.raises(ValueError):
        bat.submit([], max_new_tokens=4)
    # exact-fit boundary: prompt+max_new-1 == rung is admissible (the
    # last sampled token never re-enters the cache), one more is not
    f = bat.submit([1, 2, 3, 4], max_new_tokens=5)  # writes 0..7
    bat.drive()
    np.testing.assert_array_equal(f.result(0), _counting_seq(4, 5))
    with pytest.raises(BucketError):
        bat.submit([1, 2, 3, 4], max_new_tokens=6)  # needs 9 > 8


# -- real step program ------------------------------------------------------

def _build_lm_pair(scope, ctx_cap=32, seed=3):
    import paddle_tpu as fluid
    from paddle_tpu import models

    cfg = models.transformer.lm_step_config(
        vocab=29, d_model=16, d_ff=32, n_head=2, n_layer=2,
        ctx_cap=ctx_cap, pos_cap=64)
    full_cfg = {k: v for k, v in cfg.items() if k != "ctx_cap"}
    full_main, full_start = fluid.Program(), fluid.Program()
    full_main.random_seed = full_start.random_seed = seed
    with fluid.program_guard(full_main, full_start), \
            fluid.scope_guard(scope):
        fluid.unique_name.switch()
        spec = models.transformer.transformer_lm(seq_len=8, **full_cfg)
    step_main, step_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(step_main, step_start), \
            fluid.scope_guard(scope):
        fluid.unique_name.switch()
        fetch_vars, dspec = models.transformer.transformer_lm_step(**cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(full_start)
    from paddle_tpu.inference import ProgramPredictor

    feeds = [dspec["token_feed"], dspec["pos_feed"]] \
        + [c["feed"] for c in dspec["cache_feeds"]]
    pred = ProgramPredictor(step_main, feeds, fetch_vars, scope=scope)
    return pred, dspec, spec, full_main


def test_decode_solo_vs_batched_bitwise_greedy():
    """THE continuous-batching correctness claim: a request decoded
    batched-with-strangers is BITWISE-identical to the same request
    decoded solo at the same bucket geometry (dead slots masked)."""
    import paddle_tpu as fluid

    scope = fluid.Scope()
    pred, dspec, _spec, _fm = _build_lm_pair(scope)
    prompt = [3, 7, 11]

    solo_b = DecodeBatcher(pred, dspec, ladder=(4,), ctx_ladder=(16,),
                           start=False)
    f = solo_b.submit(prompt, max_new_tokens=6)
    solo_b.drive()
    solo = f.result(0)

    bat = DecodeBatcher(pred, dspec, ladder=(4,), ctx_ladder=(16,),
                        start=False)
    futs = [bat.submit(prompt, max_new_tokens=6),
            bat.submit([1, 2], max_new_tokens=9),
            bat.submit([5], max_new_tokens=3),
            bat.submit([8, 9, 10, 11], max_new_tokens=4)]
    bat.drive()
    np.testing.assert_array_equal(solo, futs[0].result(0))
    # and slot RECYCLING preserves it too: a request admitted into a
    # just-vacated slot (dirty cache rows) must match its solo decode
    bat2 = DecodeBatcher(pred, dspec, ladder=(4,), ctx_ladder=(16,),
                        start=False)
    first = [bat2.submit([5], max_new_tokens=2) for _ in range(4)]
    bat2.drive(max_steps=3)            # retires the first wave
    recycled = bat2.submit(prompt, max_new_tokens=6)
    bat2.drive()
    np.testing.assert_array_equal(solo, recycled.result(0))


def test_lm_step_matches_full_program_logits():
    """KV-cached step decode reproduces the full causal program's logits
    (teacher-forced over the same tokens) — the cache math is exact."""
    import paddle_tpu as fluid

    scope = fluid.Scope()
    pred, dspec, spec, full_main = _build_lm_pair(scope, ctx_cap=16)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 29, (2, 8)).astype("int64")
    with fluid.scope_guard(scope):
        full_logits, = exe.run(full_main, feed={"ids": ids, "lbl": ids},
                               fetch_list=[spec.extras["logits"]])
    caches = {cf["feed"]: np.zeros((2, 16, 16), "f4")
              for cf in dspec["cache_feeds"]}
    outs_at = []
    for t in range(8):
        feed = dict(caches)
        feed["tok_ids"] = ids[:, t]
        feed["pos"] = np.full((2,), t, "int32")
        outs = pred.run(feed)
        outs_at.append(outs[0])
        for cf, arr in zip(dspec["cache_feeds"], outs[1:]):
            caches[cf["feed"]] = arr
    step_logits = np.stack(outs_at, axis=1)
    np.testing.assert_allclose(step_logits, full_logits, rtol=1e-5,
                               atol=1e-5)


def test_decode_engine_end_to_end():
    """ServingEngine decode mode: continuous batching behind the same
    submit()/predict() API, threaded; new gauges populated; compile
    cache bounded by the ladder product."""
    import paddle_tpu as fluid

    scope = fluid.Scope()
    pred, dspec, _spec, _fm = _build_lm_pair(scope)
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(pred, num_replicas=1, ladder=(1, 2, 4),
                        seq_ladder=(16, 32), decode=dspec)
    try:
        assert eng.warmup() == 3 * 2
        futs = [eng.submit([3, 7, 11], max_new_tokens=6)
                for _ in range(5)]
        futs += [eng.submit({"prompt_ids": [4, 4]}, max_new_tokens=3)]
        outs = [f.result(30.0) for f in futs]
        for o in outs[:5]:
            np.testing.assert_array_equal(o, outs[0])
        m = eng.metrics()
        assert m["requests_completed"] == 6
        assert m["decode_tokens"] >= 6 * 3
        assert m["slot_occupancy"] is not None
        for p in ("p50", "p99"):
            assert m["ttft_s"][p] is not None
        assert m["tpot_s"]["p50"] is not None
        report = eng.metrics_report()
        for token in ("slot_occupancy", "ttft_p99_ms", "tpot_p50_ms"):
            assert token in report
        assert all(c <= 3 * 2 for c in eng.compiled_shape_counts())
        # the engine-side bound mirrors the real XLA compile cache
        assert len(pred._exe._cache) <= 3 * 2
    finally:
        eng.shutdown(drain=True)
    with pytest.raises(RuntimeError):
        eng.submit([1])


def test_mt_beam_solo_vs_batched_bitwise():
    """One-shot beam serving parity: the While-loop beam decoder batched
    with strangers returns bitwise-identical (ids, scores) to solo at
    the same bucket rung — every per-step op is per-row."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.inference import ProgramPredictor
    from paddle_tpu.serving import ServingEngine

    scope = fluid.Scope()
    train_m, train_s = fluid.Program(), fluid.Program()
    train_m.random_seed = train_s.random_seed = 13
    kw = dict(src_vocab=23, trg_vocab=23, seq_len=6, emb_dim=8, hid_dim=8)
    with fluid.program_guard(train_m, train_s), fluid.scope_guard(scope):
        fluid.unique_name.switch()
        models.machine_translation.seq2seq_attention(**kw)
    infer_m, infer_s = fluid.Program(), fluid.Program()
    with fluid.program_guard(infer_m, infer_s), fluid.scope_guard(scope):
        fluid.unique_name.switch()
        ids, scores = models.machine_translation.seq2seq_attention_infer(
            beam_size=2, max_out_len=4, **kw)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(train_s)
    pred = ProgramPredictor(infer_m, ["src_ids", "src_len"],
                            [ids, scores], scope=scope)
    rng = np.random.RandomState(1)
    srcs = rng.randint(2, 23, (4, 6)).astype("int64")
    lens = np.array([6, 4, 5, 3], dtype="int64")

    eng = ServingEngine(pred, num_replicas=1, ladder=(4,), max_wait_ms=50,
                        max_queue_depth=64)
    try:
        solo = eng.submit({"src_ids": srcs[:1],
                           "src_len": lens[:1]}).result(60.0)
        futs = [eng.submit({"src_ids": srcs[i:i + 1],
                            "src_len": lens[i:i + 1]}) for i in range(4)]
        got = [f.result(60.0) for f in futs]
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(solo[0], got[0][0])  # sentence ids
    np.testing.assert_array_equal(solo[1], got[0][1])  # beam scores
    # greedy entry (K=1 squeeze) builds and shares the same weights
    g_m, g_s = fluid.Program(), fluid.Program()
    with fluid.program_guard(g_m, g_s), fluid.scope_guard(scope):
        fluid.unique_name.switch()
        gids, gsc = \
            models.machine_translation.seq2seq_attention_greedy_infer(
                max_out_len=4, **kw)
    with fluid.scope_guard(scope):
        out_ids, out_sc = exe.run(
            g_m, feed={"src_ids": srcs, "src_len": lens},
            fetch_list=[gids, gsc])
    assert out_ids.shape == (4, 4) and out_sc.shape == (4,)


# ---------------------------------------------------------------------------
# placement + mp-sharded serving (8-device virtual CPU mesh — conftest sets
# xla_force_host_platform_device_count; true-chip numbers are slow-marked)
# ---------------------------------------------------------------------------

def _save_mp_model(tmp_path, annotate=True):
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = fluid.Scope()
    shard1 = dict(sharding=(None, "mp")) if annotate else {}
    shard2 = dict(sharding=("mp", None)) if annotate else {}
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        fluid.unique_name.switch()
        x = fluid.layers.data("x", shape=[16])
        h = fluid.layers.fc(
            x, size=64, act="relu",
            param_attr=fluid.ParamAttr(name="mp_fc1.w", **shard1),
            bias_attr=fluid.ParamAttr(name="mp_fc1.b"))
        out = fluid.layers.fc(
            h, size=8,
            param_attr=fluid.ParamAttr(name="mp_fc2.w", **shard2),
            bias_attr=fluid.ParamAttr(name="mp_fc2.b"))
        prob = fluid.layers.softmax(out)
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        d = str(tmp_path / ("mp_model" if annotate else "plain_model"))
        fluid.io.save_inference_model(d, ["x"], [prob], exe,
                                      main_program=main)
    return d


def test_engine_per_device_placement(tmp_path):
    """placement='per_device': replica weights land round-robin on
    distinct devices (not all on device 0) and results still match."""
    import jax
    from paddle_tpu.inference import Predictor
    from paddle_tpu.serving import ServingEngine

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    d = _save_mp_model(tmp_path)
    xs = np.random.RandomState(0).randn(3, 16).astype("f4")
    want, = Predictor(d).run({"x": xs})
    n_dev = len(jax.devices())
    eng = ServingEngine(d, num_replicas=n_dev, ladder=(1, 2, 4),
                        placement="per_device")
    try:
        futs = [eng.submit({"x": xs}) for _ in range(2 * n_dev)]
        for f in futs:
            np.testing.assert_allclose(f.result(30.0)[0], want,
                                       rtol=1e-5, atol=1e-6)
        devs = {next(iter(
            w.predictor._scope.get("mp_fc1.w").devices()))
            for w in eng._workers}
        assert len(devs) == n_dev
    finally:
        eng.shutdown()


def test_engine_mp_sharded_serving(tmp_path):
    """mp=k: tensor-parallel replicas reuse the compiler mesh strategy,
    outputs match the unsharded predictor, and the build-time HLO
    assertion really checked the annotated params stayed sharded."""
    import jax
    from paddle_tpu.inference import Predictor
    from paddle_tpu.parallel import sharding_check
    from paddle_tpu.serving import ServingEngine

    if len(jax.devices()) < 4:
        pytest.skip("needs the multi-device CPU mesh")
    d = _save_mp_model(tmp_path)
    xs = np.random.RandomState(0).randn(3, 16).astype("f4")
    want, = Predictor(d).run({"x": xs})
    eng = ServingEngine(d, num_replicas=2, ladder=(1, 2, 4), mp=4)
    try:
        got, = eng.predict({"x": xs}, timeout_s=30.0)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        # the parent the engine asserted at build really is mp-sharded
        hlo = eng._parent._exe.lowered_hlo_text()
        sharding_check.assert_param_sharded(hlo, "mp_fc1.w", (16, 64))
        sharding_check.assert_param_sharded(hlo, "mp_fc2.w", (64, 8))
    finally:
        eng.shutdown()


def test_engine_mp_unannotated_program_warns(tmp_path):
    """mp=k on a program with NO sharding annotations is full
    replication — the engine must say so loudly at build."""
    import jax
    from paddle_tpu.serving import ServingEngine

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    d = _save_mp_model(tmp_path, annotate=False)
    with pytest.warns(RuntimeWarning, match="no mp-annotated"):
        eng = ServingEngine(d, num_replicas=1, ladder=(1, 2), mp=2)
    eng.shutdown()


def test_engine_mp_and_per_device_groups(tmp_path):
    """mp=2 x placement='per_device' on 8 devices: 4 sharded replica
    groups, every one answering correctly."""
    import jax
    from paddle_tpu.inference import Predictor
    from paddle_tpu.serving import ServingEngine

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    d = _save_mp_model(tmp_path)
    xs = np.random.RandomState(0).randn(2, 16).astype("f4")
    want, = Predictor(d).run({"x": xs})
    eng = ServingEngine(d, num_replicas=4, ladder=(1, 2), mp=2,
                        placement="per_device")
    try:
        futs = [eng.submit({"x": xs}) for _ in range(8)]
        for f in futs:
            np.testing.assert_allclose(f.result(30.0)[0], want,
                                       rtol=1e-5, atol=1e-6)
        assert len(eng._workers) == 4
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# int8 serving path (contrib.quantize export -> auto-detected by Predictor)
# ---------------------------------------------------------------------------

def _train_quantized_and_save(tmp_path):
    import paddle_tpu as fluid
    from paddle_tpu.contrib.quantize import QuantizeTranspiler

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 4
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        fluid.unique_name.switch()
        x = fluid.layers.data("x", shape=[8])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=16, act="relu")
        logits = fluid.layers.fc(h, size=3)
        prob = fluid.layers.softmax(logits)
        qt = QuantizeTranspiler()
        qt.training_transpile(main, startup)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(0.05).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        for _ in range(3):
            exe.run(main, feed={"x": rng.randn(8, 8).astype("f4"),
                                "y": rng.randint(0, 3, (8, 1))},
                    fetch_list=[loss])
        infer = main.clone(for_test=True)
        qt.freeze_program(infer, scope=scope)
        d = str(tmp_path / "int8_model")
        fluid.io.save_inference_model(
            d, ["x"], [infer.global_block().var(prob.name)], exe,
            main_program=infer)
        qt.export_int8(d, scope=scope)
    return d


def test_int8_serving_parity(tmp_path):
    """fp32-vs-int8 output parity: the int8 export dequantizes onto the
    exact grid the frozen program computed with, auto-detected by
    Predictor and therefore by ServingEngine."""
    from paddle_tpu.inference import AnalysisConfig, Predictor
    from paddle_tpu.serving import ServingEngine

    d = _train_quantized_and_save(tmp_path)
    xs = np.linspace(-1, 1, 16).reshape(2, 8).astype("f4")
    cfg32 = AnalysisConfig(model_dir=d)
    cfg32.enable_int8(False)
    p32 = Predictor(cfg32)
    p8 = Predictor(d)  # auto-detect
    assert p8.int8 and not p32.int8
    a, = p32.run({"x": xs})
    b, = p8.run({"x": xs})
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    eng = ServingEngine(d, ladder=(1, 2))
    try:
        got, = eng.predict({"x": xs[:1]}, timeout_s=30.0)
        np.testing.assert_allclose(got, a[:1], rtol=1e-5, atol=1e-6)
        assert eng._parent.int8
    finally:
        eng.shutdown()
    # the flag is strict: requiring int8 without an export is an error
    cfg_req = AnalysisConfig(model_dir=str(tmp_path / "int8_model"))
    cfg_req.enable_int8(True)
    Predictor(cfg_req)  # export exists: fine
    import shutil
    d2 = str(tmp_path / "no_export")
    shutil.copytree(d, d2)
    import os
    os.remove(os.path.join(d2, "params.int8.npz"))
    cfg_bad = AnalysisConfig(model_dir=d2)
    cfg_bad.enable_int8(True)
    with pytest.raises(ValueError, match="int8"):
        Predictor(cfg_bad)


def test_decode_step_program_verifies_clean():
    """ISSUE 14 acceptance: decode programs (KV-cache step fns) verify
    clean under paddle_tpu.analysis — via the same zoo path the CLI
    sweeps (transformer.lm_step)."""
    from paddle_tpu.analysis.cli import _zoo_builders, analyze_zoo_model

    builders = _zoo_builders()
    for name in ("transformer.lm", "transformer.lm_step",
                 "transformer.lm_chunk"):
        main_res, startup_res = analyze_zoo_model(builders[name])
        assert not main_res.diagnostics, (name, main_res.diagnostics)
        assert not startup_res.diagnostics, (name, startup_res.diagnostics)


def test_decode_engine_from_saved_dir(tmp_path):
    """The whole decode tier survives the save/load round trip: step
    program + decode_spec.json on disk, ServingEngine(dir, decode=True)
    serves it through a plain Predictor."""
    import paddle_tpu as fluid
    from paddle_tpu.serving import ServingEngine, save_decode_spec

    scope = fluid.Scope()
    pred, dspec, _spec, _fm = _build_lm_pair(scope)
    # reference output through the in-process path first
    ref_b = DecodeBatcher(pred, dspec, ladder=(2,), ctx_ladder=(16,),
                          start=False)
    rf = ref_b.submit([3, 7], max_new_tokens=5)
    ref_b.drive()
    want = rf.result(0)

    d = str(tmp_path / "lm_step_model")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(
            d, pred.feed_names, pred._fetch_vars, exe,
            main_program=pred._program)
    save_decode_spec(d, dspec)
    eng = ServingEngine(d, decode=True, ladder=(2,), seq_ladder=(16,))
    try:
        got = eng.predict([3, 7], timeout_s=30.0, max_new_tokens=5)
        np.testing.assert_array_equal(got, want)
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# ISSUE 20: prefix cache, chunked prefill, speculative decode
# ---------------------------------------------------------------------------

def _build_lm_family(scope, ctx_cap=32, seed=3):
    """:func:`_build_lm_pair` plus the chunk sibling and a ``DraftLM``
    over the full program — the whole weight-sharing family on ONE
    scope (only the full startup ever runs)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.inference import ProgramPredictor
    from paddle_tpu.serving import DraftLM

    pred, dspec, spec, full_main = _build_lm_pair(scope, ctx_cap=ctx_cap,
                                                  seed=seed)
    cfg = models.transformer.lm_step_config(
        vocab=29, d_model=16, d_ff=32, n_head=2, n_layer=2,
        ctx_cap=ctx_cap, pos_cap=64)
    chunk_main, chunk_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(chunk_main, chunk_start), \
            fluid.scope_guard(scope):
        fluid.unique_name.switch()
        cfetch, cspec = models.transformer.transformer_lm_chunk(**cfg)
    cfeeds = [cspec["token_feed"], cspec["pos_feed"]] \
        + [c["feed"] for c in cspec["cache_feeds"]]
    cpred = ProgramPredictor(chunk_main, cfeeds, cfetch, scope=scope)
    fpred = ProgramPredictor(full_main, ["ids", "lbl"],
                             [spec.extras["logits"]], scope=scope)
    draft = DraftLM(fpred, fpred.fetch_names[0], seq_len=8)
    return pred, dspec, {"predictor": cpred, "spec": cspec}, draft


def _drive_all(bat, reqs):
    futs = [bat.submit(p, max_new_tokens=mn) for p, mn in reqs]
    bat.drive()
    return [tuple(int(t) for t in np.asarray(f.result(0)).ravel())
            for f in futs]


def test_chunked_prefill_bitwise_vs_step_only():
    """Chunked prefill is a latency optimization, not a math change: the
    same mixed workload through a chunk-equipped batcher returns
    bitwise-identical tokens, in fewer decode steps, with chunk
    dispatches actually recorded."""
    import paddle_tpu as fluid

    scope = fluid.Scope()
    pred, dspec, prefill, _draft = _build_lm_family(scope)
    reqs = [([3, 7, 11, 2, 5, 9, 4, 6, 1, 8, 2, 3], 6),
            ([1, 2], 4), ([5], 3), ([8, 9, 10, 11, 12, 13], 5)]

    plain = DecodeBatcher(pred, dspec, ladder=(4,), ctx_ladder=(32,),
                          start=False)
    want = _drive_all(plain, reqs)
    chunked = DecodeBatcher(pred, dspec, ladder=(4,), ctx_ladder=(32,),
                            prefill=prefill, start=False)
    got = _drive_all(chunked, reqs)
    assert got == want
    mp, mc = plain.metrics(), chunked.metrics()
    assert mc["prefill_chunks"] > 0 and mc["prefill_tokens"] > 0
    assert mc["decode_steps"] < mp["decode_steps"]


def test_speculative_bitwise_parity_greedy():
    """THE speculative guarantee: greedy accept makes the output
    bitwise-identical to plain decode for ANY draft quality — the good
    draft (the weight-sharing full program) and an adversarial garbage
    draft, including a request admitted into a recycled dirty slot."""
    import paddle_tpu as fluid

    scope = fluid.Scope()
    pred, dspec, prefill, draft = _build_lm_family(scope)
    prompt = [3, 7, 11]

    solo_b = DecodeBatcher(pred, dspec, ladder=(4,), ctx_ladder=(32,),
                           start=False)
    f = solo_b.submit(prompt, max_new_tokens=8)
    solo_b.drive()
    solo = tuple(int(t) for t in np.asarray(f.result(0)).ravel())

    class GarbageDraft:
        def propose(self, histories, n):
            return [[1] * n for _ in histories]

    for d in (draft, GarbageDraft()):
        bat = DecodeBatcher(pred, dspec, ladder=(4,), ctx_ladder=(32,),
                            prefill=prefill,
                            speculative={"draft": d, "k": 4}, start=False)
        futs = [bat.submit(prompt, max_new_tokens=8),
                bat.submit([1, 2], max_new_tokens=9),
                bat.submit([5], max_new_tokens=3)]
        bat.drive()
        got = tuple(int(t)
                    for t in np.asarray(futs[0].result(0)).ravel())
        assert got == solo, type(d).__name__
        # recycled dirty slot: after the first wave retires, the same
        # prompt admitted into a reused slot must still match solo
        rec = bat.submit(prompt, max_new_tokens=8)
        bat.drive()
        rec_got = tuple(int(t)
                        for t in np.asarray(rec.result(0)).ravel())
        assert rec_got == solo, type(d).__name__
    m = bat.metrics()
    assert m["spec_accepted"] + m["spec_rejected"] > 0


def test_prefix_cache_eviction_refcount_no_corruption():
    """Prefix-cache hits, LRU eviction under a starvation-level byte
    budget, and refcount pinning never change decoded tokens: every
    request through a churning cache matches the cache-less reference
    bitwise (clone-never-alias means an evicted donor cannot reach into
    a live slot's rows)."""
    import paddle_tpu as fluid

    scope = fluid.Scope()
    pred, dspec, _prefill, _draft = _build_lm_family(scope)
    shared = [3, 7, 11, 2, 5, 9, 4, 6]
    prompts = [shared + [t] for t in (1, 8, 13, 17, 20, 22)]
    reqs = [(p, 4) for p in prompts for _ in (0, 1)]

    def run_sequential(bat):
        # drive each request to completion before the next submit, so
        # every lookup sees the previous harvests (and the churn is
        # insert -> evict -> insert, not one cold batch)
        out = []
        for p, mn in reqs:
            f = bat.submit(p, max_new_tokens=mn)
            bat.drive()
            out.append(tuple(int(t)
                             for t in np.asarray(f.result(0)).ravel()))
        return out

    plain = DecodeBatcher(pred, dspec, ladder=(2,), ctx_ladder=(16,),
                          start=False)
    want = run_sequential(plain)

    # budget sized to hold ~2 harvested prompts: constant churn
    one_entry = 4 * (len(shared) + 1) * 16 * 4  # feeds*rows*d_model*f32
    cached = DecodeBatcher(pred, dspec, ladder=(2,), ctx_ladder=(16,),
                           prefix_cache={"max_bytes": 2 * one_entry},
                           start=False)
    got = run_sequential(cached)
    assert got == want
    m = cached.metrics()
    assert m["prefix_hits"] > 0 and m["prefix_evictions"] > 0
    assert cached.prefix_cache.nbytes <= 2 * one_entry


def test_decode_compile_cache_soak_within_bound():
    """ISSUE 20 acceptance: with prefill + speculative live, a mixed
    soak never compiles past the verdict's (batch x ctx x prefill-rung)
    bound, and the batcher's own bound agrees with the verdict."""
    import paddle_tpu as fluid
    from paddle_tpu.analysis import resources

    scope = fluid.Scope()
    pred, dspec, prefill, draft = _build_lm_family(scope)
    bat = DecodeBatcher(pred, dspec, ladder=(1, 2, 4), ctx_ladder=(16, 32),
                        prefill=prefill, prefix_cache=True,
                        speculative={"draft": draft, "k": 3}, start=False)
    vbound, res = resources.decode_cache_verdict(
        dspec, ladder=(1, 2, 4), ctx_ladder=(16, 32), budget=64,
        prefill_ladder=bat.prefill_ladder)
    assert res.ok and bat.compile_cache_bound() == vbound
    rng = np.random.RandomState(7)
    for wave in range(4):
        n = int(rng.randint(1, 5))
        futs = [bat.submit(list(rng.randint(1, 29,
                                            size=rng.randint(1, 12))),
                           max_new_tokens=int(rng.randint(1, 8)))
                for _ in range(n)]
        bat.drive()
        assert all(f.done() for f in futs)
    assert len(bat.seen_signatures) <= vbound
    assert all(c <= vbound for c in bat.compiled_shape_counts())


# ---------------------------------------------------------------------------
# the decode loop accounts for its own quantum (ISSUE 37): spans round
# admission, planning, the feed, the wait for the logits and sampling, an
# idle span where no request is live, counters for queue wait and chunk lanes
# ---------------------------------------------------------------------------

LONG_PROMPT = [3, 7, 11, 2, 5, 9, 4, 6, 1, 8, 2, 3]
STEP_PARTS = ["decode.feed", "executor.run", "decode.fetch", "decode.sample"]
LOOP_SITES = {"decode.admit", "decode.plan", "decode.feed", "decode.fetch",
              "decode.sample", "decode.step", "prefill.chunk", "spec.verify",
              "decode.idle"}


@pytest.fixture(scope="module")
def lm_family():
    import paddle_tpu as fluid

    return _build_lm_family(fluid.Scope())


@pytest.fixture
def no_tracer():
    from paddle_tpu.obs import trace

    trace.stop()
    yield trace
    trace.stop()


def _ticking_clock():
    import itertools

    ticks = itertools.count()
    return lambda: float(next(ticks))


def _family_batcher(lm_family, speculate=False, **kw):
    pred, dspec, prefill, draft = lm_family
    kw.setdefault("ladder", (4,))
    kw.setdefault("ctx_ladder", (32,))
    if speculate:
        kw["speculative"] = {"draft": draft, "k": 4}
    return DecodeBatcher(pred, dspec, prefill=prefill, start=False, **kw)


def _traced_drive(trace, bat, submits):
    """Spans of one ``drive()`` under the tracer's ticking clock, in the
    order they were opened, with the names of their children."""
    for prompt, max_new in submits:     # every executable made or staged
        bat.submit(prompt, max_new_tokens=max_new)
    bat.drive()
    tracer = trace.start(clock=_ticking_clock())
    try:
        for prompt, max_new in submits:
            bat.submit(prompt, max_new_tokens=max_new)
        bat.drive()
        spans = sorted(tracer.drain(), key=lambda s: s["t0"])
    finally:
        trace.stop()
    for s in spans:
        s["kids"] = [k["name"] for k in spans
                     if k["parent_id"] == s["span_id"]]
    return spans


@pytest.mark.parametrize("kind,speculate,submits,quantum,parts", [
    # a request's first step is followed at once by its second, dispatched
    # before the first is read; known to be the run's last, the second is
    # read in the same quantum, under a span of its own round the first's
    # (ISSUE 41)
    ("step", False, [([5], 2)], "decode.step",
     ["decode.step"] + STEP_PARTS[2:]),
    ("chunk", False, [(LONG_PROMPT, 2)], "prefill.chunk",
     ["decode.feed", "executor.run"]),
    ("verify", True, [([5], 6)], "spec.verify",
     ["decode.feed", "executor.run", "decode.fetch"]),
])
def test_a_quantum_of_each_kind_is_told_by_its_spans(
        lm_family, no_tracer, kind, speculate, submits, quantum, parts):
    bat = _family_batcher(lm_family, speculate)
    spans = _traced_drive(no_tracer, bat, submits)
    top = [s for s in spans if s["parent_id"] is None]
    # admission, the plan, then the quantum: in that order, each time
    at = next(i for i, s in enumerate(top) if s["name"] == quantum)
    assert [s["name"] for s in top[at - 2:at + 1]] == [
        "decode.admit", "decode.plan", quantum]
    admit, plan, q = top[at - 2:at + 1]
    # planning a verify chunk asks the draft model: its runs are the plan's
    assert not admit["kids"] and set(plan["kids"]) == (
        {"executor.run"} if kind == "verify" else set())
    assert q["kids"] == parts
    kids = [s for s in spans if s["parent_id"] == q["span_id"]]
    # the ticking clock is read by the spans (and once by the loop's own
    # ``now``): children follow each other inside their quantum
    assert all(q["t0"] < k["t0"] and k["t0"] + k["dur"] < q["t0"] + q["dur"]
               for k in kids)
    assert all(a["t0"] + a["dur"] < b["t0"] for a, b in zip(kids, kids[1:]))
    # a drive that ends drops the emptied table, so this one's first
    # admission sets it up again (new zero arrays: nothing live to copy)
    first = top[0]
    assert first["name"] == "decode.admit" and first["tags"] == {
        "admitted": 1, "pending": 0, "rebucketed": 1, "copied_bytes": 0}
    # (after the step quantum, which read the request's both steps, the
    # table is empty and dropped: a geometry moved to nothing)
    assert top[3]["name"] == "decode.admit" and top[3]["tags"] == (
        {"admitted": 0, "pending": 0, "rebucketed": 1, "copied_bytes": 0}
        if kind == "step" else
        {"admitted": 0, "pending": 0, "rebucketed": 0})
    assert set(plan["tags"]) == {"rows", "verifying"}
    assert "donated" not in q["tags"]
    by_name = {k["name"]: k for k in kids}
    if kind == "step":
        assert plan["tags"] == {"rows": 0, "verifying": False}
        # the span on top is the second step's: it was dispatched ahead
        assert q["tags"] == {"live": 1, "bucket": 4, "ctx": 32,
                             "generated": 1, "ahead": 1}
        # [bucket] int32 ids, not [bucket, vocabulary] float32 logits
        assert by_name["decode.fetch"]["tags"] == {"bytes": 4 * 4}
        assert by_name["decode.sample"]["tags"] == {
            "generated": 1, "retired": 1}
        # the first step's span inside it: both dispatches, then its read
        first_step = by_name["decode.step"]
        assert first_step["kids"] == STEP_PARTS[:2] * 2 + STEP_PARTS[2:]
        assert first_step["tags"] == {"live": 1, "bucket": 4, "ctx": 32,
                                      "generated": 1, "ahead": 0}
        inner = {k["name"]: k for k in spans
                 if k["parent_id"] == first_step["span_id"]}
        assert inner["decode.sample"]["tags"] == {
            "generated": 1, "retired": 0}
        assert [s["name"] for s in top[at + 1:]] == ["decode.admit"]
    elif kind == "chunk":
        assert plan["tags"] == {"rows": 1, "verifying": False}
        assert {k: q["tags"][k] for k in (
            "rows", "tokens", "lanes", "chunk", "bucket", "live")} == {
            "rows": 1, "tokens": 11, "lanes": 64, "chunk": 16, "bucket": 4,
            "live": 1}
    else:
        assert plan["tags"]["verifying"] is True
        assert q["tags"]["lanes"] == 4 * q["tags"]["chunk"]
        assert q["tags"]["generated"] >= 1
        assert by_name["decode.fetch"]["tags"]["bytes"] == \
            4 * q["tags"]["chunk"] * 29 * 4


def test_admission_says_what_a_moved_geometry_copied(no_tracer):
    model, bat = _fake_batcher(ladder=(1, 2), ctx_ladder=(8,))
    tracer = no_tracer.start(clock=_ticking_clock())
    bat.submit([1, 2, 3], max_new_tokens=4)
    bat._admit()
    bat._tick()
    bat.submit([4], max_new_tokens=2)
    bat.submit([5], max_new_tokens=2)           # no room: stays queued
    bat._admit()
    admits = [s["tags"] for s in tracer.drain()
              if s["name"] == "decode.admit"]
    bat.shutdown(drain=False)
    assert admits[0] == {"admitted": 1, "pending": 0, "rebucketed": 1,
                         "copied_bytes": 0}
    # one live row of 8 positions x [2] float32 moves into the new arrays
    assert admits[1] == {"admitted": 1, "pending": 1, "rebucketed": 1,
                         "copied_bytes": 8 * 2 * 4}


@pytest.mark.parametrize("case", ["queue_wait", "chunk_lanes"])
def test_counters_move_by_what_the_clock_and_the_geometry_give(
        lm_family, case):
    now = [10.0]
    bat = _family_batcher(lm_family, clock=lambda: now[0])
    if case == "queue_wait":
        bat.submit([5], max_new_tokens=1)
        now[0] = 12.0
        bat.submit([7], max_new_tokens=1)
        now[0] = 15.0
        bat._admit()
        m = bat.metrics()
        assert (m["admitted"], m["queue_wait_seconds"]) == (2, 5.0 + 3.0)
        bat._admit()                # nobody new: nothing counted twice
        assert bat.metrics()["admitted"] == 2
        bat.drive()
        m = bat.metrics()
        assert (m["admitted"], m["queue_wait_seconds"]) == (2, 8.0)
        # TTFT starts at the same instant: with the clock standing still
        # after admission, every first token waited what its queue did
        assert bat.metrics_.ttft.total == 8.0
    else:
        bat.submit(LONG_PROMPT, max_new_tokens=1)    # 11 of 12 by chunk
        bat.submit(LONG_PROMPT[:6], max_new_tokens=1)  # 5 of 6
        bat.drive()
        m = bat.metrics()
        # one dispatch: 4 slot rows padded to the 16-token rung
        assert (m["prefill_chunks"], m["prefill_tokens"],
                m["prefill_lanes"]) == (1, 16, 4 * 16)
        bat.submit(LONG_PROMPT[:4], max_new_tokens=1)  # 3: the rung of 4
        bat.drive()
        m = bat.metrics()
        assert (m["prefill_chunks"], m["prefill_tokens"],
                m["prefill_lanes"]) == (2, 19, 4 * 16 + 4 * 4)
    text = bat.metrics_.prometheus_text()
    for name in ("admitted", "queue_wait_seconds", "prefill_lanes",
                 "idle_seconds"):
        assert "\npaddle_tpu_serving_%s " % name in text
    # a batcher driven by hand never waits for a request
    assert bat.metrics()["idle_seconds"] == 0


@pytest.mark.parametrize("kind", ["step", "chunk", "verify", "loop"])
def test_with_tracing_off_every_site_is_handed_the_null_span(
        lm_family, no_tracer, monkeypatch, kind):
    """No tracer, no profiler trace: ``trace.span`` hands each site of the
    loop the one falsy ``_NULL_SPAN``, allocates nothing that stays, and
    nothing in the loop waits for the device on a span's behalf."""
    import inspect
    import tracemalloc

    import jax

    from paddle_tpu.serving import decode_batcher

    trace = no_tracer
    handed = []
    real = trace.span

    def recording(name, *a, **k):
        sp = real(name, *a, **k)
        handed.append((name, sp))
        return sp

    def refuse(*a, **k):
        raise AssertionError("the loop waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    if kind == "loop":
        model, bat = _fake_batcher(start=True)
        monkeypatch.setattr(trace, "span", recording)
        assert list(bat.predict([1, 2], max_new_tokens=3,
                                timeout_s=30.0)) == _counting_seq(2, 3)
        time.sleep(0.05)            # the loop goes back to its wait
        assert list(bat.predict([4], max_new_tokens=2,
                                timeout_s=30.0)) == _counting_seq(4, 2)
        bat.shutdown()
        wanted = {"decode.idle", "decode.admit", "decode.step",
                  "decode.feed", "decode.fetch", "decode.sample"}
    else:
        bat = _family_batcher(lm_family, speculate=kind == "verify")
        submits = [(LONG_PROMPT, 2)] if kind == "chunk" else [([5], 3)]

        def drive():
            for prompt, max_new in submits:
                bat.submit(prompt, max_new_tokens=max_new)
            bat.drive()

        drive()                     # executables made, lazy caches warm
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            drive()
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        leaks = [s for s in after.compare_to(before, "lineno")
                 if s.traceback[0].filename == trace.__file__
                 and s.size_diff > 0]
        assert not leaks, "spans that are off allocated: %s" % leaks
        monkeypatch.setattr(trace, "span", recording)
        drive()
        wanted = {"decode.admit", "decode.plan", "decode.feed",
                  "executor.run"} | {
            "step": {"decode.step", "decode.fetch", "decode.sample"},
            "chunk": {"prefill.chunk", "decode.step"},
            "verify": {"spec.verify", "decode.fetch"}}[kind]
    names = {name for name, _ in handed}
    assert wanted <= names, wanted - names
    assert all(sp is trace._NULL_SPAN for _, sp in handed)
    assert {n for n in names if not n.startswith("executor.")} <= LOOP_SITES
    assert "block_until_ready" not in inspect.getsource(decode_batcher)


def test_the_loops_wait_for_a_request_is_a_span_of_its_own(no_tracer):
    """The loop thread with nothing to serve sits in ``decode.idle``; the
    span ends when a request arrives, before its admission, and
    ``idle_seconds`` counts the wait as it ends."""
    tracer = no_tracer.start()
    model, bat = _fake_batcher(start=True)
    time.sleep(0.05)
    assert list(bat.predict([1, 2], max_new_tokens=3,
                            timeout_s=30.0)) == _counting_seq(2, 3)
    bat.shutdown()
    spans = sorted(tracer.drain(), key=lambda s: s["t0"])
    idle = [s for s in spans if s["name"] == "decode.idle"]
    admit = [s for s in spans if s["name"] == "decode.admit"]
    assert idle and admit and idle[0]["dur"] >= 0.04
    # the counter's two clock reads lie inside the span, round the wait
    waited = sum(s["dur"] for s in idle)
    assert 0.04 <= bat.metrics()["idle_seconds"] <= waited
    assert bat.metrics()["idle_seconds"] > waited - 0.01 * len(idle)
    assert idle[0]["t0"] + idle[0]["dur"] <= admit[0]["t0"]
    assert all(s["parent_id"] is None for s in idle + admit)
    # between two quanta with a live request the loop does not wait
    steps = [s for s in spans if s["name"] == "decode.step"]
    assert len(steps) == 4
    assert not [s for s in idle if steps[0]["t0"] < s["t0"] < steps[-1]["t0"]]
