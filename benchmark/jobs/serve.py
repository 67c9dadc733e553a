"""The ``serve`` job kind: one ``ServingEngine`` in decode mode, offered an
open-loop schedule of requests.

Set-up builds the configuration's two KV-cached programs (one token a slot
row, and a chunk of prompt tokens a row) over ONE scope, gives them the
benchmark's seeded weights in the type they are served in, starts ONE
``ServingEngine(decode=...)`` with the ladders the traffic file names, and
warms every executable the traffic will use with real requests, one chunk
rung at a time. ``setup_s`` ends where the schedule starts.

The schedule is made before the clock starts (``serve_traffic.py``: a mix
is one fixed trace, and ``--seed`` draws the token ids and the weights). A
client thread submits each request AT ITS
SCHEDULED TIME whether or not earlier ones have finished (open loop); every
time is read at the client: a request's latency runs from its scheduled
arrival, not from ``submit``, to the moment its future resolves (the
program delivers a whole answer at once: PERF.md, Open questions). The
schedule runs before the timed window opens, so that the window opens on a
system in steady state: ``ramp_s`` seconds where arrivals pace the system,
and under a backlog until answer number ``open_after`` has arrived; what
the ramp serves is not counted.

Arrivals ``poisson``: ``attempted`` = requests whose scheduled arrival lies
in the window; ``failed`` = those that raised, came back with another number
of tokens than asked, or never came (an answer is waited for up to
``DRAIN_S`` past the window, and its latency counts the wait).
Arrivals ``backlog`` (everything queued at the schedule's start, more than
the window can drain): ``attempted`` = requests answered inside the window.

``serve_tokens_per_s`` is all the work of the window over all its seconds:
the tokens the decode loop sampled between the window's two edges. The
program hands a client a whole answer and nothing before it, so the client
cannot count tokens as they come; the count is the engine's own
(``decode_tokens``), which the thread that waits for the window reads about
once a millisecond beside the count of quanta (``Ticks``), a quantum's
tokens spread evenly over the quantum, so that the reading moves
continuously with the system's speed and not in steps of an answer (3.8% of
a window) or of a decode step (1%). The count is held to the client's
books: ``tokens_miscounted`` among the compared numbers, exact whenever the
engine has come to rest. ``answers_tokens_per_s``, printed beside it, counts
the answers that arrived whole inside the window.

``correct``: ``serve_check.py``, on the same engine's answers, after the
window. ``--trace 1``: the schedule goes on after the window and
``PROFILE_S`` seconds of it run under ``jax.profiler``.
"""

import functools
import gc
import json
import os
import shutil
import threading
import time

import numpy as np

from benchmark import harness, seeded
from benchmark.jobs import serve_check, serve_traffic

DRAIN_S = 30.0
OPEN_S = 120.0
PROFILE_S = 1.5
TICK_S = 0.001


def make_weights(specs, seed, dtype):
    """{name: array} on the default device, every leaf drawn as
    ``seeded._draw`` draws it and kept in ``dtype``, in ONE jitted call with
    the seed as an argument (one executable serves every seed)."""
    import jax

    lo, hi = (np.int32(word) for word in seeded._fold(seed))
    specs = [(n, tuple(int(d) for d in s), k) for n, s, k in specs]

    @jax.jit
    def make(lo, hi):
        root = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        return {name: seeded._draw(jax.random.fold_in(root, i), shape,
                                   kind).astype(dtype)
                for i, (name, shape, kind) in enumerate(specs)}

    return make(lo, hi)


def counters(engine):
    """{counter: value} of the engine's own metrics, from its Prometheus
    exposition, with the lifetime count and sum of its TTFT and TPOT
    samples."""
    out = {}
    for line in engine.metrics_.prometheus_text().splitlines():
        if line.startswith("paddle_tpu_serving_") and "{" not in line:
            name, _, value = line.partition(" ")
            try:
                out[name[len("paddle_tpu_serving_"):]] = float(value)
            except ValueError:
                pass
    for name in ("ttft", "tpot"):
        hist = getattr(engine.metrics_, name)
        out[name + "_count"] = float(hist.count)
        out[name + "_total_s"] = float(hist.total)
    return out


class Ticks:
    """The engine's own counts, read by the thread that waits for the
    window: a row (clock, quanta, tokens sampled, prompt tokens ingested
    through chunks, chunk dispatches, live slots summed over the quanta)
    whenever the count of quanta has moved. A quantum is a decode step or
    a chunk dispatch; on the chip one lasts 30 ms or more, so a row is one
    quantum."""

    NAMES = ("decode_steps", "decode_tokens", "prefill_tokens",
             "prefill_chunks", "slot_live")

    def __init__(self, engine):
        registry = engine.metrics_.registry
        self._counters = [registry.get("paddle_tpu_serving_" + n)
                          for n in self.NAMES]
        self.rows = []
        self.read()

    def read(self):
        now = time.perf_counter()
        counts = tuple(c.value for c in self._counters)
        if not self.rows or counts[0] != self.rows[-1][1]:
            self.rows.append((now,) + counts)
        elif counts[3] == self.rows[-1][4]:
            # a quantum's tokens and live slots are counted after the
            # quantum itself; a chunk's prompt tokens before it, and belong
            # to the row to come
            self.rows[-1] = self.rows[-1][:1] + counts

    def watch(self, until, stop=None):
        """Read every ``TICK_S`` until the clock reads ``until`` or
        ``stop()`` is true. Returns whether it stopped short."""
        while time.perf_counter() < until:
            if stop is not None and stop():
                return True
            time.sleep(TICK_S)
            self.read()
        return False

    def watch_a_quantum(self, until):
        """Read on until the quantum under way has ended."""
        seen = len(self.rows)
        self.watch(until, lambda: len(self.rows) > seen)

    def _columns(self):
        return (np.array(c, float) for c in zip(*self.rows))

    def tokens_per_s(self, w0, w1):
        """Tokens sampled between two instants over the seconds between
        them, a quantum's tokens spread evenly over the quantum."""
        at, _, tokens, _, _, _ = self._columns()
        return float(np.interp(w1, at, tokens)
                     - np.interp(w0, at, tokens)) / (w1 - w0)

    def live_positions(self, requests):
        """(clock [rows], positions the live sequences hold in the cache at
        each row). A step moves every live slot on by one position, whether
        it samples a token or is fed a prompt's; a chunk moves its rows on
        by the prompt tokens it ingests; an answered request held prompt +
        answer - 1 (its last token is never fed back) and holds nothing."""
        at, quanta, _, prompt, chunks, slot_live = self._columns()
        quanta, prompt, chunks, slot_live = (
            np.diff(c, prepend=c[0]) for c in (quanta, prompt, chunks,
                                               slot_live))
        # a row that holds several quanta (a rehearsal's are that short)
        # takes its steps' share of the live slots
        steps = (quanta - chunks) / np.maximum(quanta, 1.0)
        moved = np.cumsum(slot_live * steps + prompt)
        done = sorted((r.done, r.positions - 1) for r in requests
                      if r.done is not None and r.error is None)
        if not done:
            return at, moved
        freed = np.cumsum([0.0] + [held for _, held in done])
        return at, moved - freed[np.searchsorted(
            [when for when, _ in done], at, side="right")]

    def miscounted(self, requests, slots):
        """How far the engine's count of sampled tokens, since these ticks
        began, lies outside what the client's books allow. Exact where
        every submitted request has been answered; else the requests in
        flight may have had up to their whole answers sampled (the
        ``slots`` longest of them), and the quantum under way may not be
        counted yet."""
        self.read()
        counted = self.rows[-1][2] - self.rows[0][2]
        sent = [r for r in requests if r.submitted is not None]
        answered = sum(len(r.tokens) for r in sent if r.tokens is not None)
        open_ = sorted((r.max_new for r in sent if r.done is None),
                       reverse=True)[:slots]
        slack = slots if open_ else 0
        return float(max(0, answered - slack - counted,
                         counted - answered - sum(open_)))


class Server:
    """The one object: programs, scope, weights, predictors, the engine."""

    def __init__(self, run):
        import paddle_tpu as fluid
        from paddle_tpu import serving
        from paddle_tpu.inference import ProgramPredictor

        cfg, knobs = run.config, run.traffic["engine"]
        builder = harness.load_module(run.path(cfg["builder"]))
        self.args = {k: cfg[k] for k in cfg["builder_keys"]}
        self.dtype = cfg["served_dtype"]
        self.scope = fluid.Scope()
        self.predictors, specs = {}, {}
        for kind in ("step", "chunk"):
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = seeded.PROGRAM_SEED
            with fluid.program_guard(main, startup), \
                    fluid.unique_name.guard():
                fetch, spec = getattr(builder, kind)(dtype=self.dtype,
                                                     **self.args)
            feeds = [spec["token_feed"], spec["pos_feed"]] + [
                c["feed"] for c in spec["cache_feeds"]]
            self.predictors[kind] = ProgramPredictor(main, feeds, fetch,
                                                     scope=self.scope)
            specs[kind] = spec
            if kind == "step":
                leaves = [(p.name, p.shape,
                           seeded.init_kind(p.name, cfg["init"]))
                          for p in main.global_block().all_parameters()]
        t0 = time.perf_counter()
        # the benchmark's weights; the programs' startup is never run
        self.weights = make_weights(leaves, run.seed, self.dtype)
        for name, value in self.weights.items():
            self.scope.set(name, value)
        print("set-up: %d seeded leaves, %.0f M parameters, made in %.1f s"
              % (len(leaves), sum(int(np.prod(s)) for _, s, _ in leaves)
                 / 1e6, time.perf_counter() - t0))
        self.prefill_ladder = tuple(int(k) for k in knobs["prefill_ladder"])
        self.ctx_top = max(int(c) for c in knobs["seq_ladder"])
        self.slots = max(int(b) for b in knobs["ladder"])
        self.engine = serving.ServingEngine(
            self.predictors["step"], decode=specs["step"], num_replicas=1,
            ladder=tuple(knobs["ladder"]),
            seq_ladder=tuple(knobs["seq_ladder"]),
            max_queue_depth=int(knobs["max_queue_depth"]),
            decode_prefill={"predictor": self.predictors["chunk"],
                            "spec": specs["chunk"],
                            "ladder": self.prefill_ladder})

    def warm_up(self, seed):
        """One real request a chunk rung, one after another (a prompt of
        ``rung + 1`` tokens is ingested by one chunk of exactly that rung
        and one step), so every executable of the ladders is made or
        loaded before the schedule starts. Returns the seconds each
        request took: the first makes or loads the step executable too."""
        rng = serve_traffic._rng(seed, 0x3A43)
        took = []
        for rung in self.prefill_ladder:
            t0 = time.perf_counter()
            prompt = rng.integers(0, self.args["vocab_size"], size=rung + 1)
            out = self.engine.predict(prompt, timeout_s=1200.0,
                                      max_new_tokens=2)
            if len(np.asarray(out)) != 2:
                raise RuntimeError("warm-up request came back wrong")
            took.append(time.perf_counter() - t0)
            print("set-up: chunk rung %d warm in %.1f s" % (rung, took[-1]))
        return took

    def executables(self):
        return sum(self.engine.compiled_shape_counts())

    def compile_records(self):
        """The compile records of every executable the two predictors made
        (``Executor.compile_records``: seconds of tracing, lowering and the
        backend's part of each), or [] where they keep none."""
        return [record for kind in sorted(self.predictors)
                for record in getattr(getattr(
                    self.predictors[kind], "_exe", None),
                    "compile_records", None) or ()]

    def hlo_text(self, kind):
        """Optimized HLO text of the executable the ``kind`` predictor ran
        last, or None where the predictor keeps no executor to ask."""
        exe = getattr(self.predictors.get(kind), "_exe", None)
        try:
            return exe.lowered_hlo_text(optimized=True)
        except (AttributeError, RuntimeError):
            return None

    def free(self):
        """Stop the engine, abandoning what is queued, and drop the
        program's state; the weights the benchmark made stay for the
        reference."""
        self.engine.shutdown(drain=False, timeout_s=60.0)
        self.engine = None
        self.predictors = {}
        for name in list(self.scope.var_names()):
            self.scope.drop(name)
        gc.collect()


class Client(threading.Thread):
    """Submits each request at its scheduled time; the done-callback reads
    the client's clock when the answer arrives."""

    def __init__(self, engine, requests, t0):
        super().__init__(name="bench-serve-client", daemon=True)
        self.engine, self.requests, self.t0 = engine, requests, t0
        self.stop = threading.Event()
        self.late_s = 0.0
        self.arrived = []  # the client's clock at each answer, in order

    def _done(self, request, future):
        request.done = time.perf_counter()
        self.arrived.append(request.done)
        error = future.exception()
        if error is not None:
            request.error = error
        else:
            request.tokens = np.asarray(future.result()).ravel()

    def run(self):
        for request in self.requests:
            wait = self.t0 + request.at - time.perf_counter()
            if wait > 0 and self.stop.wait(wait):
                return
            if self.stop.is_set():
                return
            try:
                future = self.engine.submit(request.prompt,
                                            max_new_tokens=request.max_new)
            except Exception as e:  # shed or refused: a failed request
                request.error, request.done = e, time.perf_counter()
                continue
            request.submitted = time.perf_counter()
            self.late_s = max(self.late_s, request.submitted - self.t0
                              - request.at)
            future.add_done_callback(functools.partial(self._done, request))


def _ok(request):
    return (request.error is None and request.tokens is not None
            and len(request.tokens) == request.max_new)


def account(requests, kind, t0, w0, w1, deadline):
    """(counted requests, failed among them, those answered well inside the
    window) by the rules of the arrivals kind; an answer that comes after
    ``deadline`` has failed."""
    answered = [r for r in requests if _ok(r) and w0 <= r.done < w1]
    if kind == "poisson":
        counted = [r for r in requests if w0 <= t0 + r.at < w1]
        failed = [r for r in counted
                  if not _ok(r) or r.done > deadline]
    else:
        broken = [r for r in requests if r.done is not None
                  and w0 <= r.done < w1 and not _ok(r)]
        counted, failed = answered + broken, broken
    return counted, failed, answered


def client_numbers(counted, failed, answered, t0, seconds):
    """What the client's clock reads of a window; the manifest says which
    of these a cell reports, end to end or beside a layer."""
    out = {"answers_tokens_per_s": sum(len(r.tokens) for r in answered)
           / seconds}
    bad = set(id(r) for r in failed)
    good = [r for r in counted if id(r) not in bad]
    if good:
        latency = np.array([r.done - (t0 + r.at) for r in good])
        tokens = np.array([len(r.tokens) for r in good], float)
        # a failed request misses every limit: it stands at the tail
        lost = np.full(len(failed), np.inf)
        out["request_p90_ms"] = 1e3 * float(np.percentile(
            np.concatenate([latency, lost]), 90, method="higher"))
        out["request_p50_ms"] = 1e3 * float(np.percentile(
            np.concatenate([latency, lost]), 50, method="higher"))
        out["request_mean_ms"] = 1e3 * float(latency.mean())
        out["token_ms_p90"] = 1e3 * float(np.percentile(
            np.concatenate([latency / tokens, lost]), 90,
            method="higher"))
        out["token_ms_mean"] = 1e3 * float(latency.sum() / tokens.sum())
    return out


def keep_books(run, ticks, requests, t0, w0, w1):
    """Leave the run's own record under ``benchmark_out/``: every quantum
    as ``Ticks`` saw it and every request's times, on one clock. No metric
    reads it; it is there to look into a run that read far off."""
    os.makedirs(run.path("benchmark_out"), exist_ok=True)
    with open(run.path("benchmark_out", "serve_books.json"), "w") as f:
        json.dump({"cell": run.cell["name"], "seed": run.seed,
                   "schedule_start": t0, "window": [w0, w1],
                   "ticks": ["clock"] + list(Ticks.NAMES),
                   "rows": ticks.rows,
                   "requests": [[r.index, len(r.prompt), r.max_new, r.at,
                                 r.submitted, r.done] for r in requests]}, f)


def serve_window(run):
    """Set-up, the schedule and the window, the traced part of a traced
    run, the books; then the program's state is freed. Returns (result so
    far, the sampled requests with the tokens they were served, the
    benchmark's weights, the builder's sizes, the length the reference pads
    a sequence to)."""
    import warnings

    run.claim_devices()
    warnings.filterwarnings("ignore", message=".*int64.*")

    traffic = run.traffic
    arrivals = traffic["arrivals"]
    t_build = time.perf_counter()
    server = Server(run)
    print("set-up: programs built, weights made, engine started in %.1f s"
          % (time.perf_counter() - t_build))
    warm = server.warm_up(run.seed)
    requests = serve_traffic.schedule(
        traffic, server.args["vocab_size"], run.seed, run.seconds)
    print("schedule: %d requests, %d prompt and %d answer tokens, arrivals "
          "%s" % (len(requests), sum(len(r.prompt) for r in requests),
                  sum(r.max_new for r in requests), arrivals["kind"]))

    compiles_before = run.compiles.count
    setup_s = time.time() - run.process_start
    ticks = Ticks(server.engine)
    t0 = time.perf_counter()
    client = Client(server.engine, requests, t0)
    client.start()
    if arrivals["kind"] == "backlog":
        # nothing paces a backlog but the system itself, so the window
        # opens on an event of the system's own course, the arrival of
        # answer number ``open_after``: every run's window then holds the
        # same stretch of the same work, whatever the start-up took
        nth = int(arrivals["open_after"])
        if not ticks.watch(t0 + OPEN_S, lambda: len(client.arrived) >= nth):
            raise RuntimeError("%d answers had not arrived %.0f s after the "
                               "schedule started" % (nth, OPEN_S))
        w0 = client.arrived[nth - 1]
    else:
        w0 = t0 + float(arrivals["ramp_s"])
        ticks.watch(w0)
    w1 = w0 + run.seconds
    at_open = counters(server.engine)
    ticks.watch(w1)
    at_close = counters(server.engine)
    # the quantum under way when the window closes, to its end
    ticks.watch_a_quantum(w1 + 2.0)

    ctx = None
    if run.trace:
        # the schedule goes on; PROFILE_S seconds of it under the profiler
        trace_dir = run.path("benchmark_out", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        before = counters(server.engine)
        p0 = time.perf_counter()
        xplane = harness.profile(trace_dir,
                                 lambda: ticks.watch(p0 + PROFILE_S))
        ctx = {"xplane": xplane, "requests": requests, "ticks": ticks,
               "profiled": (p0, p0 + PROFILE_S),
               "profile_counters": (before, counters(server.engine)),
               "hlo_text": {k: server.hlo_text(k)
                            for k in ("step", "chunk")},
               "window_counters": (at_open, at_close),
               "window": (w0, w1), "slots": server.slots,
               "positions": server.slots * server.ctx_top,
               "warmup_s": sum(warm), "first_step_s": warm[0],
               "compile_records": server.compile_records()}
    client.stop.set()
    client.join(10.0)
    if arrivals["kind"] == "poisson":
        # no new work; what was sent is waited for, so the engine comes to
        # rest and its count of tokens can be held to the answers exactly
        sent = [r for r in requests if r.submitted is not None]
        while time.perf_counter() < w1 + DRAIN_S and any(
                r.done is None for r in sent):
            time.sleep(0.02)
    counted, failed, answered = account(requests, arrivals["kind"], t0, w0,
                                        w1, w1 + DRAIN_S)
    compiled_inside = run.compiles.count - compiles_before
    if compiled_inside:
        raise RuntimeError("%d compilation(s) inside the measured window"
                           % compiled_inside)
    metrics = client_numbers(counted, failed, answered, t0, run.seconds)
    metrics["serve_tokens_per_s"] = ticks.tokens_per_s(w0, w1)
    metrics["setup_s"] = setup_s
    miscounted = ticks.miscounted(requests, server.slots)
    at, live = ticks.live_positions(requests)
    inside = (at >= w0) & (at <= w1)
    print("window: %d requests counted, %d failed, %d answered inside it; "
          "the generator ran at most %.1f ms late; %d quanta" % (
              len(counted), len(failed), len(answered),
              client.late_s * 1e3, int(inside.sum())))
    print("window: " + "  ".join("%s %.6g" % kv
                                 for kv in sorted(metrics.items())))
    if inside.any():
        reserved = server.slots * server.ctx_top
        print("window: the live sequences held %.0f of the %d reserved cache "
              "positions in the mean (%.1f%%), %.0f at most" % (
                  live[inside].mean(), reserved,
                  100.0 * live[inside].mean() / reserved,
                  live[inside].max()))
    if inside.sum() > 1:
        took = np.diff(at[inside])
        print("window: the longest time between two quanta was %.0f ms, the median %.0f ms"
              % (1e3 * took.max(), 1e3 * np.median(took)))
    keep_books(run, ticks, requests, t0, w0, w1)
    at_rest = None
    if all(r.done is not None for r in requests
           if r.submitted is not None):
        at_rest = float(live[-1])  # 0 where the books of positions hold
        print("window: with every answer in, the books leave %.0f positions "
              "live" % at_rest)
    for r in failed[:5]:
        print("failed: request %d (%d + %d tokens): %r"
              % (r.index, len(r.prompt), r.max_new, r.error))

    result = {"attempted": len(counted), "failed": len(failed),
              "answered": len(answered), "metrics": metrics,
              "miscounted": miscounted, "live_at_rest": at_rest}
    if ctx is not None:
        ctx["executables"] = server.executables()
        ctx["client"] = metrics
    sampled = serve_check.sample(answered, run.seed,
                                 int(traffic["check"]["sample"]))
    result["memory_peak_bytes"] = run.memory_peak_bytes()
    weights, args, pad_to = server.weights, server.args, server.ctx_top
    server.free()

    if ctx is not None:
        from benchmark import serve_trace

        ctx["trace"] = serve_trace.load(ctx["xplane"], len(run.devices),
                                        ctx["hlo_text"])
        result["metrics"] = run.read_layer_metrics(ctx)
        for name in ("decode.step", "prefill.chunk"):
            print("traced: %d %s spans, %.1f ms in all" % (
                ctx["trace"].count(name), name,
                ctx["trace"].total_ms(name) or 0.0))
        print("traced: " + "  ".join("%s %.6g" % kv for kv in sorted(
            result["metrics"].items())))
        result["busy_s"] = ctx["trace"].busy_s
        result["window_s"] = ctx["trace"].window_s
        result["breakdown"] = ctx["trace"].breakdown()
    return result, sampled, weights, args, pad_to


def run(run):
    result, sampled, weights, args, pad_to = serve_window(run)
    # the reference, after the window, with the program's state gone from
    # the chip; its time is in no metric
    t_ref = time.perf_counter()
    gaps = serve_check.served_gaps(
        serve_check.load_reference(run), weights, sampled, args,
        serve_check.precision(run, "exact"), pad_to)
    rows = serve_check.compare(gaps, run.config["limits"])
    print("reference: %d requests, %d served tokens followed in %.1f s"
          % (len(sampled), len(gaps), time.perf_counter() - t_ref))
    rows.append({"name": "tokens_miscounted", "value": result["miscounted"],
                 "limit": 0.0, "ok": result["miscounted"] == 0,
                 "note": "the engine's count of sampled tokens against the "
                         "answers the clients hold"})
    result["compared"] = rows
    result["correct"] = all(r["ok"] for r in rows) \
        and result["failed"] == 0 and result["answered"] > 0
    return result
