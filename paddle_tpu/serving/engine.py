"""ServingEngine: the in-process serving layer over the predictor stack.

Reference shape: ``AnalysisPredictor::Init`` loads once, ``Clone()`` hands
each serving thread a predictor sharing the weights, and the server in
front batches requests. Here the same three-layer split is TPU-native:

  * load once — one ``Predictor`` (isolated ``Scope`` holding the weights)
    or one ``StableHLOPredictor`` (immutable exported computation);
  * replicate — ``clone()`` per worker thread: weights shared, per-worker
    Executor compile cache (``inference.py`` clone contract), so replicas
    never contend on a cache dict while XLA releases the GIL during runs;
  * batch — a ``DynamicBatcher`` cuts size-or-deadline micro-batches,
    ``buckets.pad_to_bucket`` pads them onto the ladder so every dispatch
    hits one of at most ``len(ladder)`` compiled executables, and
    ``warmup()`` pre-compiles every rung before traffic lands.

``submit(feed) -> Future`` is the whole client API; ``shutdown(drain=True)``
stops intake, serves what's queued, and joins the workers.

Self-healing (``paddle_tpu.reliability``): a failed batch gets ONE
cross-replica retry before its futures fail (inference is idempotent —
``donate_state=False`` since PR 1 means no state mutation); each replica
carries a :class:`~paddle_tpu.reliability.CircuitBreaker` that, after K
consecutive batch failures, evicts the predictor and rebuilds it from the
parent via ``clone()``; a supervisor thread respawns worker threads that
die outright; and under overload new arrivals with *earlier* deadlines
displace the least-urgent queued request (EDF shedding) instead of being
turned away FIFO-blind. Every event is counted in ``ServingMetrics``
(shed / retried / evicted / respawned) — zero in a healthy run.
"""

import threading
import warnings
from concurrent.futures import Future

import numpy as np

from ..inference import AnalysisConfig, Predictor
from ..obs import flight, trace
from ..reliability import faults
from ..reliability.policy import CircuitBreaker
from .admission import (AdmissionController, DeadlineExceededError,
                        ServerOverloadedError)
from .batcher import DynamicBatcher, Request
from .buckets import (bucket_for, edge_pad, pad_to_bucket, pow2_ladder,
                      unpad_fetch)
from .metrics import ServingMetrics

__all__ = ["ServingEngine", "EngineShutdownError"]


class EngineShutdownError(RuntimeError):
    """The engine shut down before this admitted request could be served
    (a worker was stuck or dead at shutdown); safe to retry elsewhere."""


class _Worker:
    """One replica: a predictor clone, the shape signatures it has
    dispatched (the engine-side view of its compile cache, valid for both
    predictor types), and its circuit breaker (touched only by this
    replica's worker thread)."""

    def __init__(self, predictor, index, breaker):
        self.predictor = predictor
        self.index = index
        self.breaker = breaker
        self.seen_signatures = set()
        self.thread = None


class ServingEngine:
    def __init__(self, model, num_replicas=1, max_batch_size=8,
                 ladder=None, seq_ladder=None, max_wait_ms=5.0,
                 max_queue_depth=256, default_timeout_s=None, clock=None,
                 latency_window=8192, max_replica_failures=3,
                 cross_replica_retry=True, shed_on_overload=True,
                 supervisor_interval_s=0.05, placement="single", mp=1,
                 devices=None, decode=None, default_max_new_tokens=64,
                 eos_id=None, prefix_cache=None, decode_prefill=None,
                 decode_speculative=None):
        """``model``: a model directory / ``AnalysisConfig`` (loaded via
        ``Predictor``), or an already-constructed predictor exposing
        ``run``/``clone``/``feed_names`` (``Predictor``,
        ``ProgramPredictor`` or ``StableHLOPredictor``).

        Placement (ISSUE 14): ``placement="per_device"`` pins replicas
        round-robin over ``devices`` (jax devices or fluid places; default
        ``jax.devices()``) via
        ``clone(device=...)`` — each replica's weights live on its own
        chip instead of all landing on device 0. ``mp=k`` serves a
        tensor-parallel predictor sharded over a k-device ``("mp",)``
        mesh per the program's ``ParamAttr(sharding=...)`` annotations
        (``Predictor.shard``); at build the compiled step is asserted to
        KEEP annotated params sharded (``parallel/sharding_check``) — an
        accidental full replication fails construction, not production.
        With both, devices are partitioned into ``len(devices)//mp``
        groups and replicas round-robin the groups.

        Decode (continuous batching): pass ``decode=<decode-spec dict>``
        (a step builder's spec, e.g. ``transformer_lm_step``) — or
        ``decode=True`` with a model dir carrying ``decode_spec.json`` —
        and the engine serves autoregressive generation through a
        slot-recycled :class:`~.decode_batcher.DecodeBatcher` per replica
        behind the same ``submit()``/``predict()`` API: feeds become
        ``submit(prompt_ids, max_new_tokens=..., eos_id=...)`` and the
        future resolves to the generated ids. ``ladder`` bounds the slot
        table, ``seq_ladder`` the KV-cache capacity rungs.

        Decode fast paths (ISSUE 20): ``prefix_cache=True`` (or a kwargs
        dict / :class:`~.prefix_cache.PrefixCache`) shares ONE prefix-KV
        cache across every replica, with the engine's metrics carrying
        the hit/eviction/bytes counters. ``decode_prefill={"predictor":
        chunk_pred, "spec": chunk_spec, "ladder": ...}`` attaches the
        K-token chunk program (``transformer_lm_chunk``) — replica 0
        uses the predictor as-is, later replicas ``clone()`` it — and
        extends the build-time compile-cache verdict with the prefill
        ladder. ``decode_speculative={"draft": DraftLM, "k": ...}``
        turns on speculative decode (requires ``decode_prefill``); the
        draft proposer is SHARED across replicas, so multi-replica
        engines need a thread-safe draft predictor.

        Reliability knobs: ``max_replica_failures`` consecutive batch
        failures evict a replica and rebuild it from the parent
        (``None``/0 disables); ``cross_replica_retry`` re-enqueues a
        failed batch's requests once before failing their futures;
        ``shed_on_overload`` lets a full queue shed its least-urgent
        (latest-deadline) entry for a more urgent arrival;
        ``supervisor_interval_s`` is the dead-worker-thread sweep cadence
        (``None`` disables the supervisor)."""
        if num_replicas < 1:
            raise ValueError("num_replicas must be >= 1")
        if placement not in ("single", "per_device"):
            raise ValueError("placement must be 'single' or 'per_device', "
                             "got %r" % (placement,))
        faults.maybe_install_from_env()
        model_dir = model.model_dir if isinstance(model, AnalysisConfig) \
            else (model if isinstance(model, str) else None)
        if isinstance(model, (str, AnalysisConfig)):
            model = Predictor(model)
        if not callable(getattr(model, "clone", None)):
            raise TypeError("model must be a dir/AnalysisConfig or a "
                            "predictor with clone(); got %r" % (model,))
        self.ladder = tuple(sorted(set(
            ladder if ladder is not None else pow2_ladder(max_batch_size))))
        # normalized exactly the way DecodeBatcher normalizes it, so the
        # build-time compile-cache verdict and the batcher's actual
        # ladder can never disagree
        self.seq_ladder = tuple(sorted(set(
            int(c) for c in seq_ladder))) if seq_ladder else None
        self.max_batch_size = max(self.ladder)
        self.feed_names = list(getattr(model, "feed_names", []))
        self.default_timeout_s = default_timeout_s
        self.placement = placement
        self.mp = int(mp)

        self._batcher = DynamicBatcher(self.max_batch_size,
                                       max_wait_ms=max_wait_ms, clock=clock)
        self._admission = AdmissionController(max_queue_depth)
        # serializes the shutdown/_closed transition against supervisor
        # respawns (see _maybe_respawn) — created before the decode-mode
        # early return so both construction paths have it
        self._lifecycle_lock = threading.Lock()
        self.metrics_ = ServingMetrics(latency_window=latency_window)
        self.metrics_.bind_gauges(self._batcher.depth,
                                  lambda: self._admission.in_flight)

        parents = self._build_parents(model, placement, self.mp, devices,
                                      num_replicas)
        self._parent = parents[0]
        # model-swap plane (streaming hot-reload): the checkpoint version
        # currently served + how many live swaps happened; reload() bumps
        # them after the per-replica scope flip
        self.serve_version = None
        self.swap_count = 0
        self._staged_swap = None  # (version, updates) held between
        # prepare and commit of a two-phase fleet swap
        self.max_replica_failures = max_replica_failures or 0
        self.cross_replica_retry = bool(cross_replica_retry)
        self.shed_on_overload = bool(shed_on_overload)

        # decode mode: continuous batching replaces the one-shot pipeline
        decode_spec = self._resolve_decode_spec(decode, model_dir)
        self._decoders = None
        if decode_spec is not None:
            from .decode_batcher import DecodeBatcher
            from .prefix_cache import PrefixCache

            # build-time resource verification (ISSUE 15): prove the
            # compile-cache bound from the decode spec — dead ctx rungs
            # and an over-budget ladder product are construction-time
            # warnings, not a production surprise at warmup
            self._verify_decode_build(decode_spec, decode_prefill)
            # one prefix cache for the whole fleet: any replica's
            # harvest serves any replica's admission
            self._prefix_cache = None
            # NOT a truthiness test: an empty PrefixCache is len()==0
            if prefix_cache is not None and prefix_cache is not False:
                if isinstance(prefix_cache, PrefixCache):
                    self._prefix_cache = prefix_cache
                else:
                    kw = (dict(prefix_cache)
                          if isinstance(prefix_cache, dict) else {})
                    kw.setdefault("metrics", self.metrics_)
                    self._prefix_cache = PrefixCache(**kw)
                pc = self._prefix_cache
                self.metrics_.bind_prefix_bytes(lambda: pc.nbytes)
            self._decoders = []
            for i in range(num_replicas):
                parent = parents[i % len(parents)]
                pred = parent if i < len(parents) else parent.clone()
                prefill = None
                if decode_prefill is not None:
                    prefill = dict(decode_prefill)
                    if i > 0:
                        prefill["predictor"] = \
                            decode_prefill["predictor"].clone()
                self._decoders.append(DecodeBatcher(
                    pred, decode_spec, ladder=self.ladder,
                    ctx_ladder=self.seq_ladder,
                    max_queue_depth=max_queue_depth,
                    default_timeout_s=default_timeout_s,
                    default_max_new_tokens=default_max_new_tokens,
                    eos_id=eos_id, clock=clock, metrics=self.metrics_,
                    prefix_cache=self._prefix_cache, prefill=prefill,
                    speculative=decode_speculative))
            # aggregate gauges over every replica's queue (each batcher
            # got the shared metrics and deliberately did NOT bind its
            # own — a per-replica bind would report only the last one)
            decoders = self._decoders
            self.metrics_.bind_gauges(
                lambda: sum(len(d._pending) for d in decoders),
                lambda: sum(d._admission.in_flight for d in decoders))
            self._workers = []
            self._closed = False
            self._shutdown_done = False
            self._stop_event = threading.Event()
            self._supervisor = None
            return

        def breaker():
            return CircuitBreaker(
                failure_threshold=max(1, self.max_replica_failures or 1),
                reset_timeout_s=0.0, clock=self._batcher.now)

        self._workers = []
        for i in range(num_replicas):
            parent = parents[i % len(parents)]
            pred = parent if i < len(parents) else parent.clone()
            self._workers.append(_Worker(pred, i, breaker()))
        self._closed = False
        self._shutdown_done = False
        self._stop_event = threading.Event()
        for w in self._workers:
            self._spawn_worker_thread(w)
        self._supervisor = None
        if supervisor_interval_s:
            self._supervisor = threading.Thread(
                target=self._supervisor_loop, args=(supervisor_interval_s,),
                name="paddle-tpu-serve-supervisor", daemon=True)
            self._supervisor.start()

    def _verify_decode_build(self, decode_spec, decode_prefill=None):
        """Static compile-cache verdict for the decode tier
        (``analysis.resources.decode_cache_verdict``): the scheduler's
        executable count is bounded by len(ladder) x len(valid ctx
        rungs) — times (1 + len(prefill rungs)) when a chunk program
        rides along — proved from the spec's cache capacity, checked
        against the budget at CONSTRUCTION. Findings surface as warnings
        and the result is kept on ``self.build_verification``; the
        proved bound on ``self.compile_cache_bound``."""
        from ..analysis.resources import decode_cache_verdict
        from .decode_batcher import (default_ctx_ladder,
                                     default_prefill_ladder)

        ctx_ladder = self.seq_ladder
        if ctx_ladder is None:
            ctx_ladder = default_ctx_ladder(decode_spec)
        prefill_ladder = None
        if decode_prefill is not None:
            prefill_ladder = decode_prefill.get("ladder")
            if prefill_ladder is None:
                prefill_ladder = default_prefill_ladder(decode_spec)
        bound, result = decode_cache_verdict(decode_spec, self.ladder,
                                             ctx_ladder,
                                             prefill_ladder=prefill_ladder)
        self.compile_cache_bound = bound
        self.build_verification = result
        for d in result.diagnostics:
            warnings.warn("serving build verification: %s" % d,
                          RuntimeWarning, stacklevel=3)

    # -- placement ----------------------------------------------------------
    @staticmethod
    def _resolve_decode_spec(decode, model_dir):
        if decode is None or decode is False:
            return None
        if isinstance(decode, dict):
            return decode
        if decode is True:
            if model_dir is None:
                raise ValueError("decode=True needs a model DIRECTORY "
                                 "carrying decode_spec.json; pass the "
                                 "spec dict for in-process predictors")
            from .decode_batcher import load_decode_spec

            return load_decode_spec(model_dir)
        raise TypeError("decode must be None, True, or a decode-spec "
                        "dict; got %r" % (decode,))

    def _build_parents(self, model, placement, mp, devices, num_replicas):
        """The replica-parent predictors placement produces: one
        weight-holder per device (per_device), per device GROUP (mp>1 +
        per_device), one mesh-sharded parent (mp>1), or just ``model``.
        Never more parents than replicas — an unused parent is an unused
        HBM-resident weight copy. mp parents are sharding-asserted
        before any replica clones."""
        if placement == "single" and mp <= 1:
            return [model]
        import jax

        # fluid places name their device; none given = JAX's default
        # backend (a TPUPlace never resolves to a CPU device)
        from ..core.executor import as_jax_devices

        devices = (as_jax_devices(devices) if devices is not None
                   else jax.devices())
        if mp > 1:
            import numpy as np
            from jax.sharding import Mesh

            if len(devices) < mp:
                raise ValueError(
                    "mp=%d needs %d devices, found %d"
                    % (mp, mp, len(devices)))
            if not callable(getattr(model, "shard", None)):
                raise TypeError(
                    "mp>1 needs a program-path predictor with .shard() "
                    "(Predictor/ProgramPredictor); got %r" % (model,))
            n_groups = (len(devices) // mp if placement == "per_device"
                        else 1)
            n_groups = max(1, min(n_groups, num_replicas))
            parents = []
            for g in range(n_groups):
                mesh = Mesh(np.array(devices[g * mp:(g + 1) * mp]),
                            ("mp",))
                parent = model.shard(mesh)
                self._assert_mp_sharded(parent, mesh)
                parents.append(parent)
            return parents
        # per_device, mp=1: one pinned weight copy per device in use
        return [model.clone(device=d)
                for d in devices[:max(1, min(len(devices), num_replicas))]]

    def _assert_mp_sharded(self, parent, mesh):
        """Build-time HLO assertion (``parallel/sharding_check``): every
        mp-annotated >=2-D parameter must enter the compiled step
        actually sharded, and no all-gather may reassemble one — the
        failure mode where GSPMD silently replicates a 'sharded' model
        and mp=k buys k chips of nothing."""
        from ..parallel import sharding_check

        prog = getattr(parent, "_program", None)
        if prog is None:
            return
        mesh_axes = set(mesh.axis_names)
        annotated = []
        for v in prog.list_vars():
            spec = getattr(v, "sharding", None)
            if not v.persistable or spec is None:
                continue
            if not any(a in mesh_axes for a in spec if a is not None):
                continue
            if v.shape is None or len(v.shape) < 2 or \
                    any(d is None or d < 0 for d in v.shape):
                continue
            annotated.append(v)
        if not annotated:
            warnings.warn(
                "mp=%d serving: the program carries no mp-annotated "
                "parameters — every weight will be fully replicated "
                "and tensor parallelism buys nothing"
                % mesh.devices.size, RuntimeWarning, stacklevel=3)
            return
        feed = self._synthesize_example_for(parent)
        padded, _ = pad_to_bucket(feed, (min(self.ladder),),
                                  seq_ladder=(min(self.seq_ladder),)
                                  if self.seq_ladder else None)
        parent.run(padded)
        hlo = parent._exe.lowered_hlo_text()
        for v in annotated:
            sharding_check.assert_param_sharded(hlo, v.name,
                                                logical_shape=v.shape)
        sharding_check.assert_no_param_allgather(
            hlo, [tuple(v.shape) for v in annotated])

    # -- client surface -----------------------------------------------------
    def submit(self, feed, timeout_s=None, max_new_tokens=None,
               eos_id=None):
        """Enqueue one request; returns a ``concurrent.futures.Future``.

        One-shot mode: ``feed`` is the usual dict/list of arrays and the
        future resolves to the fetch list (sliced to this request's
        rows). Decode mode (``decode=`` at construction): ``feed`` is the
        prompt — a 1-D int array/list, or a dict with a single
        ``prompt_ids`` entry — and the future resolves to the generated
        token ids; the request is continuously batched per STEP, so it
        shares every decode step with whatever else is in flight and
        retires the moment it finishes.

        Raises :class:`ServerOverloadedError` immediately when the bounded
        queue is full, ``BucketError`` when the request's batch exceeds the
        top rung, ``RuntimeError`` after shutdown."""
        if self._closed:
            raise RuntimeError("ServingEngine is shut down")
        if self._decoders is not None:
            if isinstance(feed, dict):
                if set(feed) != {"prompt_ids"}:
                    raise ValueError(
                        "decode-mode submit takes a prompt (1-D ids) or "
                        "{'prompt_ids': ids}; got keys %s" % sorted(feed))
                feed = feed["prompt_ids"]
            # least-loaded replica: pending + occupied slots
            dec = min(self._decoders,
                      key=lambda d: d._admission.in_flight)
            return dec.submit(feed, max_new_tokens=max_new_tokens,
                              eos_id=eos_id, timeout_s=timeout_s)
        if isinstance(feed, (list, tuple)):
            if len(feed) != len(self.feed_names):
                raise ValueError("expected %d inputs (%s), got %d"
                                 % (len(self.feed_names), self.feed_names,
                                    len(feed)))
            feed = dict(zip(self.feed_names, feed))
        feed = {k: np.asarray(v) for k, v in feed.items()}
        if self.feed_names:
            missing = set(self.feed_names) - set(feed)
            if missing:
                raise ValueError("missing feeds: %s" % sorted(missing))
        sizes = {k: a.shape[0] for k, a in feed.items() if a.ndim}
        if not sizes:
            raise ValueError("feeds need a leading batch dim to serve")
        if len(set(sizes.values())) > 1:
            raise ValueError("feeds disagree on batch size: %s" % sizes)
        n = next(iter(sizes.values()))
        bucket_for(n, self.ladder)  # validates n fits the ladder
        if self.seq_ladder:
            for a in feed.values():
                if a.ndim >= 2:
                    # reject an over-long sequence at the door, not inside
                    # a batch where it would fail innocent co-riders
                    bucket_for(a.shape[1], self.seq_ladder)
        timeout_s = (timeout_s if timeout_s is not None
                     else self.default_timeout_s)
        now = self._batcher.now()
        deadline = now + timeout_s if timeout_s is not None else None
        while True:
            try:
                self._admission.acquire(n)
                break
            except ServerOverloadedError:
                # EDF degradation: a full queue sheds its least-urgent
                # (latest-deadline) entry for a strictly more urgent
                # arrival; deadline-less work is the first to go and can
                # displace nothing itself. The batcher checks feasibility
                # atomically — enough strictly-later-deadline examples
                # must be queued to cover the whole shortfall (shedding
                # cannot reach capacity held by in-flight batches or
                # more-urgent work), so no victim dies for an arrival
                # that gets rejected anyway
                short = self._admission.shortfall(n)
                if short == 0:
                    continue  # racing release freed the capacity: retry
                victim = (self._batcher.shed_for(deadline, short)
                          if self.shed_on_overload else None)
                if victim is None:
                    self.metrics_.observe_rejected()
                    raise
                self._fail(victim, ServerOverloadedError(
                    "shed under overload: the slot went to a request "
                    "with an earlier deadline"))
                self.metrics_.observe_shed()
                flight.record("edf.shed", where="engine", n=victim.n)
        req = Request(feed, n, Future(), now, deadline=deadline)
        req.trace_ctx = trace.current()
        try:
            self._batcher.put(req)
        except RuntimeError:
            self._admission.release(n)
            raise RuntimeError("ServingEngine is shut down")
        return req.future

    def predict(self, feed, timeout_s=None, **decode_kw):
        """Synchronous convenience: submit + wait."""
        return self.submit(feed, timeout_s=timeout_s,
                           **decode_kw).result(timeout_s)

    def warmup(self, example_feed=None):
        """Pre-compile every (batch rung x seq rung) bucket on every
        replica, so the first real request at any bucket hits a warm
        executable. ``example_feed`` is one representative example
        (leading dim 1) — with a ``seq_ladder``, give it the SHORTEST
        sequence you expect, since padding can only lengthen it: seq
        rungs below the example's length can't be warmed from it and are
        skipped. Returns the number of (replica, bucket) compilations
        actually warmed."""
        from .buckets import BucketError

        if self._decoders is not None:
            return sum(d.warmup() for d in self._decoders)
        feed = example_feed
        if feed is None:
            feed = self._synthesize_example()
        feed = {k: np.asarray(v) for k, v in feed.items()}
        warmed = 0
        seq_rungs = self.seq_ladder or (None,)
        for w in self._workers:
            for rung in self.ladder:
                for s in seq_rungs:
                    try:
                        padded, _ = pad_to_bucket(
                            feed, (rung,),
                            seq_ladder=None if s is None else (s,))
                    except BucketError:
                        continue  # example longer than this seq rung
                    w.predictor.run(padded)
                    w.seen_signatures.add(self._signature(padded))
                    warmed += 1
        return warmed

    def metrics(self):
        return self.metrics_.snapshot()

    def metrics_report(self):
        return self.metrics_.report()

    def compiled_shape_counts(self):
        """Distinct dispatched feed signatures per replica — the bound the
        ladder guarantees (<= len(ladder), or len(ladder)*len(seq_ladder)
        with sequence bucketing). For program-path replicas this mirrors
        the Executor's real compile-cache size."""
        if self._decoders is not None:
            return [c for d in self._decoders
                    for c in d.compiled_shape_counts()]
        return [len(w.seen_signatures) for w in self._workers]

    def decode_compile_records(self):
        """Decode mode: the compile records of every replica's step and
        chunk executables (:meth:`DecodeBatcher.compile_records`)."""
        return [r for d in self._decoders or () for r in d.compile_records()]

    def shutdown(self, drain=True, timeout_s=None):
        """Stop intake; with ``drain`` serve everything queued, otherwise
        cancel it. Joins the worker threads (warning on any that outlive
        ``timeout_s``); requests still queued after the join — a dead or
        stuck replica raced a full batcher — fail with
        :class:`EngineShutdownError` and return their admission slots
        rather than leaking callers' futures. Idempotent."""
        # _closed flips under _lifecycle_lock: once we hold it, no
        # in-progress _maybe_respawn can still spawn a thread, and none
        # started after this point will — every worker thread the join
        # sweep below must reap already exists
        with self._lifecycle_lock:
            self._closed = True
            if self._shutdown_done:
                return
            self._shutdown_done = True
        self._stop_event.set()
        if self._decoders is not None:
            for d in self._decoders:
                d.shutdown(drain=drain, timeout_s=timeout_s)
            return
        if self._supervisor is not None:
            self._supervisor.join(timeout_s if timeout_s is not None
                                  else 5.0)
        if not drain:
            for r in self._batcher.drain():
                if r.future.cancel():
                    self.metrics_.observe_expired()
                else:
                    self.metrics_.observe_failed()
                self._admission.release(r.n)
        self._batcher.close()
        for w in self._workers:
            if w.thread is not None:
                w.thread.join(timeout_s)
                if w.thread.is_alive():
                    warnings.warn(
                        "ServingEngine.shutdown: replica %d (%s) still "
                        "busy after %.1fs join timeout; its in-flight "
                        "batch is abandoned to the daemon thread"
                        % (w.index, w.thread.name, timeout_s or 0.0),
                        RuntimeWarning, stacklevel=2)
        # drain=True normally empties the queue through the workers; if
        # one died/stuck with requests still queued, fail them loudly and
        # give their admission capacity back
        for r in self._batcher.drain():
            self._fail(r, EngineShutdownError(
                "ServingEngine shut down before this request was served"))
            self.metrics_.observe_failed()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown(drain=exc[0] is None)

    # -- worker side --------------------------------------------------------
    @staticmethod
    def _signature(feed):
        return tuple(sorted((k, a.shape, str(a.dtype))
                            for k, a in feed.items()))

    def _synthesize_example(self):
        return self._synthesize_example_for(self._workers[0].predictor)

    def _synthesize_example_for(self, predictor):
        """Build a 1-example feed from the program's var metadata (program
        path only — the StableHLO manifest doesn't carry shapes)."""
        prog = getattr(predictor, "_program", None)
        if prog is None or not self.feed_names:
            raise ValueError("warmup() needs example_feed for this "
                             "predictor type")
        feed = {}
        for name in self.feed_names:
            var = prog.global_block().var(name)
            shape = [1 if (d is None or d < 0) else int(d)
                     for d in (var.shape or (1,))]
            shape[0] = 1
            dtype = np.dtype(var.dtype or "float32")
            if dtype.kind in "iu":
                feed[name] = np.zeros(shape, dtype=dtype)
            else:
                feed[name] = np.full(shape, 0.5, dtype=dtype)
        return feed

    def _spawn_worker_thread(self, worker):
        worker.thread = threading.Thread(
            target=self._worker_loop, args=(worker,),
            name="paddle-tpu-serve-%d" % worker.index, daemon=True)
        worker.thread.start()

    def _maybe_respawn(self, w):
        """Respawn ``w``'s dead thread — unless shutdown has begun. The
        ``_closed`` check and the spawn are one atomic step under
        ``_lifecycle_lock``: without it the supervisor could pass the
        check, lose the CPU to ``shutdown()``'s join sweep, then spawn a
        thread nobody will ever join — parked forever on a closed
        batcher. Returns True iff a thread was actually spawned."""
        with self._lifecycle_lock:
            if self._closed:
                return False
            if w.thread is not None and not w.thread.is_alive():
                self._spawn_worker_thread(w)
                self.metrics_.observe_respawned()
                flight.record("thread.respawn", where="engine", replica=w.index)
                return True
        return False

    def _supervisor_loop(self, interval_s):
        """Self-healing sweep: a worker thread that died outright (an
        escape below the batch-level containment) is respawned; its
        replica state (predictor, breaker) carries over — the breaker
        still evicts if the predictor itself is the problem."""
        while not self._stop_event.wait(interval_s):
            if self._closed:
                return
            for w in self._workers:
                if self._closed:
                    return
                self._maybe_respawn(w)

    def _worker_loop(self, worker):
        while True:
            # deterministic thread-death drills land here, BEFORE a batch
            # is claimed: a killed worker never strands futures
            try:
                faults.trip("serving.worker")
            except faults.InjectedFault:
                return  # die quietly; the supervisor's sweep respawns us
            batch = self._batcher.get_batch()
            if batch is None:
                return
            try:
                self._serve_batch(worker, batch)
            except BaseException:
                # _serve_batch already failed the batch's futures; a throw
                # reaching here (e.g. from metrics accounting) must not
                # take the replica down with it
                pass

    def _rebuild_replica(self, worker):
        """Evict a repeatedly-failing replica and rebuild it from the
        parent predictor (weights shared; per-replica executor state —
        the likely contaminant — is fresh)."""
        try:
            fresh = self._parent.clone()
        except Exception as e:
            # keep the old predictor; the breaker re-arms so another
            # failure_threshold failures trigger the next rebuild attempt
            # (not counted as an eviction — nothing was rebuilt)
            warnings.warn(
                "replica %d eviction: rebuild clone() failed (%r); "
                "keeping the old predictor and re-arming the breaker"
                % (worker.index, e), RuntimeWarning)
        else:
            worker.predictor = fresh
            worker.seen_signatures = set()
            self.metrics_.observe_evicted()
            flight.record("replica.evict", where="engine", replica=worker.index)
        worker.breaker.reset()

    # -- live hot-swap (the streaming publish plane) -------------------------
    def reload(self, source, version=None):
        """Hot-swap model parameters into every live replica WITHOUT
        stopping serving — the streaming publish plane's engine verb.

        ``source`` is a checkpoint directory (``checkpoint.load_staged``
        stages the newest intact — or the given ``version`` — with CRC
        verification and fallback past corrupt versions) or an
        already-staged ``[(name, array), ...]`` update list.

        Swap mechanics: each distinct predictor scope is COPIED into a
        fresh scope with the updated parameters overlaid (placement
        preserved — pinned/sharded arrays are ``device_put`` with the old
        array's sharding), then a single reference assignment flips each
        replica to it. A replica's in-flight micro-batch already read the
        old scope reference and finishes on the old weights; its next
        batch reads the new one — no request is dropped, no lock is held
        across a predictor call. Compiled step caches stay warm (shapes
        and dtypes are unchanged).

        Returns the version served. Raises on a model/checkpoint mismatch
        (no staged name present in any replica scope); decode-mode
        engines do not support reload."""
        if self._decoders is not None:
            raise NotImplementedError(
                "reload() is not supported in decode mode: KV caches are "
                "conversation state entangled with the weights")
        if self._closed:
            raise RuntimeError("engine is shut down")
        if isinstance(source, str):
            from .. import checkpoint

            prog = getattr(self._parent, "_program", None)
            if prog is None:
                raise TypeError(
                    "reload from a checkpoint dir needs a program-backed "
                    "predictor; got %r" % (type(self._parent).__name__,))
            version, updates, _extra = checkpoint.load_staged(
                source, prog, version=version)
        else:
            updates = list(source)
        sp = trace.span("model.swap")
        with sp:
            if sp:
                sp.set(version=version, replicas=len(self._workers))
            applied = self._swap_scopes(updates)
        self.serve_version = version
        self.swap_count += 1
        flight.record("model.swap", version=version, applied=applied,
                      replicas=len(self._workers), swap=self.swap_count)
        return version

    def prepare(self, source, version=None):
        """Phase 1 of the two-phase fleet swap: CRC-stage a version
        WITHOUT touching the served weights. The staged update list is
        held until :meth:`commit` applies it or :meth:`abort_swap` drops
        it; re-preparing replaces the staged version. Serving continues
        on the old weights throughout — an aborted prepare leaves no
        trace. Fault site ``swap.prepare``: ``error``/``corrupt`` fail
        the stage (the fleet publisher must then abort everywhere).
        Returns the staged version."""
        mode = faults.trip("swap.prepare")
        if self._decoders is not None:
            raise NotImplementedError(
                "prepare() is not supported in decode mode: KV caches "
                "are conversation state entangled with the weights")
        if self._closed:
            raise RuntimeError("engine is shut down")
        if isinstance(source, str):
            from .. import checkpoint

            prog = getattr(self._parent, "_program", None)
            if prog is None:
                raise TypeError(
                    "prepare from a checkpoint dir needs a program-backed "
                    "predictor; got %r" % (type(self._parent).__name__,))
            version, updates, _extra = checkpoint.load_staged(
                source, prog, version=version)
        else:
            updates = list(source)
        if mode == "corrupt":
            raise IOError("swap.prepare: staged bytes corrupt (injected)")
        self._staged_swap = (version, updates)
        flight.record("swap.prepare", version=version,
                      staged=len(updates))
        return version

    def commit(self, version=None):
        """Phase 2: atomically swap the prepared version in. Idempotent
        under retry — committing a ``version`` that already serves
        returns success, so a fleet publisher's RetryPolicy can re-drive
        a commit whose ACK was lost. Raises when nothing (or a different
        version) is staged. Fault site ``swap.commit`` drills the
        partial-commit / quarantine path."""
        faults.trip("swap.commit")
        staged = self._staged_swap
        if staged is None:
            if version is not None and self.serve_version == version:
                return version  # lost-ACK retry of a landed commit
            raise RuntimeError(
                "commit(%r): no staged version (prepare first)"
                % (version,))
        sv, updates = staged
        if version is not None and sv != version:
            raise RuntimeError(
                "commit(%r): staged version is %r" % (version, sv))
        sp = trace.span("model.swap")
        with sp:
            if sp:
                sp.set(version=sv, replicas=len(self._workers))
            applied = self._swap_scopes(updates)
        self._staged_swap = None
        self.serve_version = sv
        self.swap_count += 1
        flight.record("swap.commit", version=sv, applied=applied,
                      replicas=len(self._workers), swap=self.swap_count)
        return sv

    def abort_swap(self):
        """Drop a staged-but-uncommitted version (any target failing
        prepare aborts the whole fleet — nothing swaps). Returns True
        when something was staged."""
        staged, self._staged_swap = self._staged_swap, None
        if staged is not None:
            flight.record("swap.abort", version=staged[0])
        return staged is not None

    def _swap_scopes(self, updates):
        """Copy-and-overlay every distinct predictor scope, then flip the
        references. Returns the number of parameter names applied."""
        import jax

        from ..core.executor import Scope

        def overlay(old_get, names):
            hits = {}
            for name, val in updates:
                if name.startswith("@") or name not in names:
                    continue  # RNG stream / optimizer-only state
                ref = old_get(name)
                if isinstance(ref, jax.Array):
                    val = jax.device_put(val, ref.sharding)
                hits[name] = val
            return hits

        with self._lifecycle_lock:  # no respawn/rebuild mid-swap
            preds = {id(self._parent): self._parent}
            for w in self._workers:
                preds.setdefault(id(w.predictor), w.predictor)
            staged = {}  # id(old scope/state) -> (new scope/state, hits)
            applied = 0
            for pred in preds.values():
                if hasattr(pred, "_scope"):
                    old = pred._scope
                    if id(old) not in staged:
                        names = set(old.var_names())
                        hits = overlay(old.get, names)
                        fresh = Scope()
                        for n in names:
                            fresh.set(n, hits.get(n, old.get(n)))
                        staged[id(old)] = (fresh, len(hits))
                elif hasattr(pred, "_state"):  # StableHLOPredictor
                    old = pred._state
                    if id(old) not in staged:
                        hits = overlay(old.__getitem__, set(old))
                        fresh = dict(old)
                        fresh.update(hits)
                        staged[id(old)] = (fresh, len(hits))
                else:
                    continue
            if not any(n for _, n in staged.values()):
                raise ValueError(
                    "reload: no staged parameter matches any replica "
                    "scope — wrong checkpoint for this model?")
            for pred in preds.values():
                if hasattr(pred, "_scope"):
                    fresh, n = staged[id(pred._scope)]
                    pred._scope = fresh  # atomic: in-flight runs hold old
                    applied = max(applied, n)
                elif hasattr(pred, "_state"):
                    fresh, n = staged[id(pred._state)]
                    pred._state = fresh
                    applied = max(applied, n)
        return applied

    def _serve_batch(self, worker, batch):
        now = self._batcher.now()
        live = []
        for r in batch:
            if r.future.cancelled():
                self._admission.release(r.n)
                continue
            if r.deadline is not None and now > r.deadline:
                self._fail(r, DeadlineExceededError(
                    "request waited %.1f ms, deadline was %.1f ms"
                    % ((now - r.enqueue_t) * 1e3,
                       (r.deadline - r.enqueue_t) * 1e3)))
                self.metrics_.observe_expired()
                continue
            live.append(r)
        if not live:
            return
        # phase 1 — batch assembly. Failures here (disagreeing scalar
        # feeds, bucket violations) are REQUEST-CONTENT errors: the
        # replica is healthy and a retry can only repeat them, so they
        # fail the batch without touching the breaker or the retry budget
        try:
            if len(live) == 1:
                merged = live[0].feed
            else:
                merged = {}
                for k in live[0].feed:
                    vals = [r.feed[k] for r in live]
                    if vals[0].ndim == 0:
                        # scalar feeds (temperature etc.) have no batch dim
                        # to concatenate on; they can share one XLA call
                        # only when every request agrees on the value
                        if any(not np.array_equal(v, vals[0])
                               for v in vals[1:]):
                            raise ValueError(
                                "scalar feed %r differs across batched "
                                "requests; scalars must be equal to "
                                "coalesce" % k)
                        merged[k] = vals[0]
                    else:
                        if self.seq_ladder and vals[0].ndim >= 2:
                            # different seq lengths in one micro-batch:
                            # pad each rider to the rung covering the
                            # longest before the rows can concatenate
                            tgt = bucket_for(
                                max(v.shape[1] for v in vals),
                                self.seq_ladder)
                            vals = [edge_pad(v, tgt, 1) for v in vals]
                        merged[k] = np.concatenate(vals, axis=0)
            padded, n = pad_to_bucket(merged, self.ladder,
                                      seq_ladder=self.seq_ladder)
            rung = bucket_for(n, self.ladder)
        except Exception as e:
            for r in live:
                self._fail(r, e)
            self.metrics_.observe_failed(len(live))
            return
        # phase 2 — dispatch. Failures here are REPLICA faults: they
        # count on the breaker (evict+rebuild on trip) and the batch's
        # requests get their one cross-replica retry
        # parent the batch span onto the first live request's trace so a
        # propagated router trace stitches through the queue hand-off; the
        # predictor.run below reaches Executor.run on this same thread, so
        # the executor span nests here by ambient context
        sp = trace.span("engine.batch",
                        parent=next((r.trace_ctx for r in live
                                     if r.trace_ctx is not None), None))
        try:
            sig = self._signature(padded)
            hit = sig in worker.seen_signatures
            worker.seen_signatures.add(sig)
            faults.trip("predictor.run")
            with sp:
                if sp:  # tags must land before the span closes
                    sp.set(n=n, rung=rung, requests=len(live),
                           replica=worker.index)
                outs = worker.predictor.run(padded)
            outs = unpad_fetch(outs, n, padded_to=rung)
        except Exception as e:
            # fail only this batch; the replica (and its clone-shared
            # weights) keeps serving — unless its breaker says the
            # replica itself is the pattern, in which case evict+rebuild
            if (self.max_replica_failures
                    and worker.breaker.record_failure()):
                self._rebuild_replica(worker)
            self._dispose_failed(live, e)
            return
        worker.breaker.record_success()
        self.metrics_.observe_batch(actual=n, bucket=rung, cache_hit=hit)
        done_t = self._batcher.now()
        off = 0
        for r in live:
            rows = [o[off:off + r.n]
                    if (getattr(o, "ndim", 0) >= 1 and o.shape[0] == n)
                    else o for o in outs]
            off += r.n
            try:
                r.future.set_result(rows)
            except Exception:
                pass  # racing cancel; capacity still returns below
            self.metrics_.observe_completed(done_t - r.enqueue_t)
            self._admission.release(r.n)

    def _dispose_failed(self, live, exc):
        """A batch's ``predictor.run`` threw: requests that still have a
        retry budget re-enqueue for another replica to pick up (inference
        is idempotent — ``donate_state=False`` keeps clones read-only, so
        a replay cannot double-apply anything); the rest fail with the
        batch's exception. Retried requests KEEP their admission slot —
        they never left the system."""
        retry = []
        for r in live:
            if (self.cross_replica_retry and r.retries < 1
                    and not self._closed):
                r.retries += 1
                retry.append(r)
            else:
                self._fail(r, exc)
                self.metrics_.observe_failed()
        for r in retry:
            try:
                self._batcher.put(r)
            except RuntimeError:  # racing shutdown: no second chance left
                self._fail(r, exc)
                self.metrics_.observe_failed()
                continue
            self.metrics_.observe_retried()

    def _fail(self, req, exc):
        try:
            req.future.set_exception(exc)
        except Exception:
            pass
        self._admission.release(req.n)
