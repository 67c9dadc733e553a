"""``paddle_tpu.obs`` — the stdlib-only telemetry plane (ISSUE 17).

Three pillars, each importable on its own and none touching jax (so the
serving router, the workers, and the tools can all load them in under a
millisecond):

  * :mod:`~paddle_tpu.obs.trace` — Dapper-style request tracing:
    context-manager spans with thread-local context, 64-bit trace/span
    ids, an injectable clock (the ``reliability/policy.py`` fake-clock
    discipline), cross-process propagation through the ``serving/rpc.py``
    frame header, and Perfetto/chrome-trace export. One
    ``RouterClient.predict`` yields ONE stitched trace spanning the
    router door, the dispatch hop, the worker queue, the engine
    micro-batch, and ``Executor.run`` with its host phases
    (``executor.prepare`` / ``feed_put`` / ``dispatch`` / ``writeback``).
    The same spans are the program's own on any ``jax.profiler`` trace:
    ``paddle_tpu/profiler.py`` installs the ``TraceAnnotation`` sink, so
    this package still imports nothing of JAX.
  * :mod:`~paddle_tpu.obs.registry` — named Counter/Gauge/Histogram
    primitives with Prometheus-text exposition, unifying
    ``ServingMetrics``' ad-hoc counters.
  * :mod:`~paddle_tpu.obs.flight` — a bounded ring buffer of
    reliability events (fault-site decisions, breaker transitions,
    respawns, EDF displacements, deadline refusals, per-request
    outcomes), dumped as JSON on unhandled crash, SIGUSR2, and
    shutdown; ``tools/chaos_router.py`` audits the dump against its
    accepted-request ledger.

The disabled hot path costs zero allocations: ``trace.span(...)``
returns a module singleton when no tracer is active (the
``faults.trip`` fast-path pattern), and ``flight.record`` appends one
dict to a bounded deque — nothing grows without bound anywhere here.
"""

from . import flight, registry, trace  # noqa: F401

__all__ = ["trace", "registry", "flight"]
