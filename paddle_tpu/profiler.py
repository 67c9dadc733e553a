"""Profiler (ref ``python/paddle/fluid/profiler.py`` +
``platform/profiler.h`` + CUPTI ``device_tracer.h`` + ``tools/timeline.py``).

TPU-native: jax.profiler XPlane traces (viewable in TensorBoard/Perfetto —
the chrome-trace parity) + a lightweight host-event aggregator giving the
reference's sorted-table report.

This is the module that gives the program's spans (``obs.trace.span``:
``Executor.run``'s phases, the serving path, ``record_event``) their second
sink: while a ``jax.profiler`` trace is being taken, by whoever started it,
each span is also a ``jax.profiler.TraceAnnotation`` named
``paddle_tpu.<span>`` on the host plane of that trace, on the clock the
device events are on."""

import bisect
import contextlib
import threading
from collections import defaultdict, deque

import jax

from .obs import trace as obs_trace

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "record_event", "Histogram"]

_events = defaultdict(lambda: [0.0, 0])  # name -> [total_s, count]
# serving threads record events concurrently with a sampling stop/report
# on another thread; every _events touch goes through this lock
_events_lock = threading.Lock()
_trace_dir = None
_enabled = False

SPAN_PREFIX = "paddle_tpu."


def _annotation(name):
    """The span primitive's profiler sink: None while no trace is taken
    (one C++ flag read), else the annotation to enter."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
    return None


obs_trace.install_annotator(_annotation)


def start_profiler(state="All", trace_dir=None):
    global _enabled, _trace_dir
    _enabled = True
    _trace_dir = trace_dir
    if trace_dir:
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key="total", profile_path=None, silent=False):
    """``silent=True`` returns the report without printing — for callers
    (e.g. the serving metrics loop) that sample the profiler on a cadence
    and must not spam stdout."""
    global _enabled
    _enabled = False
    if _trace_dir:
        jax.profiler.stop_trace()
    report = _report(sorted_key)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(report)
    elif not silent:
        print(report)
    return report


def reset_profiler():
    with _events_lock:
        _events.clear()


def _report(sorted_key="total"):
    lines = ["%-40s %10s %12s %12s" % ("Event", "Calls", "Total(ms)",
                                       "Avg(ms)")]
    with _events_lock:
        items = [(name, list(v)) for name, v in _events.items()]
    if sorted_key == "total":
        items.sort(key=lambda kv: -kv[1][0])
    elif sorted_key == "calls":
        items.sort(key=lambda kv: -kv[1][1])
    for name, (total, count) in items:
        lines.append("%-40s %10d %12.3f %12.3f"
                     % (name, count, total * 1e3,
                        total * 1e3 / max(count, 1)))
    return "\n".join(lines)


@contextlib.contextmanager
def record_event(name):
    """RAII host event (ref ``RecordEvent`` ``profiler.h:41``): a span of
    the program like any other (``obs.trace.span``: the tracer, the
    profiler's trace), whose duration also feeds the sorted-table report
    while the profiler is on; also opens a jax.named_scope so the device
    trace carries the same label."""
    sp = obs_trace.span(name, timed=_enabled)
    try:
        with sp, jax.named_scope(name.replace("/", "_")):
            yield
    finally:
        if _enabled and sp:
            with _events_lock:
                ev = _events[name]
                ev[0] += sp.duration
                ev[1] += 1


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None,
             trace_dir=None):
    start_profiler(state, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class Histogram:
    """Thread-safe sliding-window sample store with exact percentiles.

    The host-event table above aggregates to (total, count) — enough for a
    training report, useless for a latency SLO, where the tail IS the
    metric. This keeps the most recent ``max_samples`` raw observations
    (a sliding window, so a long-running server reports *current* tail
    behavior, not its lifetime average) and computes exact
    nearest-rank percentiles on demand.
    """

    def __init__(self, max_samples=8192):
        self._samples = deque(maxlen=max_samples)
        self._lock = threading.Lock()
        self._count = 0
        self._total = 0.0

    def add(self, value):
        with self._lock:
            self._samples.append(float(value))
            self._count += 1
            self._total += float(value)

    @property
    def count(self):
        """Lifetime observation count (not capped by the window)."""
        return self._count

    @property
    def total(self):
        return self._total

    @staticmethod
    def _at_rank(data, p):
        """Nearest-rank percentile over an already-sorted sample list."""
        if not 0 <= p <= 100:
            raise ValueError("percentile must be in [0, 100], got %r" % p)
        rank = max(0, min(len(data) - 1,
                          int(round(p / 100.0 * (len(data) - 1)))))
        return data[rank]

    def percentile(self, p):
        """Nearest-rank percentile of the current window; None if empty."""
        with self._lock:
            data = sorted(self._samples)
        return self._at_rank(data, p) if data else None

    def percentiles(self, ps=(50, 95, 99)):
        with self._lock:
            data = sorted(self._samples)
        return {"p%g" % p: (self._at_rank(data, p) if data else None)
                for p in ps}

    def cdf(self, value):
        """Fraction of windowed samples <= value (SLO attainment check)."""
        with self._lock:
            data = sorted(self._samples)
        if not data:
            return None
        return bisect.bisect_right(data, value) / float(len(data))
