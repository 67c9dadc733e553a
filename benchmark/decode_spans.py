"""The decode loop's own account of a quantum, read out of a serving run.

Since PR 37 ``DecodeBatcher`` opens, beside ``decode.step`` and
``prefill.chunk``: ``decode.idle`` (the loop's wait while no request is
live), ``decode.admit`` and ``decode.plan`` between two quanta, and inside a
quantum ``decode.feed``, ``executor.run`` (unchanged), ``decode.fetch`` (the
wait for the logits and their way to the host) and ``decode.sample``; its
metrics count ``admitted``, ``queue_wait_seconds``, ``prefill_lanes`` and
``idle_seconds``.
The readers under ``layer_metrics/`` that read these are one line each over
the functions here, which are plain Python on rows (``serve_trace``'s host
rows, ``trace_reduce``'s busy intervals) and are tested on
``tests/benchmark/recorded_decode_spans.json``.

A program without these spans and counters (the parent of PR 37) gives
``None`` everywhere: the metric is then left out of the line.

The fifteen readers' entries are in ``BENCHMARK.json`` since PR 39. The
rule that splits a device's idle time by span is ``program_spans``'s
(``idle_by_span``): one reader for training and serving traces.
"""

from benchmark import serve_trace
from benchmark.program_spans import NO_SPAN, idle_by_span

PREFIX = serve_trace.PREFIX
STEP, CHUNK = serve_trace.STEP, serve_trace.CHUNK
QUANTA = (STEP, CHUNK, "spec.verify")
IDLE, ADMIT, PLAN = "decode.idle", "decode.admit", "decode.plan"
FETCH = "decode.fetch"


def accounts(host):
    """Whether the traced loop accounts for its quanta: it opens
    ``decode.admit`` before every one of them."""
    return any(name == PREFIX + ADMIT for name, _, _ in host)


def admit_plan_ms(trace):
    """Milliseconds of the ``decode.admit`` and ``decode.plan`` spans that
    lie between the median ``decode.step`` span and the quantum before it:
    what the loop did between two quanta on the way to that step."""
    at = trace.median_span(STEP)
    if at is None or not accounts(trace.host):
        return None
    before = [e for q in QUANTA
              for _, e in serve_trace.spans_named(trace.host, q)
              if e <= at[0]]
    since = max(before) if before else float("-inf")
    return sum(e - s for name in (ADMIT, PLAN)
               for s, e in serve_trace.spans_named(trace.host, name)
               if since <= s and e <= at[0]) / 1e6


def chunk_wait_ms(trace):
    """Of the ``decode.fetch`` inside the median ``decode.step`` span, the
    milliseconds during which the first chip ran a chunk executable: a
    ``prefill.chunk`` span ends at its dispatch, so the step that follows
    waits for the chunk's run before its own; that part of the step's span
    is another quantum's device time. 0.0 where the median step follows no
    chunk; None without a device trace or without the span."""
    at = trace.median_span(STEP)
    runs = (getattr(trace, "quanta", None) or {}).get(CHUNK)
    if at is None or runs is None:
        return None
    fetches = [(s, e) for s, e in serve_trace.spans_named(trace.host, FETCH)
               if at[0] <= s and e <= at[1]]
    if not fetches:
        return None
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for s, e in fetches for lo, hi in runs) / 1e6


def accounted(host, busy):
    """``busy`` cut to the stretch of the trace in which every span of the
    loop is on record: from the first ``decode.admit`` or ``decode.idle`` to
    the end of the last quantum span. A span that was open when the
    profiler started, or still is when it stops, is not recorded, so the
    idle time of the quantum under way at either edge would read as under
    no span (2.2-2.4 ms of a trace's 60-300: my chip runs, PR 37). Where
    an edge cuts a gap, it stands as a busy instant and the gap keeps its
    inner part."""
    lo = max(busy[0][0], min(s for name in (ADMIT, IDLE)
                             for s, _ in serve_trace.spans_named(host, name)))
    hi = min(busy[-1][1], max((e for q in QUANTA for _, e in
                               serve_trace.spans_named(host, q)), default=lo))
    inner = [(max(s, lo), min(e, hi)) for s, e in busy if e > lo and s < hi]
    return [(lo, lo)] + inner + [(hi, hi)]


def idle_split(trace):
    """(seconds of the first chip's idle time under ``decode.idle``, under
    any other span of the program, under none) over the gaps between the
    device operations of the stretch the loop accounts for
    (:func:`accounted`); None without a device trace or where the loop does
    not account for itself."""
    busy = getattr(trace, "busy", None)
    if not busy or not busy[0] or not accounts(trace.host):
        return None
    by_span = idle_by_span(trace.host, accounted(trace.host, busy[0]))
    waiting = by_span.get(PREFIX + IDLE, 0.0)
    unspanned = by_span.get(NO_SPAN, 0.0)
    return waiting, sum(by_span.values()) - waiting - unspanned, unspanned


def idle_host_ms(trace):
    """The first chip's idle milliseconds a quantum while the program was
    inside a span other than ``decode.idle``: the host holding the chip
    back with a request there to serve."""
    split = idle_split(trace)
    quanta = sum(trace.count(q) for q in QUANTA)
    if split is None or not quanta:
        return None
    return split[1] / quanta * 1e3


def idle_unspanned_pct(trace):
    """The share of the first chip's idle time under no span of the
    program: what the instrumentation cannot name."""
    split = idle_split(trace)
    if split is None or not sum(split):
        return None
    return 100.0 * split[2] / sum(split)


def counter_ratio(ctx, over, under):
    """Δ``over`` / Δ``under`` of the engine's counters between the window's
    two edges; None where the program keeps no such counter or it stood."""
    before, after = ctx["window_counters"]
    if over not in after or under not in after:
        return None
    moved = after[under] - before.get(under, 0.0)
    if moved <= 0:
        return None
    return (after[over] - before.get(over, 0.0)) / moved


def idle_no_request_pct(ctx):
    """The share of the timed window the decode loop spent waiting with no
    request live or queued: Δ``idle_seconds`` of the engine's counters over
    the window's length. The counter moves when a wait ends, so a wait
    under way at either edge is counted whole or not at all: at most one
    arrival gap in ten seconds. (The ``decode.idle`` span says the same of
    a trace, but a 1.5 s trace holds few whole waits and its device window
    ends at the last device operation, where the waits are.) None where the
    program keeps no such counter."""
    before, after = ctx["window_counters"]
    if "idle_seconds" not in after:
        return None
    opened, closed = ctx["window"]
    return 100.0 * (after["idle_seconds"]
                    - before.get("idle_seconds", 0.0)) / (closed - opened)


def cache_alias_pct(records):
    """Of the bytes of feeds the executables were handed over
    (``donated_feed_bytes`` of each compile record), the share their
    compiler aliased to an output (``memory["alias_bytes"]``). Under 100:
    some executable copies a cache it was given. A serving predictor hands
    over its caches and nothing else (``donate_state=False``), so there the
    two count the same arrays; an executable that also aliases state of its
    own reads over 100 here, and that says the numerator counts more than
    the caches: it is not cut off. None where nothing is handed over or the
    records say nothing of it."""
    handed = aliased = 0
    for record in records or ():
        given = record.get("donated_feed_bytes") or 0
        memory = record.get("memory") or {}
        if given and "alias_bytes" in memory:
            handed += given
            aliased += memory["alias_bytes"]
    return 100.0 * aliased / handed if handed else None
