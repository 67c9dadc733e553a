"""The program's own account of a run: its host spans in the profiler's
trace, and the record its executor keeps of each compile.

Spans. While a ``jax.profiler`` trace is taken, every span the program opens
(``paddle_tpu/obs/trace.py``: ``Executor.run`` and its phases ``prepare``,
``feed_put``, ``dispatch``, ``writeback``; ``trace``, ``lower``,
``backend_compile`` when a variant is staged) is also an event
``paddle_tpu.<span>`` on the host plane of that trace, on the clock the
device events are on. ``trace_reduce.load`` keeps only the benchmark's own
``bench.*`` annotations, so this file reads the run's xplane for the
``paddle_tpu.*`` ones (``load``) and reduces plain rows (``ProgramSpans``:
the phases of the median call, and the first device's idle gaps put down to
the innermost span that covers them), tested on ``tests/benchmark/recorded_program_spans.json``.

Compile record. ``Executor.compile_records`` holds one dict a compiled
variant: seconds of tracing, lowering and backend compile, whether the
persistent cache served the executable, its ``memory_analysis()``, the
gates' decisions. ``compile_record`` finds the training step's; a serving
job hands the records of all its executables as ``ctx["compile_records"]``.

A program without these (the parent of the PR that brought them) gives
``None`` everywhere: the metric is then left out of the line.
"""

import glob
import json
import os
import statistics

PREFIX = "paddle_tpu."
NO_SPAN = "(no program span)"


def load(xplane_path):
    """[(name, start_ns, duration_ns)] of the ``paddle_tpu.*`` events on the
    host planes of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    rows.append((ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)))
    return rows


def innermost(rows):
    """Sorted, disjoint [(start, end, name)]: at every instant that some
    span of ``rows`` covers, the innermost one (the shortest, where several
    do)."""
    edges = sorted({t for _, s, d in rows for t in (s, s + d)})
    opening = sorted(((s, d, name) for name, s, d in rows), reverse=True)
    active, out = [], []
    for lo, hi in zip(edges, edges[1:]):
        while opening and opening[-1][0] <= lo:
            s, d, name = opening.pop()
            active.append((d, name, s + d))
        active = [a for a in active if a[2] > lo]
        if active:
            out.append((lo, hi, min(active)[1]))
    return out


def idle_by_span(rows, busy):
    """{span name: idle seconds of one device under it}. ``rows``: host rows
    (name, start_ns, duration_ns); ``busy``: the device's merged, sorted
    [start, end) busy intervals. Every nanosecond of every gap between two
    busy intervals goes to the innermost ``paddle_tpu.*`` span that covers
    it, or to ``NO_SPAN``. One sweep over spans and gaps together: a
    serving trace holds a hundred quanta and as many gaps as device events
    (0.08 s there, where a pass over every row for every gap took 1.65-3 s:
    my chip runs, PR 37)."""
    segments = innermost([r for r in rows if r[0].startswith(PREFIX)])
    out, k = {}, 0
    for (_, lo), (hi, _) in zip(busy, busy[1:]):
        if hi <= lo:
            continue
        while k < len(segments) and segments[k][1] <= lo:
            k += 1
        covered, j = 0.0, k
        while j < len(segments) and segments[j][0] < hi:
            a, b, name = segments[j]
            part = min(b, hi) - max(a, lo)
            out[name] = out.get(name, 0.0) + part / 1e9
            covered += part
            j += 1
        if hi - lo > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (hi - lo - covered) / 1e9
    return out


class ProgramSpans:
    """``rows``: host rows as ``load`` gives them. ``busy``: merged, sorted
    [start, end) intervals in which an operation runs on the first device
    (``trace_reduce.Trace.busy[0]``), or None where there is no device
    trace. ``steps``: how many steps the traced window holds."""

    def __init__(self, rows, busy=None, steps=1):
        self.rows = list(rows)
        self.busy = busy
        self.steps = steps

    def durations_ms(self, span):
        return [dur / 1e6 for name, _, dur in self.rows
                if name == PREFIX + span]

    def median_ms(self, span):
        """Median milliseconds of the events of one span; None if the trace
        holds none."""
        found = self.durations_ms(span)
        return statistics.median(found) if found else None

    def median_run(self):
        """(start, end) of the ``executor.run`` event whose duration is the
        median one (the lower middle of an even number); None if the trace
        holds none."""
        runs = sorted((dur, start) for name, start, dur in self.rows
                      if name == PREFIX + "executor.run")
        if not runs:
            return None
        dur, start = runs[(len(runs) - 1) // 2]
        return start, start + dur

    def phase_ms(self, span):
        """Milliseconds of ``span`` inside the median ``executor.run`` of the
        trace: the phases of one and the same call, so that they add up to
        that call (medians taken phase by phase over five steps do not: each
        would come from another step). None if the trace holds no such run
        or the run no such span."""
        run = self.median_run()
        if run is None:
            return None
        found = [dur for name, start, dur in self.rows
                 if name == PREFIX + span and run[0] <= start
                 and start + dur <= run[1]]
        return sum(found) / 1e6 if found else None

    def idle_gaps(self):
        """[start, end) between consecutive busy intervals of the first
        device: inside the traced window, no operation running."""
        if not self.busy:
            return []
        return [[end, start] for (_, end), (start, _)
                in zip(self.busy, self.busy[1:]) if start > end]

    def idle_by_span(self):
        """{span name: idle seconds of the first device under it}
        (:func:`idle_by_span` over this trace's rows and busy intervals).
        None without a device trace."""
        if self.busy is None:
            return None
        return idle_by_span(self.rows, self.busy)

    def idle_ms_a_step_under(self, prefix):
        """First-device idle milliseconds a step that fall inside a span
        whose name starts with ``prefix``. None without a device trace or
        without any program span in the trace."""
        if self.busy is None or not self.rows:
            return None
        by_span = self.idle_by_span()
        return sum(v for k, v in by_span.items()
                   if k.startswith(PREFIX + prefix)) / self.steps * 1e3


def newest_xplane(directory):
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


def of(ctx):
    """The ``ProgramSpans`` of a traced run, read once a run from the
    xplane the ``train`` job wrote under ``benchmark_out/trace`` and kept
    in ``ctx``."""
    if "program_spans" not in ctx:
        xplane = newest_xplane(ctx["run"].path("benchmark_out", "trace"))
        trace = ctx.get("trace")
        busy = getattr(trace, "busy", None)
        ctx["program_spans"] = ProgramSpans(
            load(xplane) if xplane else [], busy[0] if busy else None,
            getattr(trace, "steps", 1))
    return ctx["program_spans"]


def phase_ms(ctx, span):
    return of(ctx).phase_ms(span)


def compile_record(ctx):
    """The compile record of the training step's variant (the one that
    fetches the loss; the first, should a later call have staged it again),
    or None where the executor keeps no records."""
    trainer = ctx.get("trainer")
    records = getattr(getattr(trainer, "exe", None), "compile_records", None)
    if not records:
        return None
    if not ctx.get("compile_records_printed"):
        ctx["compile_records_printed"] = True
        for r in records:  # the run's log keeps every variant's record
            print("compile record  " + json.dumps(
                {k: (round(v, 3) if isinstance(v, float) else v)
                 for k, v in r.items() if k != "feed_names"},
                sort_keys=True))
    loss = trainer.loss.name
    for record in records:
        if loss in record.get("fetch_names", ()):
            return record
    return None


def compile_seconds(ctx, phase):
    """Seconds of one phase (``trace_s``, ``lower_s``,
    ``backend_compile_s``) of the training step's compile, or, where a job
    hands its own ``compile_records`` (serving: every executable of the
    ladders), of them all together; on the chip. A rehearsal's are the CPU
    backend's and are not reported."""
    if ctx["run"].devices[0].platform != "tpu":
        return None
    if "compile_records" in ctx:
        seconds = [r[phase] for r in ctx["compile_records"] if phase in r]
        return sum(seconds) if seconds else None
    record = compile_record(ctx)
    return None if record is None else record.get(phase)
