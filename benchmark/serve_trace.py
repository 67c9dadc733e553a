"""A serving run's profiler trace, reduced.

The decode loop opens one program span a scheduler quantum
(``paddle_tpu.decode.step``: one token a live slot; ``paddle_tpu.
prefill.chunk``: a chunk of prompt tokens a row), and ``Executor.run`` its
own inside them (``paddle_tpu.executor.run`` and its phases). One trace
holds the runs of several executables, whose instruction names collide, so a
device event has to find the executable it ran in before it can be looked
up in an HLO text. The device plane's "XLA Modules" line has one event a
run of an executable. A ``decode.step`` span ends only when the host has
read that step's logits, so the run that ends last inside a step's span is
the step executable's; a ``prefill.chunk`` span ends when the chunk is
dispatched (nothing of it is read back), so its run lies in a LATER span:
every other run after the first recorded quantum is a chunk's (the window
dispatches nothing else). A device event belongs to the run that holds its
start. Without a modules line (a trace reduced by hand), an event belongs
to the quantum whose span holds it.

Everything after ``load`` is plain Python on rows (``trace_reduce``'s).
"""

import bisect
import re

from benchmark import trace_reduce

PREFIX = "paddle_tpu."
STEP, CHUNK = "decode.step", "prefill.chunk"
MODULES_LINE = "XLA Modules"


def load(xplane_path, chips, hlo_text):
    """``hlo_text``: {"step": text or None, "chunk": text or None}."""
    from jax.profiler import ProfileData

    devices, modules, host = {}, {}, []
    for plane in ProfileData.from_file(xplane_path).planes:
        match = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if match:
            rows = [(trace_reduce.instruction_name(ev.name),
                     float(ev.start_ns), float(ev.duration_ns))
                    for line in plane.lines
                    if line.name == trace_reduce.OPS_LINE
                    for ev in line.events]
            if rows:
                devices[int(match.group(1))] = rows
                modules[int(match.group(1))] = sorted(
                    (float(ev.start_ns), float(ev.start_ns
                                               + ev.duration_ns))
                    for line in plane.lines if line.name == MODULES_LINE
                    for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((PREFIX, "bench.")):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    if not devices:
        return NoDeviceServeTrace(host)
    order = sorted(devices)[:chips]
    return ServeTrace([devices[k] for k in order], host, hlo_text,
                      [modules[k] for k in order])


def spans_named(host, name):
    """Sorted [(start, end)] of the program spans called ``name``."""
    return sorted((s, s + d) for n, s, d in host if n == PREFIX + name)


def _inside(intervals, at):
    """Index of the interval that holds ``at``, or None."""
    i = bisect.bisect_right(intervals, (at, float("inf"))) - 1
    return i if i >= 0 and intervals[i][0] <= at < intervals[i][1] else None


class SpanReadings:
    """What the host spans alone give (a rehearsal has these too)."""

    def __init__(self, host):
        self.host = list(host)

    def median_span(self, name):
        """(start, end) of the span of that name whose duration is the
        median one (the lower middle of an even number), or None."""
        found = sorted((e - s, s) for s, e in spans_named(self.host, name))
        if not found:
            return None
        dur, start = found[(len(found) - 1) // 2]
        return start, start + dur

    def child_ms(self, parent, child):
        """Milliseconds of ``child`` spans inside the median ``parent``
        span: parts of one and the same quantum, so that they add up."""
        at = self.median_span(parent)
        if at is None:
            return None
        found = [e - s for s, e in spans_named(self.host, child)
                 if at[0] <= s and e <= at[1]]
        return sum(found) / 1e6 if found else None

    def total_ms(self, name):
        found = [e - s for s, e in spans_named(self.host, name)]
        return sum(found) / 1e6 if found else None

    def count(self, name):
        return len(spans_named(self.host, name))


class NoDeviceServeTrace(SpanReadings, trace_reduce.NoDeviceTrace):
    def device_ms_a_quantum(self, name):
        return None

    def device_ms(self, name):
        return None

    def scope_ms_a_quantum(self, name, op_types):
        return None


def runs_by_kind(modules, steps, chunks):
    """{STEP: [(start, end)], CHUNK: [(start, end)]} of one device's
    executable runs: the run that ends last inside a ``decode.step`` span
    is that step's; every other run that starts after the first recorded
    quantum began is a chunk's."""
    out = {STEP: [], CHUNK: []}
    if not modules or not (steps or chunks):
        return out
    first = min(s for s, _ in list(steps) + list(chunks))
    taken = set()
    for lo, hi in steps:
        inside = [m for m in modules if lo < m[1] <= hi]
        if inside:
            taken.add(max(inside, key=lambda m: m[1]))
    for m in modules:
        if m in taken:
            out[STEP].append(m)
        elif m[0] >= first:
            out[CHUNK].append(m)
    return out


class ServeTrace(SpanReadings, trace_reduce.Trace):
    def __init__(self, devices, host, hlo_text, modules=None):
        spans = {STEP: spans_named(host, STEP),
                 CHUNK: spans_named(host, CHUNK)}
        scopes = {STEP: trace_reduce.hlo_scopes(hlo_text.get("step") or ""),
                  CHUNK: trace_reduce.hlo_scopes(
                      hlo_text.get("chunk") or "")}
        rows4, self.quantum_of, self.quanta = [], [], None
        for k, rows in enumerate(devices):
            runs = runs_by_kind(modules[k], spans[STEP], spans[CHUNK]) \
                if modules and modules[k] else spans
            if self.quanta is None:
                self.quanta = runs  # the first chip's
            scoped, owner = [], []
            for name, start, dur in rows:
                kind = next((q for q in (STEP, CHUNK)
                             if _inside(runs[q], start) is not None), None)
                scope = scopes[kind].get(name, "") if kind else ""
                scoped.append((name, start, dur, scope))
                owner.append(kind)
            rows4.append(scoped)
            self.quantum_of.append(dict(zip(
                ((n, s) for n, s, _, _ in scoped), owner)))
        SpanReadings.__init__(self, host)
        trace_reduce.Trace.__init__(self, rows4, host, steps=1)

    def device_ms_a_quantum(self, name):
        """Device busy milliseconds (union of the first chip's events) a
        run of that kind of quantum; None if the trace holds none."""
        spans = self.quanta.get(name)
        if not spans:
            return None
        busy = sum(min(e, hi) - max(s, lo)
                   for lo, hi in spans for s, e in self.busy[0]
                   if s < hi and e > lo)
        return busy / len(spans) / 1e6

    def device_ms(self, name):
        """Device busy milliseconds of all the runs of that kind."""
        each = self.device_ms_a_quantum(name)
        return None if each is None else each * len(self.quanta[name])

    def scope_ms_a_quantum(self, name, op_types):
        """Device milliseconds a run (self time, first chip) of the events
        inside that kind of quantum whose scope names one of ``op_types``;
        None if no event does."""
        spans = self.quanta.get(name)
        if not spans:
            return None
        pattern = trace_reduce.scope_pattern(op_types)
        owner = self.quantum_of[0]
        total, found = 0.0, False
        for (ev, start, _, scope), own in self.own[0]:
            if owner.get((ev, start)) == name and pattern.search(scope):
                total += own
                found = True
        return total / len(spans) / 1e6 if found else None
