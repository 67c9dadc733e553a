"""From a profiler trace to numbers: device busy and idle time, device time
under an op's scope, collective time and its exposed part, the breakdown.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` (nothing
but JAX) into plain rows; everything after that is plain Python on rows, so
the arithmetic is tested on a small recorded trace
(``tests/benchmark/recorded_trace.json``).

A device row is ``(name, start_ns, duration_ns, scope)``: one event of a
device plane's "XLA Ops" line. On a TPU v5e (JAX 0.9.0) such an event's
name is the whole HLO instruction (``%fusion.12 = bf16[...] fusion(...)``)
and it carries no ``op_name`` of its own, so ``scope`` is looked up by the
instruction's name in the compiled step's HLO text (``hlo_scopes``): its
``op_name`` path carries the ``jax.named_scope(op.type)`` the program puts
around every op, ``jit(step)/autodiff/jvp(matmul)/dot_general`` forward and
``.../transpose(jvp(matmul))/...`` backward. A host row is ``(name,
start_ns, duration_ns)`` of a ``bench.*`` annotation the benchmark wrote
around its own calls, on the same clock.
"""

import re

OPS_LINE = "XLA Ops"
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")


def instruction_name(event_name):
    """``fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    return event_name.lstrip("%").split(" ", 1)[0]


def hlo_scopes(hlo_text):
    """{instruction name: op_name path} of a compiled module's text."""
    scopes = {}
    for line in hlo_text.splitlines():
        match = _INSTRUCTION.match(line)
        if match:
            scopes[match.group(1)] = match.group(2)
    return scopes


def load(xplane_path, chips, steps, hlo_text=""):
    from jax.profiler import ProfileData

    scopes = hlo_scopes(hlo_text)
    devices, host = {}, []
    for plane in ProfileData.from_file(xplane_path).planes:
        match = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if match:
            rows = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = instruction_name(ev.name)
                    rows.append((name, float(ev.start_ns),
                                 float(ev.duration_ns),
                                 scopes.get(name, "")))
            if rows:
                devices[int(match.group(1))] = rows
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    if not devices:
        return NoDeviceTrace()
    return Trace([devices[k] for k in sorted(devices)][:chips], host, steps)


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def length(intervals):
    return sum(end - start for start, end in intervals)


def subtract(intervals, holes):
    """The parts of merged ``intervals`` that no merged ``holes`` cover."""
    out = []
    holes = list(holes)
    for start, end in intervals:
        at = start
        for h0, h1 in holes:
            if h1 <= at or h0 >= end:
                continue
            if h0 > at:
                out.append([at, h0])
            at = max(at, h1)
        if at < end:
            out.append([at, end])
    return out


def self_times(rows):
    """[(row, self_ns)]: an event's duration less what the events nested in
    it cover (a ``while`` or ``call`` holds the ops of its body), so that
    sums over events never count a nanosecond twice."""
    order = sorted(rows, key=lambda r: (r[1], -r[2]))
    selfs = [r[2] for r in order]
    stack = []  # indices of open events
    for i, (_, start, dur, _) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= dur
        stack.append(i)
    return [(row, max(0.0, s)) for row, s in zip(order, selfs)]


def scope_pattern(op_types):
    """Matches a scope path with one of ``op_types`` as a whole component,
    bare (forward) or wrapped as in ``transpose(jvp(matmul))``."""
    return re.compile(r"(?:^|[/(])(?:%s)(?:$|[/)])"
                      % "|".join(re.escape(t) for t in op_types))


_WRAPPED = re.compile(r"^(?:transpose\(|jvp\(|vmap\(|remat\(|checkpoint\()*"
                      r"([\w.\-]*)\)*$")


def op_type_of(scope):
    """``matmul`` of ``jit(step)/autodiff/transpose(jvp(matmul))/dot`` and of
    ``jit(step)/autodiff/transpose(autodiff)/jvp(matmul)/dot``: the first
    component after the jit and autodiff wrappers; ``(none)`` for an event
    without a scope."""
    for part in scope.split("/"):
        match = _WRAPPED.match(part)
        inner = match.group(1) if match else part
        # jit(...) wrappers and the autodiff op, which holds the forward
        # and, as transpose(autodiff), the backward ops of the others
        if not part or part.startswith(("jit(", "pjit")) or \
                inner == "autodiff":
            continue
        return inner or part
    return "(none)"


def is_collective(name, kinds=("all-reduce", "reduce-scatter", "all-gather",
                               "all-to-all", "collective-permute")):
    return name.startswith(kinds)


def collective_intervals(rows, kind="all-reduce"):
    """Intervals during which a collective of ``kind`` is under way on this
    device: a synchronous event's own span, or from an asynchronous
    ``<kind>-start`` to the end of its ``<kind>-done``."""
    spans, starts = [], {}
    for name, start, dur, _ in sorted(rows, key=lambda r: r[1]):
        if not name.startswith(kind):
            continue
        rest = name[len(kind):]
        if rest.startswith("-start"):
            starts.setdefault(rest[len("-start"):], []).append(start)
        elif rest.startswith("-done"):
            opened = starts.get(rest[len("-done"):])
            begin = opened.pop(0) if opened else start
            spans.append([begin, start + dur])
        else:
            spans.append([start, start + dur])
    return union(spans)


class NoDeviceTrace:
    """What a rehearsal on the CPU gets: no device plane, so every reading
    finds nothing to read."""

    steps = 1
    busy_s = window_s = None

    def seconds_under(self, op_types):
        return None

    def ms_a_step_under(self, op_types):
        return None

    def collective_seconds(self, kind="all-reduce"):
        return None

    def ms_a_step_by_op_type(self):
        return []

    def breakdown(self, top=10):
        return None


class Trace:
    def __init__(self, devices, host=(), steps=1):
        """``steps``: how many steps the traced window holds."""
        self.steps = steps
        if not devices or not any(devices):
            raise RuntimeError("the trace holds no device operation")
        self.devices = devices
        self.host = list(host)
        first = min(r[1] for rows in devices for r in rows)
        last = max(r[1] + r[2] for rows in devices for r in rows)
        self.window_s = (last - first) / 1e9
        self.busy = [union([r[1], r[1] + r[2]] for r in rows)
                     for rows in devices]
        self.busy_s = sum(length(b) for b in self.busy) / len(devices) / 1e9
        self.own = [self_times(rows) for rows in devices]

    def seconds_under(self, op_types):
        """Device seconds (self time, mean over the chips) of events whose
        scope names one of ``op_types``. None if no event does."""
        pattern = scope_pattern(op_types)
        total, found = 0.0, False
        for rows in self.own:
            for (_, _, _, scope), own in rows:
                if pattern.search(scope):
                    total += own
                    found = True
        return total / len(self.devices) / 1e9 if found else None

    def ms_a_step_under(self, op_types):
        seconds = self.seconds_under(op_types)
        return None if seconds is None else seconds / self.steps * 1e3

    def ms_a_step_by_op_type(self):
        """[(op scope, ms a step)], heaviest first: self time on the first
        device by the program op whose scope an event lies under (the
        innermost of ``jit(step)/autodiff/jvp(<op>)/...``), backward passes
        under their forward op's name."""
        totals = {}
        for (name, _, _, scope), own in self.own[0]:
            totals[op_type_of(scope)] = totals.get(op_type_of(scope), 0) + own
        return sorted(((k, v / self.steps / 1e6) for k, v in totals.items()),
                      key=lambda kv: -kv[1])

    def collective_seconds(self, kind="all-reduce"):
        """(under way, exposed) seconds on the first device: the time a
        collective of ``kind`` is under way, and the part of it during which
        no other operation runs there. None where there is none."""
        rows = self.devices[0]
        spans = collective_intervals(rows, kind)
        if not spans:
            return None
        others = union([r[1], r[1] + r[2]] for r in rows
                       if not is_collective(r[0]))
        return length(spans) / 1e9, length(subtract(spans, others)) / 1e9

    def breakdown(self, top=10):
        """The device operations that took most time (self time, first
        device), and the longest idle gaps by what the host was doing."""
        by_name = {}
        for (name, _, _, scope), own in self.own[0]:
            tail = "/".join(scope.split("/")[-2:]) if scope else ""
            key = "%s [%s]" % (name, tail) if tail else name
            by_name[key] = by_name.get(key, 0.0) + own / 1e9
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        busy = self.busy[0]
        for (_, end), (start, _) in zip(busy, busy[1:]):
            gaps.append((start - end, (start + end) / 2))
        gaps.sort(reverse=True)
        labelled = [[self._host_activity(mid), dur / 1e9]
                    for dur, mid in gaps[:top]]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": labelled}

    def _host_activity(self, at_ns):
        inside = [(dur, name) for name, start, dur in self.host
                  if start <= at_ns < start + dur]
        return min(inside)[1] if inside else "host_other"
