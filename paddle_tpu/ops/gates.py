"""Structured kernel-gate decisions (ISSUE 15 satellite).

Every Pallas kernel family guards itself with a gate (VMEM budget,
supported geometry, dtype, platform). Those gates used to answer with a
bare bool — a refused kernel silently fell back and nothing recorded
WHY. A :class:`GateDecision` carries the chosen kernel plus one
:class:`GateReason` per failed (or decisive) check, so:

  * op impls record the decision in their op's attrs
    (``op.attrs["_kernel_choice"]``) at trace time — inspectable after
    a build, cloned with the program;
  * the static resource pass (``analysis/resources.py``) evaluates the
    SAME gates shape-only (``static_only=True`` skips the platform
    checks) and surfaces refusals as findings with op provenance,
    under the honest kernel name;
  * every gated site also hands its decision to :func:`note`, and
    ``Executor._stage`` gathers them (:func:`collect`) round the trace of
    a step: a tag on its ``executor.trace`` span and an entry of the
    variant's compile record. That count survives the fusion pass, which
    rewrites the ops ``_kernel_choice`` is recorded on.
"""

import contextlib
import threading

import jax

__all__ = ["GateReason", "GateDecision", "collect", "note", "tally",
           "PLACEMENT", "placed", "placed_platform", "single_tpu",
           "placement_reason", "platform_reason"]


class GateReason:
    """One gate check's outcome: the check name ('vmem' / 'geometry' /
    'dtype' / 'platform' / ...), a human detail string, and
    whether this check blocked admission."""

    __slots__ = ("check", "detail", "blocking")

    def __init__(self, check, detail, blocking=True):
        self.check = check
        self.detail = detail
        self.blocking = bool(blocking)

    def to_dict(self):
        return {"check": self.check, "detail": self.detail,
                "blocking": self.blocking}

    def __repr__(self):
        return "GateReason(%s%s: %s)" % (
            self.check, "" if self.blocking else " [info]", self.detail)


class GateDecision:
    """The gate's verdict: ``kernel`` is what will actually run (the
    Pallas kernel name when admitted, the fallback name when refused);
    ``reasons`` records every failed check (refusals) or decisive note.
    Truthiness == admitted, so ``if gate(...):`` keeps working."""

    __slots__ = ("admitted", "kernel", "fallback", "reasons")

    def __init__(self, admitted, kernel, fallback=None, reasons=()):
        self.admitted = bool(admitted)
        self.kernel = kernel
        self.fallback = fallback
        self.reasons = list(reasons)

    def __bool__(self):
        return self.admitted

    @property
    def blocking_reasons(self):
        return [r for r in self.reasons if r.blocking]

    def blocked_only_by(self, *checks):
        """True when every blocking reason is one of ``checks`` — e.g.
        'the ONLY thing keeping this shape off the kernel is the VMEM
        budget' (the actionable finding class)."""
        blocking = self.blocking_reasons
        return bool(blocking) and all(r.check in checks for r in blocking)

    def to_dict(self):
        return {"admitted": self.admitted, "kernel": self.kernel,
                "fallback": self.fallback,
                "reasons": [r.to_dict() for r in self.reasons]}

    def describe(self):
        why = "; ".join("%s: %s" % (r.check, r.detail)
                        for r in self.blocking_reasons)
        if self.admitted and not self.blocking_reasons:
            return "kernel %s" % self.kernel
        if self.admitted:
            # admitted-with-demotion (e.g. head-split instead of the
            # packed streaming path): the reason IS the actionable part
            return "runs kernel %s instead of %s: %s" % (
                self.kernel, self.fallback or "the preferred kernel", why)
        return "fell back to %s (wanted %s): %s" % (
            self.kernel, self.fallback or "pallas", why or "no reason")

    def __repr__(self):
        return "GateDecision(%s)" % self.describe()


# ---------------------------------------------------------------------------
# decisions taken during one trace
# ---------------------------------------------------------------------------

_gathering = threading.local()


@contextlib.contextmanager
def collect():
    """Gather ``(site, GateDecision)`` of every gated site evaluated on
    this thread inside the block (an op the autodiff replay traces again
    is evaluated, and counted, again)."""
    before = getattr(_gathering, "rows", None)
    _gathering.rows = rows = []
    try:
        yield rows
    finally:
        _gathering.rows = before


def note(site, decision):
    """A gated site's decision, handed on to whoever gathers; returns it."""
    rows = getattr(_gathering, "rows", None)
    if rows is not None:
        rows.append((site, decision))
    return decision


def tally(rows):
    """``{site: {decision as one line: times taken}}`` of gathered rows."""
    out = {}
    for site, decision in rows:
        by = out.setdefault(site, {})
        line = decision.describe()
        by[line] = by.get(line, 0) + 1
    return out


# ---------------------------------------------------------------------------
# Placement (trace-time state). The Pallas gates must know where the
# computation being traced will RUN, which is not what ``jax.devices()`` says
# about the process: a CPUPlace executor on a TPU host runs on the CPU, a
# one-chip program on a four-chip host still has its chip to itself, and a
# step compiled for a described (not attached) TPU topology is a TPU step.
# The Executor sets this around every step it traces, from its place and its
# mesh; outside an Executor (dygraph, a bare ``build_step_fn`` + ``jax.jit``)
# the answer is JAX's default backend.
# ---------------------------------------------------------------------------

class _Placement(threading.local):
    platform = None   # None: not placed by an Executor -> default backend
    meshed = False    # True: the step is partitioned over a device mesh


PLACEMENT = _Placement()


@contextlib.contextmanager
def placed(platform, meshed=False):
    """Declare where the computation traced inside the block will run."""
    prev = (PLACEMENT.platform, PLACEMENT.meshed)
    PLACEMENT.platform, PLACEMENT.meshed = platform, bool(meshed)
    try:
        yield
    finally:
        PLACEMENT.platform, PLACEMENT.meshed = prev


def placed_platform():
    """Platform name ('tpu' / 'cpu' / ...) the traced computation runs on."""
    return PLACEMENT.platform or jax.default_backend()


def single_tpu():
    """True when the traced computation runs on ONE TPU device — the only
    placement where a Pallas custom call doesn't fight GSPMD (under a mesh
    it would force gathers of sharded operands)."""
    return placed_platform() == "tpu" and not PLACEMENT.meshed


def placement_reason():
    """Human detail for a gate's 'platform' refusal."""
    if PLACEMENT.meshed:
        return ("the step is partitioned over a mesh (GSPMD would gather "
                "the custom call's sharded operands)")
    return "placed on %r, not a TPU" % placed_platform()


def platform_reason(interpret=False):
    """The one placement rule every Pallas kernel family shares: ``None``
    where a kernel may run (one TPU device, or ``interpret``: a family's
    test mode, which runs its kernels on the CPU), else the blocking
    'platform' :class:`GateReason`."""
    if interpret or single_tpu():
        return None
    return GateReason("platform", placement_reason())
