"""Static verification passes over the Program IR.

The reference validates programs across three C++ layers: per-op
``OperatorWithKernel::InferShape`` before every kernel launch, the
ParallelExecutor's SSA dependency graph making write hazards explicit
(``details/build_strategy.cc``, ``parallel_executor.cc``), and the inference
analysis passes linting a graph before deployment
(``inference/analysis/analyzer.cc``). This module is the Python-IR
equivalent, run BEFORE lowering:

  * use-before-def / dangling inputs — a typo'd var name is reported with
    the op and the user line that created it, instead of a ``KeyError``
    deep inside ``executor.py``;
  * unordered double writes — two ops writing the same var with no
    dependency path between them (ambiguous under any reordering);
  * dead-op / unused-var lint, cross-checked against ``Program.prune``;
  * static shape/dtype propagation through the registered per-op
    ``infer_shape`` rules (``core/opimpl/shape_rules.py``) — mismatches
    surface at build time with op provenance, not as XLA trace errors;
  * donation-alias safety — proves the fetch list disjoint from donated
    state (the PR-3 serving use-after-free class), and tells it from a feed
    the caller handed over (``Executor.run(donate_feeds=...)``), whose
    buffer a fetch is MEANT to take;
  * compiled-HLO sharding checks (wrapping ``parallel/sharding_check``) so
    mesh-strategy assertions share this diagnostic surface and the CLI.

Entry points: :func:`analyze_program` (returns an :class:`AnalysisResult`)
and :func:`verify_program` (raises :class:`VerificationError` on errors) —
both also reachable through ``Executor.run(verify=...)`` /
``PADDLE_TPU_VERIFY`` and ``python -m paddle_tpu.analysis``.
"""

import numpy as np

from ..core.op_registry import ShapeError, shape_rule
from .dataflow import own_reads, program_region, SIDE_EFFECT_OPS

__all__ = ["Diagnostic", "AnalysisResult", "VerificationError", "ShapeCtx",
           "analyze_program", "verify_program", "analyze_hlo_sharding",
           "DEFAULT_CHECKS"]

DEFAULT_CHECKS = ("use-before-def", "double-write", "dead-op", "unused-var",
                  "shape")


class Diagnostic:
    """One finding: severity ('error' | 'warning'), the check that produced
    it, a message, and (when known) the offending op with its creation
    site."""

    def __init__(self, severity, check, message, op=None, var=None,
                 region="global"):
        self.severity = severity
        self.check = check
        self.message = message
        self.op = op
        self.var = var
        self.region = region

    def __str__(self):
        loc = ""
        if self.op is not None:
            loc = " [op '%s' created at %s]" % (self.op.type, self.op.where())
        reg = "" if self.region == "global" else " (in %s)" % self.region
        return "[%s] %s: %s%s%s" % (self.severity, self.check, self.message,
                                    reg, loc)

    __repr__ = __str__


class AnalysisResult:
    def __init__(self, diagnostics):
        self.diagnostics = list(diagnostics)

    @property
    def errors(self):
        return [d for d in self.diagnostics if d.severity == "error"]

    @property
    def warnings(self):
        return [d for d in self.diagnostics if d.severity == "warning"]

    @property
    def ok(self):
        return not self.errors

    def report(self):
        if not self.diagnostics:
            return "no findings"
        return "\n".join(str(d) for d in self.diagnostics)

    def raise_for_errors(self):
        if self.errors:
            raise VerificationError(self)
        return self


class VerificationError(RuntimeError):
    """Raised when verification finds errors; carries the full result."""

    def __init__(self, result):
        self.result = result
        n = len(result.errors)
        super().__init__(
            "program verification failed with %d error%s:\n%s"
            % (n, "s" if n != 1 else "", result.report()))


# ---------------------------------------------------------------------------
# use-before-def / dangling inputs
# ---------------------------------------------------------------------------

def check_use_before_def(region, defined, diags):
    # own_reads without the Switch RMW self-read (a guarded op may be its
    # var's first definition); body closures are reported by the recursion
    # at the inner op for precise provenance
    live = set(defined)
    for node in region.nodes:
        for name in sorted(own_reads(node.op, switch_rmw=False) - live):
            diags.append(Diagnostic(
                "error", "use-before-def",
                "op '%s' reads var '%s' which has no definition at this "
                "point (not produced by an earlier op, not a feed, not "
                "persistable state)" % (node.op.type, name),
                op=node.op, var=name, region=region.name))
        for _, sub, bound in node.subs:
            check_use_before_def(sub, live | set(bound), diags)
        live |= node.writes


# ---------------------------------------------------------------------------
# unordered double writes (the SSA-graph write-hazard analog)
# ---------------------------------------------------------------------------

def check_double_writes(region, diags):
    for name in sorted(region.writers):
        ws = region.writers[name]
        if len(ws) < 2:
            continue
        for w1, w2 in zip(ws, ws[1:]):
            if not region.reaches(w1, w2):
                op1, op2 = region.nodes[w1].op, region.nodes[w2].op
                diags.append(Diagnostic(
                    "error", "double-write",
                    "var '%s' is written by op '%s' (created at %s) and "
                    "again by op '%s' with no dependency path ordering the "
                    "two writes — ambiguous under reordering"
                    % (name, op1.type, op1.where(), op2.type),
                    op=op2, var=name, region=region.name))
    for node in region.nodes:
        for _, sub, _ in node.subs:
            check_double_writes(sub, diags)


# ---------------------------------------------------------------------------
# dead-op / unused-var lint (cross-checked against Program.prune)
# ---------------------------------------------------------------------------

def _sub_exports(op, sub_label):
    """The names a control-flow body must produce for its enclosing op —
    the liveness roots of that sub-region."""
    if op.type == "cond_block":
        attr = ("true_out_names" if sub_label.endswith("true_ops")
                else "false_out_names")
        return set(op.attr(attr) or
                   (v.name for v in op.output_list("Out")))
    if op.type == "while_block":
        names = {v.name for v in op.input_list("Carry")}
        if op.attr("cond_name"):
            names.add(op.attr("cond_name"))
        return names
    if op.type == "scan_block":
        return set(op.attr("carry_out_names") or ()) | \
            set(op.attr("y_names") or ())
    return {n for v in op.outputs.values() for n in (x.name for x in v)}


def check_dead_ops(region, fetch_names, persistable, diags, program=None):
    """Backward liveness from (fetches ∪ persistable writes ∪ side-effect
    ops), recursing into control-flow bodies with each body's export
    contract as its roots. When ``program`` is given, cross-check against
    ``Program.prune``: prune keeps only the value chain to the fetches, so
    every op it keeps must be in the dataflow live set — a kept-but-dead
    op means the two disagree about the graph."""
    for node in region.nodes:
        for label, sub, _ in node.subs:
            check_dead_ops(sub, _sub_exports(node.op, label), persistable,
                           diags)
    needed = set(fetch_names or ())
    live = set()
    for node in reversed(region.nodes):
        is_live = (bool(node.writes & needed)
                   or bool(node.writes & persistable)
                   or node.op.type in SIDE_EFFECT_OPS
                   or node.op.attrs.get("_switch_cond") is not None)
        if is_live:
            live.add(node.index)
            needed |= node.reads
    for node in region.nodes:
        if node.index not in live:
            outs = sorted(node.writes)
            diags.append(Diagnostic(
                "warning", "dead-op",
                "op '%s' is dead: output%s %s never read, fetched, or "
                "persisted" % (node.op.type, "s" if len(outs) != 1 else "",
                               outs),
                op=node.op, region=region.name))
    if program is not None and fetch_names:
        try:
            gb = program.global_block()
            fetchable = [n for n in fetch_names if gb.has_var(n)]
            pruned = program.prune(fetchable) if fetchable else None
        except Exception:
            pruned = None  # prune itself can reject exotic targets
        if pruned is not None:
            # prune clones 1:1 in order, so recover kept source positions
            # by greedy (type, outputs) matching
            kept_idx = set()
            src_ops = program.global_block().ops
            dst_ops = pruned.global_block().ops
            di = 0
            for si, op in enumerate(src_ops):
                if di < len(dst_ops) and dst_ops[di].type == op.type and \
                        dst_ops[di].output_arg_names == op.output_arg_names:
                    kept_idx.add(si)
                    di += 1
            for si in sorted(kept_idx):
                if si not in live:
                    op = src_ops[si]
                    diags.append(Diagnostic(
                        "warning", "dead-op",
                        "Program.prune keeps op '%s' but dataflow liveness "
                        "marks it dead — prune/dataflow disagree about this "
                        "graph" % op.type, op=op, region=region.name))


def check_unused_vars(region, block_vars, fetch_names, diags):
    """Orphaned declarations: vars with neither a producing op nor a reader
    anywhere in the region tree (feeds/persistables/fetches excluded)."""
    produced, read = set(), set()
    for _, node in region.walk():
        produced |= node.writes
        read |= node.reads
    fetch = set(fetch_names or ())
    for name, var in sorted(block_vars.items()):
        if name in produced or name in read or name in fetch:
            continue
        if var.persistable or getattr(var, "is_data", False):
            continue
        diags.append(Diagnostic(
            "warning", "unused-var",
            "var '%s' is declared but never produced or consumed" % name,
            var=name, region=region.name))


# ---------------------------------------------------------------------------
# static shape/dtype propagation
# ---------------------------------------------------------------------------

def _norm_shape(shape):
    if shape is None:
        return None
    return tuple(-1 if (s is None or int(s) < 0) else int(s) for s in shape)


def _dims_compatible(a, b):
    return a == -1 or b == -1 or a == b


def _shapes_compatible(computed, declared):
    if computed is None or declared is None:
        return True
    if len(computed) != len(declared):
        return False
    return all(_dims_compatible(c, d) for c, d in zip(computed, declared))


class ShapeCtx:
    """Propagation state for the infer-shape rules: per-var inferred
    (shape, dtype), falling back to the Variable's declared values. Rules
    call ``shape``/``dtype`` on input vars and ``set`` on outputs; ``set``
    records a mismatch when the computed value contradicts the declaration
    (-1 dims are wildcards — the batch dim stays symbolic, exactly like the
    reference's InferShape treating dim -1 as runtime-determined)."""

    def __init__(self):
        self._vals = {}       # name -> (shape|None, np.dtype|None)
        self.mismatches = []  # (var, kind, computed, declared)

    def shape(self, var):
        if var is None:
            return None
        ent = self._vals.get(var.name)
        if ent is not None and ent[0] is not None:
            return ent[0]
        return _norm_shape(getattr(var, "shape", None))

    def dtype(self, var):
        if var is None:
            return None
        ent = self._vals.get(var.name)
        if ent is not None and ent[1] is not None:
            return ent[1]
        dt = getattr(var, "dtype", None)
        return np.dtype(dt) if dt is not None else None

    def set(self, var, shape=None, dtype=None):
        if var is None:
            return
        shape = _norm_shape(shape)
        declared = _norm_shape(getattr(var, "shape", None))
        if shape is not None and not _shapes_compatible(shape, declared):
            self.mismatches.append((var, "shape", shape, declared))
        elif shape is not None and declared is not None:
            # refine wildcards from the declaration (keeps later checks
            # as tight as either source allows)
            shape = tuple(d if c == -1 else c
                          for c, d in zip(shape, declared))
        decl_dt = getattr(var, "dtype", None)
        decl_dt = np.dtype(decl_dt) if decl_dt is not None else None
        if dtype is not None:
            dtype = np.dtype(dtype)
            if decl_dt is not None and dtype != decl_dt:
                self.mismatches.append((var, "dtype", dtype, decl_dt))
        self._vals[var.name] = (shape, dtype)


def check_shapes(region, diags):
    ctx = ShapeCtx()
    for reg, node in region.walk():
        rule = shape_rule(node.op.type)
        if rule is None:
            continue
        n_before = len(ctx.mismatches)
        try:
            rule(ctx, node.op)
        except ShapeError as e:
            diags.append(Diagnostic(
                "error", "shape",
                "op '%s' is statically infeasible: %s" % (node.op.type, e),
                op=node.op, region=reg.name))
            continue
        except Exception as e:  # a buggy rule must never block a run
            diags.append(Diagnostic(
                "warning", "shape",
                "infer_shape rule for '%s' crashed (%s: %s) — op skipped"
                % (node.op.type, type(e).__name__, e),
                op=node.op, region=reg.name))
            continue
        for var, kind, computed, declared in ctx.mismatches[n_before:]:
            diags.append(Diagnostic(
                "error", "shape",
                "op '%s' produces %s %s for var '%s' but it is declared "
                "as %s" % (node.op.type, kind,
                           computed if kind == "dtype" else list(computed),
                           var.name,
                           declared if kind == "dtype" else
                           (list(declared) if declared is not None
                            else None)),
                op=node.op, var=var.name, region=reg.name))


# ---------------------------------------------------------------------------
# donation-alias safety (the PR-3 serving use-after-free class)
# ---------------------------------------------------------------------------

# ops XLA may lower to views of their input buffer; fetching through a
# chain of these from un-rewritten donated state still exposes the
# donated buffer
ALIAS_OPS = frozenset({"assign", "reshape", "reshape2", "squeeze",
                       "squeeze2", "unsqueeze", "unsqueeze2", "flatten",
                       "flatten2"})


def check_donation_alias(region, fetch_names, state_names, diags,
                         donate_state=True, donate_feeds=(), block_vars=None):
    """Errors when a fetched var aliases DONATED state: the step donates
    the state pytree, so a fetch that resolves (possibly through
    view/identity ops) to a state input whose buffer no op rewrote returns
    an invalidated buffer — exactly the bug class ``Executor.run(
    donate_state=False)`` exists for (serving from concurrent clones).

    A feed the caller HANDED OVER (``donate_feeds``) is the other case:
    the scope and the clones hold donated state, but nobody holds a
    handed-over feed except the caller who gave it up, so a fetch that
    takes its buffer — a cache written in place, or the feed itself
    through views — is what the hand-over is for and no finding. What IS
    one there: a handed-over feed whose buffer no fetch can take (none of
    its declared shape and dtype), which deletes the caller's array and
    buys nothing (a warning)."""
    state = set(state_names or ()) if donate_state else set()
    handed = set(donate_feeds or ())
    if (not state and not handed) or not fetch_names:
        return
    last_writer = {}
    for node in region.nodes:
        for n in node.writes:
            last_writer[n] = node

    def alias_root(name, depth=0):
        node = last_writer.get(name)
        if node is None:
            return name  # resolves to an entry binding
        if node.op.type in ALIAS_OPS and depth < 64:
            srcs = node.op.input_arg_names
            if srcs:
                return alias_root(srcs[0], depth + 1)
        return None  # produced fresh by real compute

    for f in fetch_names:
        root = alias_root(f)
        # a handed-over feed shadows state of the same name in the step
        if root is None or root in handed or root not in state:
            continue
        node = last_writer.get(f)
        if f == root:
            msg = ("fetch '%s' reads donated state directly: the state "
                   "pytree is donated to the step, so the fetched buffer "
                   "is invalidated mid-call (run with donate_state=False "
                   "or fetch a computed copy)" % f)
        else:
            msg = ("fetch '%s' aliases donated state var '%s' through "
                   "view op%s — the fetched buffer may share the donated "
                   "allocation (run with donate_state=False or copy "
                   "through real compute)"
                   % (f, root, " '%s'" % node.op.type if node else ""))
        diags.append(Diagnostic(
            "error", "donation-alias", msg,
            op=node.op if node else None, var=f, region=region.name))

    def kind(name):
        var = (block_vars or {}).get(name)
        if var is None or var.shape is None:
            return None
        return tuple(var.shape), str(var.dtype)

    takers = {kind(f) for f in fetch_names} - {None}
    for name in sorted(handed):
        if kind(name) is not None and kind(name) not in takers:
            diags.append(Diagnostic(
                "warning", "donation-alias",
                "feed '%s' is handed over (donate_feeds) but no fetch has "
                "its shape and dtype, so nothing can take its buffer: the "
                "caller's array is deleted for no gain" % name,
                var=name, region=region.name))


# ---------------------------------------------------------------------------
# compiled-HLO sharding checks (promoted from parallel/sharding_check)
# ---------------------------------------------------------------------------

def analyze_hlo_sharding(hlo_text, param_shapes=None, require_sharded=(),
                         logical_shapes=None):
    """Run the compiled-module sharding assertions as an analysis pass:
    ``param_shapes`` (logical parameter shape tuples) enables the
    no-full-parameter-all-gather check; ``require_sharded`` names state
    vars whose entry parameters must be actually sharded (optionally with
    ``logical_shapes[name]`` to also require a smaller local shape).
    Returns an :class:`AnalysisResult` — same surface as the IR checks, so
    mesh-strategy and IR verification share one entry point."""
    from ..parallel import sharding_check as sc

    diags = []
    if param_shapes:
        try:
            sc.assert_no_param_allgather(hlo_text, param_shapes)
        except AssertionError as e:
            diags.append(Diagnostic("error", "sharding-allgather", str(e)))
    for name in require_sharded or ():
        try:
            sc.assert_param_sharded(
                hlo_text, name, (logical_shapes or {}).get(name))
        except AssertionError as e:
            diags.append(Diagnostic("error", "sharding-param", str(e),
                                    var=name))
    return AnalysisResult(diags)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def analyze_program(program, feed_names=None, fetch_names=None,
                    state_names=None, donate_state=False, checks=None,
                    donate_feeds=()):
    """Run the verification passes; returns an :class:`AnalysisResult`.

    ``feed_names`` defaults to the program's declared data vars;
    ``state_names`` defaults to all persistable vars (the executor passes
    the actual scope-resident state). ``donate_state=True`` additionally
    runs the donation-alias check against ``fetch_names``, and
    ``donate_feeds`` (the feeds the caller hands over) its other half."""
    checks = set(DEFAULT_CHECKS if checks is None else checks)
    if feed_names is None:
        feed_names = [v.name for v in program.list_vars()
                      if getattr(v, "is_data", False)]
    if state_names is None:
        state_names = [v.name for v in program.list_vars() if v.persistable]
    persistable = {v.name for v in program.list_vars() if v.persistable}
    region = program_region(program)
    diags = []

    entry = set(feed_names) | set(state_names) | persistable
    if "use-before-def" in checks:
        check_use_before_def(region, entry, diags)
    if "double-write" in checks:
        check_double_writes(region, diags)
    if "dead-op" in checks:
        check_dead_ops(region, fetch_names, persistable, diags,
                       program=program)
    if "unused-var" in checks:
        check_unused_vars(region, program.global_block().vars, fetch_names,
                          diags)
    if "shape" in checks:
        check_shapes(region, diags)
    if donate_state or donate_feeds:
        check_donation_alias(region, fetch_names, state_names, diags,
                             donate_state, donate_feeds,
                             program.global_block().vars)
    return AnalysisResult(diags)


def verify_program(program, feed_names=None, fetch_names=None,
                   state_names=None, donate_state=False, checks=None,
                   warn=False, donate_feeds=()):
    """:func:`analyze_program` + raise :class:`VerificationError` on any
    error finding (warnings go through ``warnings.warn``). ``warn=True``
    downgrades errors to warnings (the ``PADDLE_TPU_VERIFY=warn`` mode)."""
    import warnings as _warnings

    result = analyze_program(program, feed_names, fetch_names, state_names,
                             donate_state, checks, donate_feeds)
    for d in result.warnings:
        _warnings.warn("program verification: %s" % d)
    if warn:
        for d in result.errors:
            _warnings.warn("program verification: %s" % d)
        return result
    result.raise_for_errors()
    return result
