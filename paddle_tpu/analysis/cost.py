"""Static cost / roofline engine over the Program IR (ISSUE 15).

One bytes/FLOP model of a program: per-op cost rules registered beside
the shape rules (``core/op_registry.register_cost``, rules in
``core/opimpl/cost_rules.py``) roll up into a per-program
:class:`CostEstimate`, and :meth:`CostEstimate.roofline` prices it at
the four constants below. They are round-5 readings of an installation
that is gone, not the benchmark's peaks (``benchmark/peaks.json``), and
no chip run has confirmed an estimate since: a lint, not a measurement
(``ROADMAP.md`` D8).

Modeling stance — a FLOOR model: each op is charged its *minimum
achievable* HBM traffic under ideal XLA fusion, so
activations/casts/reductions that ride a producer's epilogue charge
zero bytes, while genuinely irreducible passes (conv operand streams,
residual merges reading a distant tensor, transposes, optimizer state
passes, pooling) charge theirs. Embedding-bound ops are charged in
ROWS, not bytes (TPU row ops are latency-bound), and the roofline adds
the row term on top of max(compute, HBM).

The reference's analog is the inference-analysis pass tier
(``paddle/fluid/inference/analysis``) — graph-level passes computing
static properties before deployment; here the property is the roofline.
"""

import numpy as np

from ..core.op_registry import cost_rule

__all__ = ["CostCtx", "OpCost", "CostEstimate", "estimate_program",
           "comm_bytes_model"]

# ops whose backward is replayed from an op-list attr (never walked as
# region ops for cost; the engine charges their fwd_ops' bwd columns)
_REPLAY_OPS = ("autodiff", "autodiff_vjp")


# what roofline() prices at, and the label they ride under in its dict
_MM_TFLOPS = 185.3
_HBM_GBS = 552.2
_GATHER_NS = 2.0
_SCATTER_NS = 15.0
_CEILING_SOURCE = ("round-5 v5e readings of an installation that is gone; "
                  "not benchmark/peaks.json")


def comm_bytes_model(n_ids, width, n_shards, esize=4):
    """Analytic per-step ICI bytes of both sharded-lookup formulations
    (re-derivable, not measured): the ONE model the SPMD pass's
    per-collective volumes and ``parallel/sharded_embedding.py`` read.

    psum: every shard contributes a FULL [n, D] partial; the reduction
    combines mp of them (total reduced volume mp*n*D*e; per-link on a
    bidirectional ring all-reduce ~2*(mp-1)/mp*n*D*e).
    alltoall: n ids out + n*D payload back + (mp-1)/mp*n*D output
    replication — per-shard O(n*D + n), mp-independent."""
    n, d, m = int(n_ids), int(width), int(n_shards)
    nd = n * d * esize
    return {
        "psum_total_bytes": m * nd,
        "psum_per_link_bytes": int(2 * (m - 1) / max(m, 1) * nd),
        "alltoall_total_bytes": n * 4 + nd + int((m - 1) / max(m, 1) * nd),
        "alltoall_per_link_bytes": int(
            (m - 1) / max(m, 1) * (n * 4 + 2 * nd)),
    }


# ---------------------------------------------------------------------------
# propagation context + per-op records
# ---------------------------------------------------------------------------

class OpCost:
    """One op's charged cost: forward and (separately) backward columns —
    the engine counts the backward column only for ops an ``autodiff``
    op actually replays. ``rows`` are latency-bound row operations
    (embedding gathers / scatter-adds) priced per-row, not per-byte."""

    __slots__ = ("op", "region", "flops", "hbm_bytes", "bwd_flops",
                 "bwd_hbm_bytes", "row_reads", "row_writes",
                 "bwd_row_reads", "bwd_row_writes", "unresolved", "note",
                 "bwd_counted")

    def __init__(self, op, region="global", flops=0, hbm_bytes=0,
                 bwd_flops=0, bwd_hbm_bytes=0, row_reads=0, row_writes=0,
                 bwd_row_reads=0, bwd_row_writes=0, unresolved=False,
                 note=None):
        self.op = op
        self.region = region
        self.flops = float(flops)
        self.hbm_bytes = float(hbm_bytes)
        self.bwd_flops = float(bwd_flops)
        self.bwd_hbm_bytes = float(bwd_hbm_bytes)
        self.row_reads = int(row_reads)
        self.row_writes = int(row_writes)
        self.bwd_row_reads = int(bwd_row_reads)
        self.bwd_row_writes = int(bwd_row_writes)
        self.unresolved = bool(unresolved)
        self.note = note
        self.bwd_counted = False

    def __repr__(self):
        return ("OpCost(%s, flops=%.3g, bytes=%.3g%s)"
                % (self.op.type, self.flops, self.hbm_bytes,
                   ", bwd" if self.bwd_counted else ""))


class CostCtx:
    """What a cost rule sees: resolved static shapes (the symbolic batch
    dim -1 substituted with ``batch``), element sizes under the AMP
    convention (f32 activations/weights stream as bf16 when ``amp`` —
    master-precision passes charge 4 bytes explicitly), and ``add`` to
    record the op's cost columns."""

    def __init__(self, batch=None, amp=False):
        self.batch = int(batch) if batch else None
        self.amp = bool(amp)
        self.records = []
        self._region = "global"

    def shape(self, var):
        """Fully-resolved static shape tuple, or None when a non-batch
        dim is unknown (the rule should then charge zero and mark the
        record unresolved)."""
        if var is None:
            return None
        shape = getattr(var, "shape", None)
        if shape is None:
            return None
        out = []
        for i, d in enumerate(shape):
            d = -1 if (d is None or int(d) < 0) else int(d)
            if d == -1:
                if i == 0 and self.batch:
                    d = self.batch
                else:
                    return None
            out.append(d)
        return tuple(out)

    def nelems(self, var):
        s = self.shape(var)
        if s is None:
            return None
        n = 1
        for d in s:
            n *= d
        return n

    def esize(self, var):
        """Streamed element size: f32 activations/weights move as bf16
        under AMP (``mxu_cast`` / bf16-resident activations — the same
        convention the committed resnet bytes model uses)."""
        dt = getattr(var, "dtype", None)
        if dt is None:
            return 4
        try:
            size = np.dtype(dt).itemsize
        except TypeError:
            return 4
        if self.amp and np.dtype(dt) == np.float32:
            return 2
        return size

    def add(self, op, **kw):
        rec = OpCost(op, region=self._region, **kw)
        self.records.append(rec)
        return rec


class CostEstimate:
    """Per-program rollup of the op records. Totals count the backward
    columns of exactly the ops an ``autodiff`` op replays (``train`` is
    True when one exists), and carry the honesty lists: op types with NO
    cost rule (charged zero, loudly) and ops whose shapes could not be
    statically resolved."""

    def __init__(self, records, train, uncosted, batch=None, amp=False):
        self.records = records
        self.train = bool(train)
        self.uncosted = sorted(uncosted)
        self.batch = batch
        self.amp = amp

    def _total(self, fwd_field, bwd_field):
        total = 0
        for r in self.records:
            total += getattr(r, fwd_field)
            if r.bwd_counted:
                total += getattr(r, bwd_field)
        return total

    @property
    def flops(self):
        return self._total("flops", "bwd_flops")

    @property
    def hbm_bytes(self):
        return self._total("hbm_bytes", "bwd_hbm_bytes")

    @property
    def row_reads(self):
        return int(self._total("row_reads", "bwd_row_reads"))

    @property
    def row_writes(self):
        return int(self._total("row_writes", "bwd_row_writes"))

    @property
    def unresolved(self):
        return [r for r in self.records if r.unresolved]

    def by_type(self):
        """op type -> {flops, hbm_bytes} (counted columns only)."""
        out = {}
        for r in self.records:
            ent = out.setdefault(r.op.type, {"flops": 0.0, "hbm_bytes": 0.0,
                                             "rows": 0})
            ent["flops"] += r.flops + (r.bwd_flops if r.bwd_counted else 0)
            ent["hbm_bytes"] += r.hbm_bytes + (
                r.bwd_hbm_bytes if r.bwd_counted else 0)
            ent["rows"] += (r.row_reads + r.row_writes
                            + ((r.bwd_row_reads + r.bwd_row_writes)
                               if r.bwd_counted else 0))
        return out

    def roofline(self):
        """Price the rollup at the module's constants: the step's static
        floor is ``max(compute, HBM)`` overlapped, plus the row-latency
        term on top (row DMAs serialize behind the streams). The
        constants and what they are ride in the dict, so the estimate is
        re-derivable."""
        mm, hbm = _MM_TFLOPS * 1e12, _HBM_GBS * 1e9
        g_ns, s_ns = _GATHER_NS, _SCATTER_NS
        t_c = self.flops / mm
        t_b = self.hbm_bytes / hbm
        t_r = (self.row_reads * g_ns + self.row_writes * s_ns) * 1e-9
        roof = max(t_c, t_b) + t_r
        bound = ("rows" if t_r > max(t_c, t_b)
                 else ("hbm" if t_b >= t_c else "compute"))
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "row_reads": self.row_reads,
            "row_writes": self.row_writes,
            "t_compute_s": t_c,
            "t_hbm_s": t_b,
            "t_row_s": t_r,
            "roofline_s": roof,
            "bound": bound,
            "train": self.train,
            "batch": self.batch,
            "amp": self.amp,
            "ceilings": {
                "matmul_flops": mm, "hbm_bytes_per_s": hbm,
                "gather_ns_per_row": g_ns, "scatter_ns_per_row": s_ns,
                "source": _CEILING_SOURCE},
            "uncosted_ops": self.uncosted,
            "unresolved_ops": sorted({r.op.type for r in self.unresolved}),
        }


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def estimate_program(program, batch=None, amp=False, feed_names=None):
    """Walk the program's dataflow region charging every op through its
    registered cost rule; returns a :class:`CostEstimate`.

    ``batch`` resolves the symbolic -1 batch dims (default 1).
    Training is detected structurally: an ``autodiff`` op's replay list
    names exactly the forward ops whose backward columns count — ops
    after it (optimizer updates) are forward-only by construction.
    Control-flow bodies are charged ONCE per build (trip counts are a
    runtime property); such records carry their region name."""
    # defer heavy imports so `import paddle_tpu.analysis` stays light
    from .dataflow import program_region

    ctx = CostCtx(batch=batch or 1, amp=amp)
    region = program_region(program)
    uncosted = set()
    by_id = {}
    replayed = []
    for reg, node in region.walk():
        op = node.op
        if op.type in _REPLAY_OPS:
            replayed.extend(op.attr("fwd_ops") or ())
            continue
        rule = cost_rule(op.type)
        ctx._region = reg.name
        if rule is None:
            uncosted.add(op.type)
            by_id[id(op)] = ctx.add(op, unresolved=False,
                                    note="no cost rule")
            continue
        n_before = len(ctx.records)
        try:
            rule(ctx, op)
        except Exception as e:  # a buggy rule must never block analysis
            by_id[id(op)] = ctx.add(
                op, unresolved=True,
                note="cost rule crashed (%s: %s)" % (type(e).__name__, e))
            continue
        for rec in ctx.records[n_before:]:
            by_id[id(rec.op)] = rec
    train = bool(replayed)
    for op in replayed:
        rec = by_id.get(id(op))
        if rec is not None:
            rec.bwd_counted = True
    return CostEstimate(ctx.records, train, uncosted,
                        batch=ctx.batch, amp=amp)
