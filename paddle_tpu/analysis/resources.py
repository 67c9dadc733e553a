"""Static resource lints over the Program IR (ISSUE 15).

Three lint families, all opt-in (``RESOURCE_CHECKS`` — wired through
``Executor.run(verify="strict")`` / ``PADDLE_TPU_VERIFY=strict``, the
CLI, and ``ServingEngine`` build-time verification; they are NOT part of
``DEFAULT_CHECKS`` because a resource verdict is advice about a chip,
not a correctness property of the program):

  * **vmem-gate** — the Pallas kernel family's admission gates
    (``ops/fused_conv.gate``, ``ops/scatter.gate``,
    ``ops/flash_attention.kernel_plan``, ``ops/gated_delta.kernel_plan``)
    evaluated SHAPE-ONLY
    (``static_only`` / no ``platform``): a program that will
    silently fall off its fused kernel on the chip is reported at
    build time as a finding with op provenance and the gate's structured
    reasons, instead of a quiet perf cliff.
  * **recompile-hazard** — an op output with an unknown (-1) dim in a
    NON-batch position makes every distinct runtime shape a fresh XLA
    compilation (the dynamic-shape decode outputs class).
  * **compile-cache** — the serving bucket ladders' executable-count
    bound, PROVED from the decode spec (rungs above the spec's
    ``ctx_cap`` can never be dispatched): ``len(ladder) x
    len(valid ctx rungs)`` compared against the budget.
"""

import os

from .passes import AnalysisResult, Diagnostic

__all__ = ["RESOURCE_CHECKS", "check_resources", "check_vmem_gates",
           "check_recompile_hazard", "decode_cache_verdict",
           "DEFAULT_CACHE_BUDGET"]

RESOURCE_CHECKS = ("vmem-gate", "recompile-hazard")

# compiled-executable budget per fetch program: beyond this, serving
# warmup/compile time and XLA cache memory dominate (override with
# PADDLE_TPU_COMPILE_CACHE_BUDGET)
DEFAULT_CACHE_BUDGET = 64


def _gate_diag(op, decision, region, wanted):
    return Diagnostic(
        "warning", "vmem-gate",
        "op '%s' %s" % (op.type, decision.describe())
        + (" — the op was created expecting the %s kernel" % wanted
           if wanted else ""),
        op=op, region=region)


def check_vmem_gates(region, batch=None, amp=False, diags=None):
    """Evaluate every Pallas-family op's admission gate statically
    (shape/VMEM checks only — platform checks assume the chip).
    Findings:

      * ``fused_conv2d`` refused for ANY static reason — the epilogue
        fusion created the op expecting the kernel, so a refusal means
        the rewrite buys nothing on this geometry;
      * sparse-update ``scatter``/optimizer tables and ``flash_attention``
        sites blocked ONLY by the VMEM budget — the actionable class
        (raise the budget or shrink the shape; everything else about the
        shape qualifies);
      * ``gated_delta_rule`` refused for any static reason: the site then
        runs XLA's chunk scan (a head dimension that is no multiple of
        128, another chunk than 64)."""
    from .cost import CostCtx

    diags = [] if diags is None else diags
    ctx = CostCtx(batch=batch or 1, amp=amp)
    for reg, node in region.walk():
        op = node.op
        if op.type == "fused_conv2d":
            _check_fused_conv(ctx, op, reg.name, diags)
        elif op.type == "flash_attention":
            _check_flash(ctx, op, reg.name, diags)
        elif op.type in ("lookup_table", "sharded_lookup_table"):
            _check_sparse_table(ctx, op, reg.name, diags)
        elif op.type == "gated_delta_rule":
            _check_gated_delta(ctx, op, reg.name, diags)
    return diags


def _check_fused_conv(ctx, op, region, diags):
    from ..ops import fused_conv

    xs = ctx.shape(op.input("Input"))
    ws = ctx.shape(op.input("Filter"))
    if xs is None or ws is None:
        return
    esize = 2 if ctx.amp else ctx.esize(op.input("Input"))
    decision = fused_conv.gate(
        xs, ws, tuple(op.attr("strides", [1, 1])),
        tuple(op.attr("paddings", [0, 0])),
        tuple(op.attr("dilations", [1, 1])), op.attr("groups", 1) or 1,
        esize, op.input("Residual") is not None, static_only=True)
    if not decision:
        diags.append(_gate_diag(op, decision, region,
                                "pallas_fused_conv"))


def _check_flash(ctx, op, region, diags):
    from ..ops import flash_attention as fa

    qs = ctx.shape(op.input("Q"))
    ks = ctx.shape(op.input("K"))
    if qs is None or ks is None or len(qs) != 3 or len(ks) != 3:
        return
    bias = op.input("Bias")
    bias_kind = None
    if bias is not None:
        bs = ctx.shape(bias)
        key_form = bs is not None and (
            (len(bs) == 4 and bs[1] == 1 and bs[2] == 1)
            or len(bs) == 2)
        bias_kind = "key" if key_form else "rich"
    esize = 2 if ctx.amp else ctx.esize(op.input("Q"))
    plan = fa.kernel_plan(
        qs, ks, op.attr("num_heads", 1), esize,
        causal=op.attr("causal", False),
        dropout_rate=op.attr("dropout_rate", 0.0) or 0.0,
        bias_kind=bias_kind, rng_available=True)
    # a site that only the VMEM budget keeps off the copy-free kernels: one
    # lane window's full-T q/do/dq no longer fit (bf16: past T=3072 at
    # D=64 and D=128, past T=1024 at D=256), so the head-split kernels run,
    # with their [B,T,H,D] relayout copies round the site
    if plan.kernel in ("reference", "head_split_stream") and \
            plan.blocked_only_by("vmem"):
        diags.append(_gate_diag(op, plan, region, "packed_stream"))


def _check_gated_delta(ctx, op, region, diags):
    from ..ops import gated_delta

    qs, vs = ctx.shape(op.input("Q")), ctx.shape(op.input("V"))
    if qs is None or vs is None or len(qs) != 3 or len(vs) != 3:
        return
    hk, hv = int(op.attr("num_k_heads")), int(op.attr("num_v_heads"))
    plan = gated_delta.kernel_plan(
        vs[1], hk, hv, qs[-1] // hk, vs[-1] // hv,
        int(op.attr("chunk", 64)))
    if not plan:
        diags.append(_gate_diag(op, plan, region, "gated_delta"))


def _check_sparse_table(ctx, op, region, diags):
    """The table this lookup's backward scatter-adds into: report when
    the ONLY thing keeping it off the VMEM-resident Pallas scatter is
    the budget (the DeepFM [100k, 32] class)."""
    from ..ops import scatter as scatter_mod

    ws = ctx.shape(op.input("W"))
    ids = ctx.shape(op.input("Ids"))
    if ws is None or ids is None or len(ws) != 2:
        return
    if len(ids) >= 2 and ids[-1] == 1:
        ids = ids[:-1]
    n = 1
    for d in ids:
        n *= d
    dt = getattr(op.input("W"), "dtype", "float32")
    decision = scatter_mod.gate(ws[0], ws[1], n, dt, static_only=True)
    if not decision and decision.blocked_only_by("vmem"):
        diags.append(Diagnostic(
            "warning", "vmem-gate",
            "op '%s': this table's sparse backward %s"
            % (op.type, decision.describe()), op=op, region=region))


def check_recompile_hazard(region, diags=None):
    """An op output declaring -1 in a non-leading dim: the leading dim
    is the symbolic batch (one bucket ladder bounds it), but an unknown
    INNER dim means every distinct runtime extent is a fresh XLA
    compilation — the dynamic-shape decode-output class."""
    diags = [] if diags is None else diags
    for reg, node in region.walk():
        op = node.op
        for vs in op.outputs.values():
            for v in vs:
                shape = getattr(v, "shape", None)
                if shape is None:
                    continue
                dyn = [i for i, d in enumerate(shape)
                       if (d is None or int(d) < 0) and i > 0]
                if dyn:
                    diags.append(Diagnostic(
                        "warning", "recompile-hazard",
                        "op '%s' output '%s' has unknown dim%s %s beyond "
                        "the batch dim — every distinct runtime extent "
                        "compiles a fresh executable (bucket it, or pad "
                        "to a ladder)" % (op.type, v.name,
                                          "s" if len(dyn) != 1 else "",
                                          dyn),
                        op=op, var=v.name, region=reg.name))
    return diags


def check_resources(program, batch=None, amp=False, checks=None):
    """Run the resource lints; returns an :class:`AnalysisResult`."""
    from .dataflow import program_region

    checks = set(RESOURCE_CHECKS if checks is None else checks)
    region = program_region(program)
    diags = []
    if "vmem-gate" in checks:
        check_vmem_gates(region, batch=batch, amp=amp, diags=diags)
    if "recompile-hazard" in checks:
        check_recompile_hazard(region, diags=diags)
    return AnalysisResult(diags)


def cache_budget():
    try:
        return int(os.environ.get("PADDLE_TPU_COMPILE_CACHE_BUDGET",
                                  DEFAULT_CACHE_BUDGET))
    except ValueError:
        return DEFAULT_CACHE_BUDGET


def decode_cache_verdict(spec, ladder, ctx_ladder, budget=None,
                         prefill_ladder=None):
    """Prove the serving decode tier's compile-cache bound from the
    ladders: the scheduler dispatches (and ``warmup`` pre-compiles) one
    step executable per (batch rung, ctx rung) pair and — when a chunked
    prefill/verify program rides along (``prefill_ladder``) — one chunk
    executable per (batch rung, ctx rung, prefill rung) triple, so the
    bound is ``len(ladder) * len(ctx_ladder) * (1 + len(prefill_ladder))``
    — structural, not empirical (duplicate rungs are deduped the way
    ``DecodeBatcher`` dedups them). A chunk executable is made for the
    rung's own height, ``decode_batcher.chunk_rows(k, b)`` rows of the
    bucket's ``b`` (``[rows, k]`` tokens, ``[rows, c, *tail]`` caches),
    and for nothing else, so sub-batched chunks add no triple; the two
    jitted row copies beside a sub-batched rung are not step programs
    and are not counted. Returns ``(bound, AnalysisResult)``:
    a finding when the bound exceeds the budget, plus one for each ctx
    rung above the decode spec's ``ctx_cap`` and one for each prefill
    rung above it (suspect ladder config: the programs were sized for
    ``ctx_cap``, so a larger rung is paying compile + cache memory for
    geometries the model was not built to use — still counted in the
    bound, because nothing stops it being dispatched)."""
    budget = cache_budget() if budget is None else int(budget)
    cap = int(spec.get("ctx_cap", 0) or 0) if isinstance(spec, dict) else 0
    ladder = tuple(sorted(set(ladder or ())))
    ctx_ladder = tuple(sorted(set(ctx_ladder or ())))
    prefill_ladder = tuple(sorted(set(prefill_ladder or ())))
    suspect = tuple(c for c in ctx_ladder if cap and c > cap)
    bound = max(len(ladder), 1) * max(len(ctx_ladder), 1) \
        * (1 + len(prefill_ladder))
    diags = []
    for c in suspect:
        diags.append(Diagnostic(
            "warning", "compile-cache",
            "ctx ladder rung %d exceeds the decode spec's cache capacity "
            "%d — the step program was sized for %d, so this rung spends "
            "compile time and cache memory on a geometry the model was "
            "not built for (drop it, or rebuild the step with a larger "
            "capacity)" % (c, cap, cap)))
    for k in (p for p in prefill_ladder if cap and p > cap):
        diags.append(Diagnostic(
            "warning", "compile-cache",
            "prefill ladder rung %d exceeds the decode spec's cache "
            "capacity %d — a chunk can never be longer than the cache it "
            "writes into, so this rung compiles a geometry no admissible "
            "prompt dispatches (drop it) — still counted in the bound, "
            "because nothing stops it being dispatched" % (k, cap)))
    if bound > budget:
        chunk_note = ("%d batch rungs x %d ctx rungs"
                      % (max(len(ladder), 1), max(len(ctx_ladder), 1)))
        if prefill_ladder:
            chunk_note += (" x (1 step + %d chunk rungs)"
                           % len(prefill_ladder))
        diags.append(Diagnostic(
            "warning", "compile-cache",
            "decode bucket ladders compile up to %d executables "
            "(%s), over the %d budget — "
            "warmup and XLA cache memory scale with this product "
            "(PADDLE_TPU_COMPILE_CACHE_BUDGET overrides)"
            % (bound, chunk_note, budget)))
    return bound, AnalysisResult(diags)
