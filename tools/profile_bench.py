"""Capture a jax.profiler trace of a bench config and print the device-op
time breakdown (top HLO ops by self time, grouped by category).

Usage: python tools/profile_bench.py --model transformer [--steps 10]
Writes the raw trace under /tmp/jaxtrace-<model> and prints a table.

``--bytes`` additionally prints profiler-MEASURED HBM bytes/step (the
per-op "bytes accessed" xplane stats) next to the analytic bytes model's
prediction (attribute_resnet's floor model for resnet50) — so every
roofline claim is one flag away from being cross-checked against what the
chip actually moved (``bench.py --attribute`` runs this automatically).
"""

import argparse
import glob
import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def capture(model, steps, batch=None, seq=None):
    import jax
    import paddle_tpu as fluid
    from bench import _build

    on_tpu = jax.devices()[0].platform == "tpu"
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        spec, dbatch, metric, unit, per_example, _seq = _build(
            model, on_tpu, seq_override=seq)
        opt = fluid.optimizer.Adam(learning_rate=1e-4)
        if os.environ.get("BENCH_AMP", "1") == "1":
            opt = fluid.amp.decorate(opt)
        opt.minimize(spec.loss)
    batch = batch or int(os.environ.get("BENCH_BATCH", dbatch))

    exe = fluid.Executor(fluid.XLAPlace(0))
    scope = fluid.Scope()
    trace_dir = "/tmp/jaxtrace-%s" % model
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = spec.sample_batch(batch, np.random.RandomState(0))
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        for _ in range(3):
            loss_val, = exe.run(main_prog, feed=feed, fetch_list=[spec.loss])
        np.asarray(loss_val)
        jax.profiler.start_trace(trace_dir)
        for _ in range(steps):
            loss_val, = exe.run(main_prog, feed=feed,
                                fetch_list=[spec.loss], return_numpy=False)
        np.asarray(loss_val)
        jax.profiler.stop_trace()
    return trace_dir, main_prog, batch


def _device_planes(trace_dir):
    """Device planes of the newest xplane proto under ``trace_dir``, read
    with ``jax.profiler.ProfileData`` (nothing but JAX)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise SystemExit("no xplane found under " + trace_dir)
    return [plane for plane in ProfileData.from_file(paths[-1]).planes
            if "TPU" in plane.name or "/device" in plane.name.lower()]


def parse_xplane(trace_dir):
    """Parse the newest xplane proto under ``trace_dir`` into
    (plane_name, line_name, op_name, seconds) rows. Shared by
    ``tools/attribute_transformer.py``."""
    return [(plane.name, line.name, ev.name, ev.duration_ns / 1e9)
            for plane in _device_planes(trace_dir)
            for line in plane.lines for ev in line.events]


def parse_xplane_bytes(trace_dir):
    """Per-op HBM bytes from the xplane per-event "bytes accessed" stats
    (summed over occurrences) on the sync op line. Returns {} when the
    platform/profiler version doesn't record them."""
    try:
        planes = _device_planes(trace_dir)
    except SystemExit:
        return {}
    agg = defaultdict(int)
    for plane in planes:
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                for stat, value in ev.stats:
                    # EXACT name: ops also carry per-memory-space
                    # breakdown stats ("bytes accessed0", ...) that would
                    # double-count against the aggregate
                    if stat.lower() == "bytes accessed":
                        agg[ev.name.split(" =")[0].lstrip("%")] += int(value)
    return dict(agg)


def bytes_report(trace_dir, steps, model=None, program=None, batch=None):
    """Measured HBM bytes/step vs the analytic bytes model (resnet50 has
    one — attribute_resnet's floor model; other configs print measured
    only). The cross-check every roofline claim should survive."""
    per_op = parse_xplane_bytes(trace_dir)
    total = sum(per_op.values())
    print("\n== HBM bytes/step (xplane 'bytes accessed' stats) ==")
    if not total:
        print("  no bytes-accessed stats in this trace "
              "(CPU run or profiler version without per-op memory stats)")
        measured = None
    else:
        measured = total / steps
        print("  measured : %8.2f GB/step over %d ops"
              % (measured / 1e9, len(per_op)))
    analytic = None
    if model == "resnet50" and program is not None:
        from attribute_resnet import floors as resnet_floors

        _, _, analytic = resnet_floors(program, batch)
        print("  analytic : %8.2f GB/step (RESNET_ROOFLINE bytes model)"
              % (analytic / 1e9))
        if measured:
            print("  measured/model = %.2fx  (>1: traffic the model does "
                  "not count — un-fused passes, spills; <1: fusions "
                  "sharing passes the model charges separately)"
                  % (measured / analytic))
    return measured, analytic


def analyze(trace_dir, steps, topk=40):
    """Aggregate device-op self time from an xplane trace."""
    rows = parse_xplane(trace_dir)

    # Aggregate by op name on op-level lines
    by_line = defaultdict(float)
    for pn, ln, name, dur in rows:
        by_line[(pn, ln)] += dur
    print("== device lines (total s over %d steps) ==" % steps)
    for (pn, ln), tot in sorted(by_line.items(), key=lambda kv: -kv[1]):
        print("  %-60s %8.4f" % (pn + " :: " + ln, tot))

    # EXACT line match: "Async XLA Ops" carries overlapped copy/slice
    # starts whose durations double-count against the sync op stream.
    oprows = [r for r in rows if r[1] == "XLA Ops"]
    if not oprows:
        oprows = rows
    agg = defaultdict(lambda: [0.0, 0])
    for pn, ln, name, dur in oprows:
        agg[name][0] += dur
        agg[name][1] += 1
    total = sum(v[0] for v in agg.values())
    print("\n== top ops by self time (total device %.4f s, %.2f ms/step) =="
          % (total, total / steps * 1e3))
    out = []
    for name, (tot, cnt) in sorted(agg.items(), key=lambda kv: -kv[1][0])[:topk]:
        pct = 100.0 * tot / max(total, 1e-12)
        print("  %6.2f%%  %9.3f ms  %6d  %s"
              % (pct, tot * 1e3, cnt, name[:110]))
        out.append({"name": name, "ms": tot * 1e3, "pct": pct, "count": cnt})
    with open(os.path.join(trace_dir, "summary.json"), "w") as f:
        json.dump(out, f, indent=1)

    # category roll-up: the ms-by-ms budget table
    cat = defaultdict(float)
    for pn, ln, name, dur in oprows:
        cat[_categorize(name)] += dur
    print("\n== category budget (ms/step) ==")
    for c, tot in sorted(cat.items(), key=lambda kv: -kv[1]):
        print("  %8.3f ms  %5.1f%%  %s"
              % (tot / steps * 1e3, 100.0 * tot / max(total, 1e-12), c))


def _categorize(name):
    """Bucket an HLO op name into a budget category."""
    n = name.lower()
    if "custom-call" in n or "tpu_custom_call" in n or "pallas" in n:
        return "pallas-custom-call"
    if n.startswith("%copy") or "copy-start" in n or "copy-done" in n:
        return "copies"
    if "slice-start" in n or "slice-done" in n or "async" in n:
        return "async-slices"
    if ("convolution" in n or n.lstrip("%").startswith("dot")
            or "dot_general" in n):
        return "matmul"
    if "rng" in n or "bitcast-convert" in n and "threefry" in n:
        return "rng"
    if "all-reduce" in n or "all-gather" in n or "collective" in n:
        return "collectives"
    if "reduce" in n:
        return "reduce"
    if "fusion" in n:
        return "fusion-other"
    return "other"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="transformer")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--analyze-only", default=None)
    ap.add_argument("--seq", type=int, default=None,
                    help="transformer seq_len override (so the traced "
                         "workload matches e.g. the seq-2048 bench line)")
    ap.add_argument("--bytes", action="store_true",
                    help="print measured HBM bytes/step vs the analytic "
                         "bytes model")
    args = ap.parse_args()
    if args.analyze_only:
        analyze(args.analyze_only, args.steps)
        if args.bytes:
            bytes_report(args.analyze_only, args.steps, args.model)
    else:
        td, prog, batch = capture(args.model, args.steps, args.batch,
                                  args.seq)
        analyze(td, args.steps)
        if args.bytes:
            bytes_report(td, args.steps, args.model, prog, batch)
