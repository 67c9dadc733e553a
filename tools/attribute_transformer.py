"""Join an xplane profile with the optimized HLO's per-op metadata to get a
semantic ms-by-ms budget of a bench step (VERDICT r3 ask #1a).

The profile gives per-HLO-op self time on the sync "XLA Ops" line; the HLO
text gives each op's jax-level op_name metadata (e.g.
"jit(step)/autodiff/transpose(jvp(mul))/dot_general" with a source file of
the emitting layer). Grouping by metadata attributes time to model-level
components, which per-op names alone cannot (XLA output-fuses backward
matmuls into optimizer updates, etc.).

Usage:
  python tools/attribute_transformer.py --model transformer --steps 10
  (or --trace /tmp/jaxtrace-transformer --hlo /tmp/opt_hlo.txt to reuse)
"""

import argparse
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from profile_bench import parse_xplane  # shared xplane walk


def profile_self_times(trace_dir):
    agg = defaultdict(float)
    for pn, ln, name, dur in parse_xplane(trace_dir):
        if ln != "XLA Ops":  # exact: skip the overlapped async line
            continue
        # bare instruction name: "%foo.12 = ..." -> "foo.12"
        agg[name.split(" =")[0].lstrip("%")] += dur
    return agg


def hlo_metadata(hlo_path):
    """instruction name -> (op_name metadata, source_file:line)."""
    meta = {}
    pat = re.compile(r"%([\w.\-]+) = .*?metadata=\{op_name=\"([^\"]*)\""
                     r"(?:.*?source_file=\"([^\"]*)\".*?source_line=(\d+))?")
    with open(hlo_path) as f:
        for ln in f:
            m = pat.search(ln)
            if m:
                name, op_name, sf, sl = m.groups()
                meta[name] = (op_name, "%s:%s" % (os.path.basename(sf or ""),
                                                  sl or ""))
    return meta


BUCKETS = [
    # (label, regex over "op_name || src")
    # attention-adjacent relayouts FIRST: transposes/copies emitted from
    # flash_attention.py are the [B,T,H,D] head-split copies around the
    # streaming custom calls — the packed streaming path exists to zero
    # this bucket
    ("attn-layout-copy",
     r"(?=.*flash_attention)(?=.*(transpose|copy|reshape))"),
    ("attention-kernel", r"flash_attention|attn_fwd|attn_bwd"),
    ("vocab-head-ce", r"fused_linear_smooth_ce|softmax_with_cross_entropy|"
                      r"label_smooth|out_proj"),
    ("dropout-rng", r"dropout|rng|threefry|random_bits"),
    ("layer-norm", r"layer_norm"),
    ("embedding", r"lookup_table|embedding|one_hot|gather"),
    ("adam-update", r"adam|moment|beta|optimizer"),
    # "mul" here means the framework's mul OP (matmul, math_ops.py) — match
    # on the source file, not the jax op_name, so elementwise multiplies
    # (".../jvp(mul)") don't land in this bucket
    ("matmul-fwd-bwd", r"dot_general|matmul"),
    ("elementwise-residual", r"elementwise|add|relu|scale|softmax"),
    ("reduce-loss", r"reduce|mean|sum"),
]


def bucket_of(op_name, src):
    s = (op_name + " " + src).lower()
    for label, rx in BUCKETS:
        if re.search(rx, s):
            return label
    return "other"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="/tmp/jaxtrace-transformer")
    ap.add_argument("--hlo", default="/tmp/opt_hlo.txt")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--detail", action="store_true",
                    help="print top unmatched/other ops")
    args = ap.parse_args()

    times = profile_self_times(args.trace)
    meta = hlo_metadata(args.hlo)
    steps = args.steps

    cat = defaultdict(float)
    misses = []
    rows = defaultdict(float)
    for name, t in times.items():
        op_name, src = meta.get(name, ("", ""))
        if not op_name:
            # async done/start markers etc.: classify by instruction name
            op_name = name
        b = bucket_of(op_name, src)
        cat[b] += t
        rows[(b, op_name.split("/")[-1], src)] += t
        if b == "other":
            misses.append((t, name, op_name))

    total = sum(times.values())
    print("== semantic budget (over %d steps; total %.1f ms/step) =="
          % (steps, total / steps * 1e3))
    for b, t in sorted(cat.items(), key=lambda kv: -kv[1]):
        print("  %8.2f ms  %5.1f%%  %s"
              % (t / steps * 1e3, 100 * t / total, b))
    copies_ms = cat.get("attn-layout-copy", 0.0) / steps * 1e3
    print("attention layout copies: %.2f ms/step (0 = the packed "
          "streaming path is copy-free; pre-r6 head-split measured "
          "~36 ms at seq-2048)" % copies_ms)
    if args.detail:
        print("\n== top rows ==")
        top = sorted(rows.items(), key=lambda kv: -kv[1])[:40]
        for (b, tail, src), t in top:
            print("  %7.2f ms  %-22s %-40s %s"
                  % (t / steps * 1e3, b, tail[:40], src))
        print("\n== top 'other' ==")
        for t, name, op_name in sorted(misses, reverse=True)[:15]:
            print("  %7.2f ms  %-30s %s"
                  % (t / steps * 1e3, name[:30], op_name[:70]))


if __name__ == "__main__":
    main()
