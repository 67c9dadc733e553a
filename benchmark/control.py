"""The control of a training cell: the plain reference put in the program's
place and computed in fp8, the nearest precision below the bfloat16 the
configurations state (``reference/precision.py``). For each seed it follows
the cell's first three steps twice, at the cell's own size, from the same
seeded weights and batches, in float32 and in fp8, and compares the second
with the first exactly as a run compares the program with the reference. It
has to come out as NOT correct on every seed. The benchmark's own runs never
run it; ``PERF.md`` records what it read on the chip.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, seeded  # noqa: E402


def control(manifest, workload, seed, rehearse, precision="fp8"):
    """Rows of ``train_check.compare`` for one seed of the control."""
    from benchmark.jobs import train_check

    run = harness.Run(manifest, workload, seed, 0, 0, rehearse, time.time())
    run.claim_devices()
    train = harness.load_module(run.path("benchmark", "jobs", "train.py"))
    _, main, _, _, _, args = train.build_program(run)
    specs = train.weight_specs(main, run.config)
    weights_fn = seeded.make_weights_fn(specs, seed)
    batches = seeded.make_batches(run.config["feeds"], run.traffic["sizes"],
                                  seed, train.CHECK_STEPS)
    exact = train_check.follow(run, weights_fn, batches, args, "exact")
    lower = train_check.follow(run, weights_fn, batches, args, precision)
    train_check.dump(run, precision, lower, exact)
    return train_check.compare(lower, exact, run.config["limits"],
                               train.leaf_sizes(specs))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    caught = 0
    for seed in args.seeds:
        rows = control(args.manifest, args.workload, seed, args.rehearse)
        correct = all(r["ok"] for r in rows)
        caught += not correct
        print(json.dumps({"control": "fp8", "workload": args.workload,
                          "seed": seed, "correct": correct,
                          "compared": {r["name"]: [r["value"], r["limit"]]
                                       for r in rows}}))
    print("control came out not correct on %d of %d seeds"
          % (caught, len(args.seeds)))
    return 0 if caught == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
