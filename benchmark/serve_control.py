"""The control of a serving cell: the plain reference put in the program's
place and computed in fp8, the nearest precision below the bfloat16 the
configuration states (``reference/precision.py``). For each seed it drives
the cell as a run does (set-up, the schedule at the cell's own load, a
window of ``--seconds``), takes the sample a run's check takes with the
tokens the program served, frees the program, and then reads, at each
position of the same prompts and tokens, the gap of the token that the fp8
logits put first; the same positions read in float32 against the served
tokens are the program's own reading, printed beside it. The control's
numbers are compared exactly as a run's are and have to come out NOT
correct on every seed. The benchmark's own runs never run it; ``PERF.md``
records what it read on the chip.

    python3 benchmark/serve_control.py --workload <cell> --seeds 1 2 3

A chip serves one process, and a run's set-up builds one engine: each seed
is a process of its own (``--seeds`` starts them one after another).
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def control(manifest, workload, seed, seconds, rehearse, precision="fp8"):
    """(rows of the control, rows of the program) for one seed."""
    from benchmark.jobs import serve, serve_check

    run = harness.Run(manifest, workload, seed, seconds, 0, rehearse,
                      time.time())
    _, sampled, weights, args, pad_to = serve.serve_window(run)
    reference = serve_check.load_reference(run)
    exact = serve_check.precision(run, "exact")
    lower = serve_check.precision(run, precision)
    limits = run.config["limits"]
    program = serve_check.compare(serve_check.served_gaps(
        reference, weights, sampled, args, exact, pad_to), limits)
    control = serve_check.compare(serve_check.control_gaps(
        reference, weights, sampled, args, exact, lower, pad_to), limits)
    return control, program


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if len(args.seeds) > 1:  # one process a seed: one engine a process
        caught = 0
        for seed in args.seeds:
            caught += subprocess.call(
                [sys.executable, os.path.abspath(__file__), "--workload",
                 args.workload, "--seeds", str(seed), "--seconds",
                 str(args.seconds), "--manifest", args.manifest]
                + (["--rehearse"] if args.rehearse else [])) == 0
        print("control came out not correct on %d of %d seeds"
              % (caught, len(args.seeds)))
        return 0 if caught == len(args.seeds) else 1
    seed = args.seeds[0]
    control_rows, program_rows = control(
        args.manifest, args.workload, seed, args.seconds, args.rehearse)
    correct = all(r["ok"] for r in control_rows)
    print(json.dumps({
        "control": "fp8", "workload": args.workload, "seed": seed,
        "correct": correct,
        "compared": {r["name"]: [r["value"], r["limit"]]
                     for r in control_rows},
        "program": {r["name"]: [r["value"], r["limit"]]
                    for r in program_rows}}))
    return 0 if not correct else 1


if __name__ == "__main__":
    sys.exit(main())
