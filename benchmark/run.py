"""Entry point of the benchmark: one cell, one run, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, its traffic
mix, the runner of its job kind and the reader of each per-layer metric are
files under ``benchmark/`` that are found by the names the manifest gives
(see ``benchmark/README.md``). Nothing here knows a model or a job kind.

``--rehearse`` lets a run go through on the CPU (tests, dry runs). Its result
line names platform ``cpu`` and carries no device metric; it is never a
measurement. Without it a run that finds no TPU, or fewer chips than the
cell asks for, exits with code 3 and prints no result.
"""

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another manifest (tests); paths in it are relative "
                         "to its directory")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU; prints platform cpu and no device "
                         "metric")
    args = ap.parse_args(argv)

    run = harness.Run.from_args(args, PROCESS_START)
    job = harness.load_module(run.path("benchmark", "jobs",
                                       run.traffic["job"] + ".py"))
    result = job.run(run)
    harness.emit(run, result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
