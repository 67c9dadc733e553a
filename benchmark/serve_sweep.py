"""The knee's sweep: one engine, set up once as a run sets it up, offered the
cell's own traffic at one fixed rate after another. For each rate it prints
what a run's window would read (tokens offered and delivered, the latency
readings, the server's own TTFT and TPOT, slot occupancy, how long the first
and the last quarter of the window's requests waited). The program delivers
an answer whole, so the tokens delivered inside a window lag those offered
by the answers in flight; the knee is therefore read from the slots: the
highest rate at which they are not all taken (the backlog cell's output
tokens a second over the mix's mean answer gives the same rate). A cell is
fixed below it, at about four fifths; no run ever searches for a rate.
Never part of a run; ``PERF.md`` records what it read on the chip.

    python3 benchmark/serve_sweep.py --workload <cell> --seed 1 \\
        --rates 2 3 4 5 6 --seconds 10
"""

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--settle", type=float, default=30.0,
                    help="longest wait for a rate's requests to finish")
    ap.add_argument("--ladder", type=int, nargs="+",
                    help="batch rungs in place of the mix's (what a cell "
                         "with other rungs would read)")
    ap.add_argument("--seq-ladder", type=int, nargs="+",
                    help="context rungs in place of the mix's")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.jobs import serve, serve_traffic

    run = harness.Run(args.manifest, args.workload, args.seed, args.seconds,
                      0, args.rehearse, time.time())
    run.claim_devices()
    if args.ladder:
        run.traffic["engine"]["ladder"] = args.ladder
    if args.seq_ladder:
        run.traffic["engine"]["seq_ladder"] = args.seq_ladder
    server = serve.Server(run)
    server.warm_up(run.seed)
    for k, rate in enumerate(args.rates):
        traffic = copy.deepcopy(run.traffic)
        traffic["arrivals"].update(kind="poisson", rate_per_s=rate,
                                   tail_s=0.0)
        traffic["arrivals"].setdefault("ramp_s", 8.0)
        ramp = float(traffic["arrivals"]["ramp_s"])
        requests = serve_traffic.schedule(
            traffic, server.args["vocab_size"], run.seed + k, args.seconds)
        compiles = run.compiles.count
        ticks = serve.Ticks(server.engine)
        t0 = time.perf_counter()
        w0, w1 = t0 + ramp, t0 + ramp + args.seconds
        client = serve.Client(server.engine, requests, t0)
        client.start()
        ticks.watch(w0)
        at_open = serve.counters(server.engine)
        ticks.watch(w1)
        at_close = serve.counters(server.engine)
        ticks.watch_a_quantum(w1 + 2.0)
        while time.perf_counter() < w1 + args.settle and any(
                r.done is None for r in requests):
            time.sleep(0.02)
        client.stop.set()
        client.join(10.0)
        counted, failed, answered = serve.account(
            requests, "poisson", t0, w0, w1, w1 + serve.DRAIN_S)
        row = serve.client_numbers(counted, failed, answered, t0,
                                   args.seconds)
        row["serve_tokens_per_s"] = ticks.tokens_per_s(w0, w1)
        offered = sum(r.max_new for r in counted)
        in_time = sum(len(r.tokens) for r in counted
                      if serve._ok(r) and r.done <= w1 + serve.DRAIN_S)
        waits = [r.done - (t0 + r.at) for r in counted if serve._ok(r)]
        steps = at_close["decode_steps"] - at_open["decode_steps"]
        ttft_n = at_close["ttft_count"] - at_open["ttft_count"]
        tpot_n = at_close["tpot_count"] - at_open["tpot_count"]
        row.update(
            rate_per_s=rate, requests=len(counted), failed=len(failed),
            offered_tokens=offered, delivered_by_drain=in_time,
            delivered_share=in_time / max(1, offered),
            finished_inside=sum(1 for r in counted
                                if serve._ok(r) and r.done < w1),
            first_quarter_wait_ms=1e3 * float(np.mean(
                waits[:max(1, len(waits) // 4)])) if waits else None,
            last_quarter_wait_ms=1e3 * float(np.mean(
                waits[-max(1, len(waits) // 4):])) if waits else None,
            occupancy_pct=100.0 * (at_close["slot_live"]
                                   - at_open["slot_live"])
            / max(1.0, at_close["slot_total"] - at_open["slot_total"]),
            decode_steps=steps,
            prefill_chunks=at_close["prefill_chunks"]
            - at_open["prefill_chunks"],
            server_ttft_mean_ms=1e3 * (at_close["ttft_total_s"]
                                       - at_open["ttft_total_s"])
            / max(1.0, ttft_n),
            server_tpot_mean_ms=1e3 * (at_close["tpot_total_s"]
                                       - at_open["tpot_total_s"])
            / max(1.0, tpot_n),
            late_ms=client.late_s * 1e3,
            compiled=run.compiles.count - compiles,
            memory_peak_bytes=run.memory_peak_bytes())
        print("sweep " + json.dumps(row, sort_keys=True))
        sys.stdout.flush()
    server.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
