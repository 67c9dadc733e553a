"""Scheduler: mean milliseconds from ``submit`` to a request's first
sampled token as the SERVER reads it (``ServingMetrics.ttft``), over the
requests whose first token fell in the timed window: queueing for a slot
plus prefill. Not a client's reading: the program hands a client nothing
before the whole answer (PERF.md, Open questions). Program counter."""


def read(ctx):
    before, after = ctx["window_counters"]
    n = after.get("ttft_count", 0) - before.get("ttft_count", 0)
    if n <= 0:
        return None
    return 1e3 * (after["ttft_total_s"] - before["ttft_total_s"]) / n
