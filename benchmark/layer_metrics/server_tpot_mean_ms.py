"""Scheduler: mean milliseconds an output token after the first, as the
SERVER reads it (``ServingMetrics.tpot``: last minus first sampled token
over tokens minus one), over the requests that retired in the timed
window. Program counter."""


def read(ctx):
    before, after = ctx["window_counters"]
    n = after.get("tpot_count", 0) - before.get("tpot_count", 0)
    if n <= 0:
        return None
    return 1e3 * (after["tpot_total_s"] - before["tpot_total_s"]) / n
