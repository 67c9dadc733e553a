"""Scheduler, below the knee: mean milliseconds from ``submit`` to admission
into a slot over the requests admitted in the timed window (the engine's
``queue_wait_seconds`` over ``admitted``). ``server_ttft_mean_ms.chat`` runs
from the same instant: the difference is prefill. Program counter (PR 37)."""

from benchmark import decode_spans


def read(ctx):
    waited = decode_spans.counter_ratio(ctx, "queue_wait_seconds",
                                        "admitted")
    return None if waited is None else 1e3 * waited
