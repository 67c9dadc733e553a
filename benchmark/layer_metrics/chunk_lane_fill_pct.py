"""Scheduler: of the token lanes the chunk dispatches of the timed window
ran over (the engine's ``prefill_lanes``: the rows a chunk run computed
times the chunk rung, which since PR 38 is a sub-batch of
``chunk_rows(k, b)`` rows and before it every slot row of the bucket), the
share that ingested a prompt token
(``prefill_tokens``). The rest is a chunk run's work on pad lanes. Program
counter (PR 37)."""

from benchmark import decode_spans


def read(ctx):
    fill = decode_spans.counter_ratio(ctx, "prefill_tokens",
                                      "prefill_lanes")
    return None if fill is None else 100.0 * fill
