"""Device: milliseconds the chip spent in the chunk executable's runs of the
traced window (chunked prefill: up to a rung of prompt tokens a row and
dispatch; since PR 38 over a sub-batch of the rows that ingest,
``chunk_rows(k, b)`` of them, gathered from the slot table and scattered
back by two jitted copies whose runs count here too, where before every
slot row rode along padded) for each 1000 prompt tokens
they ingested (the engine's ``prefill_tokens`` counter over the same
window). Device trace over program counter: a ``prefill.chunk`` span ends
at the dispatch, so the host's span says nothing of a chunk's cost."""


def read(ctx):
    total = ctx["trace"].device_ms("prefill.chunk")
    before, after = ctx["profile_counters"]
    tokens = after.get("prefill_tokens", 0) - before.get("prefill_tokens", 0)
    if total is None or tokens <= 0:
        return None
    return total / (tokens / 1000.0)
