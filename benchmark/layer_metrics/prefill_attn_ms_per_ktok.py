"""XLA-lowered ops: device milliseconds the chunk executable's runs of the
traced window spent under the op scopes ``sparse_index_chunk`` and
``latent_attention_chunk`` (the indexer's scores and radix select, the
block-wise attention under the mask), for each 1000 prompt tokens those
runs ingested (the engine's ``prefill_tokens`` over the same window).
``prefill_ms_per_ktok`` is the whole chunk run by the same division. None
where the chunk program has no such ops."""

OP_TYPES = ("sparse_index_chunk", "latent_attention_chunk")


def read(ctx):
    trace = ctx["trace"]
    each = trace.scope_ms_a_quantum("prefill.chunk", OP_TYPES)
    before, after = ctx["profile_counters"]
    tokens = after.get("prefill_tokens", 0) - before.get("prefill_tokens", 0)
    if each is None or tokens <= 0:
        return None
    return each * len(trace.quanta["prefill.chunk"]) / (tokens / 1000.0)
