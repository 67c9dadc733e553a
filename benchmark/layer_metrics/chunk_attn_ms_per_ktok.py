"""Kernels: device milliseconds the chunk executable's runs of the traced
window spent under the scopes ``attn.full`` and ``attn.window``
(``ops/cache_attention.py``: the chunk's attention over the caches that hold
the context, on one TPU the kernel ``cache_chunk.fwd`` with the
transpositions of the queries and of the result beside it, and over the
rings; the ``jnp`` forms run under the same scopes), self time, for each
1000 prompt tokens those runs ingested (the engine's ``prefill_tokens`` over
the same window). ``prefill_ms_per_ktok`` is the whole chunk run by the same
division, and under-reads as it does where a chunk run is not told from a
step's. None where the chunk program has no such scope."""

SCOPES = ("attn.full", "attn.window")


def read(ctx):
    trace = ctx["trace"]
    each = trace.scope_ms_a_quantum("prefill.chunk", SCOPES)
    before, after = ctx["profile_counters"]
    tokens = after.get("prefill_tokens", 0) - before.get("prefill_tokens", 0)
    if each is None or tokens <= 0:
        return None
    return each * len(trace.quanta["prefill.chunk"]) / (tokens / 1000.0)
