"""XLA-lowered ops: device milliseconds a decode step under the scope
``attn.full`` (``ops/cache_attention.py``: the attention of the layers that
cache the whole context; scores, softmax and mix over the context rung's
capacity under a mask, the caches read as they are stored, a group of query
heads at a time), all held full layers, self time from the device trace. A
Pallas kernel for it would run under the same scope. None where the step
program has no such scope."""

SCOPE = ("attn.full",)


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", SCOPE)
