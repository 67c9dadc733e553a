"""XLA-lowered ops: device milliseconds a decode step under the scope
``eva.summary`` (``ops/eva_attention.py``: the pooling of the chunk a row's
position ends and the two writes into the summary caches, all held layers),
self time from the device trace. None where the step program has no such
scope."""

SCOPE = ("eva.summary",)


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", SCOPE)
