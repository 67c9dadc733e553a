"""XLA-lowered ops: device milliseconds a decode step under the op scope
``sparse_index`` (the learned indexer of the ``full`` layers: every cached
index key against every index head in float32, the weighted sum over the
heads, the exact top-k), self time from the device trace. None where the
step program has no such op."""


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", ("sparse_index",))
