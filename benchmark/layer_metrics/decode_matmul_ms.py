"""XLA-lowered ops: device milliseconds a decode step under the op scopes
``mul`` and ``matmul`` (q, k, v, out, the feed-forward pair, the tied
head): the part of a step that reads the weights."""


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", ("mul", "matmul"))
