"""XLA-lowered ops: device milliseconds a decode step under the scope
``attn.window`` (``ops/cache_attention.py``: the attention of the
sliding-window layers over their rings of ``sliding_window`` positions a
slot row, the learned sink in the softmax), all held window layers, self
time from the device trace. None where the step program has no such
scope."""

SCOPE = ("attn.window",)


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", SCOPE)
