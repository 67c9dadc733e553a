"""XLA-lowered ops: device milliseconds a decode step under the scope
``attn.eva`` (``ops/eva_attention.py``: one softmax over a layer's window
cache and its summary cache, all held layers), self time from the device
trace. None where the step program has no such scope."""

SCOPE = ("attn.eva",)


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", SCOPE)
