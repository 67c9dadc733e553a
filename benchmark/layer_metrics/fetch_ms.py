"""Decode loop: milliseconds of ``paddle_tpu.decode.fetch`` inside the median
``decode.step`` span: the wait for the step's run on the device (and for a
chunk's run dispatched before it: ``chunk_wait_ms``) and the copy of the
[slots, vocabulary] logits to the host. With ``predict_ms``, ``sample_ms``
and the feed it adds up to ``decode_step_ms``. Program span (PR 37)."""


def read(ctx):
    return ctx["trace"].child_ms("decode.step", "decode.fetch")
