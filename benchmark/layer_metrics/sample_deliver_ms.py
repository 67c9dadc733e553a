"""Decode loop: what is left of the median ``decode.step`` span outside the
predictor call: the feed dict, then the wait for the device, the copy of the
whole [slots, vocabulary] logits to the host, ``np.argmax`` a row, futures
and retirement. The program opens no span of its own round these yet
(PERF.md, Open questions), so they are read as one remainder."""


def read(ctx):
    trace = ctx["trace"]
    at = trace.median_span("decode.step")
    inside = trace.child_ms("decode.step", "executor.run")
    if at is None or inside is None:
        return None
    return (at[1] - at[0]) / 1e6 - inside
