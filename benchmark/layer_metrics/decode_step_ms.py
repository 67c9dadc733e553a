"""Decode loop: milliseconds of the median ``paddle_tpu.decode.step`` span of
the traced window (the lower middle of an even number: one real quantum, so
that its parts add up to it). A step advances every live slot by one token
(prompt tokens go through ``prefill.chunk`` quanta; only a prompt's last
token rides a step); one that follows a chunk also waits for the chunk's run. Program span, host side: feed assembly,
the predictor call, the wait for the logits, the host's argmax, delivery."""


def read(ctx):
    at = ctx["trace"].median_span("decode.step")
    return None if at is None else (at[1] - at[0]) / 1e6
