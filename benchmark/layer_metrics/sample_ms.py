"""Decode loop: milliseconds of ``paddle_tpu.decode.sample`` inside the
median ``decode.step`` span: ``np.argmax`` a live row, the slots' books,
prefix harvest, retirement (futures resolved). Program span (PR 37)."""


def read(ctx):
    return ctx["trace"].child_ms("decode.step", "decode.sample")
