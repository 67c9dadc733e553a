"""Decode loop: milliseconds of the ``paddle_tpu.decode.admit`` and
``decode.plan`` spans between the median ``decode.step`` span and the
quantum before it: admission (re-bucketing inside it) and the chunk plan,
which before PR 37 lay under no span of the program. Program span."""

from benchmark import decode_spans


def read(ctx):
    return decode_spans.admit_plan_ms(ctx["trace"])
