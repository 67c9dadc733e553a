"""Decode loop: the share of the first chip's idle time in the traced window
that falls under NO span of the program: the loop between two spans, another
thread holding the interpreter. What the instrumentation still cannot name.
Program span (PR 37)."""

from benchmark import decode_spans


def read(ctx):
    return decode_spans.idle_unspanned_pct(ctx["trace"])
