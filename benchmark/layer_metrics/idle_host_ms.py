"""Decode loop: milliseconds a quantum (decode steps and chunk dispatches of
the traced window) in which the first chip ran nothing while the program was
inside one of its spans other than ``decode.idle``: the serving cells'
``device_wait_host_ms``. Idle time under ``decode.idle`` (no request to
serve) and under no span at all is not counted here
(``idle_no_request_pct.chat``, ``idle_unspanned_pct``). Program span."""

from benchmark import decode_spans


def read(ctx):
    return decode_spans.idle_host_ms(ctx["trace"])
