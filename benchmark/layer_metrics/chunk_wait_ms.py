"""Decode loop: of the median ``decode.step`` span's ``decode.fetch``, the
milliseconds during which the first chip ran a chunk executable (the runs of
the device plane's "XLA Modules" line that are no step's,
``ServeTrace.quanta``): a ``prefill.chunk`` span ends at its dispatch, so
this part of ``decode_step_ms`` is another quantum's device time. Program
span on the device trace's clock (PR 37)."""

from benchmark import decode_spans


def read(ctx):
    return decode_spans.chunk_wait_ms(ctx["trace"])
