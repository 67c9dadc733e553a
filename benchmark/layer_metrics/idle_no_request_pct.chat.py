"""Decode loop, below the knee: the share of the timed window in which the
loop sat in its wait for a request, no slot live and nothing queued (the
engine's ``idle_seconds``, which counts the ``decode.idle`` waits as they
end, over the window's length): idle because nothing was asked, which no
optimisation removes. Program counter (PR 37)."""

from benchmark import decode_spans


def read(ctx):
    return decode_spans.idle_no_request_pct(ctx)
