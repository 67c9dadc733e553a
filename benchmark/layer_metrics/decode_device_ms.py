"""Device: milliseconds a decode step in which an operation ran on the chip
(the union of the device events inside the ``decode.step`` spans of the
traced window, over their number). ``decode_step_ms`` less this is the time
a step holds the chip idle."""


def read(ctx):
    return ctx["trace"].device_ms_a_quantum("decode.step")
