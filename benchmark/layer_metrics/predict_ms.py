"""Decode loop: milliseconds of ``paddle_tpu.executor.run`` (the predictor
call: feeds placed, the step dispatched, fetches handed back; the device
runs on after it returns) inside the median ``decode.step`` span, so that it
and ``sample_deliver_ms`` add up to ``decode_step_ms``. Program span."""


def read(ctx):
    return ctx["trace"].child_ms("decode.step", "executor.run")
