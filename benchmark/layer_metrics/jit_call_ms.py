"""Program -> step: host milliseconds of the call of the compiled step (the
enqueue alone: it does not wait for the device) in one ``exe.run``: the
program's own span ``paddle_tpu.executor.dispatch`` on the profiler's trace
(opened in ``Executor.run``), read in the profiled step whose
``paddle_tpu.executor.run`` span is the median one, so that the four phases
add up to that span."""

from benchmark import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "executor.dispatch")
