"""Program -> step: host milliseconds of the scope update after the call (and,
with return_numpy=True, the wait for the fetches; the window fetches device
arrays) in one ``exe.run``: the program's own span
``paddle_tpu.executor.writeback`` on the profiler's trace (opened in
``Executor.run``), read in the profiled step whose
``paddle_tpu.executor.run`` span is the median one, so that the four phases
add up to that span."""

from benchmark import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "executor.writeback")
