"""Program -> step: milliseconds a step during which the first device runs
nothing while the host is inside ``Executor.run``: the idle gaps of the
device trace that fall under a ``paddle_tpu.executor.*`` span of the
program (the innermost one; ``program_spans.ProgramSpans.idle_by_span``).
Idle time under the benchmark's own wait for a loss is not counted: that
is the pipeline being empty, not the program being slow."""

from benchmark import program_spans


def read(ctx):
    return program_spans.of(ctx).idle_ms_a_step_under("executor.")
