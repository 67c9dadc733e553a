"""Program -> step: seconds of tracing the program into one jaxpr: the Python
of every op's implementation, run once (span ``executor.trace``). From the
compile record the executor keeps of the training step's variant
(``Executor.compile_records``, written in ``Executor._stage``), or summed
over the executables of a serving cell's ladders; on the chip only."""

from benchmark import program_spans


def read(ctx):
    return program_spans.compile_seconds(ctx, "trace_s")
