"""Program -> step: seconds of the backend's part: XLA compiling the step, or
the persistent cache loading its executable (span
``executor.backend_compile``; the record's ``persistent_cache`` says which).
From the compile record the executor keeps of the training step's variant
(``Executor.compile_records``, written in ``Executor._stage``), or summed
over the executables of a serving cell's ladders; on the chip only."""

from benchmark import program_spans


def read(ctx):
    return program_spans.compile_seconds(ctx, "backend_compile_s")
