"""Program -> step: seconds around the first ``exe.run(main)``: trace,
lower, compile or load from the compile cache, and the first execution.
Host clock, from the benchmark's own span."""


def read(ctx):
    return ctx.get("first_step_s")
