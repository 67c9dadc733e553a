"""Program -> step: seconds around the first ``exe.run(main)``: trace,
lower, compile or load from the compile cache, and the first execution; in
a serving cell, around the first warm-up request (the step executable and
the lowest chunk rung's are made or loaded, the caches are first fed from
the host). Host clock, from the benchmark's own span."""


def read(ctx):
    return ctx.get("first_step_s")
