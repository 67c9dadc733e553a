"""Set-up: distinct (batch rung, context rung[, chunk rung]) geometries the
engine dispatched, which is how many executables set-up made or loaded
(``ServingEngine.compiled_shape_counts``). Program counter."""


def read(ctx):
    return ctx.get("executables")
