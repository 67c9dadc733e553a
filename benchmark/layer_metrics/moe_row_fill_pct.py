"""Kernels: the share of the rows the experts' products ran over that held
a token, in the last profiled step, over the layers. Each ``routed_experts``
op writes a persistable int32 ``<name>.rows`` [2] inside the step: the rows
of its table that hold a token, and the rows its products ran over (each
expert's padded up to whole tiles); they are read from the trainer's scope.
None where the program keeps no such counter."""

import numpy as np


def read(ctx):
    scope = ctx["trainer"].scope
    rows = [np.asarray(scope.get(name)) for name in scope.var_names()
            if name.endswith(".moe.rows")]
    run = sum(int(r[1]) for r in rows)
    if not run:
        return None
    return 100.0 * sum(int(r[0]) for r in rows) / run
