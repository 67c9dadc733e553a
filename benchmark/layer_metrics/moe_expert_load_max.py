"""XLA-lowered ops: the most tokens any held expert took in the last
profiled step, over the layers. Each ``routed_experts`` op writes its
counts into a persistable int32 ``<name>.load`` [held] inside the step;
they are read from the trainer's scope. None where the program keeps no
such counter."""

import numpy as np


def read(ctx):
    scope = ctx["trainer"].scope
    loads = [np.asarray(scope.get(name)) for name in scope.var_names()
             if name.endswith(".moe.load")]
    if not loads:
        return None
    return float(max(int(load.max()) for load in loads))
