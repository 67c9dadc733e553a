"""XLA-lowered ops: the matmul and mul ops (projections, FFN, classifier),
forward and backward. Device milliseconds a step: self time of the
events under these op scopes, from the device trace."""

OP_TYPES = ('matmul', 'mul')


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
