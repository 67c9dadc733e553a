"""Kernels: the attention core alone (the projections are matmul ops),
forward and backward. Device milliseconds a step: self time of the
events under these op scopes, from the device trace."""

OP_TYPES = ('flash_attention',)


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
