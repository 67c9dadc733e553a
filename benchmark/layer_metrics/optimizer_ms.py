"""XLA-lowered ops: the optimizer update, per parameter or in the fused
groups of core/opt_fusion.py. Device milliseconds a step: self time of the
events under these op scopes, from the device trace."""

OP_TYPES = ('adam', 'momentum', 'fused_adam', 'fused_momentum')


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
