"""XLA-lowered ops: the optimizer update, one op a parameter. Device
milliseconds a step: self time of the events under these op scopes, from the
device trace: only what XLA did not fuse into the gradient product that
feeds an update (PERF.md, Blind spots)."""

OP_TYPES = ('adam', 'momentum')


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
