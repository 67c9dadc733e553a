"""Kernels: the Gated DeltaNet core (op ``gated_delta_rule``: gates, L2
norms, the chunked delta rule; not the projections, the convolution or the
gated norm), forward and backward. Device milliseconds a step: self time of
the events under this op scope, from the device trace."""

OP_TYPES = ("gated_delta_rule",)


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
