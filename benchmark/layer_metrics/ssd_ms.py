"""XLA-lowered ops: the Mamba-2 state-space core (op ``mamba2_ssd``: the
step's softplus, the decays, the chunked scan and the skip; not the
projections, the convolution or the gated norm), forward and backward.
Device milliseconds a step: self time of the events under this op scope,
from the device trace."""

OP_TYPES = ("mamba2_ssd",)


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
