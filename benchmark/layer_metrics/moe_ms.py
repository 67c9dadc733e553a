"""XLA-lowered ops: the mixture-of-experts block (op ``routed_experts``:
router, top-k, binning, the held experts' blocks, combine, the shared
expert), forward and backward. Device milliseconds a step: self time of the
events under this op scope, from the device trace."""

OP_TYPES = ("routed_experts",)


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
