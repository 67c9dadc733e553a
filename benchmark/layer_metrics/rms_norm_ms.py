"""XLA-lowered ops: the RMS norms (op ``rms_norm``: the zero-centred norms
before each mixer and each expert block and before the head, the per-head
q/k norms of the full-attention layer, the Gated DeltaNet's gated output
norm), forward and backward. Device milliseconds a step: self time of the
events under this op scope, from the device trace."""

OP_TYPES = ("rms_norm",)


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
