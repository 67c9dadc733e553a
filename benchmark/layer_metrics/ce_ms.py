"""Kernels: the output projection fused with the label-smoothed
cross-entropy (``fused_linear_smooth_ce``: the 30000-wide head), forward and
backward. Device milliseconds a step: self time of the events under this op
scope, from the device trace. An optimizer update that XLA fuses into the
head's weight-gradient product is counted here, under the fusion's root."""

OP_TYPES = ("fused_linear_smooth_ce",)


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
