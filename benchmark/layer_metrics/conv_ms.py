"""Kernels: convolutions, plain and with the fused batch-norm epilogue,
forward and backward. Device milliseconds a step: self time of the
events under these op scopes, from the device trace."""

OP_TYPES = ('conv2d', 'fused_conv2d')


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
