"""XLA-lowered ops: the causal depthwise convolution of the Gated DeltaNet
layers with its SiLU (op ``causal_conv1d``; ``conv_ms`` reads ``conv2d``
only), forward and backward. Device milliseconds a step: self time of the
events under this op scope, from the device trace."""

OP_TYPES = ("causal_conv1d",)


def read(ctx):
    return ctx["trace"].ms_a_step_under(OP_TYPES)
