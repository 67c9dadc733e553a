"""Kernels: the fused conv + batch-norm Pallas kernels alone
(``fused_conv.fwd``: convolution with the batch moments, ``fused_conv.apply``:
scale, shift, residual and ReLU; ``fused_conv.infer``): device milliseconds
a step of the events that carry those names. ``conv_ms`` also holds the XLA
fusions round them under the same scope, the whole backward among them."""

from benchmark import named_kernels


def read(ctx):
    return named_kernels.ms_a_step(ctx["trace"], ("fused_conv",),
                                   ("fwd", "apply", "infer"))
