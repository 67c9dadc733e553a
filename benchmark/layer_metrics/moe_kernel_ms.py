"""Kernels: the held experts' grouped products alone
(``grouped_experts.fwd`` and ``.bwd``, ``paddle_tpu/ops/grouped_experts.py``):
device milliseconds a step of the events that carry those names. It is no
more than ``moe_ms``, which also holds the router, top-k, binning, the
gathers and scatter-adds round the kernels and the shared expert. None
where no event carries the names (a program without the kernels, the
CPU)."""

from benchmark import named_kernels

FAMILIES = ("grouped_experts",)


def read(ctx):
    return named_kernels.ms_a_step(ctx["trace"], FAMILIES, ("fwd", "bwd"))
