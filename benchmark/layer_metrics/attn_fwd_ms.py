"""Kernels: the forward attention kernels alone (``dense_vmem.fwd``,
``packed_stream.fwd``, ``head_split_stream.fwd``): device milliseconds a
step of the events that carry those names. With ``attn_bwd_ms`` it is no
more than ``attn_ms``, which also holds the XLA operations under the op's
scope (head-split layout copies, pads, slices)."""

from benchmark import named_kernels

FAMILIES = ("dense_vmem", "packed_stream", "head_split_stream")


def read(ctx):
    return named_kernels.ms_a_step(ctx["trace"], FAMILIES, ("fwd",))
