"""Kernels: the backward attention kernels alone (``dense_vmem.bwd``,
``packed_stream.bwd``, ``head_split_stream.bwd``: dq, dk and dv in one
call each): device milliseconds a step of the events that carry those
names."""

from benchmark import named_kernels

FAMILIES = ("dense_vmem", "packed_stream", "head_split_stream")


def read(ctx):
    return named_kernels.ms_a_step(ctx["trace"], FAMILIES, ("bwd",))
