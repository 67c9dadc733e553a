"""Device time of the Pallas kernels by the names the program gives them.

Every ``pl.pallas_call`` of the program carries ``<family>.<part>``
(``paddle_tpu/ops/kernel_names.py``: ``dense_vmem.fwd``,
``head_split_stream.bwd``, ``fused_conv.apply``, ...), as the name of its
HLO instruction (which is what a TPU device event is called:
``dense_vmem.fwd.56``) and as a component of the instruction's ``op_name``
path. A row of ``trace_reduce.Trace`` holds both; either counts. Only the
kernel's own events match: the XLA operations round it under the same op's
scope (layout copies, pads, slices, the batch-norm backward) do not."""

import re


def pattern(families, parts):
    """Matches ``<family>.<part>`` as a whole path component, or at the
    head of an instruction's name (``dense_vmem.fwd.56``)."""
    return re.compile(r"(?:^|/)(?:%s)\.(?:%s)(?:$|[/.])" % (
        "|".join(re.escape(f) for f in families),
        "|".join(re.escape(p) for p in parts)))


def ms_a_step(trace, families, parts):
    """Device milliseconds a step (self time, mean over the chips) of the
    events of these kernels. None where no event is one (no device trace,
    or a program that does not name its kernels)."""
    own = getattr(trace, "own", None)
    if not own:
        return None
    wanted = pattern(families, parts)
    total, found = 0.0, False
    for rows in own:
        for (name, _, _, scope), self_ns in rows:
            if wanted.search(scope) or wanted.search(name):
                total += self_ns
                found = True
    return total / len(own) / trace.steps / 1e6 if found else None
