"""XLA-lowered ops: the mixture-of-experts block on the decode tier (op
``routed_experts``: router, top-k, binning, the held experts' blocks over
the few rows a step sends them, the shared expert), all expert layers.
Device milliseconds a decode step: self time of the events under this op
scope inside the step executable's runs. None where the step program has no
such op."""

OP_TYPES = ("routed_experts",)


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", OP_TYPES)
