"""XLA-lowered ops: device milliseconds a decode step under the op scope
``latent_attention`` (all held layers: the query taken into the latent, the
gather of the selected rows, scores, softmax and mix over the set, the mix
taken out through ``W_uv``), self time from the device trace. A Pallas
kernel for it would run under the same scope. None where the step program
has no such op."""

OP_TYPES = ("latent_attention",)


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", OP_TYPES)
