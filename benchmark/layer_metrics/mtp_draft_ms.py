"""XLA-lowered ops: device milliseconds a decode step under the prediction
module's scopes, ``mtp.embed_proj`` (the two norms and ``eh_proj``),
``mtp.block`` (its one whole layer: latent attention over its own cache, its
experts) and ``mtp.head`` (its norm, the head over both lanes, the greedy
token), self time from the device trace. What a draft costs a step. None
where the step program has no such scopes."""

SCOPES = ("mtp.embed_proj", "mtp.block", "mtp.head")


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", SCOPES)
