"""XLA-lowered ops: device milliseconds a decode step under the op scope
``cached_attention`` (scores, softmax and mix over the bucket's whole
capacity under a mask, plain ``jnp``)."""


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step",
                                           ("cached_attention",))
