"""XLA-lowered ops: device milliseconds a decode step under the op scope
``kv_cache_write`` (one new key and value row a layer and slot; an
undonated step copies the whole [slots, capacity, width] array to write
it)."""


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step",
                                           ("kv_cache_write",))
