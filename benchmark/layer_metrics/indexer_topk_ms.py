"""XLA-lowered ops: the part of ``indexer_ms`` under the scope
``indexer.top_k`` inside ``sparse_index``: the exact top-k over a row's
float32 scores alone (``indexer_ms`` less this is the scores). Device
milliseconds a decode step. None where the step program has no such
scope."""


def read(ctx):
    return ctx["trace"].scope_ms_a_quantum("decode.step", ("indexer.top_k",))
