"""Operations and bytes EvaByte's serving path requires, from shapes alone
(the benchmark's own count, the same whatever implements the ops: a PR that
claims a gain cannot change it). ``cfg`` is the configuration file's dict:
the source's widths, ``layers_held`` ``[first, count]`` the stage.

One multiply-add is 2 operations; norms, softmaxes, rotary and the pooling
of a summary are not counted. Weights, caches and activations are
``dtype_bytes`` wide (bfloat16 as served).

A layer's two caches hold ENTRIES of one kind: a key row and a value row of
``hidden_size`` each, 16,384 bytes at the published widths; a window cache
entry stands for one position, a summary cache entry for ``chunk_size``. A
sequence that has fed positions ``0 .. p`` reads ``p % window_size + 1``
window entries and ``(p // window_size) * (window_size / chunk_size)``
summary entries a layer (:func:`entries_read`): never more than
``window_size + (max_position_embeddings - window_size) / chunk_size``.
"""


def held(cfg):
    """How many layers the chip holds."""
    return int(cfg["layers_held"][1])


def layer_matrices(cfg):
    """Elements of a layer's seven matrices: q, k, v, o and the gated
    feed-forward's three."""
    d = cfg["hidden_size"]
    return 4 * d * d + 3 * d * cfg["intermediate_size"]


def parameter_count(cfg):
    """Parameters the chip holds, from shapes: a layer's matrices, its two
    norm weights and its pooling query and key offset (``phi``, ``mu``:
    ``hidden_size`` each); embedding, head 0 and the final norm."""
    d = cfg["hidden_size"]
    return held(cfg) * (layer_matrices(cfg) + 4 * d) \
        + 2 * cfg["vocab_size"] * d + d


def bytes_per_position(cfg, dtype_bytes=2):
    """Bytes of ONE entry of a layer's caches: a key row and a value row.
    In the window cache an entry is a position; in the summary cache it
    stands for ``chunk_size`` positions."""
    return 2 * dtype_bytes * cfg["hidden_size"]


def cache_bytes(cfg, slots, context, dtype_bytes=2):
    """(bytes of the window caches, bytes of the summary caches) a slot
    table of ``slots`` rows reserves at a context rung."""
    entry = bytes_per_position(cfg, dtype_bytes) * held(cfg) * slots
    return (entry * cfg["window_size"], entry * (context // cfg["chunk_size"]))


def entries_read(cfg, p):
    """(window entries, summary entries) a layer's attention reads for the
    query at position ``p`` (whole or not: a mean)."""
    w, c = cfg["window_size"], cfg["chunk_size"]
    return p % w + 1, (p // w) * (w // c)


def eva_attention_step(cfg, live, window_entries, summary_entries,
                       dtype_bytes=2):
    """(operations, bytes) of ONE decode step's attention in all held
    layers, for the entries the rows HOLD: ``live`` sequences that read
    ``window_entries`` and ``summary_entries`` in all, a layer. A head
    scores and mixes each entry it reads (2 D multiply-adds, over the heads
    ``2 hidden_size``); an entry's key and value rows are read once, the
    queries in and the outputs out."""
    d = cfg["hidden_size"]
    entries = float(window_entries) + float(summary_entries)
    ops = 4.0 * d * entries
    nbytes = bytes_per_position(cfg, dtype_bytes) * entries \
        + 2.0 * dtype_bytes * live * d
    return held(cfg) * ops, held(cfg) * nbytes


def decode_step(cfg, live, positions, dtype_bytes=2):
    """(operations, bytes) of one decode step over ``live`` sequences whose
    contexts hold ``positions`` bytes in all. Every matrix is read once (the
    embedding's few gathered rows are not counted); every sequence is taken
    to hold the mean, and reads what :func:`entries_read` gives for it; a
    key and a value row a layer are written, and a summary every
    ``chunk_size`` steps."""
    d = cfg["hidden_size"]
    live = max(float(live), 0.0)
    n = held(cfg)
    matrices = n * layer_matrices(cfg) + d * cfg["vocab_size"]
    small = n * 4 * d + d                      # norms, phi, mu
    mean = (positions + live) / live if live else 0.0
    window, summary = entries_read(cfg, max(mean - 1.0, 0.0))
    attn_ops, attn_bytes = eva_attention_step(
        cfg, live, live * window, live * summary, dtype_bytes)
    written = live * n * bytes_per_position(cfg, dtype_bytes) \
        * (1.0 + 1.0 / cfg["chunk_size"])
    ops = 2.0 * live * matrices + attn_ops
    return ops, dtype_bytes * (matrices + small) + attn_bytes + written
