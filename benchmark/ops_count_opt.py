"""Operations and bytes one decode step of the OPT decoder requires, from
shapes alone (the benchmark's own count: a PR that claims a gain cannot
change it). ``cfg`` is the configuration file's dict.

A decode step advances ``live`` sequences by one token each. It must read
every weight once (the word embedding once, as the tied head; the few rows
the embedding lookup gathers are not counted), read the keys and values of
the positions that are LIVE (``positions``: the tokens already in the cache,
summed over the live sequences; not the bucket's capacity), and write one
new key and value a layer and sequence. Products: each weight matrix once a
sequence, the scores and the mix over the live positions. One multiply-add
is 2 operations; layer norms, softmax and biases are not counted.
"""


def parameter_count(cfg):
    d, f, layers = cfg["hidden_size"], cfg["ffn_dim"], cfg["num_hidden_layers"]
    table = (cfg["vocab_size"] + cfg["max_position_embeddings"] + 2) * d
    block = 4 * (d * d + d) + 2 * d * f + f + d + 4 * d
    return table + layers * block + 2 * d


def bytes_per_position(cfg, dtype_bytes=2):
    """Cache bytes one position of one sequence holds: a key and a value
    row a layer."""
    return 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * dtype_bytes


def decode_step(cfg, live, positions, dtype_bytes=2):
    """(operations, bytes) of one decode step over ``live`` sequences whose
    caches hold ``positions`` tokens in all."""
    d, f, layers = cfg["hidden_size"], cfg["ffn_dim"], cfg["num_hidden_layers"]
    position_table = (cfg["max_position_embeddings"] + 2) * d
    weights = (parameter_count(cfg) - position_table) * dtype_bytes
    cache = (positions + live) * bytes_per_position(cfg, dtype_bytes)
    matrices = layers * (4 * d * d + 2 * d * f) + d * cfg["vocab_size"]
    core = layers * 2 * positions * d
    return 2 * (live * matrices + core), weights + cache
