"""Operations and bytes GLM-5.2's serving path requires, from shapes alone
(the benchmark's own count, the same whatever implements the ops: a PR that
claims a gain cannot change it). ``cfg`` is the configuration file's dict:
the source's widths, ``layers_held`` [first, count], ``experts_held`` [first,
count], ``vocab_size`` the rows of the vocabulary held.

One multiply-add is 2 operations; norms, softmax, rotary and the router's
sigmoid are not counted. Weights, the latent cache and activations are
``dtype_bytes`` wide (bfloat16 as served); an index key is ``INDEX_BYTES``
wide (the index path is float32: the configuration's ``precision``).
"""

INDEX_BYTES = 4


def _layers(cfg):
    first, count = cfg["layers_held"]
    return range(first, first + count)


def _full(cfg):
    return [l for l in _layers(cfg) if cfg["indexer_types"][l] == "full"]


def _sparse(cfg):
    return [l for l in _layers(cfg) if cfg["mlp_layer_types"][l] != "dense"]


def attention_matrices(cfg):
    """Elements of one layer's MLA matrices (``kv_b`` among them)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r = cfg["kv_lora_rank"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (r + cfg["qk_rope_head_dim"])
            + r * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def indexer_matrices(cfg):
    d, hi, di = cfg["hidden_size"], cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * hi * di + d * di + d * hi


def expert_matrices(cfg):
    """Elements of one expert (routed or shared): three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def parameter_count(cfg):
    """Parameters the chip holds, from shapes: matrices, norm weights, the
    index key's layer norm; the experts' selection bias is a buffer (as in
    the source) and is not counted."""
    d = cfg["hidden_size"]
    total = 2 * cfg["vocab_size"] * d + d            # embedding, head, norm
    for l in _layers(cfg):
        total += attention_matrices(cfg) + cfg["q_lora_rank"] \
            + cfg["kv_lora_rank"] + 2 * d            # two latent, two layer norms
        if cfg["indexer_types"][l] == "full":
            total += indexer_matrices(cfg) + 2 * cfg["index_head_dim"]
        if cfg["mlp_layer_types"][l] == "dense":
            total += 3 * d * cfg["intermediate_size"]
        else:
            total += d * cfg["n_routed_experts"] + expert_matrices(cfg) * (
                cfg["experts_held"][1] + cfg["n_shared_experts"])
    return total


def bytes_per_position(cfg, dtype_bytes=2):
    """Cache bytes one position of one sequence holds: a latent row a layer,
    an index key a ``full`` layer."""
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return dtype_bytes * len(_layers(cfg)) * latent \
        + INDEX_BYTES * len(_full(cfg)) * cfg["index_head_dim"]


def latent_attention_step(cfg, live, selected, dtype_bytes=2):
    """(operations, bytes) of the latent attention over the index sets in
    ONE decode step, all held layers: ``live`` sequences, ``selected``
    positions in their sets in all (a layer; every layer of a period reads
    the same sets). A head takes its query into the latent (N x R), scores
    and mixes the set's rows (R + P and R wide) and takes the mix out (R x
    V); ``kv_b`` is read once a layer, a selected row once a sequence (its
    64 heads share it), the queries in and the outputs out."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    n, p, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    layers = len(_layers(cfg))
    ops = 2.0 * layers * h * (live * r * (n + v) + selected * (2 * r + p))
    nbytes = dtype_bytes * layers * (r * h * (n + v) + selected * (r + p)
                                     + live * h * (n + p + v))
    return ops, nbytes


def decode_step(cfg, live, positions, dtype_bytes=2):
    """(operations, bytes) of one decode step over ``live`` sequences whose
    caches hold ``positions`` tokens in all. Every matrix outside the routed
    experts is read once (the embedding's few gathered rows are not
    counted); a held expert is read if a pick falls on it: ``live x
    num_experts_per_tok`` picks spread evenly over ``n_routed_experts``
    reach ``held x (1 - (1 - k/E)^live)`` of them in expectation. An indexer
    reads every cached index key; the attention reads the selected rows
    only, ``min(index_topk, held)`` a sequence, every sequence taken to hold
    the mean. One new row a cache is written."""
    d = cfg["hidden_size"]
    live = max(float(live), 0.0)
    held, experts = cfg["experts_held"][1], cfg["n_routed_experts"]
    k = cfg["num_experts_per_tok"]
    layers, full, sparse = len(_layers(cfg)), len(_full(cfg)), \
        len(_sparse(cfg))
    dense = layers - sparse
    shared = cfg["n_shared_experts"]
    matrices = (layers * attention_matrices(cfg)
                + full * indexer_matrices(cfg)
                + dense * 3 * d * cfg["intermediate_size"]
                + sparse * (d * experts + shared * expert_matrices(cfg))
                + d * cfg["vocab_size"])
    reached = held * (1.0 - (1.0 - float(k) / experts) ** live)
    weights = dtype_bytes * (matrices
                             + sparse * reached * expert_matrices(cfg))
    mean = positions / live if live else 0.0
    selected = live * min(float(cfg["index_topk"]), mean + 1.0)
    attn_ops, attn_bytes = latent_attention_step(cfg, live, selected,
                                                 dtype_bytes)
    kv_b = layers * cfg["kv_lora_rank"] * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    index_ops = 2.0 * full * cfg["index_n_heads"] * cfg["index_head_dim"] \
        * (positions + live)
    index_bytes = INDEX_BYTES * full * cfg["index_head_dim"] \
        * (positions + live)
    routed = live * k * float(held) / experts * expert_matrices(cfg)
    ops = 2.0 * live * (matrices - kv_b) + 2.0 * sparse * routed \
        + attn_ops + index_ops
    nbytes = weights - dtype_bytes * kv_b + attn_bytes + index_bytes \
        + live * bytes_per_position(cfg, dtype_bytes)
    return ops, nbytes
