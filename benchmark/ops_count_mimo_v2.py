"""Operations and bytes MiMo-V2-Flash's serving path requires, from shapes
alone (the benchmark's own count, the same whatever implements the ops: a
PR that claims a gain cannot change it). ``cfg`` is the configuration
file's dict: the source's widths, ``layers_held`` the published indices of
the layers held, ``experts_held`` [first, count], ``vocab_size`` the rows of
the vocabulary held.

One multiply-add is 2 operations; norms, softmax, rotary and the router's
sigmoid are not counted. Weights, caches and activations are ``dtype_bytes``
wide (bfloat16 as served).
"""


def _kind(cfg, l):
    """(query heads, key/value heads, key width, value width) of layer l."""
    pre = "swa_" if cfg["hybrid_layer_pattern"][l] else ""
    return (cfg[pre + "num_attention_heads"],
            cfg[pre + "num_key_value_heads"], cfg[pre + "head_dim"],
            cfg[pre + "v_head_dim"])


def full_layers(cfg):
    return [l for l in cfg["layers_held"]
            if not cfg["hybrid_layer_pattern"][l]]


def window_layers(cfg):
    return [l for l in cfg["layers_held"] if cfg["hybrid_layer_pattern"][l]]


def expert_layers(cfg):
    return [l for l in cfg["layers_held"] if cfg["moe_layer_freq"][l]]


def attention_matrices(cfg, l):
    """Elements of layer l's q, k, v and o matrices."""
    d = cfg["hidden_size"]
    h, kv, dk, dv = _kind(cfg, l)
    return d * h * dk + d * kv * dk + d * kv * dv + h * dv * d


def expert_matrices(cfg):
    """Elements of one routed expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def parameter_count(cfg):
    """Parameters the chip holds, from shapes: matrices, norm weights, the
    window layers' sinks; the experts' selection bias is a buffer (as in
    the source) and is not counted."""
    d = cfg["hidden_size"]
    total = 2 * cfg["vocab_size"] * d + d            # embedding, head, norm
    for l in cfg["layers_held"]:
        windowed = bool(cfg["hybrid_layer_pattern"][l])
        total += attention_matrices(cfg, l) + 2 * d  # two norms a layer
        if cfg["add_swa_attention_sink_bias" if windowed
               else "add_full_attention_sink_bias"]:
            total += _kind(cfg, l)[0]
        if cfg["moe_layer_freq"][l]:
            total += d * cfg["n_routed_experts"] \
                + expert_matrices(cfg) * cfg["experts_held"][1]
        else:
            total += 3 * d * cfg["intermediate_size"]
    return total


def bytes_per_position(cfg, l, dtype_bytes=2):
    """Cache bytes one position of one sequence holds in layer l: a key and
    a value row, each of the layer kind's own width."""
    _h, kv, dk, dv = _kind(cfg, l)
    return dtype_bytes * kv * (dk + dv)


def cache_bytes(cfg, slots, context, dtype_bytes=2):
    """(bytes of the full layers' caches, bytes of the window layers'
    rings) a slot table of ``slots`` rows reserves at a context rung."""
    full = sum(bytes_per_position(cfg, l, dtype_bytes)
               for l in full_layers(cfg)) * slots * context
    rings = sum(bytes_per_position(cfg, l, dtype_bytes)
                for l in window_layers(cfg)) * slots * cfg["sliding_window"]
    return full, rings


def _attention(cfg, layers, live, read, dtype_bytes):
    """(operations, bytes) of ONE decode step's attention in ``layers``:
    ``live`` sequences that read ``read`` positions in all (a layer). A
    query head scores and mixes each position it reads (Dk + Dv
    multiply-adds); a position's key and value rows are read once a
    sequence (the query heads of a group share them), the queries in and
    the outputs out."""
    ops = nbytes = 0.0
    for l in layers:
        h, _kv, dk, dv = _kind(cfg, l)
        ops += 2.0 * h * (dk + dv) * read
        nbytes += bytes_per_position(cfg, l, dtype_bytes) * read \
            + dtype_bytes * live * h * (dk + dv)
    return ops, nbytes


def attention_step(cfg, live, positions, dtype_bytes=2):
    """(operations, bytes) of the FULL layers' attention in one decode
    step: ``live`` sequences whose caches hold ``positions`` tokens in all,
    each of which every full layer reads (2,560 bytes a position a layer at
    the published widths: 4 key heads of 192 and 4 value heads of 128 in
    bfloat16)."""
    return _attention(cfg, full_layers(cfg), live, positions, dtype_bytes)


def window_step(cfg, live, positions, dtype_bytes=2):
    """The same of the WINDOW layers: a sequence reads its last
    ``sliding_window`` positions at most, every sequence taken to hold the
    mean."""
    mean = positions / live if live else 0.0
    read = live * min(float(cfg["sliding_window"]), mean)
    return _attention(cfg, window_layers(cfg), live, read, dtype_bytes)


def decode_step(cfg, live, positions, dtype_bytes=2):
    """(operations, bytes) of one decode step over ``live`` sequences whose
    caches hold ``positions`` tokens in all. Every matrix outside the
    routed experts is read once (the embedding's few gathered rows are not
    counted); a held expert is read if a pick falls on it: ``live x
    num_experts_per_tok`` picks spread evenly over ``n_routed_experts``
    reach ``held x (1 - (1 - k/E)^live)`` of them in expectation, not all
    that are held. The full layers read every live position, the window
    layers their window; one new key and value row a layer is written."""
    d = cfg["hidden_size"]
    live = max(float(live), 0.0)
    held, experts = cfg["experts_held"][1], cfg["n_routed_experts"]
    k = cfg["num_experts_per_tok"]
    sparse = len(expert_layers(cfg))
    dense = len(cfg["layers_held"]) - sparse
    matrices = (sum(attention_matrices(cfg, l) for l in cfg["layers_held"])
                + dense * 3 * d * cfg["intermediate_size"]
                + sparse * d * experts + d * cfg["vocab_size"])
    reached = held * (1.0 - (1.0 - float(k) / experts) ** live)
    weights = dtype_bytes * (matrices
                             + sparse * reached * expert_matrices(cfg))
    routed = live * k * float(held) / experts * expert_matrices(cfg)
    full_ops, full_bytes = attention_step(cfg, live, positions + live,
                                          dtype_bytes)
    win_ops, win_bytes = window_step(cfg, live, positions + live,
                                     dtype_bytes)
    written = live * sum(bytes_per_position(cfg, l, dtype_bytes)
                         for l in cfg["layers_held"])
    ops = 2.0 * live * matrices + 2.0 * sparse * routed + full_ops + win_ops
    return ops, weights + full_bytes + win_bytes + written
