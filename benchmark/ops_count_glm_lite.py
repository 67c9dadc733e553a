"""Operations and bytes GLM-4.7-Flash's serving path requires, from shapes
alone (the benchmark's own count, the same whatever implements the ops: a
PR that claims a gain cannot change it). ``cfg`` is the configuration
file's dict: the source's widths, ``layers_held`` [first, count] the main
model's layers here, ``experts_held`` [first, count], ``vocab_size`` the
rows of the vocabulary, ``num_nextn_predict_layers`` 1: the prediction
module, one more whole expert layer with a latent cache of its own, held
beside them.

A decode step here is a VERIFYING step: ``LANES`` = 2 lanes a row (the
committed token and the draft of the next) through the main model's layers,
the head over both lanes, then the module over both lanes and its head.
That is ONE of the engine's ``decode_steps``, which the readers divide by.

One multiply-add is 2 operations; norms, softmax, rotary and the router's
sigmoid are not counted. Weights, both latent caches and activations are
``dtype_bytes`` wide (bfloat16 as served).
"""

LANES = 2


def _layers(cfg):
    first, count = cfg["layers_held"]
    return range(first, first + count)


def _sparse(cfg):
    return [l for l in _layers(cfg) if l >= cfg["first_k_dense_replace"]]


def latent_layers(cfg):
    """Layers that keep a latent cache: the main model's and the module's."""
    return len(_layers(cfg)) + cfg["num_nextn_predict_layers"]


def attention_matrices(cfg):
    """Elements of one layer's MLA matrices (``kv_b`` among them)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r = cfg["kv_lora_rank"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (r + cfg["qk_rope_head_dim"])
            + r * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def kv_b_matrix(cfg):
    return cfg["kv_lora_rank"] * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def expert_matrices(cfg):
    """Elements of one expert (routed or shared): three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _norms(cfg):
    """A layer's norm weights: the two latents' and the two of the layer."""
    return cfg["q_lora_rank"] + cfg["kv_lora_rank"] + 2 * cfg["hidden_size"]


def expert_layer_parameters(cfg):
    d = cfg["hidden_size"]
    return (attention_matrices(cfg) + _norms(cfg)
            + d * cfg["n_routed_experts"] + expert_matrices(cfg) * (
                cfg["experts_held"][1] + cfg["n_shared_experts"]))


def module_parameters(cfg):
    """The prediction module: ``eh_proj`` [2d, d], its three norms, one
    whole expert layer; embedding and head are the main model's."""
    d = cfg["hidden_size"]
    return cfg["num_nextn_predict_layers"] * (
        2 * d * d + 3 * d + expert_layer_parameters(cfg))


def parameter_count(cfg):
    """Parameters the chip holds, from shapes: matrices and norm weights;
    the experts' selection bias is a buffer (as in the source) and is not
    counted."""
    d = cfg["hidden_size"]
    total = 2 * cfg["vocab_size"] * d + d            # embedding, head, norm
    for l in _layers(cfg):
        if l < cfg["first_k_dense_replace"]:
            total += attention_matrices(cfg) + _norms(cfg) \
                + 3 * d * cfg["intermediate_size"]
        else:
            total += expert_layer_parameters(cfg)
    return total + module_parameters(cfg)


def bytes_per_position(cfg, dtype_bytes=2):
    """Cache bytes one position of one sequence holds: a latent row in each
    main layer and one in the module."""
    return dtype_bytes * latent_layers(cfg) * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def cache_bytes(cfg, slots, context, dtype_bytes=2):
    """Bytes of the latent caches a slot table of ``slots`` rows reserves
    at a context rung."""
    return bytes_per_position(cfg, dtype_bytes) * slots * context


def _reached(cfg, live):
    """Held experts of ONE layer that the picks of a step reach in
    expectation: ``live x LANES x num_experts_per_tok`` picks spread evenly
    over ``n_routed_experts``, not all that are held whatever the step."""
    held, experts = cfg["experts_held"][1], cfg["n_routed_experts"]
    k = cfg["num_experts_per_tok"]
    return held * (1.0 - (1.0 - float(k) / experts) ** (LANES * live))


def _latent_attention(cfg, layers, live, positions, dtype_bytes):
    """(operations, bytes) of ``layers`` layers' latent attention in one
    verifying step: ``live`` sequences whose caches hold ``positions``
    tokens in all, each read by both lanes of its row. A head takes each
    lane's query into the latent (N x R), scores and mixes every live row
    (R + P and R wide) and takes the mix out (R x V); ``kv_b`` is read once
    a layer, a cached row once a sequence (its heads and both lanes share
    it), the queries in and the outputs out."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    n, p, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    ops = 2.0 * layers * h * LANES * (live * r * (n + v)
                                      + positions * (2 * r + p))
    nbytes = dtype_bytes * layers * (kv_b_matrix(cfg) + positions * (r + p)
                                     + LANES * live * h * (n + p + v))
    return ops, nbytes


def attention_step(cfg, live, positions, dtype_bytes=2):
    """The latent attention of ONE verifying step, every latent layer (the
    module's with the main model's): what ``latent_attn_ms`` measures."""
    return _latent_attention(cfg, latent_layers(cfg), live, positions,
                             dtype_bytes)


def _expert_layer_step(cfg, live, dtype_bytes):
    """(operations, bytes) of one expert layer outside its attention's
    cache: its matrices read once, a routed expert's if a pick falls on it,
    ``LANES x live`` lanes through them."""
    d = cfg["hidden_size"]
    lanes = LANES * live
    matrices = (attention_matrices(cfg) + d * cfg["n_routed_experts"]
                + cfg["n_shared_experts"] * expert_matrices(cfg))
    routed = lanes * cfg["num_experts_per_tok"] * float(
        cfg["experts_held"][1]) / cfg["n_routed_experts"] \
        * expert_matrices(cfg)
    ops = 2.0 * lanes * (matrices - kv_b_matrix(cfg)) + 2.0 * routed
    nbytes = dtype_bytes * (matrices - kv_b_matrix(cfg)
                            + _reached(cfg, live) * expert_matrices(cfg))
    return ops, nbytes


def draft_step(cfg, live, positions, dtype_bytes=2):
    """(operations, bytes) of the prediction module in ONE verifying step:
    ``eh_proj``, its one whole layer (its attention over the live positions
    of its own cache, its experts as a pick reaches them), and the head read
    once more over both lanes (the module's input is the model's own choice
    of token, so the two heads cannot share a pass); one new row a lane
    written to its cache. What ``mtp_draft_ms`` measures."""
    d = cfg["hidden_size"]
    live = max(float(live), 0.0)
    lanes = LANES * live
    layer_ops, layer_bytes = _expert_layer_step(cfg, live, dtype_bytes)
    attn_ops, attn_bytes = _latent_attention(cfg, 1, live, positions + lanes,
                                             dtype_bytes)
    own = 2 * d * d + d * cfg["vocab_size"]
    written = lanes * dtype_bytes * (cfg["kv_lora_rank"]
                                     + cfg["qk_rope_head_dim"])
    return (2.0 * lanes * own + layer_ops + attn_ops,
            dtype_bytes * own + layer_bytes + attn_bytes + written)


def decode_step(cfg, live, positions, dtype_bytes=2):
    """(operations, bytes) of one VERIFYING step over ``live`` sequences
    whose caches hold ``positions`` tokens in all: ``LANES`` lanes a row
    through the main model's layers and the head, then the module
    (:func:`draft_step`). Every matrix outside the routed experts is read
    once a pass (the embedding's few gathered rows are not counted); a
    routed expert is read if a pick falls on it (:func:`_reached`); every
    latent layer reads every live position once a sequence; one new row a
    lane a cache is written."""
    d = cfg["hidden_size"]
    live = max(float(live), 0.0)
    lanes = LANES * live
    sparse = len(_sparse(cfg))
    dense = len(_layers(cfg)) - sparse
    layer_ops, layer_bytes = _expert_layer_step(cfg, live, dtype_bytes)
    dense_matrices = attention_matrices(cfg) - kv_b_matrix(cfg) \
        + 3 * d * cfg["intermediate_size"]
    attn_ops, attn_bytes = _latent_attention(
        cfg, len(_layers(cfg)), live, positions + lanes, dtype_bytes)
    written = lanes * dtype_bytes * len(_layers(cfg)) * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    ops = (sparse * layer_ops + 2.0 * lanes * (
        dense * dense_matrices + d * cfg["vocab_size"]) + attn_ops)
    nbytes = (sparse * layer_bytes + dtype_bytes * (
        dense * dense_matrices + d * cfg["vocab_size"])
        + attn_bytes + written)
    module_ops, module_bytes = draft_step(cfg, live, positions, dtype_bytes)
    return ops + module_ops, nbytes + module_bytes
