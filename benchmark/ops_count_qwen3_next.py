"""Operations and bytes that ``qwen3-next-80b-a3b`` requires, from shapes
alone (the benchmark's own counts, as ``ops_count.py``'s: one multiply-add =
2 operations, backward twice the forward, recomputation and element-wise
work not counted). ``args`` are the configuration's ``builder_args`` with
the traffic's sizes filled in.
"""


def _held(args):
    held = args.get("experts_held")
    return int(held[1]) if held else int(args["num_experts"])


def _layers(args):
    n, every = args["num_hidden_layers"], args["full_attention_interval"]
    full = sum(1 for i in range(n) if (i + 1) % every == 0)
    return n - full, full


def _delta_core_macs_per_token(args):
    """Multiply-adds a token of one Gated DeltaNet layer's chunked core:
    in a chunk of C tokens the lower triangles of k k^T and q k^T (C/2 * Dk
    each), the unit-triangular solve for the Dv + Dk right-hand columns
    (C/2 * (Dv + Dk)) and scores times values (C/2 * Dv); with the carried
    state two reads and one write of Dk * Dv a token. All value heads."""
    c = args["chunk"]
    dk, dv = args["linear_key_head_dim"], args["linear_value_head_dim"]
    a_head = c * dk + c / 2.0 * (dv + dk) + c / 2.0 * dv + 3 * dk * dv
    return args["linear_num_value_heads"] * a_head


def train_flops_per_sample(args):
    """Forward + backward operations per target token: the projections of
    both kinds of layer, the chunked delta-rule core, the causal half of
    the softmax core, the router, the shared expert and the held experts'
    expected share of the picks, the head over the held vocabulary."""
    d = args["hidden_size"]
    linear, full = _layers(args)
    hk, hv = args["linear_num_key_heads"], args["linear_num_value_heads"]
    dk, dv = args["linear_key_head_dim"], args["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    linear_proj = (d * (2 * key_dim + 2 * value_dim) + d * 2 * hv
                   + (2 * key_dim + value_dim) * args["linear_conv_kernel_dim"]
                   + value_dim * d)
    h, hkv, hd = args["num_attention_heads"], args["num_key_value_heads"], \
        args["head_dim"]
    full_proj = d * 2 * h * hd + 2 * d * hkv * hd + h * hd * d
    full_core = 2 * args["seq_len"] * h * hd / 2.0
    expert = 3 * d * args["moe_intermediate_size"]
    picked_here = args["num_experts_per_tok"] * _held(args) \
        / float(args["num_experts"])
    moe = (d * args["num_experts"]
           + 3 * d * args["shared_expert_intermediate_size"] + d
           + picked_here * expert)
    vocab = args.get("vocab_held") or args["vocab_size"]
    macs = (linear * (linear_proj + _delta_core_macs_per_token(args))
            + full * (full_proj + full_core)
            + (linear + full) * moe + d * vocab)
    return 2 * 3 * macs


def gated_delta_core_step(args, batch):
    """(operations, bytes) one training step requires of the chunked
    delta-rule core alone, forward and backward, over the Gated DeltaNet
    layers: no projections, convolution or norms. Bytes: q, k, v (2 bytes
    each, bfloat16) and the two gates (float32) read and the output written
    forward; those and the output's gradient read and five gradients
    written backward. The states carried between chunks are not counted: a
    kernel may keep or recompute them."""
    linear, _ = _layers(args)
    tokens = batch * args["seq_len"]
    flops = 2 * 3 * tokens * linear * _delta_core_macs_per_token(args)
    hk, hv = args["linear_num_key_heads"], args["linear_num_value_heads"]
    qkv = (2 * hk * args["linear_key_head_dim"]
           + hv * args["linear_value_head_dim"]) * 2
    out = hv * args["linear_value_head_dim"] * 2
    gates = 2 * hv * 4
    a_token = (qkv + gates + out) + (qkv + gates + out) + (qkv + gates)
    return flops, tokens * linear * a_token
