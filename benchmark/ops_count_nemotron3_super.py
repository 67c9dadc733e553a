"""Operations and bytes that ``nemotron-3-super-120b-a12b`` requires, from
shapes alone (the benchmark's own counts, as ``ops_count.py``'s: one
multiply-add = 2 operations, backward twice the forward, recomputation and
element-wise work not counted). ``args`` are the configuration's
``builder_args`` with the traffic's sizes filled in; the counts are of the
share the chip holds.
"""


def _pattern(args):
    first, count = args.get("layers_held") or (
        0, len(args["hybrid_override_pattern"]))
    return args["hybrid_override_pattern"][first:first + count]


def _held(args):
    """(Mamba heads, groups, query heads, key/value heads, experts, shared
    units, rows of the vocabulary) held."""
    share, ways = args.get("heads_held") or (0, 1)
    q = args["num_attention_heads"] // ways
    per_kv = args["num_attention_heads"] // args["num_key_value_heads"]
    kv = ((share + 1) * q - 1) // per_kv - share * q // per_kv + 1
    experts = args.get("experts_held") or (0, args["n_routed_experts"])
    units = args.get("shared_units_held") or (
        0, args["moe_shared_expert_intermediate_size"])
    return (args["mamba_num_heads"] // ways, args["n_groups"] // ways, q, kv,
            experts[1], units[1], args.get("vocab_held") or args["vocab_size"])


def _ssd_core_macs_per_token(args):
    """Multiply-adds a token of one Mamba-2 layer's chunked core: in a chunk
    of C tokens the lower triangle of C B^T a group (C/2 * N) and of its
    masked product with dt x a head (C/2 * P); a head's addition to the
    carried state and its read of the state that entered the chunk (P * N
    each). The products between the chunks of a group are not counted."""
    heads, groups = _held(args)[:2]
    c, p, n = args["chunk_size"], args["mamba_head_dim"], \
        args["ssm_state_size"]
    return groups * c / 2.0 * n + heads * (c / 2.0 * p + 2 * p * n)


def train_flops_per_sample(args):
    """Forward + backward operations per target token: the projections and
    the convolution of the Mamba-2 layers and their chunked core, the
    projections and the causal half of the softmax core of the attention
    layers, the router, the latent projections, the shared expert's held
    units and the held experts' expected share of the picks, the head over
    the held vocabulary."""
    d = args["hidden_size"]
    heads, groups, q, kv, experts, units, vocab = _held(args)
    inner = heads * args["mamba_head_dim"]
    bc = groups * args["ssm_state_size"]
    mamba = (d * (2 * inner + 2 * bc + heads)
             + (inner + 2 * bc) * args["conv_kernel"] + inner * d
             + _ssd_core_macs_per_token(args))
    hd = args["head_dim"]
    attention = d * q * hd + 2 * d * kv * hd + q * hd * d \
        + 2 * args["seq_len"] * q * hd / 2.0
    latent = args["moe_latent_size"]
    picked_here = args["num_experts_per_tok"] * experts \
        / float(args["n_routed_experts"])
    moe = (d * args["n_routed_experts"] + 2 * d * latent + 2 * d * units
           + picked_here * 2 * latent * args["moe_intermediate_size"])
    pattern = _pattern(args)
    macs = (pattern.count("M") * mamba + pattern.count("*") * attention
            + pattern.count("E") * moe + d * vocab)
    return 2 * 3 * macs


def ssd_core_step(args, batch):
    """(operations, bytes) one training step requires of the chunked
    state-space core alone, forward and backward, over the Mamba-2 layers:
    no projections, convolution or norm. Bytes: x, B, C and the raw step
    read and y written forward (2 bytes each, bfloat16); those and y's
    gradient read and four gradients written backward. The states carried
    between chunks are not counted: a kernel may keep or recompute them."""
    layers = _pattern(args).count("M")
    tokens = batch * args["seq_len"]
    flops = 2 * 3 * tokens * layers * _ssd_core_macs_per_token(args)
    heads, groups = _held(args)[:2]
    inner = heads * args["mamba_head_dim"]
    read = (inner + 2 * groups * args["ssm_state_size"] + heads) * 2
    out = inner * 2
    a_token = (read + out) + (read + out) + read
    return flops, tokens * layers * a_token
