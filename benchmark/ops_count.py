"""Operations and bytes that the algorithms require, from shapes alone.

These are the benchmark's own counts (a PR that claims a gain cannot change
them): matrix-multiplication and convolution operations of the forward and
backward passes, one multiply-add = 2 operations, recomputation not counted,
element-wise work not counted. ``args`` are a configuration's
``builder_args`` with the traffic's sizes filled in.
"""


def transformer_train_flops_per_sample(args, causal_halved=True):
    """Forward + backward operations per TARGET token of the encoder-decoder
    (source and target rows have the same length, so each target token also
    pays for one source token's pass through the encoder).

    Per layer and token: the four attention projections 4*d^2, the FFN
    2*d*d_ff, and the attention core, scores and context, 2*T*d; a decoder
    layer has a second (cross) attention; the output projection d*V.
    Backward costs twice the forward. Under a causal mask half of the core's
    products are never needed; ``causal_halved=False`` gives the count the
    repo's ``transformer_flops_per_token`` makes (384.7 MFLOP at s256).
    """
    d, d_ff, layers = args["d_model"], args["d_ff"], args["n_layer"]
    seq, vocab = args["seq_len"], args["trg_vocab"]
    proj = 4 * d * d
    ffn = 2 * d * d_ff
    core = 2 * seq * d
    causal_core = core / 2 if causal_halved else core
    enc = layers * (proj + ffn + core)
    dec = layers * (2 * proj + ffn + causal_core + core)
    macs = enc + dec + d * vocab
    return 2 * 3 * macs


def attention_core_step(args, batch):
    """(operations, bytes) one training step requires of the attention core
    alone (no projections), forward and backward, over all the attention
    sites of the encoder-decoder: per site, head and row 2 matrix products
    forward and 4 backward of 2*T*T*D operations each, halved under the
    causal mask. Bytes: q, k, v read and the output written forward; q, k, v,
    the output and its gradient read and three gradients written backward,
    2 bytes each (bfloat16); scores are never required in memory."""
    d, layers, seq = args["d_model"], args["n_layer"], args["seq_len"]
    per_site = 6 * 2 * seq * seq * d * batch
    full_sites, causal_sites = 2 * layers, layers
    flops = per_site * (full_sites + causal_sites / 2)
    tensor = batch * seq * d * 2
    nbytes = (4 + 8) * tensor * 3 * layers
    return flops, nbytes


def _resnet50_convs(image_hw):
    """(c_in, c_out, kernel, output side) of every convolution, in order."""
    side = image_hw // 2
    convs = [(3, 64, 7, side)]
    side //= 2  # the max pool
    c_in = 64
    for stage, count in enumerate((3, 4, 6, 3)):
        mid = 64 * 2 ** stage
        for block in range(count):
            stride = 2 if (block == 0 and stage > 0) else 1
            out_side = side // stride
            if block == 0:
                convs.append((c_in, mid * 4, 1, out_side))
            convs.append((c_in, mid, 1, side))
            convs.append((mid, mid, 3, out_side))
            convs.append((mid, mid * 4, 1, out_side))
            c_in, side = mid * 4, out_side
    return convs


def resnet50_train_flops_per_sample(args):
    """Forward + backward operations per image: every convolution and the
    classifier, backward twice the forward except the stem, whose input
    gradient nobody needs."""
    image_hw = args["image_shape"][-1]
    macs = 0.0
    for i, (c_in, c_out, k, side) in enumerate(_resnet50_convs(image_hw)):
        forward = c_in * c_out * k * k * side * side
        macs += forward * (2 if i == 0 else 3)
    macs += 2048 * args["class_num"] * 3
    return 2 * macs

