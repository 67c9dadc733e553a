"""Static per-op cost rules (FLOPs + HBM bytes + row ops) — ISSUE 15.

Registered via :func:`core.op_registry.register_cost`, beside the shape
rules; consumed by ``analysis/cost.py``'s :func:`estimate_program`. The
registry-parity test (``tests/test_cost_engine.py``) holds every op with
a shape rule to having a cost rule (or an explicit zero-cost
registration), so a new op cannot silently fall out of the roofline.

Modeling convention — a FLOOR stance (``analysis/cost.py``):

  * elementwise/activation/reduction/cast ops ride a producer's fusion
    epilogue: zero extra HBM traffic, FLOPs counted;
  * irreducible passes charge bytes: matmul/conv operand streams,
    same-shape residual merges (2R+1W of a distant tensor), transposes
    (a real relayout), max-pool select-and-scatter, optimizer state
    passes (master precision, f32);
  * conv backward carries the BN/relu riders the fused lowering pays:
    one extra full activation pass on each of the dX and dW fusions
    (relu mask + BN x-hat reads ride dX, dgamma/dbeta reduction reads
    ride dW) — batch_norm itself then charges zero, exactly the
    committed accounting;
  * embedding lookups / scatter-adds charge ROWS, not bytes (TPU row
    ops are latency-bound); the roofline adds
    the row term on top of max(compute, HBM).

Backward columns (``bwd_*``) are charged only for ops an ``autodiff``
op actually replays — the engine handles that; rules just fill both.
"""

from ..op_registry import register_cost, register_zero_cost
from .shape_rules import _COMPARE, _ELEMENTWISE, _LIKE_X, _LOGICAL


def _prod(dims):
    n = 1
    for d in dims:
        n *= int(d)
    return n


def _nel(ctx, var):
    return None if var is None else ctx.nelems(var)


# ---------------------------------------------------------------------------
# elementwise / unary / reductions: FLOPs only (epilogue-fused floor),
# except same-shape >=3-D merges (residual adds) which read a distant
# tensor: 2 reads + 1 write, the committed residual accounting
# ---------------------------------------------------------------------------

def _elementwise_cost(ctx, op):
    out = op.output("Out")
    n = _nel(ctx, out)
    if n is None:
        ctx.add(op, unresolved=True)
        return
    xs = ctx.shape(op.input("X"))
    ys = ctx.shape(op.input("Y"))
    merge = (xs is not None and ys is not None and xs == ys
             and len(xs) >= 3)
    ctx.add(op, flops=n, bwd_flops=n,
            hbm_bytes=3 * n * ctx.esize(out) if merge else 0,
            note="residual merge: 2R+1W" if merge else None)


for _n in _ELEMENTWISE:
    register_cost(_n)(_elementwise_cost)


def _flops_like_out(ctx, op, slot="Out", per_elem=1):
    v = op.output(slot) or op.output("Y")
    n = _nel(ctx, v)
    if n is None:
        ctx.add(op, unresolved=True)
        return
    ctx.add(op, flops=per_elem * n, bwd_flops=per_elem * n)


for _n in _COMPARE + _LOGICAL + ("logical_not",):
    register_cost(_n)(_flops_like_out)

for _n in _LIKE_X:
    register_cost(_n)(_flops_like_out)

register_cost("dropout")(_flops_like_out)


def _reduce_cost(ctx, op):
    n = _nel(ctx, op.input("X"))
    if n is None:
        ctx.add(op, unresolved=True)
        return
    ctx.add(op, flops=n, bwd_flops=n)


for _n in ("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
           "reduce_prod", "mean"):
    register_cost(_n)(_reduce_cost)


@register_cost("sum")
def _sum_cost(ctx, op):
    vs = op.input_list("X")
    ns = [_nel(ctx, v) for v in vs]
    if any(n is None for n in ns):
        ctx.add(op, unresolved=True)
        return
    ctx.add(op, flops=sum(ns), bwd_flops=sum(ns))


# views / scalar bookkeeping / trace-time constants: fold away
register_zero_cost(
    "cast", "reshape", "reshape2", "squeeze", "squeeze2", "unsqueeze",
    "unsqueeze2", "flatten", "flatten2", "concat", "split", "stack",
    "slice", "expand", "shape", "fill_constant", "uniform_random",
    "gaussian_random", "truncated_gaussian_random",
    "fill_constant_batch_size_like", "uniform_random_batch_size_like",
    "gaussian_random_batch_size_like", "accuracy")


@register_cost("transpose", "transpose2")
def _transpose_cost(ctx, op):
    # a real relayout: read + write, both directions (the seq-2048
    # head-split copies were exactly this bucket)
    n = _nel(ctx, op.input("X"))
    if n is None:
        ctx.add(op, unresolved=True)
        return
    e = ctx.esize(op.input("X"))
    ctx.add(op, hbm_bytes=2 * n * e, bwd_hbm_bytes=2 * n * e)


# ---------------------------------------------------------------------------
# matmul family: 2MNK forward, 4MNK backward (dX + dW); operand streams
# ---------------------------------------------------------------------------

@register_cost("mul")
def _mul_cost(ctx, op):
    xv, yv = op.input("X"), op.input("Y")
    xs, ys = ctx.shape(xv), ctx.shape(yv)
    if xs is None or ys is None:
        ctx.add(op, unresolved=True)
        return
    xnc = op.attr("x_num_col_dims", 1)
    ync = op.attr("y_num_col_dims", 1)
    m, k = _prod(xs[:xnc]), _prod(xs[xnc:])
    n2 = _prod(ys[ync:])
    f = 2.0 * m * k * n2
    e = ctx.esize(xv)
    streams = (m * k + k * n2 + m * n2) * e
    ctx.add(op, flops=f, hbm_bytes=streams,
            bwd_flops=2 * f, bwd_hbm_bytes=2 * streams)


@register_cost("matmul")
def _matmul_cost(ctx, op):
    xv, yv = op.input("X"), op.input("Y")
    xs, ys = ctx.shape(xv), ctx.shape(yv)
    if xs is None or ys is None or len(xs) < 2 or len(ys) < 2:
        ctx.add(op, unresolved=True)
        return
    if op.attr("transpose_X", False):
        xs = xs[:-2] + (xs[-1], xs[-2])
    if op.attr("transpose_Y", False):
        ys = ys[:-2] + (ys[-1], ys[-2])
    batch = max(_prod(xs[:-2]), _prod(ys[:-2]))
    m, k, n2 = xs[-2], xs[-1], ys[-1]
    f = 2.0 * batch * m * k * n2
    e = ctx.esize(xv)
    streams = (_prod(xs) + _prod(ys)
               + batch * m * n2) * e
    ctx.add(op, flops=f, hbm_bytes=streams,
            bwd_flops=2 * f, bwd_hbm_bytes=2 * streams)


# ---------------------------------------------------------------------------
# conv / pool / norm: the committed resnet bytes model, per-op
# ---------------------------------------------------------------------------

@register_cost("conv2d", "depthwise_conv2d")
def _conv2d_cost(ctx, op):
    xv, wv = op.input("Input"), op.input("Filter")
    ov = op.output("Output")
    xs, ws, os_ = ctx.shape(xv), ctx.shape(wv), ctx.shape(ov)
    if xs is None or ws is None or os_ is None or len(xs) != 4 \
            or len(ws) != 4 or len(os_) != 4:
        ctx.add(op, unresolved=True)
        return
    n, c, h, w_ = xs
    o, _, kh, kw = ws
    _, _, oh, ow = os_
    f = 2.0 * n * o * oh * ow * c * kh * kw
    e = ctx.esize(xv)
    xb = n * c * h * w_ * e
    yb = n * o * oh * ow * e
    wb = o * c * kh * kw * e
    # images carry no gradient: a conv fed straight from a data var has
    # no dX (XLA DCEs it) — the stem-conv exclusion, per-op
    has_dx = not getattr(xv, "is_data", False)
    stride2 = h > oh  # resnet uses stride only to halve resolution
    # dX of a stride-2 conv lowers as lhs_dilated (zero-stuffed) conv on
    # the MXU: 4x the MAC grid — a lowering property, so it is floor
    dx_f = f * (4 if stride2 else 1) if has_dx else 0.0
    dx_b = (yb + wb + xb) if has_dx else 0
    dw_b = xb + yb + o * c * kh * kw * 4  # f32 dW
    # BN/relu ride the conv fusions: one extra full activation pass on
    # each of dX (relu mask + BN x-hat) and dW (dgamma/dbeta reads).
    # The note carries the dx/dw split, so a per-bucket attribution
    # reads THESE numbers and not a second model.
    ctx.add(op, flops=f, hbm_bytes=xb + wb + yb,
            bwd_flops=f + dx_f, bwd_hbm_bytes=dx_b + dw_b + 2 * yb,
            note={"kind": "conv", "dx_flops": dx_f, "dx_bytes": dx_b,
                  "dw_flops": f, "dw_bytes": dw_b, "ride_bytes": 2 * yb,
                  "fwd_1x": f if has_dx else 0.0})


@register_cost("fused_conv2d")
def _fused_conv2d_cost(ctx, op):
    """The epilogue-fused chain: conv streams + one residual read when
    present; BN stats ride the output pass (that is the point of the
    fusion), backward identical to the unfused conv's accounting."""
    xv, wv = op.input("Input"), op.input("Filter")
    ov = op.output("Y")
    xs, ws, os_ = ctx.shape(xv), ctx.shape(wv), ctx.shape(ov)
    if xs is None or ws is None or os_ is None or len(xs) != 4 \
            or len(ws) != 4:
        ctx.add(op, unresolved=True)
        return
    n, c, h, w_ = xs
    o, _, kh, kw = ws
    oh, ow = os_[2], os_[3]
    f = 2.0 * n * o * oh * ow * c * kh * kw
    e = ctx.esize(xv)
    xb = n * c * h * w_ * e
    yb = n * o * oh * ow * e
    wb = o * c * kh * kw * e
    rb = yb if op.input("Residual") is not None else 0
    has_dx = not getattr(xv, "is_data", False)
    stride2 = h > oh
    dx_f = f * (4 if stride2 else 1) if has_dx else 0.0
    dx_b = (yb + wb + xb) if has_dx else 0
    dw_b = xb + yb + o * c * kh * kw * 4
    ctx.add(op, flops=f, hbm_bytes=xb + wb + yb + rb,
            bwd_flops=f + dx_f, bwd_hbm_bytes=dx_b + dw_b + 2 * yb)


@register_cost("pool2d")
def _pool2d_cost(ctx, op):
    xb_n = _nel(ctx, op.input("X"))
    ob_n = _nel(ctx, op.output("Out"))
    if xb_n is None or ob_n is None:
        ctx.add(op, unresolved=True)
        return
    e = ctx.esize(op.input("X"))
    xb, ob = xb_n * e, ob_n * e
    if op.attr("pooling_type", "max") == "max":
        # fwd read+write; bwd select-and-scatter reads x, dy, writes dx
        ctx.add(op, hbm_bytes=xb + ob, bwd_hbm_bytes=xb + 2 * ob)
    else:
        ctx.add(op, hbm_bytes=xb + ob, bwd_hbm_bytes=xb + ob)


@register_cost("batch_norm")
def _batch_norm_cost(ctx, op):
    # rides the conv fusions in this lowering (measured standalone BN
    # ~0.6 ms = fused): fwd stats/scale/shift fuse into the conv output
    # pass; the backward's activation re-reads are charged on the conv
    # rule's dX/dW riders — charging them here too would double-count
    n = _nel(ctx, op.input("X"))
    ctx.add(op, flops=2 * (n or 0), bwd_flops=2 * (n or 0),
            note="bytes ride the conv epilogue fusions")


@register_cost("layer_norm", "group_norm")
def _layer_norm_cost(ctx, op):
    # a two-pass statistic op XLA cannot fully fuse away: read + write
    # forward, one extra activation read backward (x-hat)
    n = _nel(ctx, op.input("X"))
    if n is None:
        ctx.add(op, unresolved=True)
        return
    e = ctx.esize(op.input("X"))
    ctx.add(op, flops=8 * n, hbm_bytes=2 * n * e,
            bwd_flops=8 * n, bwd_hbm_bytes=3 * n * e)


# ---------------------------------------------------------------------------
# embedding / indexing: ROW ops (latency-bound, priced per-row)
# ---------------------------------------------------------------------------

def _lookup_cost(ctx, op):
    ids = ctx.shape(op.input("Ids"))
    if ids is None:
        ctx.add(op, unresolved=True)
        return
    if len(ids) >= 2 and ids[-1] == 1:
        ids = ids[:-1]  # LoD-era trailing [.., 1] squeeze
    n = _prod(ids)
    # fwd: n gathered rows; bwd: the densify / sharded backward is one
    # scatter-add of the same n rows
    ctx.add(op, row_reads=n, bwd_row_writes=n)


register_cost("lookup_table", "sharded_lookup_table")(_lookup_cost)


@register_cost("gather")
def _gather_cost(ctx, op):
    idx = ctx.shape(op.input("Index"))
    if idx is None:
        ctx.add(op, unresolved=True)
        return
    n = _prod(idx)
    ctx.add(op, row_reads=n, bwd_row_writes=n)


@register_cost("scatter")
def _scatter_cost(ctx, op):
    idx = ctx.shape(op.input("Ids"))
    if idx is None:
        ctx.add(op, unresolved=True)
        return
    n = _prod(idx)
    ctx.add(op, row_writes=n, bwd_row_reads=n)


@register_cost("one_hot")
def _one_hot_cost(ctx, op):
    n = _nel(ctx, op.output("Out"))
    ctx.add(op, flops=n or 0)


# ---------------------------------------------------------------------------
# losses / metrics / search
# ---------------------------------------------------------------------------

def _loss_cost(per_elem):
    def rule(ctx, op):
        v = op.input("X") or op.input("Logits")
        n = _nel(ctx, v)
        if n is None:
            ctx.add(op, unresolved=True)
            return
        ctx.add(op, flops=per_elem * n, bwd_flops=per_elem * n)
    return rule


register_cost("cross_entropy")(_loss_cost(3))
register_cost("softmax_with_cross_entropy",
              "smooth_softmax_with_cross_entropy")(_loss_cost(5))


@register_cost("fused_linear_smooth_ce")
def _fused_ce_cost(ctx, op):
    # vocab projection + smoothed CE in one op: the matmul dominates
    xs, ws = ctx.shape(op.input("X")), ctx.shape(op.input("W"))
    if xs is None or ws is None or len(ws) != 2:
        ctx.add(op, unresolved=True)
        return
    rows = _prod(xs[:-1])
    d, v = ws
    f = 2.0 * rows * d * v
    e = ctx.esize(op.input("X"))
    streams = (rows * d + d * v + rows) * e  # logits stay in VMEM
    ctx.add(op, flops=f, hbm_bytes=streams,
            bwd_flops=2 * f, bwd_hbm_bytes=2 * streams)


@register_cost("pow")
def _pow_cost(ctx, op):
    n = _nel(ctx, op.input("X"))
    ctx.add(op, flops=n or 0, bwd_flops=n or 0)


register_zero_cost("range", "sequence_mask")


@register_cost("top_k")
def _top_k_cost(ctx, op):
    n = _nel(ctx, op.input("X"))
    ctx.add(op, flops=2 * (n or 0))


@register_cost("argmax", "argmin")
def _arg_cost(ctx, op):
    n = _nel(ctx, op.input("X"))
    ctx.add(op, flops=n or 0)


# ---------------------------------------------------------------------------
# attention (the Pallas kernel family) + incremental decode
# ---------------------------------------------------------------------------

@register_cost("flash_attention")
def _flash_attention_cost(ctx, op):
    qs = ctx.shape(op.input("Q"))
    ks = ctx.shape(op.input("K"))
    if qs is None or ks is None or len(qs) != 3 or len(ks) != 3:
        ctx.add(op, unresolved=True)
        return
    b, t, hd = qs
    t_k = ks[1]
    e = ctx.esize(op.input("Q"))
    f = 4.0 * b * t * t_k * hd  # QK^T + AV
    # streaming kernels: q/k/v read + o write forward; backward re-reads
    # the streams and writes dq/dk/dv (flash recompute keeps logits out
    # of HBM — that is the kernel's point)
    fwd_b = (2 * b * t * hd + 2 * b * t_k * hd) * e
    ctx.add(op, flops=f, hbm_bytes=fwd_b,
            bwd_flops=2.5 * f, bwd_hbm_bytes=2 * fwd_b)


@register_cost("kv_cache_write")
def _kv_cache_write_cost(ctx, op):
    n = _nel(ctx, op.input("X"))
    if n is None:
        ctx.add(op, unresolved=True)
        return
    e = ctx.esize(op.input("X"))
    ctx.add(op, hbm_bytes=2 * n * e)  # read token slice, write rows


def _cached_attention_sizes(ctx, op):
    """(B, positions a query reads at most, Hkv*Dk, Hkv*Dv, H*Dk, H*Dv) of a
    cached-attention op, or None where a shape is open. A window bounds the
    positions a query reads; the caches are read once whatever the number
    of query heads a group."""
    ks = ctx.shape(op.input("CacheK"))
    vs = ctx.shape(op.input("CacheV"))
    qs = ctx.shape(op.input("Q"))
    if ks is None or qs is None or len(ks) != 3 or -1 in ks:
        return None
    b, cap, kd = ks
    vd = kd if vs is None or vs[-1] == -1 else vs[-1]
    h = int(op.attr("num_heads", 1))
    kv = int(op.attr("num_kv_heads", 0) or h)
    window = int(op.attr("window", 0))
    read = min(cap, window) if window else cap
    return b, read, kd, vd, kd // kv * h, vd // kv * h


@register_cost("cached_attention")
def _cached_attention_cost(ctx, op):
    sizes = _cached_attention_sizes(ctx, op)
    if sizes is None:
        ctx.add(op, unresolved=True)
        return
    b, read, kd, vd, qd, od = sizes
    e = ctx.esize(op.input("Q"))
    ctx.add(op, flops=2.0 * b * read * (qd + od),
            hbm_bytes=(b * read * (kd + vd) + b * (qd + od)) * e)


@register_cost("kv_cache_write_chunk")
def _kv_cache_write_chunk_cost(ctx, op):
    n = _nel(ctx, op.input("X"))
    if n is None:
        ctx.add(op, unresolved=True)
        return
    e = ctx.esize(op.input("X"))
    ctx.add(op, hbm_bytes=2 * n * e)  # read chunk slice, write rows


@register_cost("cached_attention_chunk")
def _cached_attention_chunk_cost(ctx, op):
    sizes = _cached_attention_sizes(ctx, op)
    qs = ctx.shape(op.input("Q"))
    if sizes is None or len(qs) != 3 or qs[1] == -1:
        ctx.add(op, unresolved=True)
        return
    b, read, kd, vd, qd, od = sizes
    kq = qs[1]
    e = ctx.esize(op.input("Q"))
    # a ring is read beside the chunk's own K rows
    rows = read + (kq if op.attr("ring", False) else 0)
    ctx.add(op, flops=2.0 * b * kq * read * (qd + od),
            hbm_bytes=(b * rows * (kd + vd) + b * kq * (qd + od)) * e)


def _index_cost(ctx, op, lanes_of):
    qs, ks = ctx.shape(op.input("Q")), ctx.shape(op.input("CacheK"))
    if qs is None or ks is None or -1 in qs or -1 in ks:
        ctx.add(op, unresolved=True)
        return
    b, cap, d = ks
    lanes = lanes_of(qs)
    e, ek = ctx.esize(op.input("Q")), ctx.esize(op.input("CacheK"))
    # every cached index key (in the type it is cached in) against every
    # index head of every query (the bucket's capacity: a static rule cannot
    # know the fill), then the selection's passes over the float32 scores
    ctx.add(op, flops=2.0 * lanes * cap * qs[-1] + 32.0 * lanes * cap,
            hbm_bytes=b * cap * d * ek + lanes * qs[-1] * e
            + 4 * lanes * cap)


@register_cost("sparse_index")
def _sparse_index_cost(ctx, op):
    _index_cost(ctx, op, lambda qs: qs[0])


@register_cost("sparse_index_chunk")
def _sparse_index_chunk_cost(ctx, op):
    _index_cost(ctx, op, lambda qs: qs[0] * qs[1])


def _latent_cost(ctx, op, lanes_of):
    qs, cs = ctx.shape(op.input("Q")), ctx.shape(op.input("Cache"))
    bs = ctx.shape(op.input("KvB"))
    if qs is None or cs is None or bs is None or -1 in qs or -1 in cs:
        ctx.add(op, unresolved=True)
        return None
    heads, nope = int(op.attr("num_heads")), int(op.attr("nope_dim"))
    v_dim = int(op.attr("v_dim"))
    b, cap, width = cs
    r = bs[0]
    lanes = lanes_of(qs)
    e = ctx.esize(op.input("Q"))
    return lanes, heads, nope, v_dim, b, cap, width, r, e


@register_cost("latent_attention")
def _latent_attention_cost(ctx, op):
    got = _latent_cost(ctx, op, lambda qs: qs[0])
    if got is None:
        return
    lanes, heads, nope, v_dim, b, cap, width, r, e = got
    ss = ctx.shape(op.input("Index"))
    picks = cap if ss is None or ss[-1] == -1 else ss[-1]
    # the query into the latent, scores and mix over the set, W_uv
    flops = 2.0 * lanes * heads * (r * (nope + v_dim)
                                   + picks * (width + r))
    ctx.add(op, flops=flops,
            hbm_bytes=(r * heads * (nope + v_dim) + lanes * picks * width
                       + lanes * heads * (width - r + nope + v_dim)) * e)


@register_cost("latent_attention_dense")
def _latent_attention_dense_cost(ctx, op):
    got = _latent_cost(ctx, op, lambda qs: qs[0] * qs[1])
    if got is None:
        return
    lanes, heads, nope, v_dim, b, cap, width, r, e = got
    # every lane scores and mixes the whole capacity, the cache read once
    # (a static rule cannot know the fill; on one TPU the step kernel stops
    # at the highest position a row's lanes hold)
    flops = 2.0 * lanes * heads * (r * (nope + v_dim) + cap * (width + r))
    ctx.add(op, flops=flops,
            hbm_bytes=(r * heads * (nope + v_dim) + b * cap * width
                       + lanes * heads * (width - r + nope + v_dim)) * e)


# a row's lane picked out of [B, K, D]; [B, 2] tokens judged and counted:
# bookkeeping beside the products they stand between
register_zero_cost("last_live_lane", "self_draft_accept")


@register_cost("latent_attention_chunk")
def _latent_attention_chunk_cost(ctx, op):
    got = _latent_cost(ctx, op, lambda qs: qs[0] * qs[1])
    if got is None:
        return
    lanes, heads, nope, v_dim, b, cap, width, r, e = got
    # blocks of the whole capacity under the mask (a static rule cannot
    # know the fill; the op stops at a row's highest live position)
    flops = 2.0 * lanes * heads * (r * (nope + v_dim) + cap * (width + r))
    ctx.add(op, flops=flops,
            hbm_bytes=(r * heads * (nope + v_dim) + b * cap * width
                       + lanes * heads * (width - r + nope + v_dim)) * e
            + lanes * cap)


def _eva_sizes(ctx, op):
    """(B, window slots, summary entries, H*D, lanes a row) of an EVA op, or
    None where a shape is open."""
    win, entries = ctx.shape(op.input("WinK")), ctx.shape(op.input("SumK"))
    new = op.input("NewK")
    lanes = 1 if new is None else (ctx.shape(new) or (0, -1))[1]
    if win is None or entries is None or -1 in win or -1 in entries \
            or lanes == -1:
        return None
    return win[0], win[1], entries[1], win[2], lanes


@register_cost("eva_summary", "eva_summary_chunk")
def _eva_summary_cost(ctx, op):
    sizes = _eva_sizes(ctx, op)
    if sizes is None:
        ctx.add(op, unresolved=True)
        return
    b, _w, _entries, hd, lanes = sizes
    chunk = int(op.attr("chunk"))
    e = ctx.esize(op.input("WinK"))
    # the positions pooled: a step's one chunk a row, a run's lanes and the
    # chunk its first lane continues; keys and values read once, a summary
    # key and value a chunk written; logits, the two pooled sums
    rows = b * (lanes + chunk)
    ctx.add(op, flops=6.0 * rows * hd,
            hbm_bytes=2 * (rows + rows // chunk) * hd * e)


@register_cost("eva_attention", "eva_attention_chunk")
def _eva_attention_cost(ctx, op):
    sizes = _eva_sizes(ctx, op)
    if sizes is None:
        ctx.add(op, unresolved=True)
        return
    b, w, entries, hd, lanes = sizes
    e = ctx.esize(op.input("Q"))
    # both caches whole under the mask (a static rule cannot know the fill),
    # a run's own rows beside the window; each read once
    own = lanes if op.input("NewK") is not None else 0
    read = w + entries + own
    ctx.add(op, flops=4.0 * b * lanes * read * hd,
            hbm_bytes=(2 * b * read * hd + 2 * b * lanes * hd) * e)


# ---------------------------------------------------------------------------
# optimizer updates: master-precision (f32) state passes, batch-amortized
# ---------------------------------------------------------------------------

def _opt_cost(state_passes):
    """read+write of param + each optimizer-state slot, f32 master
    precision (the committed adam accounting: 6 passes)."""

    def rule(ctx, op):
        p = op.input("Param") or op.input("ParamOut")
        n = _nel(ctx, p)
        if n is None:
            ctx.add(op, unresolved=True)
            return
        ctx.add(op, hbm_bytes=state_passes * n * 4)
    return rule


register_cost("sgd")(_opt_cost(2))                    # p rw
register_cost("momentum", "adagrad", "sparse_decay")(_opt_cost(4))
register_cost("lars_momentum")(_opt_cost(4))
register_cost("adam", "adamax", "adadelta", "rmsprop",
              "decayed_adagrad", "lamb")(_opt_cost(6))  # p/m/v rw
register_cost("ftrl")(_opt_cost(6))


# ---------------------------------------------------------------------------
# the hybrid blocks (models/qwen3_next.py, models/nemotron_h.py)
# ---------------------------------------------------------------------------

@register_cost("rms_norm")
def _rms_norm_cost(ctx, op):
    # like layer_norm: one statistic, read + write forward, x-hat re-read
    # backward; the gate, where given, is one more stream each way
    n = _nel(ctx, op.input("X"))
    if n is None:
        ctx.add(op, unresolved=True)
        return
    e = ctx.esize(op.input("X"))
    streams = 3 if op.input("Gate") is not None else 2
    ctx.add(op, flops=6 * n, hbm_bytes=streams * n * e,
            bwd_flops=8 * n, bwd_hbm_bytes=(streams + 1) * n * e)


@register_cost("rotary", "causal_conv1d")
def _rowwise_pass_cost(ctx, op):
    # an element-wise pass XLA does not fold into a matmul (it sits
    # between a reshape and a kernel): read + write each way
    n = _nel(ctx, op.input("X"))
    if n is None:
        ctx.add(op, unresolved=True)
        return
    e = ctx.esize(op.input("X"))
    taps = 1
    if op.type == "causal_conv1d":
        ws = ctx.shape(op.input("Filter"))
        taps = int(ws[1]) if ws is not None else 4
    ctx.add(op, flops=2 * taps * n, hbm_bytes=2 * n * e,
            bwd_flops=4 * taps * n, bwd_hbm_bytes=3 * n * e)


def _gated_delta_core_flops(tokens, heads, dk, dv, chunk):
    """Forward multiply-adds x 2 of the chunked delta rule's core: a token
    and head, the in-chunk products (k k^T, q k^T: 2 * C * Dk; the
    triangular solve: C * (Dv + Dk); scores times values: C * Dv) and the
    products with the carried state (read twice and written once: 3 * Dk *
    Dv)."""
    a_token_head = 2 * chunk * dk + chunk * (dv + dk) + chunk * dv \
        + 3 * dk * dv
    return 2.0 * tokens * heads * a_token_head


@register_cost("gated_delta_rule")
def _gated_delta_rule_cost(ctx, op):
    qs, vs = ctx.shape(op.input("Q")), ctx.shape(op.input("V"))
    if qs is None or vs is None or len(vs) != 3:
        ctx.add(op, unresolved=True)
        return
    b, t, hvd = vs
    hk, hv = int(op.attr("num_k_heads")), int(op.attr("num_v_heads"))
    dk, dv = qs[-1] // hk, hvd // hv
    chunk = int(op.attr("chunk", 64))
    f = _gated_delta_core_flops(b * t, hv, dk, dv, chunk)
    e = ctx.esize(op.input("V"))
    # q, k, v read and the output written; the state of every chunk is
    # kept for the backward pass in float32
    stream = (2 * b * t * qs[-1] + 2 * b * t * hvd) * e
    states = b * (-(-t // chunk)) * hv * dk * dv * 4
    ctx.add(op, flops=f, hbm_bytes=stream + states,
            bwd_flops=2 * f, bwd_hbm_bytes=2 * stream + states)


@register_cost("mamba2_ssd")
def _mamba2_ssd_cost(ctx, op):
    xs, bs = ctx.shape(op.input("X")), ctx.shape(op.input("Bm"))
    if xs is None or bs is None or len(xs) != 3 or -1 in xs:
        ctx.add(op, unresolved=True)
        return
    b, t, hp = xs
    heads, groups = int(op.attr("num_heads")), int(op.attr("num_groups"))
    p, n = hp // heads, bs[-1] // groups
    chunk = int(op.attr("chunk", 128))
    # a token: C B^T a group (C * N), the masked product with dt x a head
    # (C * P), the chunk's addition to the state and the read of the state
    # that entered it (2 * P * N a head)
    f = 2.0 * b * t * (groups * chunk * n + heads * (chunk * p + 2 * p * n))
    e = ctx.esize(op.input("X"))
    # x read and y written, B and C read, the step in float32
    stream = b * t * ((2 * hp + 2 * bs[-1]) * e + heads * 4)
    ctx.add(op, flops=f, hbm_bytes=stream, bwd_flops=2 * f,
            bwd_hbm_bytes=2 * stream)


@register_cost("routed_experts")
def _routed_experts_cost(ctx, op):
    xs = ctx.shape(op.input("X"))
    us = ctx.shape(op.input("ExpertUp"))
    rs = ctx.shape(op.input("Router"))
    if xs is None or us is None or rs is None or -1 in xs:
        ctx.add(op, unresolved=True)
        return
    tokens, d_model = _prod(xs[:-1]), xs[-1]
    held, f, d = us                 # d: the width the experts read
    mats = 2 if op.attr("form", "swiglu") == "relu2" else 3
    experts, top_k = rs[1], int(op.attr("top_k"))
    e = ctx.esize(op.input("X"))
    # the expected share of the picks falls on the held experts
    rows = tokens * top_k * held / float(experts)
    flops = 2.0 * tokens * d_model * experts + 2.0 * rows * mats * d * f
    nbytes = mats * held * d * f * e + 2 * tokens * d * e + 2 * rows * d * e
    ss = ctx.shape(op.input("SharedUp"))
    if ss is not None:
        gated = op.input("SharedExpertGate") is not None
        flops += 2.0 * tokens * (mats * d_model * ss[1] + d_model * gated)
        nbytes += mats * d_model * ss[1] * e
    ctx.add(op, flops=flops, hbm_bytes=nbytes, bwd_flops=2 * flops,
            bwd_hbm_bytes=2 * nbytes)
