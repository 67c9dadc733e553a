"""Attention + sampling op registrations (bridge to ``paddle_tpu.ops``),
plus the incremental-decode primitives (KV-cache write + cached attention)
the serving tier's step programs are built from."""

import math

import jax
import jax.numpy as jnp

from ..op_registry import register, get, put, next_rng


@register("flash_attention")
def _flash_attention_op(env, op):
    from ...ops.flash_attention import flash_attention, plan_for
    from ...ops.gates import note

    from ..op_registry import mxu_cast

    q = get(env, op.input("Q"))
    k = get(env, op.input("K"))
    v = get(env, op.input("V"))
    bias = get(env, op.input("Bias"))
    out_dtype = q.dtype
    q, k, v = mxu_cast(q, k, v)
    heads = op.attr("num_heads", 1)
    kv_heads = op.attr("num_kv_heads", 0) or heads
    if kv_heads != heads:
        # grouped-query heads: query head h reads key/value head
        # h // (heads / kv_heads). K and V are repeated to the query heads
        # before the kernels, which take one head count; a call with equal
        # counts is untouched
        def repeat(x):
            b, t, hd = x.shape
            x = x.reshape(b, t, kv_heads, hd // kv_heads)
            return jnp.repeat(x, heads // kv_heads, axis=2).reshape(
                b, t, -1)

        k, v = repeat(k), repeat(v)
    dropout = op.attr("dropout_rate", 0.0)
    rng = next_rng(env) if dropout > 0.0 else None
    plan = plan_for(q, k, bias, op.attr("num_heads", 1),
                    op.attr("causal", False), dropout, rng)
    # trace-time record: which attention kernel this op actually takes
    # and why a demotion happened (ISSUE 15 no-silent-fallback contract)
    op.attrs["_kernel_choice"] = plan.to_dict()
    note("flash_attention", plan)
    out = flash_attention(q, k, v, op.attr("num_heads", 1), bias=bias,
                          causal=op.attr("causal", False),
                          dropout_rate=dropout, rng=rng, plan=plan)
    put(env, op.output("Out"), out.astype(out_dtype))


@register("gated_delta_rule")
def _gated_delta_rule(env, op):
    """The Gated DeltaNet core (``ops/gated_delta.py``): Q, K [B, T, Hk*Dk]
    and V [B, T, Hv*Dv] after the causal convolution, the raw decay and
    write-strength projections A, B [B, T, Hv], the heads' ALog and DtBias;
    L2-normalised q/k, the delta rule over a [Dk, Dv] state a head in
    chunks of ``chunk`` tokens. Out [B, T, Hv*Dv] in V's dtype. On one TPU,
    at head dimensions that are multiples of 128 and chunk 64, the
    ``gated_delta`` Pallas kernels run (``gated_delta.kernel_plan``);
    elsewhere (CPU, a meshed step, other shapes) the chunked ``jnp`` form,
    and the site's decision says which and why."""
    from ...ops import gated_delta
    from ...ops.gates import note
    from ..op_registry import amp_enabled

    q, v = get(env, op.input("Q")), get(env, op.input("V"))
    chunk = int(op.attr("chunk", 64))
    hk, hv = int(op.attr("num_k_heads")), int(op.attr("num_v_heads"))
    plan = gated_delta.plan_for(q, v, hk, hv, chunk)
    op.attrs["_kernel_choice"] = plan.to_dict()
    note("gated_delta_rule", plan)
    out = gated_delta.gated_delta_attention(
        q, get(env, op.input("K")), v,
        get(env, op.input("A")), get(env, op.input("B")),
        get(env, op.input("ALog")), get(env, op.input("DtBias")),
        hk, hv, chunk, mxu_dtype=jnp.bfloat16 if amp_enabled() else None,
        plan=plan)
    put(env, op.output("Out"), out.astype(v.dtype))


@register("mamba2_ssd")
def _mamba2_ssd(env, op):
    """The Mamba-2 state-space core (``ops/mamba2.py``): X [B, T, H*P] and
    the input and output maps Bm, Cm [B, T, G*N] after the causal
    convolution, the raw step projection Dt [B, T, H], the heads' ALog,
    DtBias and D; ``dt = softplus(Dt + DtBias)``, ``A = -exp(ALog)``, a
    [P, N] state a head in chunks of ``chunk`` tokens, ``+ D x``. Out
    [B, T, H*P] in X's dtype. One form computes it everywhere, the chunked
    ``jnp`` form, and the site's decision says so."""
    from ...ops import mamba2
    from ...ops.gates import note
    from ..op_registry import amp_enabled

    x = get(env, op.input("X"))
    heads, chunk = int(op.attr("num_heads")), int(op.attr("chunk", 128))
    plan = mamba2.plan_for(x, heads, chunk)
    op.attrs["_kernel_choice"] = plan.to_dict()
    note("mamba2_ssd", plan)
    out = mamba2.mamba2_ssd(
        x, get(env, op.input("Bm")), get(env, op.input("Cm")),
        get(env, op.input("Dt")), get(env, op.input("ALog")),
        get(env, op.input("DtBias")), get(env, op.input("D")), heads,
        int(op.attr("num_groups")), chunk,
        mxu_dtype=jnp.bfloat16 if amp_enabled() else None)
    put(env, op.output("Out"), out.astype(x.dtype))


def _extended(env, op, k, v):
    """(kv_heads, window, ring, sink) where a cached-attention op
    asks for more than one head count and one width over every cached
    position (``ops/cache_attention.py``), else None: the plain form below
    then lowers as it always has."""
    h = int(op.attr("num_heads", 1))
    kv_heads = int(op.attr("num_kv_heads", 0) or h)
    window, ring = int(op.attr("window", 0)), bool(op.attr("ring", False))
    sink = op.input("Sink")
    if (kv_heads == h and not window and not ring and sink is None
            and v.shape[-1] == k.shape[-1] and op.output("Count") is None
            and op.input("NewK") is None):
        return None
    return kv_heads, window, ring, get(env, sink)


@register("kv_cache_write")
def _kv_cache_write(env, op):
    """Per-row cache update: Cache [B, C, ...], X [B, ...], Pos [B] ->
    Out[b, Pos[b]] = X[b], other entries untouched. Any tail: a key or a
    value row of H*D, a latent row ``[c | k_pe]``, an index key. Each row
    writes ONLY
    its own slot — the property the continuous batcher's solo-vs-batched
    bitwise-parity guarantee rests on (a dead slot's garbage write cannot
    leak into a live row). Out-of-range positions drop (a retired slot fed
    a zero position is harmless either way). ``ring``: the cache is a ring
    of C positions and position p lives at slot ``p % C``."""
    cache = get(env, op.input("Cache"))
    x = get(env, op.input("X"))
    pos = get(env, op.input("Pos")).reshape(-1).astype(jnp.int32)
    b = cache.shape[0]
    if op.attr("ring", False):
        pos = jnp.mod(pos, cache.shape[1])
    put(env, op.output("Out"),
        cache.at[jnp.arange(b), pos].set(x.astype(cache.dtype),
                                         mode="drop"))


@register("cached_attention")
def _cached_attention(env, op):
    """One-token attention over a fixed-capacity KV cache: Q [B, H*D],
    CacheK/CacheV [B, C, H*D], Pos [B] (the index the current token was
    just written at). Row b attends over cache positions <= Pos[b] only —
    positions past the row's own fill level (including every slot of a
    dead row) are masked out before the softmax. Numerics mirror
    ``ops.flash_attention.mha_reference``: logits * 1/sqrt(D), f32
    softmax. Strictly per-row: no cross-row reduction anywhere.

    The caches are read AS THEY ARE STORED, ``[C, H*D]`` a row with the
    heads side by side: the scores are the plain product of a row's cache
    with its query laid out block-diagonally (``[H*D, H]``: head h's D
    values in column h, zeros elsewhere), the mix the plain product of
    the probabilities ``[H, C]`` with the cache, of which head h keeps its
    own D columns. A product a head over ``[B, C, H, D]`` computes the
    same sums, but on the TPU a compiler that is given it first copies
    every cache into a layout with the positions innermost (0.27 ms an
    array a step at [16, 1280, 2048]); the zeros cost MXU passes that hide
    under the cache's read from HBM.

    ``num_kv_heads`` < ``num_heads`` (grouped heads: query head h reads
    key/value head ``h // (H / Hkv)``, the caches ``[B, C, Hkv*Dk]`` and
    ``[B, C, Hkv*Dv]`` of their own widths), ``window`` (only the
    ``window`` positions up to Pos), ``ring`` (position p at slot ``p %
    C``), a ``Sink`` [H] input (a learned scalar a head in the softmax's
    denominator) or a ``Count`` [1] int32 output (the positions the rows
    read) take the op to ``ops/cache_attention.py``: the same block-diagonal
    read of the caches as stored, a group at a time.

    **Which form runs where.** Both forms above read the whole rung under
    a mask. On ONE TPU a step over a cache that holds the context (no
    window, no ring) reads the blocks of positions up to each row's own
    ``Pos`` instead, by the Pallas kernel ``cache_step.fwd``
    (``cache_attention.step_blocks``: the same block-diagonal queries, a
    streaming softmax over a row's blocks); the extended form binds it under
    its scope ``attn.full``. ``cache_attention.plan_for`` decides it from
    the placement, the widths and the rung's length, and the decision is
    recorded in ``op.attrs["_kernel_choice"]`` and handed to the trace's
    gate count (``gates.note``). The CPU, a mesh, a window, a ring and a
    shape the gate refuses lower as they always have."""
    from ...ops import cache_attention
    from ...ops.gates import note

    q = get(env, op.input("Q"))
    k = get(env, op.input("CacheK"))
    v = get(env, op.input("CacheV"))
    pos = get(env, op.input("Pos")).reshape(-1).astype(jnp.int32)
    h = int(op.attr("num_heads", 1))
    more = _extended(env, op, k, v)
    kv_heads, window, ring, sink = more or (h, 0, False, None)
    plan = cache_attention.plan_for(q, k, v, h, kv_heads, window, ring)
    op.attrs["_kernel_choice"] = plan.to_dict()
    note("cached_attention", plan)
    if plan or more is not None:
        if plan:
            out, count = cache_attention.step_blocks(
                q, k, v, pos, h, kv_heads, sink, plain=more is None)
        else:
            out, count = cache_attention.attend_step(
                q, k, v, pos, h, kv_heads, window, sink, ring)
        put(env, op.output("Out"), out)
        put(env, op.output("Count"), count)
        return
    b, c, hd = k.shape
    d = hd // h
    own = jnp.eye(h, dtype=bool)
    q_blocks = jnp.where(own[:, None, :], q.reshape(b, h, d, 1),
                         0).reshape(b, hd, h)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bck,bkh->bhc", k, q_blocks) * scale
    mask = jnp.arange(c)[None, None, :] <= pos[:, None, None]
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
        q.dtype)
    mixed = jnp.einsum("bhc,bck->bhk", probs, v).reshape(b, h, h, d)
    put(env, op.output("Out"),
        jnp.einsum("bhhd->bhd", mixed).reshape(b, hd))


def _write_few(cache, x, pos):
    """``cache[b, pos[b, j]] = x[b, j]`` one dynamic update a lane, a lane
    past the cache writing back what is there. A scatter over [B, K] wants
    the cache row-major; where a row's tail is no multiple of 128 (a latent
    row of 576) the device keeps [B, C, tail] with the POSITIONS innermost,
    and the compiler turned every cache round for the scatter and back for
    the fetch, two copies of the whole cache a layer a step (2.4 GB a step
    at 32 rows x 4096 x 576 x 8 layers). A dynamic update of one row
    writes the cache where it lies."""
    b, c = cache.shape[:2]
    k = x.shape[1]
    tail = (0,) * (cache.ndim - 2)
    one = (1, 1) + cache.shape[2:]
    x = x.astype(cache.dtype)

    def row(i, cache):
        for j in range(k):  # a row's lanes in their order, K is 1 or 2
            p = pos[i, j]
            at = jnp.minimum(p, c - 1)
            new = jnp.where(
                p < c, jax.lax.dynamic_slice(x, (i, j) + tail, one),
                jax.lax.dynamic_slice(cache, (i, at) + tail, one))
            cache = jax.lax.dynamic_update_slice(cache, new, (i, at) + tail)
        return cache

    return jax.lax.fori_loop(0, b, row, cache)


@register("kv_cache_write_chunk")
def _kv_cache_write_chunk(env, op):
    """K-row cache update (the chunked-prefill / speculative-verify
    sibling of ``kv_cache_write``): Cache [B, C, ...], X [B, K, ...],
    Pos [B, K] -> Out[b, Pos[b, j]] = X[b, j]. Still strictly per-row —
    row b scatters only into its own cache rows, so the batcher's
    solo-vs-batched parity property carries over to chunk dispatches.
    Out-of-range positions drop: the scheduler pads partial chunks with
    Pos = capacity, so a padded lane writes nothing. ``ring``: the cache is
    a ring of C positions, a lane of Pos ``>= pad_pos`` is a pad lane, and
    of a row's live lanes (consecutive positions from lane 0 on) only those
    land, at ``Pos % C``, that no later lane of the chunk overwrites
    (``cache_attention.ring_slots``): a scatter that names a slot twice
    resolves in no defined order. ``few`` (a step's one or two lanes): one
    dynamic update a lane (:func:`_write_few`); a lane below 0 is not
    given."""
    cache = get(env, op.input("Cache"))
    x = get(env, op.input("X"))
    pos = get(env, op.input("Pos")).astype(jnp.int32)
    b = cache.shape[0]
    if op.attr("few", False):
        # a STEP's write, so under the step write's scope, which the
        # readers of a step's cache writes look for
        with jax.named_scope("kv_cache_write"):
            put(env, op.output("Out"), _write_few(cache, x, pos))
        return
    if op.attr("ring", False):
        from ...ops import cache_attention

        pos = cache_attention.ring_slots(pos, cache.shape[1],
                                         int(op.attr("pad_pos")))
    put(env, op.output("Out"),
        cache.at[jnp.arange(b)[:, None], pos].set(x.astype(cache.dtype),
                                                  mode="drop"))


@register("cached_attention_chunk")
def _cached_attention_chunk(env, op):
    """K-query attention over a fixed-capacity KV cache: Q [B, K, H*D],
    CacheK/CacheV [B, C, H*D], Pos [B, K] (the index each query token
    was just written at). Query j of row b attends cache positions
    <= Pos[b, j] — per-query causal masking over the filled prefix plus
    the chunk's own earlier tokens, exactly the step op's semantics
    applied K times. Numerics mirror ``cached_attention`` (1/sqrt(D)
    scale, f32 softmax); still strictly per-row.

    ``num_kv_heads``, ``window`` and ``Sink`` as ``cached_attention``'s;
    the caches are then read in blocks up to a row's highest live position
    under a streaming softmax (``cache_attention.attend_chunk``). ``ring``
    with ``NewK`` / ``NewV`` [B, K, ..]: CacheK / CacheV are rings as they
    were BEFORE the chunk, the chunk's own keys and values come beside
    them, and a lane reads both inside its window
    (``cache_attention.attend_chunk_ring``); the rings are written after.

    **Which form runs where.** On ONE TPU the extended form over a cache
    that holds the context (no window, no ring) is the Pallas kernel
    ``cache_chunk.fwd`` (``cache_attention.chunk_blocks``: the same blocks,
    a tile of lanes' scores in VMEM and never in HBM), under its scope
    ``attn.full``. ``cache_attention.chunk_plan_for`` decides it from the
    placement, the types, the widths and the lengths, recorded as
    ``cached_attention``'s decision is. The CPU, a mesh, a window, a ring
    and a shape the gate refuses lower as they always have, and so does the
    plain form below."""
    q = get(env, op.input("Q"))
    k = get(env, op.input("CacheK"))
    v = get(env, op.input("CacheV"))
    pos = get(env, op.input("Pos")).astype(jnp.int32)
    h = int(op.attr("num_heads", 1))
    more = _extended(env, op, k, v)
    if more is not None:
        from ...ops import cache_attention
        from ...ops.gates import note

        kv_heads, window, ring, sink = more
        plan = cache_attention.chunk_plan_for(q, k, v, h, kv_heads, window,
                                              ring)
        op.attrs["_kernel_choice"] = plan.to_dict()
        note("cached_attention_chunk", plan)
        if plan:
            out = cache_attention.chunk_blocks(q, k, v, pos, h, kv_heads,
                                               sink)
        elif ring:
            out = cache_attention.attend_chunk_ring(
                q, k, v, get(env, op.input("NewK")),
                get(env, op.input("NewV")), pos, h, kv_heads, window, sink)
        else:
            out = cache_attention.attend_chunk(q, k, v, pos, h, kv_heads,
                                               window, sink)
        put(env, op.output("Out"), out)
        return
    b, c, hd = k.shape
    kq = q.shape[1]
    d = hd // h
    qh = q.reshape(b, kq, h, d)
    kh = k.reshape(b, c, h, d)
    vh = v.reshape(b, c, h, d)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bchd->bhqc", qh, kh) * scale
    mask = jnp.arange(c)[None, None, None, :] <= pos[:, None, :, None]
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
        q.dtype)
    ctx = jnp.einsum("bhqc,bchd->bqhd", probs, vh)
    put(env, op.output("Out"), ctx.reshape(b, kq, hd))


@register("sparse_index")
def _sparse_index(env, op):
    """The indexer of a decode step (``ops/sparse_latent.py``): Q [B, H*D]
    index queries, W [B, H] a weight a head, CacheK [B, C, D] the cached
    index keys with this step's written, Pos [B]. ``I[b, s] = sum_j W[b, j]
    relu(Q[b, j] . CacheK[b, s])`` for ``s <= Pos[b]`` in float32, then the
    exact ``top_k`` largest. Index [B, min(top_k, C)] int32: the positions
    row b attends, ``C`` (past the cache) where it has fewer. Count [2]
    int32: positions selected and positions cached, summed over the rows.
    Strictly per-row."""
    from ...ops import sparse_latent

    index, count = sparse_latent.sparse_index(
        get(env, op.input("Q")), get(env, op.input("W")),
        get(env, op.input("CacheK")), get(env, op.input("Pos")),
        int(op.attr("num_heads")), int(op.attr("top_k")))
    put(env, op.output("Index"), index)
    put(env, op.output("Count"), count)


@register("sparse_index_chunk")
def _sparse_index_chunk(env, op):
    """The indexer of a chunk: Q [B, K, H*D], W [B, K, H], CacheK [B, C, D]
    with the chunk's keys written, Pos [B, K] (``C`` on a pad lane). The
    same scores and the same exact top-k a query; the set is handed on as
    Mask [B, K, C] bool (lane j of row b attends position s where it is
    set), so that what reads it walks the cache in blocks and gathers
    nothing. Count [2] int32 as ``sparse_index``'s, over the live lanes.
    Strictly per-row; a row of pad lanes costs no block."""
    from ...ops import sparse_latent

    mask, count = sparse_latent.sparse_index_chunk(
        get(env, op.input("Q")), get(env, op.input("W")),
        get(env, op.input("CacheK")), get(env, op.input("Pos")),
        int(op.attr("num_heads")), int(op.attr("top_k")))
    put(env, op.output("Mask"), mask)
    put(env, op.output("Count"), count)


def _latent_attrs(op):
    return (int(op.attr("num_heads")), int(op.attr("nope_dim")),
            int(op.attr("v_dim")), float(op.attr("scale")))


@register("latent_attention")
def _latent_attention(env, op):
    """One-token latent (MLA) attention over an index set, absorbed form
    (``ops/sparse_latent.py``): Q [B, H*(N+P)] a head's no-position and
    rotary parts, KvB [R, H*(N+V)] the up-projection of the latent, Cache
    [B, C, R+P] ONE row ``[latent | rotary key]`` a position with this
    step's written, Index [B, S] int32 from ``sparse_index`` (``>= C``:
    none). Scores ``(q_nope W_uk^T . c + q_pe . k_pe) * scale`` in float32,
    softmax over the set alone, the mixed latent times ``W_uv``. Out
    [B, H*V]: the key/query width N+P, the cached width R+P and the value
    width V all differ. Strictly per-row."""
    from ...ops import sparse_latent

    put(env, op.output("Out"), sparse_latent.latent_attention(
        get(env, op.input("Q")), get(env, op.input("KvB")),
        get(env, op.input("Cache")), get(env, op.input("Index")),
        *_latent_attrs(op)))


@register("latent_attention_chunk")
def _latent_attention_chunk(env, op):
    """K-query latent attention under ``sparse_index_chunk``'s Mask
    [B, K, C]: Q [B, K, H*(N+P)], KvB, Cache [B, C, R+P] with the chunk's
    rows written, Pos [B, K]. The step op's numerics applied K times, a
    row's cache read in blocks up to its highest live position under a
    streaming softmax: neither [K, H, C] scores nor a [K, S, R+P] gather
    exist. Without Mask (a model that has no indexer) a lane reads every
    position up to its own. Out [B, K, H*V], 0 on a pad lane. Strictly
    per-row."""
    from ...ops import sparse_latent

    put(env, op.output("Out"), sparse_latent.latent_attention_chunk(
        get(env, op.input("Q")), get(env, op.input("KvB")),
        get(env, op.input("Cache")), get(env, op.input("Mask")),
        get(env, op.input("Pos")), *_latent_attrs(op)))


@register("latent_attention_dense")
def _latent_attention_dense(env, op):
    """A step's latent attention where nothing selects: Q [B, K, H*(N+P)]
    one or two queries a row, KvB, Cache [B, C, R+P] with the step's rows
    written, Pos [B, K]. Lane k reads every position ``<= Pos[b, k]`` of
    its row's cache: no index, no gather. Out [B, K, H*V], 0 on a pad
    lane. Strictly per-row.

    **Which form runs where.** On ONE TPU the cache is read in blocks of
    positions up to the highest position a row's lanes hold, by the Pallas
    kernel ``latent_step.fwd`` (``cache_attention.latent_blocks``: the step
    kernel behind ``cached_attention`` with one group whose values are the
    latent columns of its keys, a block fetched once for the scores and
    the mix, under a streaming softmax).
    ``cache_attention.latent_plan_for`` decides it from the placement, the
    widths and the rung's length, and the decision is recorded in
    ``op.attrs["_kernel_choice"]`` and handed to the trace's gate count
    (``gates.note``). The CPU, a mesh and a shape the gate refuses score
    the whole rung under a mask (``sparse_latent.latent_attention_dense``'s
    ``jnp`` form, the kernel's reference)."""
    from ...ops import cache_attention, sparse_latent
    from ...ops.gates import note

    q, kv_b = get(env, op.input("Q")), get(env, op.input("KvB"))
    cache = get(env, op.input("Cache"))
    heads = int(op.attr("num_heads"))
    plan = cache_attention.latent_plan_for(q, cache, kv_b.shape[0], heads)
    op.attrs["_kernel_choice"] = plan.to_dict()
    note("latent_attention_dense", plan)
    put(env, op.output("Out"), sparse_latent.latent_attention_dense(
        q, kv_b, cache, get(env, op.input("Pos")), *_latent_attrs(op),
        plan=plan))


def _eva_caches(env, op):
    return [get(env, op.input(slot))
            for slot in ("WinK", "WinV", "SumK", "SumV")]


@register("eva_summary", "eva_summary_chunk")
def _eva_summary(env, op):
    """The summariser of EVA attention and its two cache writes
    (``ops/eva_attention.py``): a cache written at a stride, derived from
    another. WinK, WinV [B, W, H*D] the window caches (position p at slot ``p
    % W``), SumK, SumV [B, L, H*D] the summary caches (chunk c at entry c),
    Phi, Mu [H*D] the layer's learned pooling query and key offset. The
    summary of the ``chunk`` positions of a chunk is a softmax-pooled key
    (``+ Mu``) and value, the pooling logits ``D ** -0.5 * k . Phi`` a head,
    in float32.

    ``eva_summary`` (a step): Pos [B], the window caches with the step's
    token written; the row whose position ends a chunk writes that chunk's
    entry, the others nothing. ``eva_summary_chunk``: Pos [B, K], NewK, NewV
    [B, K, H*D] the run's own keys and values, the window caches AS THEY
    WERE BEFORE the run; every chunk whose last position is a live lane
    (``Pos < pad_pos``) is written. SumKOut, SumVOut: the summary caches.
    Strictly per-row; device events under ``eva.summary``."""
    from ...ops import eva_attention

    win_k, win_v, sum_k, sum_v = _eva_caches(env, op)
    pos = get(env, op.input("Pos"))
    args = (pos, get(env, op.input("Phi")), get(env, op.input("Mu")),
            int(op.attr("num_heads")), int(op.attr("chunk")))
    if op.type == "eva_summary":
        out = eva_attention.summarise_step(win_k, win_v, sum_k, sum_v, *args)
    else:
        out = eva_attention.summarise_chunk(
            win_k, win_v, get(env, op.input("NewK")),
            get(env, op.input("NewV")), sum_k, sum_v, *args,
            int(op.attr("pad_pos")))
    put(env, op.output("SumKOut"), out[0])
    put(env, op.output("SumVOut"), out[1])


@register("eva_attention", "eva_attention_chunk")
def _eva_attention(env, op):
    """EVA attention: ONE softmax over two caches (``ops/eva_attention.py``).
    A query at position p reads the window cache's positions of its own
    block-aligned window, ``(p // window) * window .. p``, and the summary
    cache's entries of every earlier window, ``c < (p // window) * (window /
    chunk)``; scores ``D ** -0.5 * q . k`` in float32, normalised together.

    ``eva_attention`` (a step): Q [B, H*D], Pos [B], WinK, WinV [B, W, H*D]
    with the token written (slots above ``p % W`` are masked), SumK, SumV
    [B, L, H*D]; Out [B, H*D] and Count [3] int32, the window slots and
    summary entries the rows read and the context positions they hold.
    ``eva_attention_chunk``: Q [B, K, H*D], Pos [B, K], the window caches AS
    THEY WERE BEFORE the run with NewK, NewV [B, K, H*D] beside them (a
    lane reads the lanes up to itself that lie in its own window; ``Pos >=
    pad_pos``: a pad lane), the summary caches with the run's summaries
    written; K <= window. Strictly per-row; device events under
    ``attn.eva``.

    **Which form runs where.** On ONE TPU a step reads the entries its rows
    hold, by the Pallas kernel ``eva_step.fwd``
    (``eva_attention.step_blocks``: a row's window blocks up to slot ``p %
    W``, then its summary blocks up to the entries it reads, under one
    streaming softmax). ``eva_attention.plan_for`` decides it from the
    placement, the types and the caches' lengths, and the decision is
    recorded in ``op.attrs["_kernel_choice"]`` and handed to the trace's
    gate count (``gates.note``). The CPU, a mesh and a shape the gate
    refuses read both caches whole under the mask
    (``eva_attention.attend_step``, the kernel's reference). A chunk run is
    ``jnp``: the caches in blocks under a streaming softmax up to the last
    entry a live lane reads."""
    from ...ops import eva_attention
    from ...ops.gates import note

    q, pos = get(env, op.input("Q")), get(env, op.input("Pos"))
    caches = _eva_caches(env, op)
    sizes = (int(op.attr("num_heads")), int(op.attr("window")),
             int(op.attr("chunk")))
    if op.type == "eva_attention":
        plan = eva_attention.plan_for(q, *caches, sizes[0])
        op.attrs["_kernel_choice"] = plan.to_dict()
        note("eva_attention", plan)
        step = (eva_attention.step_blocks if plan
                else eva_attention.attend_step)
        out, count = step(q, *caches, pos, *sizes)
        put(env, op.output("Count"), count)
    else:
        out = eva_attention.attend_chunk(
            q, *caches, get(env, op.input("NewK")),
            get(env, op.input("NewV")), pos, *sizes,
            int(op.attr("pad_pos")))
    put(env, op.output("Out"), out)


@register("last_live_lane")
def _last_live_lane(env, op):
    """X [B, K, D], Pos [B, K], Cache [B, C, ..]: Out [B, D], each row's
    lane of highest position inside the cache (``Pos < C``; lane 0 of a row
    of pad lanes alone). What a chunk program builds a head on where only a
    row's last prompt lane needs one."""
    x, pos = get(env, op.input("X")), get(env, op.input("Pos"))
    c = get(env, op.input("Cache")).shape[1]
    lane = jnp.argmax(jnp.where(pos < c, pos, -1), axis=1)
    put(env, op.output("Out"),
        jnp.take_along_axis(x, lane[:, None, None], axis=1)[:, 0])


@register("self_draft_accept")
def _self_draft_accept(env, op):
    """The greedy accept rule of a step that verifies ONE draft a row,
    inside the executable. Tok [B, 2]: the committed token and the draft of
    the next; Greedy [B, 2] the model's own best token after each lane;
    Draft [B, 2] the prediction module's best token after each lane, given
    that lane's greedy token; Pos [B, 2] the lanes' positions in Cache
    [B, C, ..] (a lane 1 past it is a pad lane: no draft was fed, and none
    stands). The draft stands iff it IS the token the model puts after the
    committed one; the row then yields both greedy tokens and drafts on
    from lane 1, else lane 0's alone and from lane 0. Yield [B, 4] int32:
    count (1 or 2), the two tokens, the next draft. Judged [2] int32: the
    drafts fed to live rows, and those that stood. NextTok, NextPos [B, 2],
    of Tok's and Pos's types: what the step to come is fed if every row
    goes on, the last token that stands and the next draft at the positions
    after those that stand (a row whose lane 0 is a pad lane stays one), so
    that a loop may feed it before it has read this step."""
    fed_tok, pos = get(env, op.input("Tok")), get(env, op.input("Pos"))
    tok = fed_tok.astype(jnp.int32)
    greedy = get(env, op.input("Greedy")).astype(jnp.int32)
    draft = get(env, op.input("Draft")).astype(jnp.int32)
    c = get(env, op.input("Cache")).shape[1]
    stands = (pos[:, 1] < c) & (tok[:, 1] == greedy[:, 0])
    count = 1 + stands.astype(jnp.int32)
    after = jnp.where(stands, draft[:, 1], draft[:, 0])
    put(env, op.output("Yield"), jnp.stack(
        [count, greedy[:, 0], greedy[:, 1], after], axis=1))
    put(env, op.output("Judged"), jnp.stack(
        [jnp.sum(pos[:, 1] < c), jnp.sum(stands)]).astype(jnp.int32))
    put(env, op.output("NextTok"), jnp.stack(
        [jnp.where(stands, greedy[:, 1], greedy[:, 0]), after],
        axis=1).astype(fed_tok.dtype))
    moved = pos[:, :1] + count[:, None].astype(pos.dtype)
    put(env, op.output("NextPos"), jnp.where(
        pos[:, :1] < c, jnp.concatenate([moved, moved + 1], axis=1), pos))


@register("sampling_id")
def _sampling_id(env, op):
    x = get(env, op.input("X"))  # [B, C] probabilities
    put(env, op.output("Out"),
        jax.random.categorical(next_rng(env), jnp.log(jnp.maximum(x, 1e-20)),
                               axis=-1).astype(jnp.int64))
