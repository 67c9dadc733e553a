"""Plain reference of GLM-5.2's decoder (zai-org/GLM-5.2 ``config.json``,
``model_type`` ``glm_moe_dsa``: multi-head latent attention, a learned
indexer whose selection the ``shared`` layers take from the ``full`` layer
before them, sigmoid-routed experts with one shared expert): the full
forward pass over one sequence, in ``jax.numpy`` and float32, every product
through ``ops`` (``reference/precision.py``: float32 at
``Precision.HIGHEST``, or the fp8 control). No cache, no batching, no
absorbed form, nothing of the program imported.

``logits(params, tokens, args, ops)``: ``params`` by the names the program's
builder gives the leaves (``glm.embed_tokens``, ``glm.l3.attn.q_b``, ...),
in whatever type they are served in, brought to float32 a matrix at a time;
``tokens`` [T] int; ``args`` the configuration's builder keys (the source's
keys, ``layers_held``, ``experts_held``, ``vocab_size`` the rows held).
Returns float32 [T, vocab_size]: row ``t`` is the distribution of token
``t + 1``.

Layer ``l`` (``h`` the stream, position ``t`` = the index along T):

* ``y = rms(h)``; ``c_q = rms(y W_qa)``; ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_pe]``, rotary on ``q_pe``;
* ``y W_kva = [c | k_pe]``, ``c <- rms(c)``, rotary on ``k_pe`` (one key
  for all heads);
* a ``full`` layer's indexer: ``qI = c_q W_qI`` (heads of
  ``index_head_dim``), ``kI = layernorm(y W_kI)``, rotary on the first
  ``qk_rope_head_dim`` dims of both, ``w = y W_w``; ``I[t, s] = D^-0.5
  H^-0.5 sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t`` =
  the ``min(index_topk, t + 1)`` positions of largest ``I[t, .]`` (ties to
  the lower position). A ``shared`` layer uses the ``S_t`` of the nearest
  ``full`` layer before it;
* per head, decompressed: ``k_nope[s] = c[s] W_uk``, ``v[s] = c[s] W_uv``
  (``kv_b`` split a head), ``a[t, s] = softmax over S_t of ((q_nope[t] .
  k_nope[s] + q_pe[t] . k_pe[s]) * (N + P)^-0.5)``, ``o[t] = sum_s a[t, s]
  v[s]``, ``h += concat_heads(o) W_o``;
* feed-forward on ``rms(h)``: SwiGLU (``dense``), or sigmoid scores over all
  ``n_routed_experts``, the ``num_experts_per_tok`` largest of score + bias,
  their scores divided by their sum times ``routed_scaling_factor``, the
  HELD experts' SwiGLUs (picks on absent experts are left out: this chip's
  addend), plus the shared expert.

Rotary: ``rope_theta``, default type, frequencies ``theta^(-2i/P)``,
interleaved pairs (2i, 2i + 1), the turned pair left in place (the source
moves it to (i, i + P/2) in queries and keys alike: every score is the
same). Left out: the multi-token-prediction module. Queries go in blocks of
``BLOCK`` so that a 12288-token pass fits beside the served weights."""

import functools

import jax
import jax.numpy as jnp

BLOCK = 256
_F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w.astype(_F32)


def _layer_norm(x, w, b, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.astype(_F32) \
        + b.astype(_F32)


def _rope(x, theta):
    """x [T, .., P]: pairs (2i, 2i + 1) turned by ``t * theta^(-2i/P)``."""
    t, p = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(p // 2, dtype=_F32) * 2.0 / p)
    angle = jnp.arange(t, dtype=_F32).reshape((t,) + (1,) * (x.ndim - 1)) \
        * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _blocks(t):
    size = BLOCK if t % BLOCK == 0 else t
    return t // size, size


def _top_mask(scores, k):
    """scores [Q, T], ``-inf`` where not to be had -> [Q, T] bool: the k
    largest of each row (all where it has no more), ties to the lower
    position."""
    k = min(k, scores.shape[-1])
    kth = jax.lax.top_k(scores, k)[0][:, -1:]
    above = scores > kth
    tied = (scores == kth) & (scores > -jnp.inf)
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))


def _select(c_q, y, p, a, ops):
    """The indexer: [T, T] bool, row t holding S_t."""
    t = y.shape[0]
    heads, dim, rot = a["index_n_heads"], a["index_head_dim"], a["rope_dim"]
    q = ops.dot(c_q, p["indexer.wq_b"].astype(_F32)).reshape(t, heads, dim)
    k = _layer_norm(ops.dot(y, p["indexer.wk"].astype(_F32)),
                    p["indexer.k_norm.w"], p["indexer.k_norm.b"])
    q = jnp.concatenate([_rope(q[..., :rot], a["theta"]), q[..., rot:]], -1)
    k = jnp.concatenate([_rope(k[..., :rot], a["theta"]), k[..., rot:]], -1)
    w = ops.dot(y, p["indexer.weights_proj"].astype(_F32))
    count, size = _blocks(t)

    def block(args):
        qb, wb, first = args
        s = jax.nn.relu(ops.einsum("qhd,kd->qhk", qb, k))
        scores = ops.einsum("qhk,qh->qk", s, wb) \
            * (dim ** -0.5 * heads ** -0.5)
        at = first + jnp.arange(size)
        causal = jnp.arange(t)[None, :] <= at[:, None]
        return _top_mask(jnp.where(causal, scores, -jnp.inf),
                         a["index_topk"])

    return jax.lax.map(block, (
        q.reshape(count, size, heads, dim), w.reshape(count, size, heads),
        jnp.arange(count) * size)).reshape(t, t)


def _attend(q, c, k_pe, selected, p, a, ops):
    """Per-head decompressed attention over each query's set."""
    t = c.shape[0]
    heads, nope, v_dim = a["heads"], a["nope_dim"], a["v_dim"]
    rank, rot = c.shape[-1], a["rope_dim"]
    kv_b = p["attn.kv_b"].astype(_F32).reshape(rank, heads, nope + v_dim)
    k_nope = ops.einsum("tr,rhn->thn", c, kv_b[..., :nope])
    v = ops.einsum("tr,rhv->thv", c, kv_b[..., nope:])
    scale = (nope + rot) ** -0.5
    count, size = _blocks(t)

    def block(args):
        qb, member = args
        s = (ops.einsum("qhn,khn->hqk", qb[..., :nope], k_nope)
             + ops.einsum("qhp,kp->hqk", qb[..., nope:], k_pe)) * scale
        s = jnp.where(member[None], s, -jnp.inf)
        return ops.einsum("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(count, size, heads, nope + rot),
                              selected.reshape(count, size, t)))
    return out.reshape(t, heads * v_dim)


def _swiglu(y, gate, up, down, ops):
    """Matrices [in, out]."""
    return ops.dot(jax.nn.silu(ops.dot(y, gate)) * ops.dot(y, up), down)


def _experts(y, p, a, ops):
    t = y.shape[0]
    scores = jax.nn.sigmoid(ops.dot(y, p["moe.router"].astype(_F32)))
    _, picks = jax.lax.top_k(
        scores + p["moe.router_bias"].astype(_F32), a["top_k"])
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    if a["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * a["scale"]
    dense = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], picks].set(
        weights)                                              # [T, E]
    first = a["first_expert"]
    held = p["moe.experts.gate"].shape[0]

    def expert(out, leaves):
        gate, up, down, w = leaves       # published layout [out, in]
        h = jax.nn.silu(ops.einsum("td,fd->tf", y, gate.astype(_F32))) \
            * ops.einsum("td,fd->tf", y, up.astype(_F32))
        return out + w[:, None] * ops.einsum("tf,df->td", h,
                                             down.astype(_F32)), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(y), (
        p["moe.experts.gate"], p["moe.experts.up"], p["moe.experts.down"],
        dense[:, first:first + held].T))
    return routed + _swiglu(y, p["moe.shared.gate_proj"].astype(_F32),
                            p["moe.shared.up_proj"].astype(_F32),
                            p["moe.shared.down_proj"].astype(_F32), ops)


@functools.partial(jax.jit, static_argnames=("sizes", "ops"))
def _layer(x, selected, p, sizes, ops):
    """One layer; ``selected`` [T, T] bool comes from the layer before (or
    None) and goes on to the next."""
    a = dict(sizes)
    t = x.shape[0]
    eps = a["eps"]
    y = _rms(x, p["input_norm.w"], eps)
    c_q = _rms(ops.dot(y, p["attn.q_a"].astype(_F32)), p["attn.q_a_norm.w"],
               eps)
    heads, nope, rot = a["heads"], a["nope_dim"], a["rope_dim"]
    q = ops.dot(c_q, p["attn.q_b"].astype(_F32)).reshape(t, heads,
                                                         nope + rot)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], a["theta"])],
                        axis=-1)
    kv = ops.dot(y, p["attn.kv_a"].astype(_F32))
    c = _rms(kv[:, :-rot], p["attn.kv_a_norm.w"], eps)
    k_pe = _rope(kv[:, -rot:], a["theta"])
    if a["full"]:
        selected = _select(c_q, y, p, a, ops)
    mixed = _attend(q, c, k_pe, selected, p, a, ops)
    x = x + ops.dot(mixed, p["attn.o"].astype(_F32))
    y = _rms(x, p["post_norm.w"], eps)
    if a["dense"]:
        h = _swiglu(y, p["mlp.gate"].astype(_F32), p["mlp.up"].astype(_F32),
                    p["mlp.down"].astype(_F32), ops)
    else:
        h = _experts(y, p, a, ops)
    return x + h, selected


@functools.partial(jax.jit, static_argnames=("eps", "ops"))
def _head(x, w, head, eps, ops):
    return ops.dot(_rms(x, w, eps), head.astype(_F32))


def _stream(params, tokens, args, ops):
    """(the stream after the held layers [T, D], {layer: S [T, T] bool} of
    the ``full`` layers)."""
    first, count = args.get("layers_held") or (
        0, len(args["indexer_types"]))
    lo = (args.get("experts_held") or (0, args["n_routed_experts"]))[0]
    x = jnp.take(params["glm.embed_tokens"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(_F32)
    selected, sets = None, {}
    for l in range(first, first + count):
        prefix = "glm.l%d." % l
        leaves = {k[len(prefix):]: v for k, v in params.items()
                  if k.startswith(prefix)}
        sizes = (
            ("full", args["indexer_types"][l] == "full"),
            ("dense", args["mlp_layer_types"][l] == "dense"),
            ("heads", int(args["num_attention_heads"])),
            ("nope_dim", int(args["qk_nope_head_dim"])),
            ("rope_dim", int(args["qk_rope_head_dim"])),
            ("v_dim", int(args["v_head_dim"])),
            ("index_n_heads", int(args["index_n_heads"])),
            ("index_head_dim", int(args["index_head_dim"])),
            ("index_topk", int(args["index_topk"])),
            ("theta", float(args["rope_parameters"]["rope_theta"])),
            ("eps", float(args["rms_norm_eps"])),
            ("top_k", int(args["num_experts_per_tok"])),
            ("norm_topk_prob", bool(args["norm_topk_prob"])),
            ("scale", float(args["routed_scaling_factor"])),
            ("first_expert", int(lo)))
        x, selected = _layer(x, selected, leaves, sizes=sizes, ops=ops)
        if args["indexer_types"][l] == "full":
            sets[l] = selected
    return x, sets


def logits(params, tokens, args, ops):
    x, _ = _stream(params, tokens, args, ops)
    return _head(x, params["glm.norm.w"], params["glm.lm_head"],
                 eps=float(args["rms_norm_eps"]), ops=ops)


def selections(params, tokens, args, ops):
    """{layer: [T, T] bool} of the ``full`` layers, row t holding S_t
    (``serve_selection.py`` holds the served program's picks against
    them)."""
    return _stream(params, tokens, args, ops)[1]
