"""Plain reference of GLM-4.7-Flash's decoder and of its multi-token-
prediction module (zai-org/GLM-4.7-Flash ``config.json``, ``model_type``
``glm4_moe_lite``: multi-head latent attention over EVERY earlier position,
one leading dense layer, then sigmoid-routed experts beside one shared
expert; DeepSeek-V3's equations, block and module alike): the full forward
pass over one sequence, in ``jax.numpy`` and float32, every product through
``ops`` (``reference/precision.py``: float32 at ``Precision.HIGHEST``, or
the fp8 control). No cache, no batching, no absorbed form, nothing of the
program imported.

``logits(params, tokens, args, ops)``: ``params`` by the names the program's
builder gives the leaves (``glm.embed_tokens``, ``glm.l3.attn.q_b``, ...),
in whatever type they are served in, brought to float32 a matrix (an expert)
at a time; ``tokens`` [T] int; ``args`` the configuration's builder keys.
Returns float32 [T, vocab_size]: row ``t`` is the distribution of token
``t + 1``.

``draft_logits(params, tokens, args, ops)``: the prediction module's logits
at every position, float32 [T, vocab_size]: row ``t`` is the module's
distribution of token ``t + 2``, given the main model's hidden state of
position ``t`` and the token ``t + 1`` of ``tokens`` (row ``T - 1``, which
has no next token, is fed token 0 and means nothing).

Layer ``l`` (``h`` the stream, position ``t`` = the index along T):

* ``y = rms(h)``; ``c_q = rms(y W_qa)``; ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_pe]``, rotary on ``q_pe``;
* ``y W_kva = [c | k_pe]``, ``c <- rms(c)``, rotary on ``k_pe`` (one key
  for all heads);
* per head, decompressed: ``k_nope[s] = c[s] W_uk``, ``v[s] = c[s] W_uv``
  (``kv_b`` split a head), ``a[t, s] = softmax over s <= t of ((q_nope[t] .
  k_nope[s] + q_pe[t] . k_pe[s]) * (N + P)^-0.5)``, ``o[t] = sum_s a[t, s]
  v[s]``, ``h += concat_heads(o) W_o``;
* feed-forward on ``rms(h)``: SwiGLU (``l < first_k_dense_replace``), or
  sigmoid scores over all ``n_routed_experts``, the ``num_experts_per_tok``
  largest of score + bias, their scores divided by their sum times
  ``routed_scaling_factor``, the HELD experts' SwiGLUs, plus the shared
  expert.

The module (DeepSeek-V3 section 2.2; published layer ``nextn_layer``): ``x_t
= [rms_e(Emb(tokens[t + 1])) ; rms_h(rms_f(h_t))] W_eh`` with ``rms_f`` the
main model's final norm, one layer as above over ``x``, ``Head(rms_s(.))``
with the main model's embedding and head.

Rotary: ``rope_theta``, frequencies ``theta^(-2i/P)``, interleaved pairs
(2i, 2i + 1), the turned pair left in place. Queries go in blocks of
``BLOCK`` and the head in blocks of ``HEAD_BLOCK`` columns, so that a
4096-token pass fits beside the served weights."""

import functools

import jax
import jax.numpy as jnp

BLOCK = 256
HEAD_BLOCK = 8
_F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w.astype(_F32)


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


def _attend(q, c, k_pe, p, a, ops):
    """Per-head decompressed attention, each query over the positions up to
    its own."""
    t = c.shape[0]
    heads, nope, v_dim = a["heads"], a["nope_dim"], a["v_dim"]
    rank, rot = c.shape[-1], a["rope_dim"]
    kv_b = p["attn.kv_b"].astype(_F32).reshape(rank, heads, nope + v_dim)
    k_nope = ops.einsum("tr,rhn->thn", c, kv_b[..., :nope])
    v = ops.einsum("tr,rhv->thv", c, kv_b[..., nope:])
    scale = (nope + rot) ** -0.5
    count, size = _blocks(t)

    def block(args):
        qb, first = args
        s = (ops.einsum("qhn,khn->hqk", qb[..., :nope], k_nope)
             + ops.einsum("qhp,kp->hqk", qb[..., nope:], k_pe)) * scale
        causal = jnp.arange(t)[None, :] <= (first + jnp.arange(size))[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        return ops.einsum("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(count, size, heads, nope + rot),
                              jnp.arange(count) * size))
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
def _layer(x, p, sizes, ops):
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
    x = x + ops.dot(_attend(q, c, k_pe, p, a, ops),
                    p["attn.o"].astype(_F32))
    y = _rms(x, p["post_norm.w"], eps)
    if a["dense"]:
        h = _swiglu(y, p["mlp.gate"].astype(_F32), p["mlp.up"].astype(_F32),
                    p["mlp.down"].astype(_F32), ops)
    else:
        h = _experts(y, p, a, ops)
    return x + h


@functools.partial(jax.jit, static_argnames=("eps", "ops"))
def _head(x, w, head, eps, ops):
    """``rms(x) Head`` in ``HEAD_BLOCK`` blocks of columns, so that the
    head is brought to float32 a block at a time."""
    y = _rms(x, w, eps)
    d, v = head.shape
    cuts = HEAD_BLOCK if v % HEAD_BLOCK == 0 else 1
    parts = jax.lax.map(
        lambda columns: ops.dot(y, columns.astype(_F32)),
        head.reshape(d, cuts, v // cuts).transpose(1, 0, 2))
    return parts.transpose(1, 0, 2).reshape(x.shape[0], v)


def _run_layer(params, x, l, dense, args, ops):
    prefix = "glm.l%d." % l
    leaves = {k[len(prefix):]: v for k, v in params.items()
              if k.startswith(prefix)}
    lo = (args.get("experts_held") or (0, args["n_routed_experts"]))[0]
    sizes = (
        ("dense", bool(dense)),
        ("heads", int(args["num_attention_heads"])),
        ("nope_dim", int(args["qk_nope_head_dim"])),
        ("rope_dim", int(args["qk_rope_head_dim"])),
        ("v_dim", int(args["v_head_dim"])),
        ("theta", float(args["rope_theta"])),
        ("eps", float(args["rms_norm_eps"])),
        ("top_k", int(args["num_experts_per_tok"])),
        ("norm_topk_prob", bool(args["norm_topk_prob"])),
        ("scale", float(args["routed_scaling_factor"])),
        ("first_expert", int(lo)))
    return _layer(x, leaves, sizes=sizes, ops=ops)


def _embed(params, tokens):
    return jnp.take(params["glm.embed_tokens"],
                    jnp.asarray(tokens, jnp.int32), axis=0).astype(_F32)


def _stream(params, tokens, args, ops):
    """The stream after the held layers [T, D]."""
    first, count = args.get("layers_held") or (
        0, int(args["num_hidden_layers"]))
    x = _embed(params, tokens)
    for l in range(first, first + count):
        x = _run_layer(params, x, l, l < int(args["first_k_dense_replace"]),
                       args, ops)
    return x


def logits(params, tokens, args, ops):
    return _head(_stream(params, tokens, args, ops), params["glm.norm.w"],
                 params["glm.lm_head"], eps=float(args["rms_norm_eps"]),
                 ops=ops)


@functools.partial(jax.jit, static_argnames=("eps", "ops"))
def _join(after, x, e_w, h_w, f_w, w_eh, eps, ops):
    joined = jnp.concatenate(
        [_rms(after, e_w, eps), _rms(_rms(x, f_w, eps), h_w, eps)], axis=-1)
    return ops.dot(joined, w_eh.astype(_F32))


def draft_logits(params, tokens, args, ops):
    l = int(args["nextn_layer"])
    eps = float(args["rms_norm_eps"])
    tokens = jnp.asarray(tokens, jnp.int32)
    after = jnp.concatenate([tokens[1:], jnp.zeros((1,), jnp.int32)])
    x = _join(_embed(params, after), _stream(params, tokens, args, ops),
              params["glm.l%d.enorm.w" % l], params["glm.l%d.hnorm.w" % l],
              params["glm.norm.w"], params["glm.l%d.eh_proj" % l], eps=eps,
              ops=ops)
    x = _run_layer(params, x, l, False, args, ops)
    return _head(x, params["glm.l%d.shared_head.norm.w" % l],
                 params["glm.lm_head"], eps=eps, ops=ops)
