"""Plain reference of ``qwen3-next-80b-a3b`` (Qwen/Qwen3-Next-80B-A3B-Instruct,
``config.json``; layer equations as ``transformers``'
``modeling_qwen3_next.py``): forward and loss in ``jax.numpy``, float32, no
kernels, no chunked (WY) form; gradients by ``jax.grad``.

Block ``i``: ``h = x + mixer_i(rms(x))``, ``y = h + moe(rms(h))`` with the
zero-centred RMS norm ``x * rsqrt(mean(x^2) + eps) * (1 + w)``; ``mixer_i``
is gated full attention where ``(i + 1) % full_attention_interval == 0``,
else Gated DeltaNet, whose delta rule is computed token by token as written:
``S <- exp(g) S; S <- S + k (x) beta (v - S^T k); o = S^T q``.

It is given the same share of the model as the program: the routed experts
``experts_held = [first, count]`` (the router scores all ``num_experts`` and
the weights are renormalised over all the picks; the picks on absent experts
are left out of the sum) and ``vocab_held`` rows of the vocabulary. Left out
as in the program: the multi-token-prediction module, the router's auxiliary
loss.

Computed in blocks so that it fits one chip, which changes no arithmetic:
each layer under ``jax.checkpoint``; the recurrence as an outer scan over
blocks of tokens whose inner token-by-token scan is recomputed in the
backward pass; the attention scores in blocks of query rows; the experts one
at a time under a mask (a ``lax.scan`` over the experts held). Parameters are given by the program's names.
"""

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 64      # tokens of the recurrence kept between checkpoints
QUERY_BLOCK = 512     # query rows whose scores are held at a time


def _rms(x, w, eps, zero_centered=True):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


def _rotary(x, rot, theta):
    """x: [B, T, H, D]; rotate-half pairing (j, j + rot/2) on the first
    ``rot`` dims, position = index along T."""
    t = x.shape[1]
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _attention(ops, p, name, x, args):
    b, t, _ = x.shape
    h, hkv, d = args["num_attention_heads"], args["num_key_value_heads"], \
        args["head_dim"]
    eps = args["rms_norm_eps"]
    qg = ops.dot(x, p[name + ".q_proj"]).reshape(b, t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = ops.dot(x, p[name + ".k_proj"]).reshape(b, t, hkv, d)
    v = ops.dot(x, p[name + ".v_proj"]).reshape(b, t, hkv, d)
    rot = int(d * args["partial_rotary_factor"])
    q = _rotary(_rms(q, p[name + ".q_norm.w"], eps), rot, args["rope_theta"])
    k = _rotary(_rms(k, p[name + ".k_norm.w"], eps), rot, args["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    block = min(QUERY_BLOCK, t)
    pad = (-t) % block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = qp.reshape(b, -1, block, h, d).transpose(1, 0, 2, 3, 4)
    starts = jnp.arange(qb.shape[0]) * block
    at_k = jnp.arange(t)

    @jax.checkpoint
    def rows(q_i, start):
        scores = ops.einsum("bqhd,bkhd->bhqk", q_i, k) / jnp.sqrt(float(d))
        keep = (start + jnp.arange(block))[:, None] >= at_k[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return ops.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(lambda a: rows(*a), (qb, starts))
    ctx = ctx.transpose(1, 0, 2, 3, 4).reshape(b, -1, h, d)[:, :t]
    ctx = (ctx * jax.nn.sigmoid(gate)).reshape(b, t, h * d)
    return ops.dot(ctx, p[name + ".o_proj"])


def _delta_rule(ops, q, k, v, g, beta):
    """q, k: [B, T, H, Dk]; v: [B, T, H, Dv]; g, beta: [B, T, H]. One token
    at a time; blocks of TOKEN_BLOCK tokens are recomputed in the backward
    pass so that only one state a block is kept."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-t) % TOKEN_BLOCK
    if pad:
        # padded tokens neither decay nor write; their outputs are dropped
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        r = ops.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + ops.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - r))
        return s, ops.einsum("bhkv,bhk->bhv", s, q_t)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    def blocks(a):  # [B, T, ...] -> [T/blk, blk, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((-1, TOKEN_BLOCK) + a.shape[1:])

    xs = tuple(blocks(a) for a in (q, k, v, g, beta))
    _, out = jax.lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    out = out.reshape((-1,) + out.shape[2:])
    return jnp.moveaxis(out, 0, 1)[:, :t]


def _delta_net(ops, p, name, x, args):
    b, t, _ = x.shape
    hk, hv = args["linear_num_key_heads"], args["linear_num_value_heads"]
    dk, dv = args["linear_key_head_dim"], args["linear_value_head_dim"]
    rep = hv // hk
    qkvz = ops.dot(x, p[name + ".in_proj_qkvz"]).reshape(
        b, t, hk, 2 * dk + 2 * rep * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + rep * dv].reshape(b, t, hv * dv)
    z = qkvz[..., 2 * dk + rep * dv:].reshape(b, t, hv, dv)
    ba = ops.dot(x, p[name + ".in_proj_ba"]).reshape(b, t, hk, 2 * rep)
    beta = jax.nn.sigmoid(ba[..., :rep].reshape(b, t, hv))
    a = ba[..., rep:].reshape(b, t, hv)
    g = -jnp.exp(p[name + ".A_log"]) * jax.nn.softplus(
        a + p[name + ".dt_bias"])

    mixed = jnp.concatenate([q.reshape(b, t, hk * dk),
                             k.reshape(b, t, hk * dk), v], axis=-1)
    w = p[name + ".conv"]                       # [channels, kernel]
    kernel = w.shape[1]
    padded = jnp.pad(mixed, ((0, 0), (kernel - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + t] * w[:, j]
                            for j in range(kernel)))
    q = mixed[..., :hk * dk].reshape(b, t, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)

    def l2norm(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2norm(q), rep, axis=2) * dk ** -0.5
    k = jnp.repeat(l2norm(k), rep, axis=2)
    o = _delta_rule(ops, q, k, v, g, beta)
    o = _rms(o, p[name + ".norm.w"], args["rms_norm_eps"],
             zero_centered=False) * jax.nn.silu(z)
    return ops.dot(o.reshape(b, t, hv * dv), p[name + ".out_proj"])


def _swiglu(ops, x, gate_w, up_w, down_w):
    return ops.dot(jax.nn.silu(ops.dot(x, gate_w)) * ops.dot(x, up_w),
                   down_w)


def _moe(ops, p, name, x, args):
    top_k = args["num_experts_per_tok"]
    first, count = args.get("experts_held") or (0, args["num_experts"])
    probs = jax.nn.softmax(ops.dot(x, p[name + ".router"]), axis=-1)
    weights, picks = jax.lax.top_k(probs, top_k)
    if args["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def expert(out, held):
        e, gate_w, up_w, down_w = held      # [out, in] matrices of expert e
        weight = jnp.sum(jnp.where(picks == first + e, weights, 0.0), -1)
        y = _swiglu(ops, x, gate_w.T, up_w.T, down_w.T)
        return out + weight[..., None] * y, None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.arange(count), p[name + ".experts.gate"],
         p[name + ".experts.up"], p[name + ".experts.down"]))
    shared = _swiglu(ops, x, p[name + ".shared.gate_proj"],
                     p[name + ".shared.up_proj"],
                     p[name + ".shared.down_proj"])
    return out + jax.nn.sigmoid(ops.dot(x, p[name + ".shared_gate"])) * shared


def loss(params, batch, args, ops):
    """Mean cross-entropy over all positions. ``args`` are the
    configuration's ``builder_args`` with ``seq_len`` filled in."""
    p = params
    eps = args["rms_norm_eps"]
    x = p["embed_tokens"][batch["ids"]]
    for i in range(args["num_hidden_layers"]):
        nm = "l%d" % i
        full = (i + 1) % args["full_attention_interval"] == 0

        @jax.checkpoint
        def layer(p, x, nm=nm, full=full):
            h = _rms(x, p[nm + ".input_norm.w"], eps)
            mixer = _attention if full else _delta_net
            x = x + mixer(ops, p, nm + (".attn" if full else ".gdn"), h,
                          args)
            return x + _moe(ops, p, nm + ".moe",
                            _rms(x, p[nm + ".post_norm.w"], eps), args)

        x = layer(p, x)
    x = _rms(x, p["final_norm.w"], eps)
    logp = jax.nn.log_softmax(ops.dot(x, p["lm_head"]), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, batch["labels"][..., None], axis=-1))
