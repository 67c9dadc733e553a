"""Plain reference of ``nemotron-3-super-120b-a12b`` (nvidia/NVIDIA-Nemotron-
3-Super-120B-A12B-BF16, ``config.json``, ``model_type`` ``nemotron_h``; layer
equations as the public ``modeling_nemotron_h.py``, the expert layer as the
Nemotron 3 white paper's LatentMoE): forward and loss in ``jax.numpy``,
float32, no kernels, no chunked form; gradients by ``jax.grad``.

Layer ``i``: ``x + mixer_i(rms(x))`` with the RMS norm ``x * rsqrt(mean(x^2)
+ eps) * w`` and ONE mixer, by letter ``i`` of ``hybrid_override_pattern``:

* ``M``, Mamba-2: ``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv1d(xBC)
  + bias)``, causal and depthwise; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; a head's state, token by token as written: ``S <- exp(dt
  A) S + dt x (x) B``, ``y = S C + D x`` (``B``, ``C`` of the head's
  group); ``y <- w * rms_group(y * silu(z))``, the gate BEFORE the norm, in
  groups of ``d_inner / n_groups`` channels; ``out_proj``.
* ``*``, attention: grouped-query heads, causal, plain scores, NO position
  encoding (the public modelling code applies none; the config's
  ``rope_theta`` is read by nothing), ``o_proj``.
* ``E``, experts: ``s = sigmoid(h W_r)`` over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` the selection bias,
  which no gradient reaches; zeros here); weights ``s[picks] / (sum + 1e-20)
  * routed_scaling_factor``; ``u = h W_down`` into the latent; routed
  ``= sum_picks w_e W2_e relu(W1_e u)^2``; the layer gives ``routed W_up +
  W_s2 relu(W_s1 h)^2`` (router and shared expert read ``h``, not ``u``).

It is given the same share of the model as the program, through the shapes
of the parameters it is handed (the held heads, groups, key/value heads,
shared units and rows of the vocabulary) and ``experts_held = [first,
count]`` (the router scores all ``n_routed_experts`` and the weights are
renormalised over all the picks; the picks on absent experts are left out
of the sum). Left out as in the program: the multi-token-prediction module.

Computed in blocks so that it fits one chip, which changes no arithmetic:
each layer under ``jax.checkpoint``; the recurrence as an outer scan over
blocks of tokens whose inner token-by-token scan is recomputed in the
backward pass; the attention scores in blocks of query rows; the experts one
at a time under a mask (a ``lax.scan`` over the experts held); the head and
its loss in blocks of rows. Parameters are given by the program's names.
"""

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 64      # tokens of the recurrence kept between checkpoints
QUERY_BLOCK = 512     # query rows whose scores are held at a time
HEAD_BLOCK = 1024     # rows whose logits are held at a time


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _attention(ops, p, name, x, args):
    b, t, _ = x.shape
    d = args["head_dim"]
    q = ops.dot(x, p[name + ".q_proj"]).reshape(b, t, -1, d)
    k = ops.dot(x, p[name + ".k_proj"]).reshape(b, t, -1, d)
    v = ops.dot(x, p[name + ".v_proj"]).reshape(b, t, -1, d)
    h = q.shape[2]
    k = jnp.repeat(k, h // k.shape[2], axis=2)
    v = jnp.repeat(v, h // v.shape[2], axis=2)
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
    ctx = ctx.transpose(1, 0, 2, 3, 4).reshape(b, -1, h * d)[:, :t]
    return ops.dot(ctx, p[name + ".o_proj"])


def _state_space(ops, x, dt, a, bm, cm):
    """x: [B, T, H, P]; dt: [B, T, H]; a: [H]; bm, cm: [B, T, H, N] (each
    head's group's). One token at a time; blocks of TOKEN_BLOCK tokens are
    recomputed in the backward pass so that only one state a block is
    kept. Returns ``S_t C_t`` [B, T, H, P]."""
    b, t, h, p = x.shape
    pad = (-t) % TOKEN_BLOCK
    if pad:
        # padded tokens have step 0: they neither decay nor write; their
        # outputs are dropped
        x, bm, cm = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                     for v in (x, bm, cm))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))

    def token(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = s * jnp.exp(dt_t * a)[..., None, None] + ops.einsum(
            "bhp,bhn->bhpn", x_t * dt_t[..., None], b_t)
        return s, ops.einsum("bhpn,bhn->bhp", s, c_t)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    def blocks(v):  # [B, T, ...] -> [T/blk, blk, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((-1, TOKEN_BLOCK) + v.shape[1:])

    xs = tuple(blocks(v) for v in (x, dt, bm, cm))
    _, out = jax.lax.scan(
        block, jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32), xs)
    out = out.reshape((-1,) + out.shape[2:])
    return jnp.moveaxis(out, 0, 1)[:, :t]


def _mamba(ops, p, name, u, args):
    b, t, _ = u.shape
    hp, n = args["mamba_head_dim"], args["ssm_state_size"]
    heads = p[name + ".A_log"].shape[0]
    inner = heads * hp
    conv_w = p[name + ".conv"]                  # [channels, kernel]
    groups = (conv_w.shape[0] - inner) // (2 * n)
    proj = ops.dot(u, p[name + ".in_proj"])
    z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + conv_w.shape[0]],
                  proj[..., inner + conv_w.shape[0]:])
    kernel = conv_w.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (kernel - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + t] * conv_w[:, j]
                          for j in range(kernel)) + p[name + ".conv_bias"])
    x = xbc[..., :inner].reshape(b, t, heads, hp)
    bm = xbc[..., inner:inner + groups * n].reshape(b, t, groups, n)
    cm = xbc[..., inner + groups * n:].reshape(b, t, groups, n)
    bm, cm = (jnp.repeat(v, heads // groups, axis=2) for v in (bm, cm))
    dt = jax.nn.softplus(dt + p[name + ".dt_bias"])
    y = _state_space(ops, x, dt, -jnp.exp(p[name + ".A_log"]), bm, cm) \
        + p[name + ".D"][:, None] * x
    y = y.reshape(b, t, inner) * jax.nn.silu(z)
    y = y.reshape(b, t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + args["layer_norm_epsilon"])
    y = y.reshape(b, t, inner) * p[name + ".norm.w"]
    return ops.dot(y, p[name + ".out_proj"])


def _moe(ops, p, name, h, args):
    top_k = args["num_experts_per_tok"]
    first, count = args.get("experts_held") or (0, args["n_routed_experts"])
    scores = jax.nn.sigmoid(ops.dot(h, p[name + ".router"]))
    # the selection bias is a buffer no gradient reaches; zeros unless given
    _, picks = jax.lax.top_k(scores + jax.lax.stop_gradient(
        p.get(name + ".router_bias", 0.0)), top_k)
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    if args["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    weights = weights * args["routed_scaling_factor"]
    u = ops.dot(h, p[name + ".latent_down"])

    def expert(out, held):
        e, up_w, down_w = held              # [out, in] matrices of expert e
        weight = jnp.sum(jnp.where(picks == first + e, weights, 0.0), -1)
        y = ops.dot(_relu2(ops.dot(u, up_w.T)), down_w.T)
        return out + weight[..., None] * y, None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (jnp.arange(count), p[name + ".experts.up"],
         p[name + ".experts.down"]))
    shared = ops.dot(_relu2(ops.dot(h, p[name + ".shared.up_proj"])),
                     p[name + ".shared.down_proj"])
    return ops.dot(routed, p[name + ".latent_up"]) + shared


def _head_loss(ops, x, w, labels):
    """Mean cross-entropy of ``x @ w`` against ``labels``, the logits of
    HEAD_BLOCK rows at a time."""
    rows = x.reshape(-1, x.shape[-1])
    labels = labels.reshape(-1)
    total = rows.shape[0]
    block = min(HEAD_BLOCK, total)
    pad = (-total) % block
    rows = jnp.pad(rows, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    labels = jnp.pad(labels, (0, pad)).reshape(-1, block)
    counted = (jnp.arange(total + pad) < total).reshape(-1, block)

    @jax.checkpoint
    def some(x_i, y_i, m_i):
        logp = jax.nn.log_softmax(ops.dot(x_i, w), axis=-1)
        picked = jnp.take_along_axis(logp, y_i[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(m_i, picked, 0.0))

    sums = jax.lax.map(lambda a: some(*a), (rows, labels, counted))
    return jnp.sum(sums) / total


def loss(params, batch, args, ops):
    """Mean cross-entropy over all positions. ``args`` are the
    configuration's ``builder_args`` with ``seq_len`` filled in."""
    p = params
    eps = args["layer_norm_epsilon"]
    pattern = args["hybrid_override_pattern"]
    first, count = args.get("layers_held") or (0, len(pattern))
    mixers = {"M": (_mamba, ".mamba"), "*": (_attention, ".attn"),
              "E": (_moe, ".moe")}
    x = p["embeddings"][batch["ids"]]
    for i in range(first, first + count):
        nm = "l%d" % i
        mixer, tag = mixers[pattern[i]]

        @jax.checkpoint
        def layer(p, x, nm=nm, mixer=mixer, tag=tag):
            return x + mixer(ops, p, nm + tag,
                             _rms(x, p[nm + ".norm.w"], eps), args)

        x = layer(p, x)
    x = _rms(x, p["norm_f.w"], eps)
    return _head_loss(ops, x, p["lm_head"], batch["labels"])
