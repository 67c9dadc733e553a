"""Plain reference of MiMo-V2-Flash's decoder (XiaomiMiMo/MiMo-V2-Flash
``config.json``, ``model_type`` ``mimo_v2_flash``: full-attention and
sliding-window layers side by side, grouped heads of two counts, keys wider
than values, a learned sink on the window layers, sigmoid-routed experts
with no shared expert): the full forward pass over one sequence, in
``jax.numpy`` and float32, every product through ``ops``
(``reference/precision.py``: float32 at ``Precision.HIGHEST``, or the fp8
control). No cache, no ring, no batching, nothing of the program imported.

``logits(params, tokens, args, ops)``: ``params`` by the names the program's
builder gives the leaves (``mimo.embed_tokens``, ``mimo.l6.attn.q``, ...),
in whatever type they are served in, brought to float32 a layer (an expert)
at a time; ``tokens`` [T] int; ``args`` the configuration's builder keys
(the source's keys, ``layers_held`` the published indices of the layers
held, ``experts_held``, ``vocab_size`` the rows held). Returns float32 [T,
vocab_size]: row ``t`` is the distribution of token ``t + 1``.

Layer ``l`` (``h`` the stream, position ``t`` = the index along T); its
kind is ``hybrid_layer_pattern[l]`` (0 full, 1 window; a window layer takes
the ``swa_*`` keys):

* ``y = rms(h)``; ``q = y W_q`` (heads of ``head_dim``), ``k = y W_k``
  (``n_kv`` heads of ``head_dim``), ``v = attention_value_scale * y W_v``
  (``n_kv`` heads of ``v_head_dim``);
* rotary on the first ``int(partial_rotary_factor * head_dim)`` dims of q
  and k, pairs (i, i + R/2), frequencies ``theta^(-2i/R)``, ``theta``
  ``rope_theta`` or ``swa_rope_theta``;
* query head ``i`` reads key/value head ``i // (heads / n_kv)``; ``s[t, j] =
  q[t] . k[j] / sqrt(head_dim)`` for ``j <= t`` and, on a window layer,
  ``t - j < sliding_window``; ``a = exp(s) / (exp(sink_i) + sum_j exp(s))``
  with the learned ``sink`` where the layer kind has one, else the plain
  softmax; ``o[t] = sum_j a[t, j] v[j]``; ``h += concat_heads(o) W_o``;
* feed-forward on ``rms(h)``: SwiGLU (``moe_layer_freq[l]`` 0), or sigmoid
  scores over all ``n_routed_experts``, the ``num_experts_per_tok`` largest
  of score + bias, their scores divided by their sum
  (``norm_topk_prob``) times ``routed_scaling_factor`` (null: 1), the HELD
  experts' SwiGLUs one at a time (picks on absent experts are left out:
  this chip's addend). No shared expert.

Left out: the multi-token-prediction layers; ``attention_chunk_size``
(it repeats the window). Queries go in blocks of ``BLOCK`` rows so that a
16384-token pass fits beside the served weights (64 heads x 256 x 16384
scores are 1.07 GB in float32)."""

import functools

import jax
import jax.numpy as jnp

BLOCK = 256
_F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w.astype(_F32)


def _rope(x, rot, theta):
    """x [T, H, D]: the first ``rot`` dims of a head, pairs (i, i + rot/2),
    turned by ``t * theta^(-2i/rot)``; the rest passes."""
    t = x.shape[0]
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / rot)
    angle = jnp.arange(t, dtype=_F32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _blocks(t):
    size = BLOCK if t % BLOCK == 0 else t
    return t // size, size


def _attend(q, k, v, sink, window, ops):
    """q [T, G, R, Dk], k [T, G, Dk], v [T, G, Dv], sink [G, R] or None ->
    [T, G * R * Dv]."""
    t, g, r, dk = q.shape
    count, size = _blocks(t)
    scale = dk ** -0.5

    def block(args):
        qb, first = args
        s = ops.einsum("qgrd,kgd->grqk", qb, k) * scale
        at = first + jnp.arange(size)
        seen = jnp.arange(t)[None, :] <= at[:, None]
        if window:
            seen = seen & (at[:, None] - jnp.arange(t)[None, :] < window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        if sink is not None:
            top = jnp.maximum(top, sink[:, :, None, None])
        e = jnp.exp(s - top)
        total = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            total = total + jnp.exp(sink[:, :, None, None] - top)
        return ops.einsum("grqk,kgd->qgrd", e / total, v)

    out = jax.lax.map(block, (q.reshape(count, size, g, r, dk),
                              jnp.arange(count) * size))
    return out.reshape(t, -1)


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
    return routed


@functools.partial(jax.jit, static_argnames=("sizes", "ops"))
def _layer(x, p, sizes, ops):
    a = dict(sizes)
    t = x.shape[0]
    eps, g, dk, dv = a["eps"], a["kv_heads"], a["head_dim"], a["v_dim"]
    r = a["heads"] // g
    y = _rms(x, p["input_norm.w"], eps)
    q = _rope(ops.dot(y, p["attn.q"].astype(_F32)).reshape(t, g * r, dk),
              a["rot"], a["theta"]).reshape(t, g, r, dk)
    k = _rope(ops.dot(y, p["attn.k"].astype(_F32)).reshape(t, g, dk),
              a["rot"], a["theta"])
    v = a["value_scale"] * ops.dot(
        y, p["attn.v"].astype(_F32)).reshape(t, g, dv)
    sink = p["attn.sink"].astype(_F32).reshape(g, r) \
        if "attn.sink" in p else None
    mixed = _attend(q, k, v, sink, a["window"], ops)
    x = x + ops.dot(mixed, p["attn.o"].astype(_F32))
    y = _rms(x, p["post_norm.w"], eps)
    if a["dense"]:
        h = _swiglu(y, p["mlp.gate"].astype(_F32), p["mlp.up"].astype(_F32),
                    p["mlp.down"].astype(_F32), ops)
    else:
        h = _experts(y, p, a, ops)
    return x + h


@functools.partial(jax.jit, static_argnames=("eps", "ops"))
def _head(x, w, head, eps, ops):
    return ops.dot(_rms(x, w, eps), head.astype(_F32))


def layer_sizes(args, l):
    """The static sizes of published layer ``l`` as :func:`_layer` takes
    them."""
    windowed = bool(args["hybrid_layer_pattern"][l])
    pre = "swa_" if windowed else ""
    dk = int(args[pre + "head_dim"])
    lo = (args.get("experts_held") or (0, args["n_routed_experts"]))[0]
    return (
        ("window", int(args["sliding_window"]) if windowed else 0),
        ("dense", not args["moe_layer_freq"][l]),
        ("heads", int(args[pre + "num_attention_heads"])),
        ("kv_heads", int(args[pre + "num_key_value_heads"])),
        ("head_dim", dk), ("v_dim", int(args[pre + "v_head_dim"])),
        ("rot", int(args["partial_rotary_factor"] * dk)),
        ("theta", float(args["swa_rope_theta" if windowed
                             else "rope_theta"])),
        ("value_scale", float(args["attention_value_scale"])),
        ("eps", float(args["layernorm_epsilon"])),
        ("top_k", int(args["num_experts_per_tok"])),
        ("norm_topk_prob", bool(args["norm_topk_prob"])),
        ("scale", float(args["routed_scaling_factor"] or 1.0)),
        ("first_expert", int(lo)))


def logits(params, tokens, args, ops):
    held = args.get("layers_held")
    if held is None:
        held = range(len(args["hybrid_layer_pattern"]))
    x = jnp.take(params["mimo.embed_tokens"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(_F32)
    for l in held:
        prefix = "mimo.l%d." % l
        leaves = {k[len(prefix):]: v for k, v in params.items()
                  if k.startswith(prefix)}
        x = _layer(x, leaves, sizes=layer_sizes(args, l), ops=ops)
    return _head(x, params["mimo.norm.w"], params["mimo.lm_head"],
                 eps=float(args["layernorm_epsilon"]), ops=ops)
