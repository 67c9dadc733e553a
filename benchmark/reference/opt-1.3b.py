"""Plain reference of the OPT decoder (Zhang et al. 2022, arXiv:2205.01068;
equations as ``transformers``' ``modeling_opt.py`` with
``do_layer_norm_before``): the full forward pass over one sequence under a
causal mask, in ``jax.numpy`` and float32, every matrix product through
``ops`` (``reference/precision.py``: float32 at ``Precision.HIGHEST``, or the
fp8 control). No cache, no batching, nothing of the program imported.

``logits(params, tokens, args, ops)``: ``params`` by the names the
benchmark's builder gives the leaves (``opt.embed_tokens``, ``opt.l3.q.w``,
...), in whatever type they are served in; they are brought to float32 a
layer at a time, so the reference never holds the whole model twice.
``tokens`` [T] int. Returns float32 [T, vocab_size]: row ``t`` is the
distribution of token ``t + 1``.

Departures from ``modeling_opt.py``: none in the mathematics. Dropout is off
(inference); the attention mask is the causal one alone (one sequence, no
padding); ``word_embed_proj_dim`` equals ``hidden_size``, so there is no
``project_in`` / ``project_out`` (true of opt-1.3b, not of opt-350m)."""

import functools

import jax
import jax.numpy as jnp

POSITION_OFFSET = 2
LAYER_LEAVES = ("attn_ln.w", "attn_ln.b", "q.w", "q.b", "k.w", "k.b", "v.w",
                "v.b", "out.w", "out.b", "ffn_ln.w", "ffn_ln.b", "fc1.w",
                "fc1.b", "fc2.w", "fc2.b")


def _layer_norm(x, w, b, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


@functools.partial(jax.jit, static_argnames=("heads", "ops"))
def _layer(x, leaves, heads, ops):
    p = {k: v.astype(jnp.float32) for k, v in leaves.items()}
    t, d = x.shape
    y = _layer_norm(x, p["attn_ln.w"], p["attn_ln.b"])

    def split(name):
        return (ops.dot(y, p[name + ".w"]) + p[name + ".b"]).reshape(
            t, heads, d // heads)

    q, k, v = split("q"), split("k"), split("v")
    scores = ops.einsum("qhd,khd->hqk", q * (d // heads) ** -0.5, k)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    mixed = ops.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + ops.dot(mixed.reshape(t, d), p["out.w"]) + p["out.b"]
    y = _layer_norm(x, p["ffn_ln.w"], p["ffn_ln.b"])
    h = jax.nn.relu(ops.dot(y, p["fc1.w"]) + p["fc1.b"])
    return x + ops.dot(h, p["fc2.w"]) + p["fc2.b"]


@jax.jit
def _embed(tokens, words, positions):
    at = jnp.arange(tokens.shape[0]) + POSITION_OFFSET
    return (jnp.take(words, tokens, axis=0).astype(jnp.float32)
            + jnp.take(positions, at, axis=0).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("ops",))
def _head(x, w, b, words, ops):
    y = _layer_norm(x, w.astype(jnp.float32), b.astype(jnp.float32))
    return ops.einsum("td,vd->tv", y, words.astype(jnp.float32))


def logits(params, tokens, args, ops):
    x = _embed(jnp.asarray(tokens, jnp.int32), params["opt.embed_tokens"],
               params["opt.embed_positions"])
    for i in range(int(args["num_hidden_layers"])):
        leaves = {k: params["opt.l%d.%s" % (i, k)] for k in LAYER_LEAVES}
        x = _layer(x, leaves, heads=int(args["num_attention_heads"]),
                   ops=ops)
    return _head(x, params["opt.final_ln.w"], params["opt.final_ln.b"],
                 params["opt.embed_tokens"], ops=ops)
