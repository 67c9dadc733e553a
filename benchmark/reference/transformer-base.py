"""Plain reference of ``transformer-base`` (Vaswani et al. 2017, Table 3
"base"), pre-norm as tensor2tensor's ``transformer_base``: forward and loss
in ``jax.numpy``, float32, no kernels; gradients by ``jax.grad``.

Departures from the paper that the configuration states: ReLU FFN with
biases and attention projections without (the repo's block), learned
position embeddings of ``seq_len + 1024`` rows, separate source and target
vocabularies, an untied output projection without bias, and label smoothing
spread uniformly over all classes: q = (1 - eps) * onehot + eps / V.

Parameters are given by the program's names (the benchmark makes the values
from the seed; nothing the program computed is read). Layer norms are
numbered in the order the builder creates them.
"""

import jax
import jax.numpy as jnp


def _layer_norm(x, p, n):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + 1e-5)
    return y * p["layer_norm_%d.w_0_0" % n] + p["layer_norm_%d.b_0_0" % n]


def _attention(ops, p, name, x, memory, bias, causal, n_head):
    b, tq, d = x.shape
    tk = memory.shape[1]
    dh = d // n_head

    def heads(y, t):
        return y.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)

    q = heads(ops.dot(x, p[name + ".q"]), tq)
    k = heads(ops.dot(memory, p[name + ".k"]), tk)
    v = heads(ops.dot(memory, p[name + ".v"]), tk)
    scores = ops.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(dh))
    if bias is not None:
        scores = scores + bias
    if causal:
        keep = jnp.tril(jnp.ones((tq, tk), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = ops.einsum("bhqk,bhkd->bhqd", probs, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, tq, d)
    return ops.dot(ctx, p[name + ".out"])


def _self(ops, p, name, x, bias, causal, n_head):
    return _attention(ops, p, name, x, x, bias, causal, n_head)


def _ffn(ops, p, name, x):
    h = jax.nn.relu(ops.dot(x, p[name + "_fc1.w"]) + p[name + "_fc1.b_0_0"])
    return ops.dot(h, p[name + "_fc2.w"]) + p[name + "_fc2.b_0_0"]


def _embed(p, side, ids, d_model):
    pos = jnp.arange(ids.shape[1])
    return (p[side + "_word_emb"][ids] * jnp.sqrt(float(d_model))
            + p[side + "_pos_emb"][pos][None])


def loss(params, batch, args, ops):
    """Mean smoothed cross-entropy over the target tokens inside each row's
    length. ``args`` are the configuration's ``builder_args``."""
    p = params
    d_model, n_head, n_layer = args["d_model"], args["n_head"], \
        args["n_layer"]
    eps = args["label_smooth_eps"]
    src, trg, lbl = batch["src_ids"], batch["trg_ids"], batch["lbl_ids"]
    seq = src.shape[1]
    at = jnp.arange(seq)[None, :]
    src_bias = jnp.where(at < batch["src_len"][:, None], 0.0,
                         -1e9)[:, None, None, :]

    ln = 0
    enc = _embed(p, "src", src, d_model)
    for i in range(n_layer):
        enc = enc + _self(ops, p, "enc%d_attn" % i, _layer_norm(enc, p, ln),
                          src_bias, False, n_head)
        enc = enc + _ffn(ops, p, "enc%d_ffn" % i, _layer_norm(enc, p, ln + 1))
        ln += 2
    enc = _layer_norm(enc, p, ln)
    ln += 1

    dec = _embed(p, "trg", trg, d_model)
    for i in range(n_layer):
        dec = dec + _self(ops, p, "dec%d_self" % i, _layer_norm(dec, p, ln),
                          None, True, n_head)
        dec = dec + _attention(ops, p, "dec%d_cross" % i,
                               _layer_norm(dec, p, ln + 1), enc, src_bias,
                               False, n_head)
        dec = dec + _ffn(ops, p, "dec%d_ffn" % i, _layer_norm(dec, p, ln + 2))
        ln += 3
    dec = _layer_norm(dec, p, ln)

    logits = ops.dot(dec, p["out_proj.w"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    at_label = jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
    token = -((1.0 - eps) * at_label + eps * jnp.mean(logp, axis=-1))
    mask = (at < batch["trg_len"][:, None]).astype(jnp.float32)
    return jnp.sum(token * mask) / jnp.sum(mask)
