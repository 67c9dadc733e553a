"""The OPT decoder (Zhang et al. 2022, arXiv:2205.01068) as a user of the
framework writes it: two KV-cached programs of one block definition, built
from the program's public layers and its ``kv_cache_write*`` /
``cached_attention*`` ops. ``step`` ingests one token a slot row, ``chunk``
K tokens a row; both name the same parameters, so one scope serves both.

Block, as ``transformers``' ``modeling_opt.py`` computes it with
``do_layer_norm_before``: ``x + out(attn(q, k, v of ln(x)))`` with biases on
q, k, v and out and the scores scaled by ``head_dim ** -0.5``; ``x + fc2(
relu(fc1(ln(x))))``; a final layer norm; the head tied to the word
embedding, no bias. The word embedding is not scaled; position ``p`` reads
row ``p + 2`` of a table of ``max_position_embeddings + 2`` rows.

Everything is declared in ``dtype`` (bfloat16 as served): parameters,
activations, caches; the ops accumulate in float32 on the MXU and keep
layer-norm and softmax statistics in float32. The step's logits leave as
float32. The program's own ``models.transformer.transformer_lm_step`` differs
in four places (scaled embedding, no offset, untied head, no attention
biases) and has no argument for them yet (PERF.md, Open questions)."""

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.layer_helper import LayerHelper
from paddle_tpu.core.param_attr import ParamAttr

POSITION_OFFSET = 2


def _attr(name):
    return ParamAttr(name=name)


def _linear(x, size, name, flat, act=None):
    return layers.fc(x, size=size, num_flatten_dims=flat, act=act,
                     param_attr=_attr(name + ".w"),
                     bias_attr=_attr(name + ".b"), name=name)


def _norm(x, name, axis):
    return layers.layer_norm(x, begin_norm_axis=axis,
                             param_attr=_attr(name + ".w"),
                             bias_attr=_attr(name + ".b"))


def _attend(q, new_k, new_v, pos, n_head, chunk):
    helper = LayerHelper("cached_attention_chunk" if chunk
                         else "cached_attention")
    out = helper.create_variable_for_type_inference(dtype=q.dtype,
                                                    shape=q.shape)
    helper.append_op(helper.layer_type,
                     {"Q": q, "CacheK": new_k, "CacheV": new_v, "Pos": pos},
                     {"Out": out}, {"num_heads": n_head})
    return out


def _decoder(chunk, vocab_size, hidden_size, ffn_dim, num_attention_heads,
             num_hidden_layers, max_position_embeddings, dtype):
    """Appends the program to the current main program. Returns
    ``(fetch_vars, spec)`` as ``DecodeBatcher`` takes them."""
    flat = 2 if chunk else 1
    write = layers.kv_cache_write_chunk if chunk else layers.kv_cache_write
    shape = [-1] if chunk else []
    tok = layers.data("tok_chunk" if chunk else "tok_ids", shape=shape,
                      dtype="int64")
    pos = layers.data("chunk_pos" if chunk else "pos", shape=shape,
                      dtype="int32")
    caches = [(layers.data("cache_k_%d" % i, shape=[-1, hidden_size],
                           dtype=dtype),
               layers.data("cache_v_%d" % i, shape=[-1, hidden_size],
                           dtype=dtype))
              for i in range(num_hidden_layers)]
    word = layers.embedding(tok, size=[vocab_size, hidden_size], dtype=dtype,
                            param_attr=_attr("opt.embed_tokens"))
    offset = layers.fill_constant([1], "int32", POSITION_OFFSET)
    where = layers.embedding(
        layers.elementwise_add(pos, offset),
        size=[max_position_embeddings + POSITION_OFFSET, hidden_size],
        dtype=dtype, param_attr=_attr("opt.embed_positions"))
    x = layers.elementwise_add(word, where)
    carried = []
    for i, (ck, cv) in enumerate(caches):
        nm = "opt.l%d" % i
        y = _norm(x, nm + ".attn_ln", flat)
        new_k = write(ck, _linear(y, hidden_size, nm + ".k", flat), pos)
        new_v = write(cv, _linear(y, hidden_size, nm + ".v", flat), pos)
        a = _attend(_linear(y, hidden_size, nm + ".q", flat), new_k, new_v,
                    pos, num_attention_heads, chunk)
        x = layers.elementwise_add(
            x, _linear(a, hidden_size, nm + ".out", flat))
        h = _linear(_norm(x, nm + ".ffn_ln", flat), ffn_dim, nm + ".fc1",
                    flat, act="relu")
        x = layers.elementwise_add(
            x, _linear(h, hidden_size, nm + ".fc2", flat))
        carried.append((new_k, new_v))
    x = _norm(x, "opt.final_ln", flat)
    table = fluid.default_main_program().global_block().var(
        "opt.embed_tokens")
    logits = layers.matmul(x, table, transpose_y=True)
    if not chunk:  # what the host samples from; a chunk's go unread
        logits = layers.cast(logits, "float32")
    fetch_vars, cache_feeds = [logits], []
    for i, (nk, nv) in enumerate(carried):
        fetch_vars += [nk, nv]
        for kind, var in (("k", nk), ("v", nv)):
            cache_feeds.append({"feed": "cache_%s_%d" % (kind, i),
                                "fetch": var.name, "tail": [hidden_size],
                                "dtype": dtype})
    spec = {"token_feed": tok.name, "pos_feed": pos.name,
            "logits_fetch": logits.name, "cache_feeds": cache_feeds,
            "vocab": vocab_size, "ctx_cap": max_position_embeddings}
    return fetch_vars, spec


def step(dtype="bfloat16", **sizes):
    return _decoder(False, dtype=dtype, **sizes)


def chunk(dtype="bfloat16", **sizes):
    return _decoder(True, dtype=dtype, **sizes)
