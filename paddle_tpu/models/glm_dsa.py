"""GLM-MoE-DSA on the serving path: a decoder that caches ONE latent row a
position (multi-head latent attention, MLA), reads of it only the positions a
learned indexer selects (sparse attention; the selection of a ``full`` layer
is shared by the ``shared`` layers that follow it), and feeds forward through
sigmoid-routed experts with one shared expert (source: the published
``config.json`` of zai-org/GLM-5.2, ``model_type`` ``glm_moe_dsa``; MLA and
the routing as DeepSeek-V3's ``modeling_deepseek_v3.py``, the indexer as
DeepSeek-V3.2's).

Two programs of ONE block definition over one scope, as ``DecodeBatcher``
takes them: :func:`glm_dsa_step` ingests one token a slot row,
:func:`glm_dsa_chunk` K prompt tokens a row; both name the same parameters.

Layer ``l``, ``h`` the residual stream, ``p`` the position fed for each row
or lane, every norm RMS with a plain weight, no bias anywhere:

* queries: ``c_q = rms(rms(h) W_qa)``, ``q = c_q W_qb`` in heads of
  ``[q_nope | q_pe]``, rotary on ``q_pe`` at ``p``;
* the cached row: ``rms(h) W_kva = [c | k_pe]``, ``c <- rms(c)``, rotary on
  ``k_pe`` (one key for all heads); cache ``l`` holds ``[c | k_pe]`` at ``p``;
* the indexer (``indexer_types[l] == "full"``): ``qI = c_q W_qI`` in
  ``index_n_heads`` heads, ``kI = layernorm(rms(h) W_kI)`` (cached beside the
  latent), rotary on the first ``qk_rope_head_dim`` dims of both, ``w =
  rms(h) W_w``; ``layers.sparse_index`` scores and selects ``index_topk``
  positions. A ``shared`` layer has no indexer, no second cache, and reads
  the selection of the nearest ``full`` layer before it in the same run.
  **The index path is float32** (``INDEX_DTYPE``), as the router's scores
  are: a set is a discrete choice, and one that flips at its last place
  under a bfloat16 score moves the output of every layer that reads it.
  Both programs compute ``kI`` from the stream itself in float32 (its own
  norm, an exact product, layer norm and rotary) and cache it so; the step
  program computes ``qI`` (down-projection, norm and ``W_qI`` again, exact)
  and ``w`` the same way and scores exactly. The chunk program scores its
  lanes' queries from the bfloat16 ``c_q`` in one MXU pass: K queries a
  row at six passes each cost 7% of the served rate on the chip and
  brought the logits no closer to the reference's, because a chunk's sets
  only shape the rows that later layers cache, while a step's decide the
  logits that are sampled from;
* ``layers.latent_attention`` over the selected set (absorbed form, scale
  ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``), then ``W_o``;
* feed-forward: SwiGLU (``mlp_layer_types[l] == "dense"``) or
  ``layers.routed_experts`` (sigmoid scores, a selection bias that chooses
  and does not weigh, top-k renormalised times ``routed_scaling_factor``,
  one ungated shared expert; ``n_group`` = ``topk_group`` = 1 make the
  published ``noaux_tc`` a plain top-k of score + bias);
* rotary: ``rope_parameters.rope_theta``, default type, interleaved pairs.

``layers_held`` ``[first, count]``, ``experts_held`` ``[first, count]`` and
``vocab_size`` (the rows of the vocabulary held) make the programs one
chip's share of a deployment: a pipeline stage of whole layers, a contiguous
range of each layer's routed experts (the router still scores all
``n_routed_experts``; picks on absent experts are left out), the first rows
of the embedding and of the untied head. Embedding and head are built
whatever the stage, so that tokens go in and logits come out.

Left out of GLM-5.2's programs: its multi-token-prediction module (layer
``num_hidden_layers``, ``num_nextn_predict_layers`` 1; ``transformers`` drops
its weights at load). The block itself can carry one: with ``nextn_layer``
``_decoder`` builds DeepSeek-V3's module of this same block beside the held
layers, the step program verifies one draft a row and drafts the next
inside the executable, and its decode spec states that (``self_draft``), which
is all ``DecodeBatcher`` needs to advance a row by one token or two a step.
``models/glm_lite.py`` (GLM-4.7-Flash) builds its two programs so, with
layers whose ``indexer_types`` entry is ``none``: no indexer, attention over
every live position of the latent cache.
"""

import functools

from .. import layers
from ..core.framework import default_main_program, trace_scope
from ..core.param_attr import ParamAttr

__all__ = ["glm_dsa_step", "glm_dsa_chunk", "COUNTERS", "DRAFT_COUNTERS",
           "INDEX_DTYPE"]

# the type of the index path: the cached index keys, a step's index queries
# and head weights, and the scores between them
INDEX_DTYPE = "float32"

# what the step program counts of itself, in the order of its counter fetch
COUNTERS = ("index_selected", "index_cached", "moe_rows_held",
            "moe_rows_run")
# and a step that verifies a draft and drafts the next: drafts judged and
# drafts that stood; the held experts a layer that the step's picks reached,
# summed over the expert layers (the module's with them); the rows of the
# experts' tables as above
DRAFT_COUNTERS = ("mtp_drafted", "mtp_accepted", "moe_experts_touched",
                  "moe_rows_held", "moe_rows_run")


def _attr(name):
    return ParamAttr(name=name)


def _decoder(chunk, dtype, vocab_size, hidden_size, num_attention_heads,
             q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
             v_head_dim, index_n_heads, index_head_dim, index_topk,
             indexer_types, mlp_layer_types, intermediate_size,
             moe_intermediate_size, n_routed_experts, n_shared_experts,
             num_experts_per_tok, norm_topk_prob, routed_scaling_factor,
             scoring_func, rms_norm_eps, rope_parameters, rope_interleave,
             indexer_rope_interleave, max_position_embeddings,
             layers_held=None, experts_held=None, nextn_layer=None):
    """``nextn_layer``: the published index of the multi-token-prediction
    module's layer, or None where the module is left out. With it the step
    program verifies one draft a row (two lanes) and drafts the next, and
    the chunk program ingests the prompt into the module's cache too."""
    first, count = layers_held or (0, len(indexer_types))
    held = list(range(first, first + count))
    if not held or held[-1] >= min(len(indexer_types), len(mlp_layer_types)):
        raise ValueError("layers [%d, %d) of %d" % (
            first, first + count, len(indexer_types)))
    if indexer_types[first] == "shared":
        raise ValueError("layer %d shares a selection that no held layer "
                         "makes: a stage starts on a 'full' layer" % first)
    drafting = nextn_layer is not None
    theta = float(rope_parameters["rope_theta"])
    # a drafting step has two lanes a row and is built as a chunk of two
    lanes = chunk or drafting
    flat = 2 if lanes else 1
    write = layers.kv_cache_write_chunk if lanes else layers.kv_cache_write
    if drafting and not chunk:
        # two lanes a row: written a lane at a time, the cache as it lies
        write = functools.partial(layers.kv_cache_write_chunk, few=True)
    lane = [-1] if lanes else []
    fed = layers.data("tok_chunk" if chunk else "tok_ids", shape=lane,
                      dtype="int64")
    pos = layers.data("chunk_pos" if chunk else "pos", shape=lane,
                      dtype="int32")
    tok = fed
    if chunk and drafting:
        # K + 1 tokens a row: each lane's own and, for the module, the next
        tok = layers.slice(fed, [1], [0], [-1])
    latent_width = kv_lora_rank + qk_rope_head_dim
    q_head = qk_nope_head_dim + qk_rope_head_dim

    def cache(name, width, kept=dtype):
        return layers.data(name, shape=[-1, width], dtype=kept)

    def linear(x, size, name, site=None):
        return layers.fc(x, size=size, num_flatten_dims=flat,
                         param_attr=_attr(name), bias_attr=False,
                         name=site or name)

    def exact(x, size, name):
        """A float32 ``x`` against the weight as the program keeps it."""
        return layers.fc(x, size=size, num_flatten_dims=flat,
                         param_attr=_attr(name), bias_attr=False,
                         name=name + ".exact", param_dtype=dtype,
                         precision="highest")

    def norm(x, name, dim=None):
        return layers.rms_norm(x, rms_norm_eps, norm_dim=dim,
                               param_attr=_attr(name + ".w"),
                               param_dtype=dtype)

    def rope(x, heads, interleaved, offset=0):
        return layers.rotary(x, heads, qk_rope_head_dim, theta, pos=pos,
                             interleaved=interleaved, offset=offset)

    def embed(ids):
        return layers.embedding(ids, size=[vocab_size, hidden_size],
                                dtype=dtype,
                                param_attr=_attr("glm.embed_tokens"))

    carried, counts, rows, loads = [], [], [], []

    def block(l, x, selected, kind, mlp):
        """Layer ``l`` over the stream ``x``: (the stream after it, the
        selection it read). ``kind``: its entry of ``indexer_types``, or
        ``none`` for a layer that has no indexer and reads no selection;
        ``mlp``: its entry of ``mlp_layer_types``."""
        nm = "glm.l%d" % l
        y = norm(x, nm + ".input_norm")
        c_q = norm(linear(y, q_lora_rank, nm + ".attn.q_a"),
                   nm + ".attn.q_a_norm")
        q = rope(linear(c_q, num_attention_heads * q_head, nm + ".attn.q_b"),
                 num_attention_heads, rope_interleave,
                 offset=qk_nope_head_dim)
        c, k_pe = layers.split(linear(y, latent_width, nm + ".attn.kv_a"),
                               [kv_lora_rank, qk_rope_head_dim], dim=-1)
        row = layers.concat([norm(c, nm + ".attn.kv_a_norm"),
                             rope(k_pe, 1, rope_interleave)], axis=-1)
        latent = write(cache("cache_latent_%d" % l, latent_width), row, pos)
        carried.append(("cache_latent_%d" % l, latent, latent_width, dtype))
        if kind == "full":
            y_i = norm(layers.cast(x, INDEX_DTYPE), nm + ".input_norm")
            k_i = layers.layer_norm(
                exact(y_i, index_head_dim, nm + ".indexer.wk"),
                begin_norm_axis=flat, epsilon=1e-6,
                param_attr=_attr(nm + ".indexer.k_norm.w"),
                bias_attr=_attr(nm + ".indexer.k_norm.b"),
                param_dtype=dtype)
            keys = write(cache("cache_index_%d" % l, index_head_dim,
                               INDEX_DTYPE),
                         rope(k_i, 1, indexer_rope_interleave), pos)
            carried.append(("cache_index_%d" % l, keys, index_head_dim,
                            INDEX_DTYPE))
            if chunk:
                c_q_i, y_w, project = c_q, y, linear
            else:
                c_q_i = norm(exact(y_i, q_lora_rank, nm + ".attn.q_a"),
                             nm + ".attn.q_a_norm")
                y_w, project = y_i, exact
            q_i = rope(project(c_q_i, index_n_heads * index_head_dim,
                               nm + ".indexer.wq_b"),
                       index_n_heads, indexer_rope_interleave)
            selected, count = layers.sparse_index(
                q_i, project(y_w, index_n_heads,
                             nm + ".indexer.weights_proj"),
                keys, pos, index_n_heads, index_topk)
            counts.append(count)
        elif kind == "none":
            selected = None     # every live position, whatever came before
        elif kind != "shared":
            raise ValueError("indexer type %r of layer %d" % (kind, l))
        a = layers.latent_attention(
            q, latent, selected, pos, num_attention_heads, kv_lora_rank,
            qk_nope_head_dim, v_head_dim, q_head ** -0.5,
            param_attr=_attr(nm + ".attn.kv_b"), dense=not chunk)
        x = layers.elementwise_add(
            x, linear(a, hidden_size, nm + ".attn.o"))
        y = norm(x, nm + ".post_norm")
        if mlp == "dense":
            h = layers.gated_feed_forward(
                y, intermediate_size, hidden_size, num_flatten_dims=flat,
                name=nm + ".mlp")
        else:
            h, load = layers.routed_experts(
                y, n_routed_experts, num_experts_per_tok,
                moe_intermediate_size,
                n_shared_experts * moe_intermediate_size, experts_held,
                norm_topk_prob, score=scoring_func, selection_bias=True,
                scale=routed_scaling_factor, form="swiglu",
                shared_gate=False, name=nm + ".moe")
            rows.append(default_main_program().global_block().var(
                nm + ".moe.rows"))
            loads.append(load)
        return layers.elementwise_add(x, h), selected

    x, selected = embed(tok), None
    for l in held:
        x, selected = block(l, x, selected, indexer_types[l],
                            mlp_layer_types[l])
    spec = {"token_feed": fed.name, "pos_feed": pos.name,
            "vocab": vocab_size, "ctx_cap": max_position_embeddings}
    fetch_vars = []

    def head(x, final_norm, site):
        return layers.cast(linear(norm(x, final_norm), vocab_size,
                                  "glm.lm_head", site), "float32")

    nm = "glm.l%d" % nextn_layer if drafting else None

    def module(hidden, after):
        """The multi-token-prediction module (DeepSeek-V3 section 2.2, one
        module): lane i holds the main model's normed last hidden state of
        position i and ``after``, the token of position i + 1; the stream
        after the module's one whole layer, which predicts token i + 2."""
        with trace_scope("mtp.embed_proj"):
            joined = layers.concat([norm(embed(after), nm + ".enorm"),
                                    norm(hidden, nm + ".hnorm")], axis=-1)
            x = linear(joined, hidden_size, nm + ".eh_proj")
        with trace_scope("mtp.block"):
            return block(nextn_layer, x, None, "none", "sparse")[0]

    def total(parts):
        out = parts[0]
        for part in parts[1:]:
            out = layers.elementwise_add(out, part)
        return out

    if chunk and drafting:
        # the module ingests the prompt too; its one head, on each row's
        # last live lane, drafts the token the row's first step verifies
        y = module(norm(x, "glm.norm"), layers.slice(fed, [1], [1], [2 ** 30]))
        with trace_scope("mtp.head"):
            last = layers.last_live_lane(y, pos, carried[-1][1])
            draft = layers.argmax(layers.cast(
                layers.fc(norm(last, nm + ".shared_head.norm"),
                          size=vocab_size, param_attr=_attr("glm.lm_head"),
                          bias_attr=False, name="glm.mtp_head"), "float32"),
                axis=-1)
        fetch_vars.append(draft)
        spec["self_draft"] = {"draft_fetch": draft.name, "next_token_lane": 1}
    elif drafting:
        hidden = norm(x, "glm.norm")
        logits = layers.cast(linear(hidden, vocab_size, "glm.lm_head"),
                             "float32")
        greedy = layers.argmax(logits, axis=-1)
        y = module(hidden, greedy)
        with trace_scope("mtp.head"):
            draft_logits = head(y, nm + ".shared_head.norm", "glm.mtp_head")
            drafts = layers.argmax(draft_logits, axis=-1)
        out, judged, next_tok, next_pos = layers.self_draft_accept(
            fed, greedy, drafts, pos, carried[-1][1])
        fetch_vars += [out, next_tok, next_pos]
        spec["self_draft"] = {
            "lanes": 2, "yield_fetch": out.name,
            # the step to come's two feeds, made on the device: a loop that
            # runs one step ahead feeds them unread
            "next_token_fetch": next_tok.name,
            "next_pos_fetch": next_pos.name,
            "cache_feeds": ["cache_latent_%d" % nextn_layer],
            # not fetched; a test or a look into a run asks for them by name
            "logits": [logits.name, draft_logits.name]}
        touched = total([layers.reshape(layers.reduce_sum(layers.clip(
            load, 0, 1)), [1]) for load in loads])
        counted = layers.concat([judged, touched, total(rows)], axis=0)
        fetch_vars.append(counted)
        spec["counter_fetch"] = counted.name
        spec["counters"] = list(DRAFT_COUNTERS)
    elif not chunk:
        # what the host samples from; a chunk only ingests and builds no head
        logits = head(x, "glm.norm", None)
        fetch_vars.append(logits)
        spec["logits_fetch"] = logits.name
    if not chunk and not drafting:
        if not rows:    # a stage of dense layers alone
            rows = [layers.fill_constant([2], "int32", 0)]
        if not counts:  # no indexer: nothing selected, nothing counted
            counts = [layers.fill_constant([2], "int32", 0)]
        counted = layers.concat([total(counts), total(rows)], axis=0)
        fetch_vars.append(counted)
        spec["counter_fetch"] = counted.name
        spec["counters"] = list(COUNTERS)
    spec["cache_feeds"] = []
    for feed, var, width, kept in carried:
        fetch_vars.append(var)
        spec["cache_feeds"].append({"feed": feed, "fetch": var.name,
                                    "tail": [width], "dtype": kept})
    return fetch_vars, spec


def glm_dsa_step(dtype="bfloat16", **sizes):
    """The one-token step program, appended to the current main program.
    ``sizes``: the source's keys this module's docstring names, with
    ``layers_held``, ``experts_held`` and ``vocab_size`` the chip's share.
    Everything is declared in ``dtype`` (parameters, activations, the
    latent cache) but the index path, which is ``INDEX_DTYPE`` (the cached
    index keys, the index queries and head weights, the scores); products
    accumulate in float32, norm and softmax statistics are float32; the
    logits leave as float32.
    Returns ``(fetch variables, decode spec)``; the spec's
    ``counter_fetch`` is one int32 vector of :data:`COUNTERS`."""
    return _decoder(False, dtype, **sizes)


def glm_dsa_chunk(dtype="bfloat16", **sizes):
    """The K-token chunk program over the same parameters and caches. It
    ingests and builds no head: its spec names no ``logits_fetch``, and
    ``DecodeBatcher(speculative=)``, which reads a chunk's logits, refuses
    it."""
    return _decoder(True, dtype, **sizes)
