"""MiMo-V2 on the serving path: a decoder whose layers are of two kinds in
one slot table. A FULL layer caches the whole context and reads all of it;
a WINDOW layer reads its own position and the ``sliding_window - 1`` before
it, so it caches no more than that: a ring of ``sliding_window`` positions a
slot row whatever the context rung. The two kinds differ in their key/value
head count (grouped heads of two counts), their rotary base and their
softmax (a learned sink a query head on the window layers). Keys are wider
than values. The feed-forward is dense on layer 0 and sigmoid-routed experts
with no shared expert after it (source: the published ``config.json`` of
XiaomiMiMo/MiMo-V2-Flash, ``model_type`` ``mimo_v2_flash``).

Two programs of ONE block definition over one scope, as ``DecodeBatcher``
takes them: :func:`mimo_v2_step` ingests one token a slot row,
:func:`mimo_v2_chunk` K prompt tokens a row; both name the same parameters.

Layer ``l`` (the published index), ``h`` the stream, ``p`` the position fed
for each row or lane, every norm RMS with a plain weight, no bias anywhere;
``n_kv``, the head widths and the rotary base are the layer kind's
(``hybrid_layer_pattern[l]``: 0 full, 1 window, the ``swa_*`` keys):

* ``y = rms(h)``; ``q = y W_q`` in ``num_attention_heads`` heads of
  ``head_dim``, ``k = y W_k`` in ``n_kv`` heads of ``head_dim``, ``v =
  attention_value_scale * y W_v`` in ``n_kv`` heads of ``v_head_dim``;
* rotary on the first ``int(partial_rotary_factor * head_dim)`` dims of
  every q and k head at ``p``, rotate-half pairs, base ``rope_theta``
  (full) or ``swa_rope_theta`` (window);
* the caches of layer ``l`` hold k and v at ``p`` (a ring: at ``p %
  sliding_window``); query head ``i`` reads key/value head ``i // (heads /
  n_kv)``, scores ``q . k / sqrt(head_dim)`` over the positions ``<= p``, on
  a window layer those ``> p - sliding_window`` alone; a window layer's
  softmax has a learned sink a query head in its denominator
  (``add_swa_attention_sink_bias``; ``add_full_attention_sink_bias`` for
  the full layers); ``layers.cached_attention``, then ``W_o``;
* feed-forward on ``rms(h)``: SwiGLU of ``intermediate_size``
  (``moe_layer_freq[l]`` 0) or ``layers.routed_experts`` (sigmoid scores in
  float32, a selection bias that chooses and does not weigh, the top
  ``num_experts_per_tok`` renormalised, times ``routed_scaling_factor`` or
  1, no shared expert; ``n_group`` = ``topk_group`` = 1 make the published
  ``noaux_tc`` a plain top-k of score + bias).

**A chunk and a ring.** A chunk of K lanes may be longer than the ring, and
a scatter that names a slot twice resolves in no defined order. So a window
layer's chunk reads the ring AS IT WAS BEFORE the chunk with the chunk's own
keys and values beside it (each lane sees the ``sliding_window - 1``
positions before it as they were, whatever the chunk wraps over), and then
writes only the lanes that no later lane overwrites. A pad lane must be told
from a live one (modulo the ring its position would land on a live slot):
the chunk spec states ``pad_pos`` (:data:`PAD_POS`), the position the
scheduler gives a pad lane in place of the context rung. The ring's
capacity is ``sliding_window`` exactly: no margin is needed.

``layers_held`` (the published indices of the layers held, in order),
``experts_held`` ``[first, count]`` and ``vocab_size`` (the rows of the
vocabulary held) make the programs one chip's share of a deployment: whole
layers of a pipeline stage, a contiguous range of each layer's routed
experts (the router still scores all ``n_routed_experts``; picks on absent
experts are left out), the first rows of the embedding and of the untied
head. Embedding and head are built whatever the stage.

``window_cache``: ``"ring"`` (the default) or ``"context"``, which caches
the window layers to the context rung as the full layers are and masks the
window: the same logits at ``rung / sliding_window`` times the cache, kept
for the tests that hold the ring against it.

Left out of the published model: the multi-token-prediction layers
(``DecodeBatcher(speculative=)`` drafts from token histories, and refuses a
spec with a ring: a rejected draft's writes into a ring cannot be rewound),
and ``attention_chunk_size``, which repeats the window.
"""

from .. import layers
from ..core.framework import default_main_program
from ..core.param_attr import ParamAttr

__all__ = ["mimo_v2_step", "mimo_v2_chunk", "COUNTERS", "PAD_POS"]

# what the step program counts of itself, in the order of its counter fetch:
# the positions its full layers and its window layers read (summed over the
# slot rows and over the layers of the kind; a free row counts one position
# a layer), and the experts' rows
COUNTERS = ("attn_full_positions", "attn_window_positions",
            "moe_rows_held", "moe_rows_run")

# the position a pad lane of a chunk carries: past every context rung, so
# that a full layer's cache write drops it and a ring's write knows it
PAD_POS = 1 << 30


def _attr(name):
    return ParamAttr(name=name)


def _decoder(chunk, dtype, vocab_size, hidden_size, num_attention_heads,
             num_key_value_heads, head_dim, v_head_dim,
             swa_num_attention_heads, swa_num_key_value_heads, swa_head_dim,
             swa_v_head_dim, hybrid_layer_pattern, moe_layer_freq,
             intermediate_size, moe_intermediate_size, n_routed_experts,
             num_experts_per_tok, norm_topk_prob, routed_scaling_factor,
             scoring_func, layernorm_epsilon, rope_theta, swa_rope_theta,
             partial_rotary_factor, sliding_window, attention_value_scale,
             add_swa_attention_sink_bias, add_full_attention_sink_bias,
             max_position_embeddings, layers_held=None, experts_held=None,
             window_cache="ring"):
    held = list(layers_held if layers_held is not None
                else range(len(hybrid_layer_pattern)))
    known = min(len(hybrid_layer_pattern), len(moe_layer_freq))
    if not held or min(held) < 0 or max(held) >= known \
            or sorted(set(held)) != held:
        raise ValueError("layers_held %r: ascending published indices "
                         "under %d" % (held, known))
    if window_cache not in ("ring", "context"):
        raise ValueError("window_cache %r" % (window_cache,))
    ring = window_cache == "ring"
    window = int(sliding_window)
    flat = 2 if chunk else 1
    lane = [-1] if chunk else []
    tok = layers.data("tok_chunk" if chunk else "tok_ids", shape=lane,
                      dtype="int64")
    pos = layers.data("chunk_pos" if chunk else "pos", shape=lane,
                      dtype="int32")

    def linear(x, size, name):
        return layers.fc(x, size=size, num_flatten_dims=flat,
                         param_attr=_attr(name), bias_attr=False, name=name)

    def norm(x, name):
        return layers.rms_norm(x, layernorm_epsilon,
                               param_attr=_attr(name + ".w"),
                               param_dtype=dtype)

    def write(cache, x, ringed):
        if chunk:
            return layers.kv_cache_write_chunk(
                cache, x, pos, ring=ringed,
                pad_pos=PAD_POS if ringed else None)
        return layers.kv_cache_write(cache, x, pos, ring=ringed)

    x = layers.embedding(tok, size=[vocab_size, hidden_size], dtype=dtype,
                         param_attr=_attr("mimo.embed_tokens"))
    carried, counts, rows = [], {False: [], True: []}, []
    for l in held:
        nm = "mimo.l%d" % l
        windowed = bool(hybrid_layer_pattern[l])
        if windowed:
            heads, n_kv = swa_num_attention_heads, swa_num_key_value_heads
            dk, dv, theta = swa_head_dim, swa_v_head_dim, swa_rope_theta
            sink = add_swa_attention_sink_bias
        else:
            heads, n_kv = num_attention_heads, num_key_value_heads
            dk, dv, theta = head_dim, v_head_dim, rope_theta
            sink = add_full_attention_sink_bias
        ringed = windowed and ring
        rot = int(partial_rotary_factor * dk)
        y = norm(x, nm + ".input_norm")

        def heads_of(count, name):
            # the barrier keeps the view a head at a time (192 wide, no
            # multiple of 128) from reaching the weight's layout
            return layers.rotary(
                layers.optimization_barrier(linear(y, count * dk, name)),
                count, rot, float(theta), pos=pos)

        q = heads_of(heads, nm + ".attn.q")
        k = heads_of(n_kv, nm + ".attn.k")
        v = layers.scale(linear(y, n_kv * dv, nm + ".attn.v"),
                         scale=float(attention_value_scale))
        names = ["cache_%s_%d" % (kind, l) for kind in "kv"]
        widths = [n_kv * dk, n_kv * dv]
        caches = [layers.data(name, shape=[window if ringed else -1, width],
                              dtype=dtype)
                  for name, width in zip(names, widths)]

        def attend(cache_k, cache_v, **new):
            return layers.cached_attention(
                q, cache_k, cache_v, pos, heads, n_kv,
                window=window if windowed else 0,
                sink_attr=_attr(nm + ".attn.sink") if sink else None,
                ring=ringed, count=not chunk, **new)

        if ringed and chunk:
            # the rings as they were before the chunk, the chunk's own rows
            # beside them; the rings are written after
            a = attend(*caches, new_k=k, new_v=v)
            kept = [write(cache, new, True)
                    for cache, new in zip(caches, (k, v))]
        else:
            kept = [write(cache, new, ringed)
                    for cache, new in zip(caches, (k, v))]
            a = attend(*kept)
        if not chunk:
            a, count = a
            counts[windowed].append(count)
        for name, var, width in zip(names, kept, widths):
            carried.append((name, var, width, window if ringed else None))
        x = layers.elementwise_add(
            x, linear(a, hidden_size, nm + ".attn.o"))
        y = norm(x, nm + ".post_norm")
        if not moe_layer_freq[l]:
            h = layers.gated_feed_forward(
                y, intermediate_size, hidden_size, num_flatten_dims=flat,
                name=nm + ".mlp")
        else:
            h, _ = layers.routed_experts(
                y, n_routed_experts, num_experts_per_tok,
                moe_intermediate_size, 0, experts_held, norm_topk_prob,
                score=scoring_func, selection_bias=True,
                scale=float(routed_scaling_factor or 1.0), form="swiglu",
                name=nm + ".moe")
            rows.append(default_main_program().global_block().var(
                nm + ".moe.rows"))
        x = layers.elementwise_add(x, h)
    spec = {"token_feed": tok.name, "pos_feed": pos.name,
            "vocab": vocab_size, "ctx_cap": max_position_embeddings}
    if any(cap for _f, _v, _w, cap in carried):
        spec["pad_pos"] = PAD_POS
    fetch_vars = []
    if not chunk:
        # what the host samples from; a chunk only ingests and builds no head
        logits = layers.cast(
            linear(norm(x, "mimo.norm"), vocab_size, "mimo.lm_head"),
            "float32")
        fetch_vars.append(logits)
        spec["logits_fetch"] = logits.name

        def total(parts, width):
            out = parts[0] if parts else layers.fill_constant(
                [width], "int32", 0)
            for part in parts[1:]:
                out = layers.elementwise_add(out, part)
            return out

        counted = layers.concat([total(counts[False], 1),
                                 total(counts[True], 1), total(rows, 2)],
                                axis=0)
        fetch_vars.append(counted)
        spec["counter_fetch"] = counted.name
        spec["counters"] = list(COUNTERS)
    spec["cache_feeds"] = []
    for feed, var, width, cap in carried:
        fetch_vars.append(var)
        entry = {"feed": feed, "fetch": var.name, "tail": [width],
                 "dtype": dtype}
        if cap:
            entry["capacity"] = cap
        spec["cache_feeds"].append(entry)
    return fetch_vars, spec


def mimo_v2_step(dtype="bfloat16", **sizes):
    """The one-token step program, appended to the current main program.
    ``sizes``: the source's keys this module's docstring names, with
    ``layers_held``, ``experts_held`` and ``vocab_size`` the chip's share
    and, optionally, ``window_cache``. Everything is declared in ``dtype``
    (parameters, activations, caches); products accumulate in float32, norm
    and softmax statistics and the router's scores are float32; the logits
    leave as float32. Returns ``(fetch variables, decode spec)``; a window
    layer's two cache feeds state ``capacity`` ``sliding_window``, and the
    spec's ``counter_fetch`` is one int32 vector of :data:`COUNTERS`."""
    return _decoder(False, dtype, **sizes)


def mimo_v2_chunk(dtype="bfloat16", **sizes):
    """The K-token chunk program over the same parameters and caches. It
    ingests and builds no head: its spec names no ``logits_fetch``."""
    return _decoder(True, dtype, **sizes)
