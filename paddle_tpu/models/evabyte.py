"""EvaByte on the serving path: a dense byte-level decoder whose attention
(``attention_class`` ``eva``; Zheng et al., arXiv:2302.04542, as EvaByte's
released ``eva.py`` simplifies it) keeps exact softmax attention inside the
query's own block-aligned window of ``window_size`` positions and replaces
every earlier window by one learned SUMMARY key and value for each chunk of
``chunk_size`` positions, all under ONE softmax. A layer therefore carries
four caches in the slot table, of two kinds, and neither holds the context
rung (source: the published ``config.json`` of EvaByte/EvaByte,
``model_type`` ``evabyte``):

* ``win_k_<l>``, ``win_v_<l>``: ``capacity`` ``window_size``, position ``p``
  at slot ``p % window_size``. No sliding ring: the window restarts at every
  multiple of ``window_size``, a step reads the slots ``0 .. p %
  window_size`` and masks the stale ones above;
* ``sum_k_<l>``, ``sum_v_<l>``: ``stride`` ``chunk_size``, ``rung /
  chunk_size`` entries a row, chunk ``c`` at entry ``c``: written at a
  stride, and derived from the window caches.

Two programs of ONE block definition over one scope, as ``DecodeBatcher``
takes them: :func:`evabyte_step` ingests one byte a slot row,
:func:`evabyte_chunk` K prompt bytes a row; both name the same parameters.

Layer ``l``, the stream ``h`` in float32 (``fp32_skip_add``), ``p`` the
position fed for each row or lane, ``W`` = ``window_size``, ``C`` =
``chunk_size``, heads ``i`` of ``D`` = ``hidden_size / num_attention_heads``,
``s = D ** -0.5``, no bias anywhere:

* ``y = rms(h) * (1 + w_in)`` (``norm_add_unit_offset``, ``rms_norm_eps``);
  ``q = y W_q``, ``k = y W_k``, ``v = y W_v`` in ``num_attention_heads``
  heads (``num_key_value_heads`` is the same: no grouping); rotary on all
  ``D`` dims of every q and k head at ``p``, rotate-half pairs, base
  ``rope_theta``;
* **summaries** (``layers.eva_summary``): for chunk ``c`` (positions ``C c ..
  C c + C - 1``) and head ``i``, with the layer's learned ``phi_i`` and
  ``mu_i`` in R^D: ``a_j = softmax_j(s k_j . phi_i)`` over the chunk's
  positions, ``kbar_c = sum_j a_j k_j + mu_i``, ``vbar_c = sum_j a_j v_j``
  (the ROTATED keys are pooled);
* **attention** (``layers.eva_attention``): with ``w = p // W``, one softmax
  over the scores ``s q . k_j`` of the positions ``w W <= j <= p`` and ``s q
  . kbar_c`` of the chunks ``c < w W / C`` (every chunk of every EARLIER
  window, none of the query's own), float32; the weighted sum of ``v_j`` and
  ``vbar_c``; then ``W_o`` and ``h += ..``. In window 0 this is plain causal
  attention; the query at ``p = W`` sees itself and ``W / C`` summaries;
* ``h += W_down(silu(W_gate z) * W_up z)``, ``z = rms(h) * (1 + w_post)``
  (``layers.gated_feed_forward``). After the last layer held: ``rms * (1 +
  w)``, head 0 ``[hidden_size, vocab_size]``, the logits float32 and their
  product exact (``fp32_logits``).

**What a step and a chunk run write.** A step writes its byte's key and
value into the window caches, and the summary of the chunk its position
ends (``p % C == C - 1``) from that chunk's slots of the window caches. A
chunk run writes the summaries of the chunks its lanes END (a chunk its
first lane continues takes its earlier positions from the window caches as
they were), then attends (the window caches as they were before the run,
its own keys and values beside them, the summary caches with its writes),
then writes the window caches. A prompt whose length is no multiple of ``C``
leaves its tail chunk open; the step that ends it writes it.

**A chunk run and the window boundary.** ``DecodeBatcher`` starts a row's
run wherever the row stands (forced steps run between chunk ticks, so a
start is no multiple of the rung), and a run's lanes MAY CROSS a multiple of
``W``: the lanes past it open the next window, see the lanes before it only
through the summaries the run itself has written, and their writes land on
slots the lanes before it still read, which is why the run reads the window
caches as they were. What is required is ``K <= W`` (a run crosses at most
one boundary; ``eva_attention_chunk`` refuses a longer one when it is
traced, and :func:`evabyte_chunk`'s spec carries no rung of its own). A pad
lane must be told from a live one (modulo ``W`` its position would land on
a live slot): the chunk spec states ``pad_pos`` (:data:`PAD_POS`).

``layers_held`` ``[first, count]`` makes the programs one pipeline stage of
whole layers; embedding, final norm and head are built whatever the stage.
``num_pred_heads`` must be 1: head 0 is the model's next-byte distribution,
and the further heads only draft (multibyte decoding), which is not served.

Left out of the published model: the seven drafting heads; ``mixedp_attn``,
``lazy_init``, ``init_fn``, ``init_std`` and ``fp32_ln`` (false) say nothing
of the forward pass. A prefix cache, ``speculative=`` and a self-drafting
step are refused by ``DecodeBatcher`` beside these caches: a prefix entry
would need the window caches and the summaries at its own length, and a
rejected draft's summary write cannot be rewound.
"""

from .. import layers
from ..core.param_attr import ParamAttr

__all__ = ["evabyte_step", "evabyte_chunk", "COUNTERS", "PAD_POS"]

# what the step program counts of itself, in the order of its counter fetch:
# the window slots and the summary entries its layers read and the context
# positions its rows hold, each summed over the slot rows and the layers held
# (a free row counts one position a layer): the compression a user gets
COUNTERS = ("eva_window_positions", "eva_summary_positions",
            "eva_context_positions")

# the position a pad lane of a chunk carries: past every context rung
PAD_POS = 1 << 30


def _attr(name):
    return ParamAttr(name=name)


def _decoder(chunk, dtype, vocab_size, hidden_size, num_attention_heads,
             num_key_value_heads, intermediate_size, num_hidden_layers,
             rms_norm_eps, rope_theta, window_size, chunk_size,
             max_position_embeddings, num_pred_heads=1,
             norm_add_unit_offset=True, fp32_skip_add=True,
             fp32_logits=True, layers_held=None):
    if int(num_key_value_heads) != int(num_attention_heads):
        raise ValueError("EVA attention is written for as many key/value "
                         "heads as query heads, not %d and %d" % (
                             num_key_value_heads, num_attention_heads))
    if int(num_pred_heads) != 1:
        raise ValueError("num_pred_heads %r: head 0 alone is served, the "
                         "further heads only draft" % (num_pred_heads,))
    if not (norm_add_unit_offset and fp32_skip_add and fp32_logits):
        raise ValueError("the block is written as EvaByte is published: "
                         "norms that apply 1 + w, a float32 stream and "
                         "float32 logits")
    first, count = layers_held or (0, num_hidden_layers)
    if first < 0 or count < 1 or first + count > num_hidden_layers:
        raise ValueError("layers_held %r of %d layers" % (
            layers_held, num_hidden_layers))
    heads, window, size = (int(num_attention_heads), int(window_size),
                           int(chunk_size))
    width = int(hidden_size)
    if width % heads or window % size:
        raise ValueError("hidden %d over %d heads, a window of %d over "
                         "chunks of %d" % (width, heads, window, size))
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
        """``rms(x) * (1 + w)`` of the float32 stream, in float32."""
        return layers.rms_norm(x, rms_norm_eps, zero_centered=True,
                               param_attr=_attr(name + ".w"),
                               param_dtype=dtype)

    def add(x, branch):
        return layers.elementwise_add(x, layers.cast(branch, "float32"))

    # the stream is float32 (fp32_skip_add); what a matrix reads is ``dtype``
    x = layers.cast(layers.embedding(
        tok, size=[vocab_size, width], dtype=dtype,
        param_attr=_attr("eva.embed_tokens")), "float32")
    carried, counts = [], []
    for l in range(first, first + count):
        nm = "eva.l%d" % l
        y = layers.cast(norm(x, nm + ".input_norm"), dtype)
        # the barrier keeps rotary's view a head at a time from reaching the
        # weight's layout (without it the compiler turns both matrices round
        # in every run: 32 MB each)
        q, k = (layers.rotary(layers.optimization_barrier(
            linear(y, width, nm + ".attn." + part)), heads, width // heads,
            float(rope_theta), pos=pos) for part in "qk")
        v = linear(y, width, nm + ".attn.v")
        names = ["%s_%s_%d" % (kind, part, l)
                 for kind in ("win", "sum") for part in "kv"]
        win_k, win_v, sum_k, sum_v = (
            layers.data(name, shape=[window if name.startswith("win")
                                     else -1, width], dtype=dtype)
            for name in names)
        pooling = dict(phi_attr=_attr(nm + ".attn.phi"),
                       mu_attr=_attr(nm + ".attn.mu"))
        if chunk:
            # the summaries the run's lanes end, then the lanes read (the
            # window caches as they were, the run's own rows beside them),
            # then the window caches are written
            sum_k, sum_v = layers.eva_summary(
                win_k, win_v, sum_k, sum_v, pos, heads, size, new_k=k,
                new_v=v, pad_pos=PAD_POS, **pooling)
            a = layers.eva_attention(q, win_k, win_v, sum_k, sum_v, pos,
                                     heads, window, size, new_k=k, new_v=v,
                                     pad_pos=PAD_POS)
            win_k, win_v = (
                layers.kv_cache_write_chunk(cache, new, pos, ring=True,
                                            pad_pos=PAD_POS)
                for cache, new in ((win_k, k), (win_v, v)))
        else:
            win_k, win_v = (layers.kv_cache_write(cache, new, pos, ring=True)
                            for cache, new in ((win_k, k), (win_v, v)))
            sum_k, sum_v = layers.eva_summary(
                win_k, win_v, sum_k, sum_v, pos, heads, size, **pooling)
            a, read = layers.eva_attention(q, win_k, win_v, sum_k, sum_v,
                                           pos, heads, window, size)
            counts.append(read)
        for name, var in zip(names, (win_k, win_v, sum_k, sum_v)):
            carried.append((name, var))
        x = add(x, linear(a, width, nm + ".attn.o"))
        x = add(x, layers.gated_feed_forward(
            layers.cast(norm(x, nm + ".post_norm"), dtype),
            intermediate_size, width, num_flatten_dims=flat,
            name=nm + ".mlp"))
    spec = {"token_feed": tok.name, "pos_feed": pos.name,
            "vocab": vocab_size, "ctx_cap": max_position_embeddings,
            "pad_pos": PAD_POS}
    fetch_vars = []
    if not chunk:
        # what the host samples from; a chunk only ingests and builds no head
        # (fp32_logits) the float32 stream against the head as the program
        # keeps it, the product exact
        logits = layers.fc(norm(x, "eva.norm"), size=vocab_size,
                           param_attr=_attr("eva.lm_head"), bias_attr=False,
                           name="eva.lm_head", param_dtype=dtype,
                           precision="highest")
        fetch_vars.append(logits)
        spec["logits_fetch"] = logits.name
        counted = counts[0]
        for part in counts[1:]:
            counted = layers.elementwise_add(counted, part)
        fetch_vars.append(counted)
        spec["counter_fetch"] = counted.name
        spec["counters"] = list(COUNTERS)
    spec["cache_feeds"] = []
    for feed, var in carried:
        fetch_vars.append(var)
        entry = {"feed": feed, "fetch": var.name, "tail": [width],
                 "dtype": dtype}
        entry.update({"capacity": window} if feed.startswith("win")
                     else {"stride": size})
        spec["cache_feeds"].append(entry)
    return fetch_vars, spec


def evabyte_step(dtype="bfloat16", **sizes):
    """The one-byte step program, appended to the current main program.
    ``sizes``: the source's keys this module's docstring names, with
    ``layers_held`` ``[first, count]`` the stage and ``num_pred_heads`` 1.
    Parameters, activations, caches and summaries are declared in ``dtype``;
    the stream, norm statistics, scores, both softmaxes, the pooled sums and
    the logits are float32, products accumulate in float32. Returns ``(fetch
    variables, decode spec)``; a window cache's feed states ``capacity``
    ``window_size``, a summary cache's ``stride`` ``chunk_size``, and the
    spec's ``counter_fetch`` is one int32 vector of :data:`COUNTERS`."""
    return _decoder(False, dtype, **sizes)


def evabyte_chunk(dtype="bfloat16", **sizes):
    """The K-byte chunk program over the same parameters and caches (``K <=
    window_size``). It ingests and builds no head: its spec names no
    ``logits_fetch``."""
    return _decoder(True, dtype, **sizes)
