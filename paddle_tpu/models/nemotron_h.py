"""Nemotron-H: a decoder-only hybrid of Mamba-2 state-space layers, causal
grouped-query attention layers and mixture-of-experts layers whose routed
experts work in a latent (source: the published ``config.json`` of
nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, ``model_type`` ``nemotron_h``;
layer equations as the public ``modeling_nemotron_h.py``, the expert layer
as the Nemotron 3 white paper's LatentMoE).

Every layer is ``x + mixer(rms(x))`` with ONE mixer, chosen by its letter
of ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer (``layers.
mamba2_mixer``), ``*`` causal attention without position encoding
(``layers.causal_self_attention``), ``E`` the experts: sigmoid scores over
all ``n_routed_experts``, the ``num_experts_per_tok`` largest of score +
selection bias, their scores over their sum times
``routed_scaling_factor``, two-matrix ReLU-squared experts in a latent of
``moe_latent_size``, one ungated shared expert at the model's width
(``layers.routed_experts``). RMS norms with a plain weight, no biases but
the convolution's, the head untied, the loss the mean cross-entropy over
all positions.

The program is one chip's share of a deployment that shards each layer,
and is told what it holds:

* ``layers_held`` ``[first, count]``: the layers of the published pattern
  this pipeline stage runs;
* ``heads_held`` ``[share, ways]``: the chip is share ``share`` of a
  ``ways``-way division of every mixer's heads. Of a Mamba-2 layer it
  holds heads ``[share * H / ways, (share + 1) * H / ways)`` with their
  groups (``ways`` divides ``n_groups``, so a group stays whole): the
  columns of ``in_proj`` [z | x B C | dt], the convolution's channels, the
  per-head ``A_log``, ``dt_bias`` and ``D``, the gated norm's weight and
  the rows of ``out_proj`` that belong to them. Of an attention layer it
  holds the same share of the query heads and the key/value heads they
  read;
* ``experts_held`` ``[first, count]``: the routed experts it holds (the
  router still scores all of them; the picks that fall on absent experts
  are left out of the routed sum);
* ``shared_units_held`` ``[first, count]``: the hidden units of the shared
  expert it holds (columns of ``up_proj``, rows of ``down_proj``);
* ``vocab_held``: the first rows of the embedding and columns of the head
  (ids and labels are drawn below it).

Each mixer's output is then this chip's addend of the sum over the chips
that share the layer, and that partial result goes on; nothing stands in for
the absent chips. Left out of the published model: the multi-token-prediction
module.
"""

from .. import layers
from ..core.param_attr import ParamAttr
from .common import FeedSpec, ModelSpec

__all__ = ["nemotron_h", "held_sizes"]

PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


def _share(total, ways, what):
    if total % ways:
        raise ValueError("%d %s do not divide %d ways" % (total, what, ways))
    return total // ways


def held_sizes(heads_held, mamba_num_heads, n_groups, num_attention_heads,
               num_key_value_heads):
    """(Mamba heads, groups, query heads, key/value heads) a chip holds as
    share ``heads_held = [share, ways]`` of every mixer's heads (default:
    all). A key/value head that serves query heads of several shares is held
    by each of them."""
    share, ways = heads_held or (0, 1)
    if not 0 <= share < ways:
        raise ValueError("heads_held %r: no share %d of %d"
                         % (heads_held, share, ways))
    q = _share(num_attention_heads, ways, "query heads")
    per_kv = num_attention_heads // num_key_value_heads
    first_kv = share * q // per_kv
    last_kv = ((share + 1) * q - 1) // per_kv
    return (_share(mamba_num_heads, ways, "Mamba heads"),
            _share(n_groups, ways, "Mamba groups"), q,
            last_kv - first_kv + 1)


def nemotron_h(seq_len=8192, vocab_size=131072, hidden_size=4096,
               hybrid_override_pattern=PUBLISHED_PATTERN,
               mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
               ssm_state_size=128, conv_kernel=4, chunk_size=128,
               num_attention_heads=32, num_key_value_heads=2, head_dim=128,
               n_routed_experts=512, num_experts_per_tok=22,
               moe_intermediate_size=2688, moe_latent_size=1024,
               moe_shared_expert_intermediate_size=5376,
               norm_topk_prob=True, routed_scaling_factor=5.0,
               layer_norm_epsilon=1e-5, layers_held=None, heads_held=None,
               experts_held=None, shared_units_held=None, vocab_held=None):
    """Builds the training program from ``ids`` and ``labels`` [B, T] int64
    (what each ``*_held`` means: the module's docstring; default all)."""
    vocab = int(vocab_held or vocab_size)
    first, count = layers_held or (0, len(hybrid_override_pattern))
    pattern = hybrid_override_pattern[first:first + count]
    if len(pattern) != count or set(pattern) - set("M*E"):
        raise ValueError("layers [%d, %d) of pattern %r" % (
            first, first + count, hybrid_override_pattern))
    m_heads, m_groups, q_heads, kv_heads = held_sizes(
        heads_held, mamba_num_heads, n_groups, num_attention_heads,
        num_key_value_heads)
    shared_units = (shared_units_held[1] if shared_units_held
                    else moe_shared_expert_intermediate_size)
    ids = layers.data("ids", shape=[seq_len], dtype="int64")
    labels = layers.data("labels", shape=[seq_len], dtype="int64")

    def norm(x, name):
        return layers.rms_norm(x, layer_norm_epsilon,
                               param_attr=ParamAttr(name=name + ".w"))

    x = layers.embedding(ids, size=[vocab, hidden_size],
                         param_attr=ParamAttr(name="embeddings"))
    loads, block_outs = [], []
    for i, kind in enumerate(pattern, start=first):
        nm = "l%d" % i
        h = norm(x, nm + ".norm")
        if kind == "M":
            h = layers.mamba2_mixer(
                h, m_heads, mamba_head_dim, m_groups, ssm_state_size,
                conv_kernel, layer_norm_epsilon, chunk_size,
                name=nm + ".mamba")
        elif kind == "*":
            h = layers.causal_self_attention(
                h, q_heads, kv_heads, head_dim, name=nm + ".attn")
        else:
            h, load = layers.routed_experts(
                h, n_routed_experts, num_experts_per_tok,
                moe_intermediate_size, shared_units, experts_held,
                norm_topk_prob, score="sigmoid", selection_bias=True,
                scale=routed_scaling_factor, form="relu2",
                shared_gate=False, latent_size=moe_latent_size,
                name=nm + ".moe")
            loads.append(load.name)
        x = layers.elementwise_add(x, h)
        block_outs.append(x.name)
    x = norm(x, "norm_f")
    ce = layers.fused_linear_smooth_ce(
        x, labels, size=vocab, epsilon=0.0, bias_attr=False,
        param_attr=ParamAttr(name="lm_head", sharding=(None, "mp")),
        name="lm_head")
    loss = layers.mean(ce)
    return ModelSpec(
        loss,
        feeds={"ids": FeedSpec([seq_len], "int64", 0, vocab),
               "labels": FeedSpec([seq_len], "int64", 0, vocab)},
        tokens_per_example=seq_len, sequence_feeds=["ids", "labels"],
        extras={"expert_loads": loads, "block_outs": block_outs})
