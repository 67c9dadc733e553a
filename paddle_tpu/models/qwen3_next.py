"""Qwen3-Next: a decoder-only hybrid of Gated DeltaNet linear attention and
gated grouped-query softmax attention, every block followed by a mixture of
routed and shared experts (source: the published ``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct; layer equations as ``transformers``'
``modeling_qwen3_next.py``).

Block ``i``: ``h = x + mixer_i(rms(x))``, ``y = h + moe(rms(h))``, RMS norms
zero-centred (weight ``1 + w``); ``mixer_i`` is full attention where
``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet. The head is
untied, the loss the mean cross-entropy over all positions.

``experts_held`` and ``vocab_held`` make the program one chip's share of a
deployment that shards each layer: the chip holds a contiguous range of the
routed experts (the router still scores all ``num_experts``; the picks that
fall on absent experts are left out of the routed sum) and the first
``vocab_held`` rows of the embedding and columns of the head. Left out of
the published model: the multi-token-prediction module and the router's
auxiliary loss.
"""

from .. import layers
from ..core.param_attr import ParamAttr
from .common import FeedSpec, ModelSpec

__all__ = ["qwen3_next"]


def qwen3_next(seq_len=8192, vocab_size=151936, hidden_size=2048,
               num_hidden_layers=48, num_attention_heads=16,
               num_key_value_heads=2, head_dim=256,
               partial_rotary_factor=0.25, rope_theta=1e7,
               linear_num_key_heads=16, linear_num_value_heads=32,
               linear_key_head_dim=128, linear_value_head_dim=128,
               linear_conv_kernel_dim=4, full_attention_interval=4,
               num_experts=512, num_experts_per_tok=10, norm_topk_prob=True,
               moe_intermediate_size=512,
               shared_expert_intermediate_size=512, rms_norm_eps=1e-6,
               experts_held=None, vocab_held=None, chunk=64):
    """Builds the training program from ``ids`` and ``labels`` [B, T] int64.
    ``experts_held``: ``[first, count]`` of the routed experts this chip
    holds (default all); ``vocab_held``: rows of the vocabulary it holds
    (default all; ids and labels are drawn below it)."""
    vocab = int(vocab_held or vocab_size)
    ids = layers.data("ids", shape=[seq_len], dtype="int64")
    labels = layers.data("labels", shape=[seq_len], dtype="int64")

    def norm(x, name):
        return layers.rms_norm(x, rms_norm_eps, zero_centered=True,
                               param_attr=ParamAttr(name=name + ".w"))

    x = layers.embedding(ids, size=[vocab, hidden_size],
                         param_attr=ParamAttr(name="embed_tokens"))
    loads, block_outs = [], []
    for i in range(num_hidden_layers):
        nm = "l%d" % i
        h = norm(x, nm + ".input_norm")
        if (i + 1) % full_attention_interval == 0:
            h = layers.causal_self_attention(
                h, num_attention_heads, num_key_value_heads, head_dim,
                int(head_dim * partial_rotary_factor), rope_theta,
                rms_norm_eps, qk_norm=True, output_gate=True,
                name=nm + ".attn")
        else:
            h = layers.gated_delta_net(
                h, linear_num_key_heads, linear_num_value_heads,
                linear_key_head_dim, linear_value_head_dim,
                linear_conv_kernel_dim, rms_norm_eps, chunk,
                name=nm + ".gdn")
        x = layers.elementwise_add(x, h)
        h, load = layers.routed_experts(
            norm(x, nm + ".post_norm"), num_experts, num_experts_per_tok,
            moe_intermediate_size, shared_expert_intermediate_size,
            experts_held, norm_topk_prob, name=nm + ".moe")
        x = layers.elementwise_add(x, h)
        loads.append(load.name)
        block_outs.append(x.name)
    x = norm(x, "final_norm")
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
