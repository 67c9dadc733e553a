"""GLM-4.7-Flash on the serving path (source: the published ``config.json``
of zai-org/GLM-4.7-Flash, ``model_type`` ``glm4_moe_lite``): DeepSeek-V3's
block at small widths, built of ``models/glm_dsa.py``'s ONE block definition
with no indexer (every layer's ``indexer_types`` entry is ``none``: latent
attention reads every live position of the latent cache,
``ops/sparse_latent.py::latent_attention_dense`` a step (on one TPU by the
kernel ``latent_step.fwd``, ``ops/cache_attention.py``) and
``latent_attention_chunk`` without a mask a chunk), one leading dense layer,
then sigmoid-routed experts beside one shared expert, and **the
multi-token-prediction module kept and drafting**.

The module (DeepSeek-V3 section 2.2, ``num_nextn_predict_layers`` 1; the
published layer index ``num_hidden_layers``, here ``nextn_layer``): at
position ``i``, with ``h_i`` the main model's last hidden state AFTER its
final norm and ``t_{i+1}`` the next token, ``h' = W_eh [rms_e(Emb(t_{i+1}))
; rms_h(h_i)]``, one whole decoder layer of the same kind with a latent
cache of its own (``cache_latent_<nextn_layer>``), ``logits =
Head(rms_s(.))`` with the main model's embedding and head: the distribution
of token ``i + 2``.

Two programs over one scope, as ``DecodeBatcher`` takes them, and the decode
spec of each states the self-draft (``spec["self_draft"]``):

* :func:`glm_lite_step` takes, a slot row, ``tok_ids`` [B, 2] (the
  committed token and the module's draft of the next) at ``pos`` [B, 2]
  (``p``, ``p + 1``; a lane past the cache is a pad lane: no draft), runs
  the main model over both lanes, takes the greedy token after each INSIDE
  the executable, lets the draft stand iff it is lane 0's token, runs the
  module over both lanes (its inputs the hidden states and the embeddings of
  the tokens just chosen, all on the device) and returns ``[B, 4]`` int32 a
  row: how many tokens it yields (1 or 2), the two tokens, and the draft of
  the token after those that stand; and the token and position feeds of the
  step to come, so that the decode loop can feed them before it has read
  this step. A lane that did not stand leaves a row in both latent caches
  that the next step overwrites before anything reads it.
* :func:`glm_lite_chunk` ingests K prompt tokens a row, the module's layer
  with them (``tok_chunk`` [R, K + 1]: lane j's own token and, for the
  module, lane j + 1's), and builds ONE head, the module's on each row's
  last live lane: the draft the row's first step verifies.

What the step counts of itself: ``glm_dsa.DRAFT_COUNTERS``. Named scopes in
a device trace: ``mtp.embed_proj``, ``mtp.block``, ``mtp.head`` round the
module, ``latent_attention.{absorb,core,expand}`` in every layer.
"""

from . import glm_dsa

__all__ = ["glm_lite_step", "glm_lite_chunk"]


def _sizes(num_hidden_layers, first_k_dense_replace, rope_theta,
           num_nextn_predict_layers, nextn_layer, **source):
    """The source's keys as ``glm_dsa._decoder`` names them."""
    if num_nextn_predict_layers != 1 or nextn_layer is None:
        raise ValueError(
            "num_nextn_predict_layers %r, nextn_layer %r: the programs are "
            "built with the ONE prediction module the source states, at its "
            "published layer index; leaving it out is leaving part of the "
            "configuration out" % (num_nextn_predict_layers, nextn_layer))
    n = int(num_hidden_layers)
    return dict(
        source,
        indexer_types=["none"] * n,
        mlp_layer_types=["dense" if l < first_k_dense_replace else "sparse"
                         for l in range(n)],
        rope_parameters={"rope_theta": rope_theta},
        # no indexer: its sizes are read by nothing
        index_n_heads=0, index_head_dim=0, index_topk=0,
        indexer_rope_interleave=True, scoring_func="sigmoid",
        nextn_layer=nextn_layer)


def glm_lite_step(dtype="bfloat16", **sizes):
    """The verifying step program; returns ``(fetch variables, decode
    spec)``. ``sizes``: the source's keys, ``layers_held``, ``experts_held``
    and ``nextn_layer`` (the module's published layer index)."""
    return glm_dsa._decoder(False, dtype, **_sizes(**sizes))


def glm_lite_chunk(dtype="bfloat16", **sizes):
    """The K-token chunk program over the same parameters and caches."""
    return glm_dsa._decoder(True, dtype, **_sizes(**sizes))
