"""XLA-lowered ops: the dense latent attention's share of its roofline in a
decode step. The least time the chip could take for the operations and
bytes the attention over EVERY live position requires
(``ops_count_glm_lite.attention_step``: every latent layer, the module's
with them; ``kv_b`` read once a layer, a cached row once a sequence, both
lanes of a row sharing it; the larger of operations over the bf16 peak and
bytes over the HBM peak) over ``latent_attn_ms``. The op as it stands scores
the whole context rung under a mask, so the share cannot pass the live share
of the rung. Live sequences and positions are means over the profiled
window, from the engine's own counters, as ``decode_roofline`` takes them.
None where the trace has no such scope or the configuration's count no
``attention_step`` over positions (a model with an indexer reads a
selection: ``latent_attn_roofline``)."""

import os

from benchmark import harness

SCOPE = ("latent_attention",)


def read(ctx):
    measured = ctx["trace"].scope_ms_a_quantum("decode.step", SCOPE)
    before, after = ctx["profile_counters"]
    steps = after.get("decode_steps", 0) - before.get("decode_steps", 0)
    run = ctx["run"]
    if not measured or steps <= 0 or "index_topk" in run.config:
        return None
    module = run.config.get("ops_count", "").partition(":")[0]
    count = getattr(harness.load_module(os.path.join(harness.HERE, module)),
                    "attention_step", None) if module else None
    if count is None:
        return None
    at, held = ctx["ticks"].live_positions(ctx["requests"])
    p0, p1 = ctx["profiled"]
    inside = (at >= p0) & (at <= p1)
    if not inside.any():
        return None
    live = (after["slot_live"] - before["slot_live"]) / steps
    ops, nbytes = count(run.config, live, float(held[inside].mean()) + live)
    peaks = run.peaks()
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / measured
