"""XLA-lowered ops: the latent attention's share of its roofline in a decode
step. The least time the chip could take for the operations and bytes the
attention over the index sets requires (``ops_count_glm_dsa.
latent_attention_step``: all held layers; ``kv_b`` read once a layer, a
selected row once a sequence; the larger of operations over the bf16 peak
and bytes over the HBM peak) over ``latent_attn_ms``. Live sequences are the
engine's ``slot_live`` a step, the selected positions the step program's own
count (``program_index_selected``, summed over the ``full`` layers, so
divided by their number), both over the profiled window's steps. None where
the program keeps no such counter or the trace no such scope."""

import os

from benchmark import harness

SCOPE = ("latent_attention",)


def read(ctx):
    measured = ctx["trace"].scope_ms_a_quantum("decode.step", SCOPE)
    before, after = ctx["profile_counters"]
    steps = after.get("decode_steps", 0) - before.get("decode_steps", 0)
    name = "program_index_selected"
    if not measured or steps <= 0 or name not in after:
        return None
    run = ctx["run"]
    count = harness.load_module(os.path.join(
        harness.HERE, "ops_count_glm_dsa.py"))
    cfg = run.config
    first, held = cfg["layers_held"]
    full = sum(cfg["indexer_types"][l] == "full"
               for l in range(first, first + held))
    live = (after["slot_live"] - before["slot_live"]) / steps
    selected = (after[name] - before.get(name, 0)) / steps / max(full, 1)
    ops, nbytes = count.latent_attention_step(cfg, live, selected)
    peaks = run.peaks()
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / measured
