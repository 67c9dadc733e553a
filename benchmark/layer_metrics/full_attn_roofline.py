"""XLA-lowered ops: the full layers' attention's share of its roofline in a
decode step. The least time the chip could take for the operations and
bytes the attention over the LIVE positions requires
(``ops_count_mimo_v2.attention_step``: all held full layers; a live
position's key and value rows read once a sequence, 2,560 bytes a position
a layer at the published widths; the larger of operations over the bf16
peak and bytes over the HBM peak: bytes bound it) over ``full_attn_ms``.
The op as it stands reads the whole context rung under a mask, so the share
cannot pass the live share of the rung. Live sequences are the engine's
``slot_live`` a step, the positions read the step program's own count
(``program_attn_full_positions``, summed over the full layers, so divided by
their number), both over the profiled window's steps. None where the
program keeps no such counter or the trace no such scope."""

import os

from benchmark import harness

SCOPE = ("attn.full",)


def read(ctx):
    measured = ctx["trace"].scope_ms_a_quantum("decode.step", SCOPE)
    before, after = ctx["profile_counters"]
    steps = after.get("decode_steps", 0) - before.get("decode_steps", 0)
    name = "program_attn_full_positions"
    if not measured or steps <= 0 or name not in after:
        return None
    run = ctx["run"]
    count = harness.load_module(os.path.join(
        harness.HERE, "ops_count_mimo_v2.py"))
    layers = len(count.full_layers(run.config))
    if not layers:
        return None
    live = (after["slot_live"] - before["slot_live"]) / steps
    positions = (after[name] - before.get(name, 0)) / steps / layers
    ops, nbytes = count.attention_step(run.config, live, positions)
    peaks = run.peaks()
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / measured
