"""XLA-lowered ops: the EVA attention's share of its roofline in a decode
step. The least time the chip could take for the operations and bytes the
attention over the entries the rows HOLD requires
(``ops_count_evabyte.eva_attention_step``: all held layers; an entry's key
and value rows, 16,384 bytes at the published widths, read once a sequence;
the larger of operations over the bf16 peak and bytes over the HBM peak:
bytes bound it) over ``eva_attn_ms``. It reads the same work whatever
implements the op: the ``jnp`` form reads both caches whole under a mask, so
its share cannot pass the held share of the caches. Live sequences are the
engine's ``slot_live`` a step, the entries read the step program's own
counts (``program_eva_window_positions``, ``program_eva_summary_positions``,
summed over the held layers, so divided by their number), both over the
profiled window's steps. None where the program keeps no such counters or
the trace no such scope."""

import os

from benchmark import harness

SCOPE = ("attn.eva",)
NAMES = ("program_eva_window_positions", "program_eva_summary_positions")


def read(ctx):
    measured = ctx["trace"].scope_ms_a_quantum("decode.step", SCOPE)
    before, after = ctx["profile_counters"]
    steps = after.get("decode_steps", 0) - before.get("decode_steps", 0)
    if not measured or steps <= 0 or any(n not in after for n in NAMES):
        return None
    run = ctx["run"]
    count = harness.load_module(os.path.join(
        harness.HERE, "ops_count_evabyte.py"))
    layers = count.held(run.config)
    live = (after["slot_live"] - before["slot_live"]) / steps
    window, summary = ((after[n] - before.get(n, 0)) / steps / layers
                       for n in NAMES)
    ops, nbytes = count.eva_attention_step(run.config, live, window, summary)
    peaks = run.peaks()
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / measured
