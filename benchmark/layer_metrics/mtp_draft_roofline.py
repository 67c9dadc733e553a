"""XLA-lowered ops: the prediction module's share of its roofline in a
decode step. The least time the chip could take for the operations and
bytes the module requires (``ops_count_glm_lite.draft_step``: ``eh_proj``,
its layer's matrices, a routed expert if a pick reaches it, its cache's live
rows, the head once more; the larger of operations over the bf16 peak and
bytes over the HBM peak: bytes bound it) over ``mtp_draft_ms``. Live
sequences and positions are means over the profiled window, from the
engine's own counters, as ``decode_roofline`` takes them. None where the
trace has no such scopes or the configuration's count no ``draft_step``."""

import os

from benchmark import harness

SCOPES = ("mtp.embed_proj", "mtp.block", "mtp.head")


def read(ctx):
    measured = ctx["trace"].scope_ms_a_quantum("decode.step", SCOPES)
    before, after = ctx["profile_counters"]
    steps = after.get("decode_steps", 0) - before.get("decode_steps", 0)
    if not measured or steps <= 0:
        return None
    run = ctx["run"]
    module = run.config.get("ops_count", "").partition(":")[0]
    count = getattr(harness.load_module(os.path.join(harness.HERE, module)),
                    "draft_step", None) if module else None
    if count is None:
        return None
    at, held = ctx["ticks"].live_positions(ctx["requests"])
    p0, p1 = ctx["profiled"]
    inside = (at >= p0) & (at <= p1)
    if not inside.any():
        return None
    live = (after["slot_live"] - before["slot_live"]) / steps
    ops, nbytes = count(run.config, live, float(held[inside].mean()))
    peaks = run.peaks()
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / measured
