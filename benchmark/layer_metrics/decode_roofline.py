"""Device: a decode step's share of its roofline. The least time the chip
could take for the operations and bytes one step requires (the
configuration's ``ops_count``, for ``opt-1.3b`` ``ops_count_opt.decode_step``:
every weight read once, the LIVE positions' keys and values read once, their
products; the larger of operations over the bf16 peak and bytes over the HBM
peak: bytes bound it) over ``decode_device_ms``. Live slots and positions
are means over the profiled window's quanta, from the engine's own counters
(``slot_live``; ``Ticks.live_positions``)."""

import os

from benchmark import harness


def read(ctx):
    measured = ctx["trace"].device_ms_a_quantum("decode.step")
    before, after = ctx["profile_counters"]
    steps = after.get("decode_steps", 0) - before.get("decode_steps", 0)
    if not measured or steps <= 0:
        return None
    live = (after["slot_live"] - before["slot_live"]) / steps
    at, held = ctx["ticks"].live_positions(ctx["requests"])
    p0, p1 = ctx["profiled"]
    inside = (at >= p0) & (at <= p1)
    if not inside.any():
        return None
    run = ctx["run"]
    module, _, function = run.config["ops_count"].partition(":")
    count = getattr(harness.load_module(os.path.join(harness.HERE, module)),
                    function)
    ops, nbytes = count(run.config, live, float(held[inside].mean()))
    peaks = run.peaks()
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / measured
