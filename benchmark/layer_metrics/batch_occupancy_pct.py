"""Scheduler: live slots over slots offered, summed over the quanta of the
timed window (the engine's ``slot_live`` and ``slot_total`` counters, read
when the window opens and when it closes). Program counter."""


def read(ctx):
    before, after = ctx["window_counters"]
    total = after.get("slot_total", 0) - before.get("slot_total", 0)
    if total <= 0:
        return None
    return 100.0 * (after["slot_live"] - before["slot_live"]) / total
