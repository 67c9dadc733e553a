"""Scheduler: positions of the key/value cache that live sequences hold,
over the positions the slot table reserves (slots x the top context rung),
as the mean over the quanta of the timed window. From the engine's counters
(tokens sampled, prompt tokens ingested) less what the answered requests
held, by the client's record (``jobs/serve.py``, ``Ticks.live_positions``).
``memory_peak_bytes`` counts the reserved pool; this says how much of it the
traffic fills. Program counter."""


def read(ctx):
    at, live = ctx["ticks"].live_positions(ctx["requests"])
    w0, w1 = ctx["window"]
    inside = (at >= w0) & (at <= w1)
    if not inside.any():
        return None
    return 100.0 * float(live[inside].mean()) / ctx["positions"]
