"""Scheduler: window and summary entries the EVA layers read over the
context positions the rows hold, over the decode steps of the timed window
and the slot rows: what a layer reads of a context where a full cache would
read 100 (a row at position p reads ``p % window_size + 1`` window entries
and ``(p // window_size) * (window_size / chunk_size)`` summaries). The step
program's own count (``eva_attention``'s ``Count``, added over the layers
into its counter fetch and by the decode loop into the engine's counters
``program_eva_window_positions`` / ``program_eva_summary_positions`` /
``program_eva_context_positions``; a free slot row counts one position a
layer). None where the program keeps no such counters."""

NAMES = ("program_eva_window_positions", "program_eva_summary_positions",
         "program_eva_context_positions")


def read(ctx):
    before, after = ctx["window_counters"]
    if any(name not in after for name in NAMES):
        return None
    window, summary, context = (after[name] - before.get(name, 0)
                                for name in NAMES)
    if context <= 0:
        return None
    return 100.0 * (window + summary) / context
