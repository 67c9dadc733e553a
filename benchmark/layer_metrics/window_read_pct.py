"""Scheduler: positions a window layer read over positions a full layer
read, over the decode steps of the timed window and the slot rows (what the
rings save: a window layer reads ``min(sliding_window, held)`` positions of
a sequence, a full layer all it holds). The step program's own count
(``cached_attention``'s ``Count``, added over the layers of each kind into
its counter fetch and by the decode loop into the engine's counters
``program_attn_window_positions`` / ``program_attn_full_positions``, each
divided here by the number of held layers of its kind; a free slot row
counts one position a layer). None where the program keeps no such
counters."""

import os

from benchmark import harness


def read(ctx):
    before, after = ctx["window_counters"]
    full, window = ("program_attn_full_positions",
                    "program_attn_window_positions")
    if full not in after or window not in after:
        return None
    count = harness.load_module(os.path.join(
        harness.HERE, "ops_count_mimo_v2.py"))
    config = ctx["run"].config
    n_full = len(count.full_layers(config))
    n_window = len(count.window_layers(config))
    read_full = after[full] - before.get(full, 0)
    if not n_full or not n_window or read_full <= 0:
        return None
    return 100.0 * ((after[window] - before.get(window, 0)) / n_window) \
        / (read_full / n_full)
