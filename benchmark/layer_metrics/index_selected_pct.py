"""Scheduler: positions the indexers selected over positions cached, over
the decode steps of the timed window, the live rows and the ``full`` layers
(100 while a sequence holds no more than ``index_topk`` positions; what the
sparse attention leaves unread is the rest). The step program's own count
(``sparse_index``'s ``Count``, added over the layers into its counter fetch
and by the decode loop into the engine's counters ``program_index_selected``
/ ``program_index_cached``; a free slot row counts as one position of one).
None where the program keeps no such counters."""


def read(ctx):
    before, after = ctx["window_counters"]
    picked, cached = "program_index_selected", "program_index_cached"
    if cached not in after:
        return None
    held = after[cached] - before.get(cached, 0)
    if held <= 0:
        return None
    return 100.0 * (after[picked] - before.get(picked, 0)) / held
