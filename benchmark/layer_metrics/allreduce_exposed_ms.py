"""Mesh: the part of ``allreduce_ms`` during which no other operation runs
on that chip: communication that the step waits for."""


def read(ctx):
    found = ctx["trace"].collective_seconds("all-reduce")
    if found is None:
        return None
    return found[1] / ctx["trace"].steps * 1e3
