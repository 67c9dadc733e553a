"""Mesh: device milliseconds a step during which an all-reduce is under way
on the first chip (synchronous events, or start to done of asynchronous
ones), from the device trace."""


def read(ctx):
    found = ctx["trace"].collective_seconds("all-reduce")
    if found is None:
        return None
    return found[0] / ctx["trace"].steps * 1e3
