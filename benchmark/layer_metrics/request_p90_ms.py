"""Scheduler: milliseconds from a request's SCHEDULED arrival to its whole
answer at the client, 90th percentile (nearest rank upward) over the
requests due in the timed window. A window holds 22 of them, so this is one
request's fate (the 20th) and is recorded, not judged: ``token_ms_mean``
takes every request. Host clock."""


def read(ctx):
    return ctx["client"].get("request_p90_ms")
