"""Set-up: seconds the warm-up of the ladders took (one real request a
chunk rung: executables made or loaded from the persistent cache, the
first feed of the caches from the host). Host clock."""


def read(ctx):
    return ctx.get("warmup_s")
