"""Entry points: seconds from process start until ``jax.devices()`` has
answered (interpreter, imports, reaching the chip). Host clock; read from the
harness, so every job kind reports it."""


def read(ctx):
    run = ctx["run"]
    return run.devices_answered - run.process_start
