"""EvaByte's two serving programs, as ``jobs/serve.py`` loads them: the
model is the program's own (``paddle_tpu/models/evabyte.py``); this file
hands the configuration's keys on."""

from paddle_tpu.models import evabyte


def step(dtype="bfloat16", **sizes):
    return evabyte.evabyte_step(dtype=dtype, **sizes)


def chunk(dtype="bfloat16", **sizes):
    return evabyte.evabyte_chunk(dtype=dtype, **sizes)
