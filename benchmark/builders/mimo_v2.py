"""MiMo-V2-Flash's two serving programs, as ``jobs/serve.py`` loads them:
the model is the program's own (``paddle_tpu/models/mimo_v2.py``); this file
hands the configuration's keys on."""

from paddle_tpu.models import mimo_v2


def step(dtype="bfloat16", **sizes):
    return mimo_v2.mimo_v2_step(dtype=dtype, **sizes)


def chunk(dtype="bfloat16", **sizes):
    return mimo_v2.mimo_v2_chunk(dtype=dtype, **sizes)
