"""GLM-4.7-Flash's two serving programs, as ``jobs/serve.py`` loads them: the
model is the program's own (``paddle_tpu/models/glm_lite.py``); this file
hands the configuration's keys on."""

from paddle_tpu.models import glm_lite


def step(dtype="bfloat16", **sizes):
    return glm_lite.glm_lite_step(dtype=dtype, **sizes)


def chunk(dtype="bfloat16", **sizes):
    return glm_lite.glm_lite_chunk(dtype=dtype, **sizes)
