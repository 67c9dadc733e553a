"""GLM-5.2's two serving programs, as ``jobs/serve.py`` loads them: the
model is the program's own (``paddle_tpu/models/glm_dsa.py``); this file
hands the configuration's keys on."""

from paddle_tpu.models import glm_dsa


def step(dtype="bfloat16", **sizes):
    return glm_dsa.glm_dsa_step(dtype=dtype, **sizes)


def chunk(dtype="bfloat16", **sizes):
    return glm_dsa.glm_dsa_chunk(dtype=dtype, **sizes)
