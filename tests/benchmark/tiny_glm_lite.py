"""A temporary checkout for the GLM-4.7-Flash cell's tests: a copy of
``benchmark/`` with a tiny twin of the configuration, a tiny reasoning
backlog and a manifest of the one cell ADDED to it as new files
(``tiny_glm.py`` does the same for GLM-5.2). The twin keeps the block (latent
caches read whole, one dense layer and then expert layers with every expert
held, the prediction module with a cache of its own, the step that verifies
a draft and drafts the next), the job and every metric of the real cell, and
cuts every size, so a whole run takes seconds on the CPU; its numbers mean
nothing."""

import copy
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REAL, CELL = "glm47flash.serve.reason.sat", "tiny.glm47.reason.sat"

# one dense layer, two expert layers with all 8 experts, the module at 5
SIZES = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=4, q_lora_rank=16,
    kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=8,
    intermediate_size=48, moe_intermediate_size=16, n_routed_experts=8,
    num_experts_per_tok=2, max_position_embeddings=128,
    num_hidden_layers=3, nextn_layer=5, layers_held=[0, 3],
    experts_held=[0, 8])


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)


def tiny_config(served_dtype="bfloat16", limits=None):
    config = _load("benchmark", "configs", "glm-4.7-flash.json")
    config.update(SIZES, name="tiny-glm47", served_dtype=served_dtype)
    # limits for the tiny sizes, from readings here on the CPU; the real
    # limits come from readings on the chip at the real sizes (PERF.md)
    config["limits"] = limits or {"token_gap_max": 0.15,
                                  "token_gap_mean": 0.02}
    return config


# a builder whose STEP program lets every draft stand: it feeds the accept
# rule the draft in the place of the model's own token after lane 0, so a
# wrong draft is emitted as if the model had chosen it. What a step that
# accepts a wrong draft looks like to the check.
ACCEPTS_ANYTHING = '''import jax.numpy as jnp

from paddle_tpu.core import op_registry
from paddle_tpu.models import glm_lite

SOUND = op_registry.OP_IMPLS["self_draft_accept"]


def _credulous(env, op):
    SOUND(env, op)
    name = op.output("Yield").name
    tok = op_registry.get(env, op.input("Tok")).astype(jnp.int32)
    out = env[name]
    # two tokens a row: the draft itself, then what the model put after it
    env[name] = jnp.stack([jnp.full_like(out[:, 0], 2), tok[:, 1],
                           out[:, 2], out[:, 3]], axis=1)


def step(dtype="bfloat16", **sizes):
    op_registry.OP_IMPLS["self_draft_accept"] = _credulous
    return glm_lite.glm_lite_step(dtype=dtype, **sizes)


def chunk(dtype="bfloat16", **sizes):
    return glm_lite.glm_lite_chunk(dtype=dtype, **sizes)
'''


def restore_accept_rule():
    """Put the sound accept rule back once a run of ``ACCEPTS_ANYTHING``
    is over (the op registry is the process's)."""
    import sys

    from paddle_tpu.core import op_registry

    for name, module in list(sys.modules.items()):
        if name.startswith("benchmark_file_") and hasattr(module, "SOUND") \
                and hasattr(module, "_credulous"):
            op_registry.OP_IMPLS["self_draft_accept"] = module.SOUND


def make_checkout(tmp, limits=None, served_dtype="bfloat16", builder=None):
    """Returns (root of the copy, path of its manifest). ``builder``: the
    source of a builder to serve in the real one's place."""
    tmp = str(tmp)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = tiny_config(served_dtype, limits)
    if builder is not None:
        with open(os.path.join(tmp, "benchmark", "builders",
                               "tiny_broken.py"), "w") as f:
            f.write(builder)
        config["builder"] = "benchmark/builders/tiny_broken.py"
    _dump(config, tmp, "benchmark", "configs", "tiny-glm47.json")
    mix = _load("benchmark", "traffic", "serve.reason.sat.json")
    mix["engine"].update(ladder=[4], seq_ladder=[64], prefill_ladder=[8],
                         max_queue_depth=4096)
    mix["lengths"] = {
        "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 30},
        "answer": {"median": 12, "sigma": 0.5, "min": 4, "max": 30}}
    mix["arrivals"] = {"kind": "backlog", "requests": 2000, "block": 8,
                       "open_after": 16}
    mix["check"] = {"sample": 4}
    _dump(mix, tmp, "benchmark", "traffic", "tiny.reason.sat.json")

    tiny = copy.deepcopy(_load("BENCHMARK.json"))
    tiny["configs"] = [{"name": "tiny-glm47", "source": "tests",
                        "file": "benchmark/configs/tiny-glm47.json",
                        "reduced": [], "why": "tests"}]
    tiny["workloads"] = [{"name": CELL, "config": "tiny-glm47",
                          "traffic": "tiny.reason.sat", "chips": 1,
                          "why": "tests"}]
    # the tiny cell reports what the real cell reports
    for group in ("end_to_end", "per_layer"):
        kept = []
        for metric in tiny[group]:
            if "workloads" in metric:
                if REAL not in metric["workloads"]:
                    continue
                metric["workloads"] = [CELL]
            kept.append(metric)
        tiny[group] = kept
    path = os.path.join(tmp, "BENCHMARK.json")
    _dump(tiny, path)
    return tmp, path
