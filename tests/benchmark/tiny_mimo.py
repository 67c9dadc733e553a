"""A temporary checkout for the MiMo-V2-Flash cell's tests: a copy of
``benchmark/`` with a tiny twin of the configuration, a tiny mixed-length
backlog and a manifest of the one cell ADDED to it as new files
(``tiny_glm.py`` does the same for GLM-5.2). The twin keeps the block (full
and window layers in the published pattern, grouped heads of two counts,
keys wider than values, the sink, a ring of ``sliding_window`` positions,
routed experts, the held layers, experts and vocabulary rows), the job and
every metric of the real cell, and cuts every size, so a whole run takes
seconds on the CPU; its numbers mean nothing."""

import copy
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REAL, CELL = "mimo2flash.serve.mixedlen.sat", "tiny.mimo.mixedlen.sat"

# layer 0 (full, dense) and layers 6-11 (window x5, then full) of the
# published 12-layer prefix of the pattern; a window and a ring of 8
SIZES = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=8,
    num_key_value_heads=2, head_dim=12, v_head_dim=8,
    swa_num_attention_heads=8, swa_num_key_value_heads=4, swa_head_dim=12,
    swa_v_head_dim=8, intermediate_size=48, moe_intermediate_size=16,
    n_routed_experts=8, num_experts_per_tok=2, sliding_window=8,
    sliding_window_size=8, attention_chunk_size=8,
    max_position_embeddings=128, num_hidden_layers=7,
    layers_held=[0, 6, 7, 8, 9, 10, 11], experts_held=[0, 4])


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)


def tiny_config(served_dtype="bfloat16", limits=None):
    config = _load("benchmark", "configs", "mimo-v2-flash.json")
    config.update(SIZES, name="tiny-mimo", served_dtype=served_dtype)
    # limits for the tiny sizes, from readings here on the CPU; the real
    # limits come from readings on the chip at the real sizes (PERF.md)
    config["limits"] = limits or {"token_gap_max": 0.15,
                                  "token_gap_mean": 0.02}
    return config


# a builder whose STEP program hands the first window layer's key ring back
# as it came in: the token a step ingests never reaches that ring, so the
# next step's window misses it. What a ring fault looks like to the check.
BROKEN_RING = '''from paddle_tpu import layers
from paddle_tpu.models import mimo_v2


def step(dtype="bfloat16", **sizes):
    fetch, spec = mimo_v2.mimo_v2_step(dtype=dtype, **sizes)
    ring = [c for c in spec["cache_feeds"] if c.get("capacity")][0]
    block = fetch[0].block
    stale = layers.scale(block.var(ring["feed"]), scale=1.0)
    fetch = [stale if v.name == ring["fetch"] else v for v in fetch]
    ring["fetch"] = stale.name
    return fetch, spec


def chunk(dtype="bfloat16", **sizes):
    return mimo_v2.mimo_v2_chunk(dtype=dtype, **sizes)
'''


def make_checkout(tmp, limits=None, served_dtype="bfloat16",
                  broken_ring=False):
    """Returns (root of the copy, path of its manifest)."""
    tmp = str(tmp)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = tiny_config(served_dtype, limits)
    if broken_ring:
        config["builder"] = "benchmark/builders/tiny_mimo_broken.py"
        with open(os.path.join(tmp, config["builder"]), "w") as f:
            f.write(BROKEN_RING)
    _dump(config, tmp, "benchmark", "configs", "tiny-mimo.json")
    mix = _load("benchmark", "traffic", "serve.mixedlen.sat.json")
    mix["engine"].update(ladder=[4], seq_ladder=[64], prefill_ladder=[16],
                         max_queue_depth=4096)
    mix["lengths"] = {
        "prompt": {"median": 20, "sigma": 0.5, "min": 10, "max": 44},
        "answer": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
    mix["arrivals"] = {"kind": "backlog", "requests": 2000, "block": 8,
                       "open_after": 16}
    mix["check"] = {"sample": 4}
    _dump(mix, tmp, "benchmark", "traffic", "tiny.mixedlen.sat.json")

    tiny = copy.deepcopy(_load("BENCHMARK.json"))
    tiny["configs"] = [{"name": "tiny-mimo", "source": "tests",
                        "file": "benchmark/configs/tiny-mimo.json",
                        "reduced": [], "why": "tests"}]
    tiny["workloads"] = [{"name": CELL, "config": "tiny-mimo",
                          "traffic": "tiny.mixedlen.sat", "chips": 1,
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
