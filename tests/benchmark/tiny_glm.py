"""A temporary checkout for the GLM-5.2 cell's tests: a copy of
``benchmark/`` with a tiny twin of the configuration, a tiny long-document
backlog and a manifest of the one cell ADDED to it as new files
(``tiny_serve.py`` does the same for the OPT cells). The twin keeps the
block (latent cache, indexer with a shared selection, routed experts, the
held layers, experts and vocabulary rows), the job and every metric of the
real cell, and cuts every size, so a whole run takes seconds on the CPU; its
numbers mean nothing."""

import copy
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REAL, CELL = "glm52.serve.longdoc.sat", "tiny.glm.longdoc.sat"

# layers 2-6 of a 7-layer pattern: dense + full, three shared, sparse + full
SIZES = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=4, q_lora_rank=16,
    kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=8,
    index_n_heads=2, index_head_dim=8, index_topk=8, intermediate_size=48,
    moe_intermediate_size=16, n_routed_experts=8, num_experts_per_tok=2,
    max_position_embeddings=128, num_hidden_layers=5,
    indexer_types=["full"] * 3 + ["shared"] * 3 + ["full"],
    mlp_layer_types=["dense"] * 3 + ["sparse"] * 4,
    layers_held=[2, 5], experts_held=[0, 4])


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)


def tiny_config(served_dtype="bfloat16", limits=None):
    config = _load("benchmark", "configs", "glm-5.2.json")
    config.update(SIZES, name="tiny-glm", served_dtype=served_dtype)
    # limits for the tiny sizes, from readings here on the CPU (bfloat16
    # runs up to 0.05 / 0.008); the real limits come from readings on the
    # chip at the real sizes (PERF.md)
    config["limits"] = limits or {"token_gap_max": 0.15,
                                  "token_gap_mean": 0.02}
    return config


def make_checkout(tmp, limits=None, served_dtype="bfloat16"):
    """Returns (root of the copy, path of its manifest)."""
    tmp = str(tmp)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    _dump(tiny_config(served_dtype, limits), tmp, "benchmark", "configs",
          "tiny-glm.json")
    mix = _load("benchmark", "traffic", "serve.longdoc.sat.json")
    mix["engine"].update(ladder=[4], seq_ladder=[64], prefill_ladder=[8],
                         max_queue_depth=4096)
    mix["lengths"] = {
        "prompt": {"median": 20, "sigma": 0.5, "min": 10, "max": 44},
        "answer": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
    mix["arrivals"] = {"kind": "backlog", "requests": 2000, "block": 8,
                       "open_after": 16}
    mix["check"] = {"sample": 4}
    _dump(mix, tmp, "benchmark", "traffic", "tiny.longdoc.sat.json")

    tiny = copy.deepcopy(_load("BENCHMARK.json"))
    tiny["configs"] = [{"name": "tiny-glm", "source": "tests",
                        "file": "benchmark/configs/tiny-glm.json",
                        "reduced": [], "why": "tests"}]
    tiny["workloads"] = [{"name": CELL, "config": "tiny-glm",
                          "traffic": "tiny.longdoc.sat", "chips": 1,
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
