"""A temporary checkout for the EvaByte cell's tests: a copy of
``benchmark/`` with a tiny twin of the configuration, a tiny backlog of
byte prompts and a manifest of the one cell ADDED to it as new files
(``tiny_mimo.py`` does the same for MiMo-V2-Flash). The twin keeps the block
(EVA attention over a window cache and a summary cache, the unit-offset
norms, the float32 stream and logits, the dense gated feed-forward), the
job and every metric of the real cell, and cuts every size: a window of 32
positions in chunks of 4, so a prompt of 70 bytes crosses two window
boundaries and a whole run takes seconds on the CPU; its numbers mean
nothing."""

import copy
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REAL, CELL = "evabyte.serve.bytes.sat", "tiny.evabyte.bytes.sat"

SIZES = dict(
    vocab_size=64, hidden_size=32, num_attention_heads=4,
    num_key_value_heads=4, intermediate_size=48, window_size=32,
    chunk_size=4, max_position_embeddings=128, max_seq_length=128,
    num_hidden_layers=2, layers_held=[0, 2])


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)


def tiny_config(served_dtype="bfloat16", limits=None):
    config = _load("benchmark", "configs", "evabyte-6.5b.json")
    config.update(SIZES, name="tiny-evabyte", served_dtype=served_dtype)
    # limits for the tiny sizes, from readings here on the CPU; the real
    # limits come from readings on the chip at the real sizes (PERF.md)
    config["limits"] = limits or {"token_gap_max": 0.2,
                                  "token_gap_mean": 0.03}
    return config


# builders whose STEP program hands a broken summary cache on. ``zeroed``:
# layer 0's summary keys come back as zeros after every step, so every
# earlier window scores 0 a chunk whatever it held. ``shifted``: a step's
# summaries land one entry late (chunk c at entry c + 1), so a query reads
# the chunks 0 .. n-2 where it should read 1 .. n-1 shifted by one place.
# What a fault in the derived cache looks like to the check.
BROKEN = '''from paddle_tpu import layers
from paddle_tpu.models import evabyte

HOW = %r


def step(dtype="bfloat16", **sizes):
    fetch, spec = evabyte.evabyte_step(dtype=dtype, **sizes)
    feed = [c for c in spec["cache_feeds"] if c.get("stride")][0]
    block = fetch[0].block
    good = block.var(feed["fetch"])
    if HOW == "zeroed":
        bad = layers.scale(good, scale=0.0)
    else:
        bad = layers.concat(
            [layers.slice(good, [1], [0], [1]),
             layers.slice(good, [1], [0], [-1])], axis=1)
    fetch = [bad if v.name == good.name else v for v in fetch]
    feed["fetch"] = bad.name
    return fetch, spec


def chunk(dtype="bfloat16", **sizes):
    return evabyte.evabyte_chunk(dtype=dtype, **sizes)
'''


def make_checkout(tmp, limits=None, served_dtype="bfloat16", broken=None):
    """Returns (root of the copy, path of its manifest). ``broken``:
    ``"zeroed"`` or ``"shifted"``, the fault the step program carries."""
    tmp = str(tmp)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = tiny_config(served_dtype, limits)
    if broken:
        config["builder"] = "benchmark/builders/tiny_evabyte_broken.py"
        with open(os.path.join(tmp, config["builder"]), "w") as f:
            f.write(BROKEN % broken)
    _dump(config, tmp, "benchmark", "configs", "tiny-evabyte.json")
    mix = _load("benchmark", "traffic", "serve.bytes.sat.json")
    mix["engine"].update(ladder=[4], seq_ladder=[128], prefill_ladder=[16],
                         max_queue_depth=4096)
    # every prompt past the first window, the longest past the second
    mix["lengths"] = {
        "prompt": {"median": 50, "sigma": 0.4, "min": 34, "max": 100},
        "answer": {"median": 8, "sigma": 0.5, "min": 3, "max": 16}}
    mix["arrivals"] = {"kind": "backlog", "requests": 2000, "block": 8,
                       "open_after": 16}
    mix["check"] = {"sample": 4}
    _dump(mix, tmp, "benchmark", "traffic", "tiny.bytes.sat.json")

    tiny = copy.deepcopy(_load("BENCHMARK.json"))
    tiny["configs"] = [{"name": "tiny-evabyte", "source": "tests",
                        "file": "benchmark/configs/tiny-evabyte.json",
                        "reduced": [], "why": "tests"}]
    tiny["workloads"] = [{"name": CELL, "config": "tiny-evabyte",
                          "traffic": "tiny.bytes.sat", "chips": 1,
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
