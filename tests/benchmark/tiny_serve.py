"""A temporary checkout for the serving job's tests: a copy of
``benchmark/`` with a tiny OPT configuration, tiny traffic mixes and a
manifest of serving cells ADDED to it as new files (``tiny.py`` does the
same for the training cells). The tiny cells keep the block and the job and
cut every size, so a whole run takes seconds on the CPU; their numbers mean
nothing."""

import copy
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SIZES = dict(vocab_size=96, hidden_size=32, ffn_dim=64,
             num_attention_heads=4, num_hidden_layers=2,
             max_position_embeddings=64)
CELLS = {"tiny.serve.chat": "tiny.chat", "tiny.serve.chat.sat": "tiny.sat"}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)


def make_checkout(tmp, limits=None, served_dtype="bfloat16"):
    """Returns (root of the copy, path of its manifest)."""
    tmp = str(tmp)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = _load("benchmark", "configs", "opt-1.3b.json")
    config.update(SIZES, name="tiny-opt", served_dtype=served_dtype)
    # limits for the tiny sizes, from readings here on the CPU (bfloat16
    # runs up to 0.02 / 0.002, the fp8 control from 0.09 / 0.016); the real
    # limits come from readings on the chip at the real sizes (PERF.md)
    config["limits"] = limits or {"token_gap_max": 0.06,
                                  "token_gap_mean": 0.006}
    _dump(config, tmp, "benchmark", "configs", "tiny-opt.json")

    for name, like, arrivals in (
            ("tiny.chat", "serve.chat",
             {"kind": "poisson", "rate_per_s": 6.0, "ramp_s": 0.5,
              "tail_s": 1.0}),
            ("tiny.sat", "serve.chat.sat",
             {"kind": "backlog", "requests": 3000, "block": 20,
              "open_after": 40})):
        mix = _load("benchmark", "traffic", like + ".json")
        mix["engine"].update(ladder=[4], seq_ladder=[64],
                             prefill_ladder=[8, 16], max_queue_depth=4096)
        mix["lengths"] = {
            "prompt": {"median": 12, "sigma": 0.6, "min": 3, "max": 40},
            "answer": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
        mix["arrivals"] = arrivals
        mix["check"] = {"sample": 6}
        _dump(mix, tmp, "benchmark", "traffic", name + ".json")

    manifest = _load("BENCHMARK.json")
    real = [c["name"] for c in manifest["workloads"]
            if c["config"] == "opt-1.3b"]
    tiny = copy.deepcopy(manifest)
    tiny["configs"] = [{"name": "tiny-opt", "source": "tests",
                        "file": "benchmark/configs/tiny-opt.json",
                        "reduced": [], "why": "tests"}]
    tiny["workloads"] = [
        {"name": n, "config": "tiny-opt", "traffic": t, "chips": 1,
         "why": "tests"} for n, t in CELLS.items()]
    # a tiny cell reports what the real cell of its traffic reports
    swap = dict(zip(sorted(real), sorted(CELLS)))
    kept = {}
    for group in ("end_to_end", "per_layer"):
        kept[group] = []
        for metric in tiny[group]:
            if "workloads" in metric:
                cells = [swap[c] for c in metric["workloads"] if c in swap]
                if not cells:
                    continue
                metric["workloads"] = cells
            kept[group].append(metric)
    tiny.update(kept)
    path = os.path.join(tmp, "BENCHMARK.json")
    _dump(tiny, path)
    return tmp, path
