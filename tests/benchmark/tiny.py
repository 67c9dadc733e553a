"""A temporary checkout for the benchmark's tests: a copy of ``benchmark/``
with tiny configurations, traffic mixes and a manifest ADDED to it as new
files (which is also how a later PR adds a cell: no file that is there is
edited). The tiny cells keep the layer patterns and cut every size, so a
whole run takes seconds on the CPU; their numbers mean nothing."""

import copy
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)


def make_checkout(tmp):
    """Returns (root of the copy, path of its manifest)."""
    tmp = str(tmp)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = _load("BENCHMARK.json")

    tbase = _load("benchmark", "configs", "transformer-base.json")
    tbase["name"] = "tiny-transformer"
    tbase["builder_args"].update(src_vocab=96, trg_vocab=96, d_model=32,
                                 d_ff=64, n_head=4, n_layer=1)
    for feed in ("src_ids", "trg_ids", "lbl_ids"):
        tbase["feeds"][feed]["high"] = 96
    # limits for the tiny sizes, from readings here on the CPU (sound runs
    # up to 0.013, the fp8 control from 0.042); the real limits come from
    # readings on the chip at the real sizes (PERF.md)
    tbase["limits"] = {"loss_rel_gap": 1e-3, "grad_norm_gap": 0.025,
                       "grad_large_leaf_mean_gap": 0.01, "change_norm_gap": 0.04}
    _dump(tbase, tmp, "benchmark", "configs", "tiny-transformer.json")

    resnet = _load("benchmark", "configs", "resnet50.json")
    resnet["name"] = "tiny-resnet50"
    resnet["builder_args"].update(class_num=10, image_shape=[3, 64, 64])
    resnet["feeds"]["img"]["shape"] = ["batch", 3, 64, 64]
    resnet["feeds"]["label"]["high"] = 10
    resnet["limits"] = {"loss_rel_gap": 1e-2, "grad_norm_gap": 0.08,
                        "grad_large_leaf_mean_gap": 0.02, "change_norm_gap": 0.08}
    _dump(resnet, tmp, "benchmark", "configs", "tiny-resnet50.json")

    def traffic(name, like, sizes, **more):
        mix = _load("benchmark", "traffic", like + ".json")
        mix["sizes"] = sizes
        mix.update(more)
        _dump(mix, tmp, "benchmark", "traffic", name + ".json")

    traffic("tiny.s16", "train.s256", {"batch": 8, "seq_len": 16},
            reference_row_block=4)
    traffic("tiny.i64", "train.i224", {"batch": 8})
    traffic("tiny.s16.dp4", "train.s256.dp4", {"batch": 8, "seq_len": 16},
            reference_row_block=4)

    cells = [("tiny.tbase", "tiny-transformer", "tiny.s16", 1),
             ("tiny.resnet", "tiny-resnet50", "tiny.i64", 1),
             ("tiny.tbase.dp4", "tiny-transformer", "tiny.s16.dp4", 4)]
    tiny = copy.deepcopy(manifest)
    tiny["configs"] = [
        {"name": "tiny-transformer", "source": "tests",
         "file": "benchmark/configs/tiny-transformer.json",
         "reduced": [], "why": "tests"},
        {"name": "tiny-resnet50", "source": "tests",
         "file": "benchmark/configs/tiny-resnet50.json",
         "reduced": [], "why": "tests"}]
    tiny["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "tests"}
        for n, c, t, k in cells]
    names = [c[0] for c in cells]
    # the tiny cells are training cells: they report what the real
    # training cells report, and nothing of another job kind
    trained = {w["name"] for w in manifest["workloads"]
               if _load("benchmark", "traffic",
                        w["traffic"] + ".json")["job"] == "train"}
    for group in ("end_to_end", "per_layer"):
        tiny[group] = [m for m in tiny[group] if "workloads" not in m
                       or trained & set(m["workloads"])]
        for metric in tiny[group]:
            if "workloads" in metric:
                metric["workloads"] = names
    path = os.path.join(tmp, "BENCHMARK.json")
    _dump(tiny, path)
    return tmp, path
