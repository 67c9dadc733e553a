"""The two checkouts every manifest assertion of the cell tests holds on:
the repository as it stands, and a copy to which what a later PR brings for
a served configuration has been APPENDED: one more configuration, one more
one-chip serving cell, that cell's name at the end of ``serve_tokens_per_s``
and of every per-layer list ``glm52.serve.longdoc.sat`` is on, and one more
per-layer entry of its own, each pointing at a copy of a file that is there
(``glm-5.2.json``, ``serve.longdoc.sat.json``, ``indexer_ms.py``).

A later PR may add files and entries and edit nothing that is there. A test
that holds the manifest by place or by count (``[-1]``, ``len(...) ==``)
shuts it for that PR; run on both cases it goes red in the PR that brings
it. Use::

    @pytest.mark.parametrize("case", appended.CASES)
    def test_manifest_...(case, tmp_path):
        root = appended.root(case, tmp_path)
"""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CASES = ("standing", "appended")
LIKE = "glm52.serve.longdoc.sat"
CONFIG, TRAFFIC, CELL, METRIC = (
    "appended-config", "appended.longdoc.sat", "appended.serve.longdoc.sat",
    "appended_ms")


def root(case, tmp):
    """The root of the checkout of ``case``: the repository itself, or a
    copy of ``benchmark/`` and the manifest under ``tmp`` with the appended
    entries and their files."""
    if case == "standing":
        return ROOT
    tmp = str(tmp)
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    like = {w["name"]: w for w in m["workloads"]}[LIKE]
    config = {c["name"]: c for c in m["configs"]}[like["config"]]
    for folder, old, new in (
            ("configs", like["config"] + ".json", CONFIG + ".json"),
            ("traffic", like["traffic"] + ".json", TRAFFIC + ".json"),
            ("layer_metrics", "indexer_ms.py", METRIC + ".py")):
        shutil.copy(os.path.join(bench, folder, old),
                    os.path.join(bench, folder, new))
    m["configs"].append(dict(
        config, name=CONFIG, file="benchmark/configs/%s.json" % CONFIG))
    m["workloads"].append(dict(like, name=CELL, config=CONFIG,
                               traffic=TRAFFIC, why="tests"))
    for metric in m["end_to_end"] + m["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    entry = {x["name"]: x for x in m["per_layer"]}["indexer_ms"]
    m["per_layer"].append(dict(entry, name=METRIC, workloads=[CELL]))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(m, f, indent=1)
    return tmp
