"""``chunk_attn_ms_per_ktok`` (PR 48): the manifest's entry held BY NAME on
both cases of ``appended.py``, and its reader on a recorded trace of one
chunk run between two steps, on a window that ingested nothing, on a
program whose chunk has no such scope and on a trace with no device
events."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import appended  # noqa: E402
from benchmark import harness, serve_trace  # noqa: E402

NAME = "chunk_attn_ms_per_ktok"
CELL = "mimo2flash.serve.mixedlen.sat"


def _reader():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


def _recorded(chunk_scopes):
    """A step, a chunk run, a step: the chunk executable's two fusions ran
    200 and 10 ns under ``chunk_scopes``."""
    p = serve_trace.PREFIX
    host = [(p + "decode.step", 0, 100), (p + "prefill.chunk", 110, 20),
            (p + "decode.step", 140, 460)]
    device = [("fusion.1", 10, 40), ("fusion.2", 50, 10),      # step 1
              ("fusion.1", 150, 200), ("fusion.2", 350, 10),   # the chunk
              ("fusion.1", 400, 50), ("fusion.2", 450, 30)]    # step 2
    modules = [(10, 85), (150, 360), (400, 590)]

    def hlo(*scopes):
        return "\n".join(
            '%%fusion.%d = f32[] fusion(), metadata={op_name="jit(s)/%s"}'
            % (i + 1, scope) for i, scope in enumerate(scopes))

    text = {"step": hlo("mul/dot_general",
                        "cached_attention/attn.full/dot_general"),
            "chunk": hlo(*chunk_scopes)}
    return serve_trace.ServeTrace([device], host, text, [modules])


SCOPED = ("cached_attention_chunk/attn.full/cache_chunk.fwd",
          "cached_attention_chunk/attn.window/dot_general")


def _ctx(tokens, chunk_scopes=SCOPED):
    counters = ({"prefill_tokens": 1000.0},
                {"prefill_tokens": 1000.0 + tokens})
    return {"trace": _recorded(chunk_scopes), "profile_counters": counters,
            "window_counters": counters}


@pytest.mark.parametrize("case", appended.CASES)
def test_manifest_holds_the_chunks_attention_metric_by_name(case, tmp_path):
    """Found by name wherever it stands: the cell's alone, of the layer
    ``kernels`` that the manifest already names, with its reader's file."""
    root = appended.root(case, tmp_path)
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    entry = {x["name"]: x for x in m["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert entry["layer"] in {x["layer"] for x in m["per_layer"]
                              if x["name"] != NAME}
    assert os.path.exists(os.path.join(
        root, "benchmark", "layer_metrics", NAME + ".py"))


@pytest.mark.parametrize("tokens,reads", [(512.0, 210e-6 / 0.512),
                                          (0.0, None)],
                         ids=["ingested", "nothing_ingested"])
def test_chunk_attention_reader_on_a_recorded_trace(tokens, reads):
    """The one chunk run's 200 ns under ``attn.full`` and 10 under
    ``attn.window``, for each 1000 of the 512 prompt tokens it ingested;
    nothing where no token was ingested."""
    got = _reader().read(_ctx(tokens))
    assert got == (reads if reads is None else pytest.approx(reads))


def test_chunk_attention_reader_finds_nothing_where_there_is_nothing():
    """A chunk program with no such scope, and a trace with no device
    events (a machine without a chip): the reader returns None and does
    not raise, so the line leaves the metric out."""
    ctx = _ctx(512.0, ("mul/dot_general", "add/reduce"))
    assert _reader().read(ctx) is None
    ctx = _ctx(512.0)
    ctx.update(trace=serve_trace.NoDeviceServeTrace([]))
    assert _reader().read(ctx) is None
