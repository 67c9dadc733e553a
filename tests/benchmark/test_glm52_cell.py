"""The GLM-5.2 cell: the manifest's entries and lists, the configuration's
parameter count from the program's own shapes, the traffic file's fixed
trace, a whole rehearsal of a tiny twin on the CPU (float32 declared: the
numbers mean nothing, the control flow and the checks are the real ones),
each new reader on a recorded trace and recorded counters, and the operation
counts against hand counts."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import appended  # noqa: E402
import tiny_glm  # noqa: E402
from benchmark import harness, ops_count_glm_dsa, serve_trace  # noqa: E402
from benchmark.jobs import serve, serve_traffic  # noqa: E402

CELL = "glm52.serve.longdoc.sat"
NEW = ("indexer_ms", "indexer_topk_ms", "latent_attn_ms",
       "latent_attn_roofline", "moe_ms.serve", "moe_row_fill_pct.serve",
       "index_selected_pct", "prefill_attn_ms_per_ktok")
JOINED = ("first_step_s", "trace_s", "lower_s", "backend_compile_s",
          "decode_step_ms", "predict_ms", "sample_deliver_ms",
          "prefill_ms_per_ktok", "batch_occupancy_pct", "cache_live_pct",
          "server_ttft_mean_ms", "server_tpot_mean_ms", "decode_device_ms",
          "decode_roofline", "cache_write_ms", "decode_matmul_ms",
          "warmup_s", "executables")


def _bench(*parts):
    return os.path.join(ROOT, "benchmark", *parts)


def _config():
    return harness.load_json(_bench("configs", "glm-5.2.json"))


def _reader(name):
    return harness.load_module(_bench("layer_metrics", name + ".py"))


# -- the manifest and the configuration ----------------------------------------

@pytest.mark.parametrize("case", appended.CASES)
def test_manifest_holds_the_cell_its_configuration_and_its_metrics(
        case, tmp_path):
    """Every entry is found by name: the test says nothing of how many
    configurations, cells or metrics there are, nor where GLM-5.2's stand
    among them, so a later PR appends its own (``appended.py``)."""
    root = appended.root(case, tmp_path)
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    entry = {c["name"]: c for c in m["configs"]}["glm-5.2"]
    assert len(entry["source"]) <= 200
    assert entry["source"].endswith("zai-org/GLM-5.2/blob/main/config.json")
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("glm-5.2", "serve.longdoc.sat", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert CELL not in e2e["token_ms_mean"]["workloads"]
    names = [x["name"] for x in m["per_layer"]]
    layers = dict(zip(names, m["per_layer"]))
    # GLM-5.2's eight, in their order among themselves, wherever they stand
    assert [n for n in names if n in NEW] == list(NEW)
    for name in NEW:
        assert CELL in layers[name]["workloads"]
        assert layers[name]["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(
            root, "benchmark", "layer_metrics", name + ".py"))
    for name in JOINED:
        assert CELL in layers[name]["workloads"]
    assert CELL not in layers["cached_attn_ms"]["workloads"]
    assert layers["latent_attn_roofline"]["unit"] == "%"


def test_configuration_holds_the_sources_keys_and_the_cut():
    body = _config()
    m = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in m["configs"]}["glm-5.2"]
    assert body["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "experts_held",
        "vocab_size", "num_nextn_predict_layers"]
    # no width is cut
    assert (body["hidden_size"], body["num_attention_heads"],
            body["q_lora_rank"], body["kv_lora_rank"],
            body["qk_nope_head_dim"], body["qk_rope_head_dim"],
            body["v_head_dim"], body["index_n_heads"],
            body["index_head_dim"], body["index_topk"],
            body["intermediate_size"], body["moe_intermediate_size"],
            body["n_routed_experts"], body["num_experts_per_tok"]) == (
        6144, 64, 2048, 512, 192, 64, 256, 32, 128, 2048, 12288, 2048, 256,
        8)
    assert (body["num_hidden_layers"], body["first_k_dense_replace"],
            body["vocab_size"], body["num_nextn_predict_layers"],
            body["experts_held"], body["layers_held"]) == (
        5, 1, 19360, 0, [0, 16], [2, 5])
    assert body["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "vocab_size": 154880, "num_nextn_predict_layers": 1,
        "n_routed_experts": 256}
    first, count = body["layers_held"]
    assert body["indexer_types"][first:first + count] == [
        "full", "shared", "shared", "shared", "full"]
    assert body["mlp_layer_types"][first:first + count] == [
        "dense"] + ["sparse"] * 4
    assert len(body["indexer_types"]) == len(body["mlp_layer_types"]) == 78
    assert body["served_dtype"] == "bfloat16"
    assert set(body["limits"]) == {"token_gap_max", "token_gap_mean"}
    assert set(body["builder_keys"]) <= set(body)
    assert {"head_dim", "shared layers hold no indexer"} <= set(
        body["assumed"])


def test_parameters_counted_from_the_programs_shapes():
    import paddle_tpu as fluid
    from benchmark import seeded

    body = _config()
    builder = harness.load_module(os.path.join(ROOT, body["builder"]))
    args = {k: body[k] for k in body["builder_keys"]}
    counts = {}
    for kind in ("step", "chunk"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            fetch, spec = getattr(builder, kind)(dtype="bfloat16", **args)
        leaves = {p.name: tuple(p.shape)
                  for p in main.global_block().all_parameters()}
        for name in leaves:           # every leaf has an init rule
            seeded.init_kind(name, body["init"])
        counts[kind] = leaves
        feeds = {c["feed"]: c["tail"] for c in spec["cache_feeds"]}
        assert feeds == {"cache_latent_2": [576], "cache_index_2": [128],
                         "cache_latent_3": [576], "cache_latent_4": [576],
                         "cache_latent_5": [576], "cache_latent_6": [576],
                         "cache_index_6": [128]}
    step = counts["step"]
    # the selection bias is a buffer in the source, and is not counted
    total = sum(int(np.prod(s)) for n, s in step.items()
                if not n.endswith("router_bias"))
    assert total == body["parameters"] == 3881516032
    assert ops_count_glm_dsa.parameter_count(body) == 3881516032
    assert sum(n.endswith("router_bias") for n in step) == 4
    assert seeded.init_kind("glm.l3.moe.router_bias", body["init"]) == "bias"
    # the chunk program ingests: the step's leaves less the head's two
    assert set(step) - set(counts["chunk"]) == {"glm.norm.w", "glm.lm_head"}
    assert step["glm.l2.attn.kv_b"] == (512, 64 * (192 + 256))
    assert step["glm.l6.indexer.wq_b"] == (2048, 32 * 128)
    assert "glm.l3.indexer.wq_b" not in step
    assert step["glm.l3.moe.experts.gate"] == (16, 2048, 6144)
    assert step["glm.l3.moe.router"] == (6144, 256)


def test_traffic_file_is_the_issues_fixed_trace():
    mix = harness.load_json(_bench("traffic", "serve.longdoc.sat.json"))
    assert mix["job"] == "serve" and mix["shape_seed"] == 20260929
    assert mix["arrivals"] == {"kind": "backlog", "requests": 96,
                               "block": 8, "open_after": 8}
    engine = mix["engine"]
    assert engine["ladder"] == [8] and engine["seq_ladder"] == [12288]
    assert 1 <= len(engine["prefill_ladder"]) <= 3
    assert all(256 <= k <= 2048 for k in engine["prefill_ladder"])
    assert mix["check"] == {"sample": 4}
    requests = serve_traffic.schedule(mix, 19360, 2 ** 31 + 5, 10.0)
    assert len(requests) == 96
    block = requests[:8]
    assert sorted(len(r.prompt) for r in block) == [
        2304, 2304, 2909, 3669, 4573, 5767, 7622, 11988]
    assert sum(len(r.prompt) for r in block) == 41136
    answers = sorted(r.max_new for r in block)
    assert (answers[0], answers[-1]) == (19, 86)
    # every block is the same requests; every one fits the context rung
    assert [len(r.prompt) for r in requests[8:16]] == [
        len(r.prompt) for r in block]
    assert max(r.positions for r in requests) <= 12288
    assert all(int(r.prompt.max()) < 19360 for r in block)
    assert min(len(r.prompt) for r in requests) > 2048   # the selection bites


# -- a whole rehearsal of the tiny twin -----------------------------------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_glm.make_checkout(
        tmp_path_factory.mktemp("glm"), served_dtype="float32",
        limits={"token_gap_max": 1e-3, "token_gap_mean": 1e-4})


def test_traced_rehearsal_of_the_tiny_twin_is_correct(checkout):
    run = harness.Run(checkout[1], tiny_glm.CELL, 2 ** 31 + 11, 1.0, 1,
                      True, time.time())
    result = serve.run(run)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["answered"] >= 6
    rows = {r["name"]: r for r in result["compared"]}
    assert set(rows) == {"token_gap_max", "token_gap_mean",
                         "tokens_miscounted"}
    assert rows["tokens_miscounted"]["value"] == 0
    m = result["metrics"]      # a traced run's line: the layers' metrics
    # one step and one chunk executable, and no second staging of either
    # when the experts' counters first appear in the scope
    assert m["executables"] == 2
    # the program's own counters: prompts of 10-44 tokens against a top-k of
    # 8 leave most positions unread; a step's few rows fill few table rows
    assert 10 < m["index_selected_pct"] < 90
    assert 0 < m["moe_row_fill_pct.serve"] <= 100
    assert {"decode_step_ms", "predict_ms", "batch_occupancy_pct",
            "cache_live_pct", "warmup_s"} <= set(m)
    # a rehearsal has no device plane: no device number is made up
    assert not {"indexer_ms", "indexer_topk_ms", "latent_attn_ms",
                "latent_attn_roofline", "moe_ms.serve",
                "prefill_attn_ms_per_ktok", "decode_roofline"} & set(m)


def test_selection_replay_of_the_tiny_twin_picks_the_references_sets(
        checkout, capsys):
    """``serve_selection.py`` end to end: in float32 the served programs
    pick every position of the reference's sets, in every chunk lane and
    every step where the selection bites."""
    import json

    from benchmark import serve_selection

    assert serve_selection.main([
        "--workload", tiny_glm.CELL, "--seed", "5", "--seconds", "0.5",
        "--manifest", checkout[1], "--rehearse", "--requests", "2"]) == 0
    lines = [json.loads(l.split(" ", 1)[1])
             for l in capsys.readouterr().out.splitlines()
             if l.startswith("selection ")]
    assert len(lines) == 2
    for line in lines:
        assert set(line["layers"]) == {"2", "6"}    # the full layers
        for layer in line["layers"].values():
            for kind in ("chunk", "step"):
                if layer[kind]["positions"]:
                    assert layer[kind]["least"] == pytest.approx(1.0)
    assert any(layer["chunk"]["positions"] > 0 and
               layer["step"]["positions"] > 0
               for line in lines for layer in line["layers"].values())


# -- the new readers on a recorded trace ----------------------------------------

def _recorded():
    p = serve_trace.PREFIX
    host = [(p + "decode.step", 0, 100), (p + "prefill.chunk", 110, 20),
            (p + "decode.step", 140, 460), (p + "decode.step", 800, 120)]
    device = [("fusion.1", 10, 40), ("fusion.2", 50, 10),
              ("fusion.3", 60, 20), ("fusion.4", 80, 4),      # step 1
              ("fusion.1", 150, 200), ("fusion.2", 350, 10),  # the chunk
              ("fusion.1", 400, 50), ("fusion.2", 450, 30),
              ("fusion.3", 480, 40), ("fusion.4", 520, 6),    # step 2
              ("fusion.1", 810, 60)]                          # step 3
    modules = [(10, 85), (150, 360), (400, 590), (810, 900)]

    def hlo(*scopes):
        return "\n".join(
            '%%fusion.%d = f32[] fusion(), metadata={op_name="jit(s)/%s"}'
            % (i + 1, scope) for i, scope in enumerate(scopes))

    text = {"step": hlo("mul/dot_general",
                        "sparse_index/indexer.scores/dot_general",
                        "latent_attention/latent_attention.core/reduce",
                        "sparse_index/indexer.top_k/sort"),
            "chunk": hlo("latent_attention_chunk/while/body/dot_general",
                         "sparse_index_chunk/while/body/indexer.top_k/while")}
    return serve_trace.ServeTrace([device], host, text, [modules])


def _ctx(**more):
    class Run:
        config = _config()

        @staticmethod
        def peaks():
            return {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    before = {"decode_steps": 100.0, "slot_live": 800.0,
              "prefill_tokens": 1000.0, "program_index_selected": 0.0,
              "program_index_cached": 0.0, "program_moe_rows_held": 10.0,
              "program_moe_rows_run": 100.0}
    after = {"decode_steps": 103.0, "slot_live": 824.0,
             "prefill_tokens": 1512.0,
             "program_index_selected": 2 * 3 * 8 * 2048.0,
             "program_index_cached": 2 * 3 * 8 * 4096.0,
             "program_moe_rows_held": 22.0, "program_moe_rows_run": 132.0}
    ctx = {"trace": _recorded(), "profile_counters": (before, after),
           "window_counters": (before, after), "run": Run}
    ctx.update(more)
    return ctx


def test_new_readers_on_a_recorded_trace_and_recorded_counters():
    ctx = _ctx()
    ms = 1e-6   # the recorded durations are nanoseconds
    assert _reader("indexer_ms").read(ctx) == pytest.approx(
        (10 + 4 + 30 + 6) / 3 * ms)
    assert _reader("indexer_topk_ms").read(ctx) == pytest.approx(
        (4 + 6) / 3 * ms)
    assert _reader("latent_attn_ms").read(ctx) == pytest.approx(
        (20 + 40) / 3 * ms)
    assert _reader("moe_ms.serve").read(ctx) is None      # no such scope
    # the chunk's 210 ns under the two chunk ops, 512 prompt tokens
    assert _reader("prefill_attn_ms_per_ktok").read(ctx) == pytest.approx(
        210 * ms / 0.512)
    assert _reader("index_selected_pct").read(ctx) == pytest.approx(50.0)
    assert _reader("moe_row_fill_pct.serve").read(ctx) == pytest.approx(
        100.0 * 12 / 32)
    # 8 live sequences of 2048 selected positions each, all held layers
    ops, nbytes = ops_count_glm_dsa.latent_attention_step(
        _config(), 8, 8 * 2048)
    least_ms = max(ops / 197e12, nbytes / 819e9) * 1e3
    assert _reader("latent_attn_roofline").read(ctx) == pytest.approx(
        100.0 * least_ms / (20e-6))


def test_new_readers_find_nothing_where_the_program_has_nothing():
    """The parent's program: no counters, no scopes. No reader raises."""
    ctx = _ctx()
    bare = ({"decode_steps": 1.0, "slot_live": 8.0},
            {"decode_steps": 4.0, "slot_live": 32.0})
    ctx.update(profile_counters=bare, window_counters=bare,
               trace=serve_trace.NoDeviceServeTrace([]))
    for name in NEW:
        assert _reader(name).read(ctx) is None, name


# -- the operation counts --------------------------------------------------------

def test_operation_counts_against_hand_counts():
    cfg = _config()
    mla = (6144 * 2048 + 2048 * 16384 + 6144 * 576 + 512 * 28672
           + 16384 * 6144)
    assert ops_count_glm_dsa.attention_matrices(cfg) == mla == 165019648
    assert ops_count_glm_dsa.indexer_matrices(cfg) == 9371648
    assert ops_count_glm_dsa.expert_matrices(cfg) == 37748736
    # five latent rows of 576 in bfloat16, two index keys of 128 in float32
    assert ops_count_glm_dsa.bytes_per_position(cfg) == 6784
    # the attention of one step: 8 sequences of 2048 selected rows
    ops, nbytes = ops_count_glm_dsa.latent_attention_step(cfg, 8, 16384)
    assert ops == 2 * 5 * 64 * (8 * 512 * 448 + 16384 * 1088)
    assert nbytes == 2 * 5 * (512 * 64 * 448 + 16384 * 576 + 8 * 64 * 512)
    # bytes bound it on a v5e: the rows are read once for all 64 heads
    assert nbytes / 819e9 > ops / 197e12 * 0.1
    # the whole step: every matrix outside the routed experts once, the
    # experts a pick reaches, the selected rows and every index key
    live, positions = 8, 8 * 4000
    ops, nbytes = ops_count_glm_dsa.decode_step(cfg, live, positions)
    matrices = (5 * mla + 2 * 9371648 + 3 * 6144 * 12288
                + 4 * (6144 * 256 + 37748736) + 6144 * 19360)
    reached = 16 * (1 - (1 - 8 / 256) ** 8)
    kv_b = 5 * 512 * 28672
    assert nbytes == pytest.approx(
        2 * (matrices + 4 * reached * 37748736)
        + 2 * 5 * (8 * 2048 * 576 + 8 * 64 * 512)
        + 4 * 2 * 128 * (positions + live) + live * 6784, rel=1e-12)
    assert ops == pytest.approx(
        2 * live * (matrices - kv_b) + 2 * 4 * live * 8 * 16 / 256 * 37748736
        + 2 * 5 * 64 * (8 * 512 * 448 + 8 * 2048 * 1088)
        + 2 * 2 * 32 * 128 * (positions + live), rel=1e-12)
    # the weights bound a step: 4.7 ms of bytes against 0.2 ms of operations
    assert 4e-3 < nbytes / 819e9 < 6e-3 and ops / 197e12 < 0.1 * nbytes / 819e9
