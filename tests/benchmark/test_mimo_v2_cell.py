"""The MiMo-V2-Flash cell: the manifest's entries and lists held BY NAME on
both cases of ``appended.py``, the configuration file's keys and cut, the
parameter count and the cache shapes from the program's own shapes (a window
layer's rings are ``sliding_window`` positions whatever the rung), the
traffic file's fixed trace, a whole rehearsal of a tiny twin on the CPU
(float32 declared: the numbers mean nothing, the control flow and the checks
are the real ones) that comes out correct while a broken ring does not, each
new reader on a recorded trace and recorded counters, and the operation
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
import tiny_mimo  # noqa: E402
from benchmark import harness, ops_count_mimo_v2, serve_trace  # noqa: E402
from benchmark.jobs import serve, serve_traffic  # noqa: E402

CELL = "mimo2flash.serve.mixedlen.sat"
NEW = ("full_attn_ms", "window_attn_ms", "full_attn_roofline",
       "window_read_pct")
JOINED = ("first_step_s", "trace_s", "lower_s", "backend_compile_s",
          "decode_step_ms", "predict_ms", "sample_deliver_ms",
          "prefill_ms_per_ktok", "batch_occupancy_pct", "cache_live_pct",
          "server_ttft_mean_ms", "server_tpot_mean_ms", "decode_device_ms",
          "decode_roofline", "cache_write_ms", "cached_attn_ms",
          "decode_matmul_ms", "warmup_s", "executables", "moe_ms.serve",
          "moe_row_fill_pct.serve", "fetch_ms", "sample_ms", "admit_plan_ms",
          "chunk_wait_ms", "idle_host_ms", "idle_unspanned_pct",
          "chunk_lane_fill_pct", "cache_alias_pct")
# their readers know GLM-5.2's ops by name and find nothing here
NOT_JOINED = ("indexer_ms", "indexer_topk_ms", "latent_attn_ms",
              "latent_attn_roofline", "index_selected_pct",
              "prefill_attn_ms_per_ktok")
HELD = [0, 6, 7, 8, 9, 10, 11]


def _bench(*parts):
    return os.path.join(ROOT, "benchmark", *parts)


def _config():
    return harness.load_json(_bench("configs", "mimo-v2-flash.json"))


def _reader(name):
    return harness.load_module(_bench("layer_metrics", name + ".py"))


# -- the manifest and the configuration ----------------------------------------

@pytest.mark.parametrize("case", appended.CASES)
def test_manifest_holds_the_cell_its_configuration_and_its_metrics(
        case, tmp_path):
    """Every entry is found by name: nothing here says how many
    configurations, cells or metrics there are, nor where MiMo-V2-Flash's
    stand among them, so a later PR appends its own (``appended.py``)."""
    root = appended.root(case, tmp_path)
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    entry = {c["name"]: c for c in m["configs"]}["mimo-v2-flash"]
    assert entry["source"] == ("https://huggingface.co/XiaomiMiMo/"
                               "MiMo-V2-Flash/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/mimo-v2-flash.json"
    assert entry["reduced"] == ["num_hidden_layers", "experts_held",
                                "vocab_size"]
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("mimo-v2-flash", "serve.mixedlen.sat", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert CELL not in e2e["token_ms_mean"]["workloads"]
    names = [x["name"] for x in m["per_layer"]]
    layers = dict(zip(names, m["per_layer"]))
    # the cell's four, in their order among themselves, wherever they stand
    assert [n for n in names if n in NEW] == list(NEW)
    for name in NEW:
        assert CELL in layers[name]["workloads"]
        assert layers[name]["moves"] == "serve_tokens_per_s"
        assert set(layers[name]) == {"name", "unit", "better", "source",
                                     "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(
            root, "benchmark", "layer_metrics", name + ".py"))
    for name in JOINED:
        assert CELL in layers[name]["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in layers[name]["workloads"], name
    assert layers["full_attn_roofline"]["unit"] == "%"
    assert layers["full_attn_roofline"]["source"] == "device_trace"
    assert layers["window_read_pct"]["source"] == "program_counter"
    # four-chip cells stay within a quarter of the cells, one at least
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)


def test_configuration_holds_the_sources_keys_and_the_cut():
    import json

    body = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # the catalog, where the machine has it
        with open(catalog) as f:
            row, = [json.loads(l) for l in f
                    if '"name": "MiMo-V2-Flash"' in l]
        for key, value in row["config"].items():
            if key not in body["reduced"]:
                assert body[key] == value, key
    assert body["reduced"] == ["num_hidden_layers", "experts_held",
                               "vocab_size"]
    # no width is cut
    assert (body["hidden_size"], body["intermediate_size"],
            body["moe_intermediate_size"], body["num_attention_heads"],
            body["num_key_value_heads"], body["head_dim"],
            body["v_head_dim"], body["swa_num_attention_heads"],
            body["swa_num_key_value_heads"], body["swa_head_dim"],
            body["swa_v_head_dim"], body["sliding_window"],
            body["n_routed_experts"], body["num_experts_per_tok"]) == (
        4096, 16384, 2048, 64, 4, 192, 128, 64, 8, 192, 128, 128, 256, 8)
    assert (body["num_hidden_layers"], body["vocab_size"],
            body["experts_held"], body["layers_held"]) == (
        7, 19072, [0, 16], HELD)
    assert body["published"] == {"num_hidden_layers": 48,
                                 "vocab_size": 152576,
                                 "n_routed_experts": 256}
    # the per-layer lists stay whole and are read at the published index
    assert len(body["hybrid_layer_pattern"]) == 48 \
        == len(body["moe_layer_freq"])
    assert [body["hybrid_layer_pattern"][l] for l in HELD] == [
        0, 1, 1, 1, 1, 1, 0]
    assert [body["moe_layer_freq"][l] for l in HELD] == [0] + [1] * 6
    assert body["n_shared_experts"] is None
    assert body["routed_scaling_factor"] is None
    assert body["served_dtype"] == "bfloat16"
    assert set(body["limits"]) == {"token_gap_max", "token_gap_mean"}
    assert set(body["builder_keys"]) <= set(body)
    for key in ("deployment", "precision", "reduced_why", "limits_why"):
        assert len(body[key]) > 40, key
    assert {"rms_norm epsilon", "value scale", "rotary pairs", "window",
            "sink", "no q/k norm", "sinks' init"} <= set(body["assumed"])


def test_parameters_and_cache_shapes_from_the_programs_own_shapes():
    import paddle_tpu as fluid
    from benchmark import seeded

    body = _config()
    builder = harness.load_module(os.path.join(ROOT, body["builder"]))
    args = {k: body[k] for k in body["builder_keys"]}
    counts, specs = {}, {}
    for kind in ("step", "chunk"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            fetch, spec = getattr(builder, kind)(dtype="bfloat16", **args)
        leaves = {p.name: tuple(p.shape)
                  for p in main.global_block().all_parameters()}
        for name in leaves:           # every leaf has an init rule
            seeded.init_kind(name, body["init"])
        counts[kind], specs[kind] = leaves, spec
        feeds = {c["feed"]: (c["tail"], c.get("capacity"))
                 for c in spec["cache_feeds"]}
        # the full layers hold the rung (4 key heads of 192, 4 value heads
        # of 128), the window layers rings of sliding_window positions (8
        # and 8) whatever the rung
        expected = {}
        for l in HELD:
            full = l in (0, 11)
            expected["cache_k_%d" % l] = ([768], None) if full \
                else ([1536], 128)
            expected["cache_v_%d" % l] = ([512], None) if full \
                else ([1024], 128)
        assert feeds == expected
        assert spec["pad_pos"] >= 1 << 20
    step = counts["step"]
    # the selection bias is a buffer in the source, and is not counted
    total = sum(int(np.prod(s)) for n, s in step.items()
                if not n.endswith("router_bias"))
    assert total == body["parameters"] == 3429953856
    assert ops_count_mimo_v2.parameter_count(body) == 3429953856
    assert sum(n.endswith("router_bias") for n in step) == 6
    assert seeded.init_kind("mimo.l6.moe.router_bias", body["init"]) \
        == "bias"
    # a sink is one N(0, 1) scalar a query head: ``fan_in`` on a 1-D shape
    assert seeded.init_kind("mimo.l6.attn.sink", body["init"]) == "fan_in"
    assert sorted(n for n in step if n.endswith("sink")) == [
        "mimo.l%d.attn.sink" % l for l in (10, 6, 7, 8, 9)]
    # the chunk program ingests: the step's leaves less the head's two
    assert set(step) - set(counts["chunk"]) == {"mimo.norm.w",
                                                "mimo.lm_head"}
    assert specs["chunk"].get("logits_fetch") is None
    assert step["mimo.l0.attn.q"] == (4096, 64 * 192)
    assert step["mimo.l0.attn.k"] == (4096, 4 * 192)
    assert step["mimo.l6.attn.k"] == (4096, 8 * 192)
    assert step["mimo.l6.attn.v"] == (4096, 8 * 128)
    assert step["mimo.l6.attn.o"] == (64 * 128, 4096)
    assert step["mimo.l6.moe.experts.gate"] == (16, 2048, 4096)
    assert step["mimo.l6.moe.router"] == (4096, 256)
    assert "mimo.l0.moe.router" not in step
    # what the slot table reserves at 16 slots x 16384: 1.34 GB of context
    # caches and 52 MB of rings, not the 8 GB of seven layers at the rung
    full, rings = ops_count_mimo_v2.cache_bytes(body, 16, 16384)
    assert (full, rings) == (1342177280, 52428800)


def test_traffic_file_is_the_issues_fixed_trace():
    mix = harness.load_json(_bench("traffic", "serve.mixedlen.sat.json"))
    assert mix["job"] == "serve"
    arrivals = mix["arrivals"]
    assert arrivals["kind"] == "backlog" and arrivals["block"] == 16
    assert arrivals["open_after"] == 16 and arrivals["requests"] >= 192
    assert mix["lengths"] == {
        "prompt": {"median": 2048, "sigma": 1.1, "min": 256, "max": 16128},
        "answer": {"median": 96, "sigma": 0.5, "min": 32, "max": 256}}
    engine = mix["engine"]
    assert engine["ladder"] == [16] and engine["seq_ladder"] == [16384]
    assert len(engine["prefill_ladder"]) == 1
    assert engine["prefill_ladder"][0] in (256, 512, 1024)
    assert mix["check"] == {"sample": 6}
    requests = serve_traffic.schedule(mix, 19072, 2 ** 31 + 5, 10.0)
    assert len(requests) == arrivals["requests"]
    block = requests[:16]
    assert sorted(len(r.prompt) for r in block) == [
        264, 480, 674, 872, 1083, 1316, 1578, 1879, 2232, 2659, 3188, 3873,
        4811, 6221, 8729, 15893]
    assert sum(len(r.prompt) for r in block) == 55752
    answers = sorted(r.max_new for r in block)
    assert (answers[0], answers[-1], sum(answers)) == (38, 244, 1723)
    # every block is the same requests; every one fits the context rung
    assert [len(r.prompt) for r in requests[16:32]] == [
        len(r.prompt) for r in block]
    assert max(r.positions for r in requests) <= 16384
    assert all(int(r.prompt.max()) < 19072 for r in block)
    # every prompt is longer than the window: the rings wrap in every
    # request, the longest more than a hundred times
    assert min(len(r.prompt) for r in requests) > 128
    assert max(r.positions for r in requests) // 128 > 100


# -- a whole rehearsal of the tiny twin -----------------------------------------

TIGHT = {"token_gap_max": 1e-3, "token_gap_mean": 1e-4}


def test_traced_rehearsal_of_the_tiny_twin_is_correct(tmp_path):
    _, manifest = tiny_mimo.make_checkout(tmp_path, served_dtype="float32",
                                          limits=TIGHT)
    run = harness.Run(manifest, tiny_mimo.CELL, 2 ** 31 + 11, 1.0, 1, True,
                      time.time())
    result = serve.run(run)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["answered"] >= 6
    rows = {r["name"]: r for r in result["compared"]}
    assert set(rows) == {"token_gap_max", "token_gap_mean",
                         "tokens_miscounted"}
    assert rows["tokens_miscounted"]["value"] == 0
    m = result["metrics"]      # a traced run's line: the layers' metrics
    assert m["executables"] == 2     # one step and one chunk executable
    # the program's own counters: prompts of 10-44 tokens against a window
    # of 8: a window layer reads well under what a full layer reads
    assert 5 < m["window_read_pct"] < 80
    assert 0 < m["moe_row_fill_pct.serve"] <= 100
    assert m["cache_alias_pct"] == 100.0     # rings too are handed over
    assert {"decode_step_ms", "predict_ms", "batch_occupancy_pct",
            "cache_live_pct", "warmup_s", "chunk_lane_fill_pct"} <= set(m)
    # a rehearsal has no device plane: no device number is made up
    assert not {"full_attn_ms", "window_attn_ms", "full_attn_roofline",
                "cached_attn_ms", "moe_ms.serve", "decode_roofline"} & set(m)


def test_a_broken_ring_does_not_come_out_correct(tmp_path):
    """The same rehearsal with a step program that hands the first window
    layer's key ring back unchanged: the comparison catches it."""
    _, manifest = tiny_mimo.make_checkout(tmp_path, served_dtype="float32",
                                          limits=TIGHT, broken_ring=True)
    run = harness.Run(manifest, tiny_mimo.CELL, 2 ** 31 + 11, 1.0, 0, True,
                      time.time())
    result = serve.run(run)
    assert result["failed"] == 0 and result["answered"] >= 6
    assert not result["correct"]
    rows = {r["name"]: r for r in result["compared"]}
    assert not rows["token_gap_mean"]["ok"]
    assert rows["tokens_miscounted"]["ok"]


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
                        "cached_attention/attn.full/dot_general",
                        "cached_attention/attn.window/reduce",
                        "cached_attention/attn.full/exp"),
            "chunk": hlo("cached_attention_chunk/attn.full/while/body/dot",
                         "cached_attention_chunk/attn.window/dot_general")}
    return serve_trace.ServeTrace([device], host, text, [modules])


def _ctx(**more):
    class Run:
        config = _config()

        @staticmethod
        def peaks():
            return {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    before = {"decode_steps": 100.0, "slot_live": 1600.0,
              "program_attn_full_positions": 0.0,
              "program_attn_window_positions": 0.0}
    after = {"decode_steps": 103.0, "slot_live": 1648.0,
             # three steps, 16 rows of 4000 positions: two full layers read
             # them all, five window layers 128 a row
             "program_attn_full_positions": 3 * 2 * 16 * 4000.0,
             "program_attn_window_positions": 3 * 5 * 16 * 128.0}
    ctx = {"trace": _recorded(), "profile_counters": (before, after),
           "window_counters": (before, after), "run": Run}
    ctx.update(more)
    return ctx


def test_new_readers_on_a_recorded_trace_and_recorded_counters():
    ctx = _ctx()
    ms = 1e-6   # the recorded durations are nanoseconds
    assert _reader("full_attn_ms").read(ctx) == pytest.approx(
        (10 + 4 + 30 + 6) / 3 * ms)
    assert _reader("window_attn_ms").read(ctx) == pytest.approx(
        (20 + 40) / 3 * ms)
    # the accepted reader of the op's own scope reads both kinds
    assert _reader("cached_attn_ms").read(ctx) == pytest.approx(
        (10 + 20 + 4 + 30 + 40 + 6) / 3 * ms)
    assert _reader("window_read_pct").read(ctx) == pytest.approx(
        100.0 * 128 / 4000)
    ops, nbytes = ops_count_mimo_v2.attention_step(_config(), 16, 16 * 4000)
    least_ms = max(ops / 197e12, nbytes / 819e9) * 1e3
    assert _reader("full_attn_roofline").read(ctx) == pytest.approx(
        100.0 * least_ms / ((10 + 4 + 30 + 6) / 3 * ms))


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
    full = 4096 * 12288 + 4096 * 768 + 4096 * 512 + 8192 * 4096
    window = 4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096
    assert ops_count_mimo_v2.attention_matrices(cfg, 0) == full == 89128960
    assert ops_count_mimo_v2.attention_matrices(cfg, 6) == window == 94371840
    assert ops_count_mimo_v2.expert_matrices(cfg) == 25165824
    assert ops_count_mimo_v2.full_layers(cfg) == [0, 11]
    assert ops_count_mimo_v2.window_layers(cfg) == [6, 7, 8, 9, 10]
    assert ops_count_mimo_v2.expert_layers(cfg) == [6, 7, 8, 9, 10, 11]
    # a position of a full layer: 4 x (192 + 128) bfloat16 values
    assert ops_count_mimo_v2.bytes_per_position(cfg, 0) == 2560
    assert ops_count_mimo_v2.bytes_per_position(cfg, 6) == 5120
    # the full layers' attention of one step: 16 sequences, 64000 positions
    ops, nbytes = ops_count_mimo_v2.attention_step(cfg, 16, 64000)
    assert ops == 2 * (2.0 * 64 * 320 * 64000)
    assert nbytes == 2 * (2560 * 64000 + 2 * 16 * 64 * 320)
    # bytes bound it on a v5e: a row is read once for its 16 query heads
    assert nbytes / 819e9 > ops / 197e12
    # the window layers read 128 positions a sequence at most
    ops, nbytes = ops_count_mimo_v2.window_step(cfg, 16, 64000)
    assert ops == 5 * (2.0 * 64 * 320 * 16 * 128)
    assert nbytes == 5 * (5120 * 16 * 128 + 2 * 16 * 64 * 320)
    # the whole step: every matrix outside the routed experts once, the
    # experts a pick reaches (not all sixteen), the live positions
    live, positions = 16, 16 * 4000
    ops, nbytes = ops_count_mimo_v2.decode_step(cfg, live, positions)
    matrices = (2 * full + 5 * window + 3 * 4096 * 16384
                + 6 * 4096 * 256 + 4096 * 19072)
    reached = 16 * (1 - (1 - 8 / 256) ** 16)
    assert 6 < reached < 7
    read = positions + live
    assert nbytes == pytest.approx(
        2 * (matrices + 6 * reached * 25165824)
        + 2 * (2560 * read + 2 * 16 * 64 * 320)
        + 5 * (5120 * 16 * 128 + 2 * 16 * 64 * 320)
        + live * (2 * 2560 + 5 * 5120), rel=1e-12)
    assert ops == pytest.approx(
        2 * live * matrices + 2 * 6 * live * 8 * 16 / 256 * 25165824
        + 2 * (2.0 * 64 * 320 * read) + 5 * (2.0 * 64 * 320 * 16 * 128),
        rel=1e-12)
    # the weights bound a step: ~5 ms of bytes against 0.2 ms of operations
    assert 4e-3 < nbytes / 819e9 < 6.5e-3
    assert ops / 197e12 < 0.1 * nbytes / 819e9
