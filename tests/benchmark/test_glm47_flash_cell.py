"""The GLM-4.7-Flash cell: the manifest's entries and lists held BY NAME on
both cases of ``appended.py``, the configuration file's keys and cut (depth
alone), the parameter count and the cache shapes from the programs' own
shapes, the traffic file's fixed trace, a whole rehearsal of a tiny twin on
the CPU (float32 declared: the numbers mean nothing, the control flow and the
checks are the real ones) that comes out correct and reports every new
metric, while a step that accepts a wrong draft does not, the cell's step
drafting, each new reader on a recorded trace and recorded counters, and the
operation counts against hand counts."""

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
import tiny_glm_lite  # noqa: E402
from benchmark import harness, ops_count_glm_lite, serve_trace  # noqa: E402
from benchmark.jobs import serve, serve_traffic  # noqa: E402

CELL = "glm47flash.serve.reason.sat"
NEW = ("mtp_accept_pct", "mtp_draft_ms", "mtp_draft_roofline",
       "moe_experts_touched_pct", "latent_dense_roofline")
JOINED = ("first_step_s", "trace_s", "lower_s", "backend_compile_s",
          "decode_step_ms", "predict_ms", "sample_deliver_ms",
          "prefill_ms_per_ktok", "batch_occupancy_pct", "cache_live_pct",
          "server_ttft_mean_ms", "server_tpot_mean_ms", "decode_device_ms",
          "decode_roofline", "cache_write_ms", "decode_matmul_ms",
          "warmup_s", "executables", "latent_attn_ms", "moe_ms.serve",
          "moe_row_fill_pct.serve", "prefill_attn_ms_per_ktok", "fetch_ms",
          "sample_ms", "admit_plan_ms", "chunk_wait_ms", "idle_host_ms",
          "idle_unspanned_pct", "chunk_lane_fill_pct", "cache_alias_pct")
# their readers know GLM-5.2's indexer by name and find nothing here, and
# the cached_attention op is another family's
NOT_JOINED = ("indexer_ms", "indexer_topk_ms", "latent_attn_roofline",
              "index_selected_pct", "cached_attn_ms")
PARAMETERS = 5174642688


def _bench(*parts):
    return os.path.join(ROOT, "benchmark", *parts)


def _config():
    return harness.load_json(_bench("configs", "glm-4.7-flash.json"))


def _reader(name):
    return harness.load_module(_bench("layer_metrics", name + ".py"))


# -- the manifest and the configuration ----------------------------------------

@pytest.mark.parametrize("case", appended.CASES)
def test_manifest_holds_the_cell_its_configuration_and_its_metrics(
        case, tmp_path):
    """Every entry is found by name: nothing here says how many
    configurations, cells or metrics there are, nor where GLM-4.7-Flash's
    stand among them, so a later PR appends its own (``appended.py``)."""
    root = appended.root(case, tmp_path)
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    entry = {c["name"]: c for c in m["configs"]}["glm-4.7-flash"]
    assert entry["source"] == ("https://huggingface.co/zai-org/"
                               "GLM-4.7-Flash/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/glm-4.7-flash.json"
    assert entry["reduced"] == ["num_hidden_layers"]
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("glm-4.7-flash", "serve.reason.sat", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # the module is one layer of 8 here and one of 48 in the deployment
    assert "8" in cell["why"] and "48" in cell["why"]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert CELL not in e2e["token_ms_mean"]["workloads"]
    names = [x["name"] for x in m["per_layer"]]
    layers = dict(zip(names, m["per_layer"]))
    # the cell's five, in their order among themselves, wherever they stand
    assert [n for n in names if n in NEW] == list(NEW)
    for name in NEW:
        assert layers[name]["workloads"] == [CELL]
        assert layers[name]["moves"] == "serve_tokens_per_s"
        assert set(layers[name]) == {"name", "unit", "better", "source",
                                     "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(
            root, "benchmark", "layer_metrics", name + ".py"))
    for name in JOINED:
        assert CELL in layers[name]["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in layers[name]["workloads"], name
    for name in ("mtp_draft_roofline", "latent_dense_roofline"):
        assert layers[name]["unit"] == "%"
        assert layers[name]["source"] == "device_trace"
    for name in ("mtp_accept_pct", "moe_experts_touched_pct"):
        assert layers[name]["source"] == "program_counter"
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
                    if '"name": "GLM-4.7-Flash"' in l]
        assert body["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in body["reduced"]:
                assert body[key] == value, key
    # depth alone is cut: every expert and every row of the vocabulary
    assert body["reduced"] == ["num_hidden_layers"]
    assert (body["hidden_size"], body["intermediate_size"],
            body["moe_intermediate_size"], body["num_attention_heads"],
            body["q_lora_rank"], body["kv_lora_rank"],
            body["qk_nope_head_dim"], body["qk_rope_head_dim"],
            body["v_head_dim"], body["n_routed_experts"],
            body["num_experts_per_tok"], body["n_shared_experts"],
            body["vocab_size"], body["routed_scaling_factor"],
            body["rope_theta"]) == (
        2048, 10240, 1536, 20, 768, 512, 192, 64, 256, 64, 4, 1, 154880,
        1.8, 1000000)
    assert (body["num_hidden_layers"], body["layers_held"],
            body["experts_held"], body["first_k_dense_replace"]) == (
        7, [0, 7], [0, 64], 1)
    assert body["published"] == {"num_hidden_layers": 47}
    # the module is kept: the source's count, at the source's layer index
    assert body["num_nextn_predict_layers"] == 1
    assert body["nextn_layer"] == body["published"]["num_hidden_layers"]
    assert body["served_dtype"] == "bfloat16"
    assert set(body["limits"]) == {"token_gap_max", "token_gap_mean"}
    assert set(body["builder_keys"]) <= set(body)
    for key in ("deployment", "precision", "reduced_why", "limits_why"):
        assert len(body[key]) > 40, key
    assert "48" in body["reduced_why"]      # the module's share, said
    assert {"equations", "module input order", "module hidden state",
            "rope", "weights", "sampling"} <= set(body["assumed"])


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
        # ONE latent row a position a layer, 576 wide, the module's too
        assert {c["feed"]: (c["tail"], c.get("capacity"), c["dtype"])
                for c in spec["cache_feeds"]} == {
            "cache_latent_%d" % l: ([576], None, "bfloat16")
            for l in (0, 1, 2, 3, 4, 5, 6, 47)}
    step = counts["step"]
    # the selection bias is a buffer in the source, and is not counted
    total = sum(int(np.prod(s)) for n, s in step.items()
                if not n.endswith("router_bias"))
    assert total == body["parameters"] == PARAMETERS
    assert ops_count_glm_lite.parameter_count(body) == PARAMETERS
    assert sum(n.endswith("router_bias") for n in step) == 7
    # the hand counts of ISSUE 42: MLA, an expert layer, the dense layer,
    # embedding + head, the module
    assert ops_count_glm_lite.attention_matrices(body) == 21757952
    assert ops_count_glm_lite.expert_layer_parameters(body) == 635311360
    assert ops_count_glm_lite.module_parameters(body) == 635311360 \
        + 8388608 + 3 * 2048
    assert PARAMETERS == 84677888 + 6 * 635311360 + 634388480 + 2048 \
        + 643706112
    # the chunk program ingests, the module's layer too, and builds the
    # module's one head: every leaf of the step is a leaf of it
    assert set(step) == set(counts["chunk"])
    assert step["glm.l1.moe.experts.gate"] == (64, 1536, 2048)
    assert step["glm.l47.moe.experts.down"] == (64, 2048, 1536)
    assert step["glm.l47.eh_proj"] == (4096, 2048)
    assert step["glm.l0.mlp.gate"] == (2048, 10240)
    assert step["glm.l3.attn.kv_b"] == (512, 20 * (192 + 256))
    assert step["glm.lm_head"] == (2048, 154880)
    assert "glm.l0.moe.router" not in step and "glm.l7.attn.q_a" not in step
    # the decode spec states the self-draft, and the loop needs no more
    drafts = specs["step"]["self_draft"]
    assert drafts["lanes"] == 2 and drafts["cache_feeds"] == [
        "cache_latent_47"]
    assert "logits_fetch" not in specs["step"]
    assert specs["step"]["counters"] == [
        "mtp_drafted", "mtp_accepted", "moe_experts_touched",
        "moe_rows_held", "moe_rows_run"]
    assert specs["chunk"]["self_draft"]["next_token_lane"] == 1
    assert "logits_fetch" not in specs["chunk"]
    # 8 latent caches x 1,152 B x 32 rows x 4,096 positions
    assert ops_count_glm_lite.cache_bytes(body, 32, 4096) == 1207959552


def test_traffic_file_is_the_issues_fixed_trace():
    mix = harness.load_json(_bench("traffic", "serve.reason.sat.json"))
    assert mix["job"] == "serve" and mix["shape_seed"] == 20261002
    assert mix["arrivals"] == {"kind": "backlog", "requests": 256,
                               "block": 32, "open_after": 32}
    lengths = mix["lengths"]
    assert lengths["prompt"] == {"median": 512, "sigma": 0.8, "min": 128,
                                 "max": 3072}
    assert lengths["answer"] in (
        {"median": 256, "sigma": 0.6, "min": 64, "max": 768},
        {"median": 192, "sigma": 0.6, "min": 64, "max": 512})  # fall-back
    engine = mix["engine"]
    assert engine["ladder"] == [32] and engine["seq_ladder"] == [4096]
    assert len(engine["prefill_ladder"]) == 1
    assert engine["prefill_ladder"][0] in (256, 512, 1024)
    assert mix["check"] == {"sample": 6}
    requests = serve_traffic.schedule(mix, 154880, 2 ** 31 + 5, 10.0)
    assert len(requests) == 256
    block = requests[:32]
    prompts = sorted(len(r.prompt) for r in block)
    assert (prompts[0], prompts[15], prompts[-1], sum(prompts)) == (
        128, 496, 2868, 22193)
    if lengths["answer"]["max"] == 768:
        answers = sorted(r.max_new for r in block)
        assert (answers[0], answers[15], answers[-1], sum(answers)) == (
            70, 250, 768, 9557)
        # answers several times longer than the prompts' median share
        assert sum(answers) * 2 > sum(prompts) * 0.8
    # every block is the same requests; every one fits the context rung
    assert [len(r.prompt) for r in requests[32:64]] == [
        len(r.prompt) for r in block]
    assert max(r.positions for r in requests) <= 4096
    # ids from every row of the vocabulary
    assert max(int(r.prompt.max()) for r in block) > 154880 * 0.99


# -- a whole rehearsal of the tiny twin -----------------------------------------

TIGHT = {"token_gap_max": 1e-3, "token_gap_mean": 1e-4}


def test_traced_rehearsal_of_the_tiny_twin_is_correct(tmp_path):
    _, manifest = tiny_glm_lite.make_checkout(
        tmp_path, served_dtype="float32", limits=TIGHT)
    run = harness.Run(manifest, tiny_glm_lite.CELL, 2 ** 31 + 11, 1.0, 1,
                      True, time.time())
    result = serve.run(run)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["answered"] >= 6
    rows = {r["name"]: r for r in result["compared"]}
    assert set(rows) == {"token_gap_max", "token_gap_mean",
                         "tokens_miscounted"}
    # tokens delivered, never lanes: the engine's count is the clients'
    assert rows["tokens_miscounted"]["value"] == 0
    m = result["metrics"]      # a traced run's line: the layers' metrics
    assert m["executables"] == 2     # one step and one chunk executable
    # the program's own counters: the step DRAFTS (a step that never
    # drafted would read no mtp_accept_pct at all), and under seeded
    # weights few drafts stand
    assert 0 <= m["mtp_accept_pct"] < 25
    # 4 rows x 2 lanes x 2 picks over 8 experts: most are reached
    assert 30 < m["moe_experts_touched_pct"] <= 100
    assert 0 < m["moe_row_fill_pct.serve"] <= 100
    assert m["cache_alias_pct"] == 100.0     # the module's cache with them
    assert {"decode_step_ms", "predict_ms", "batch_occupancy_pct",
            "cache_live_pct", "warmup_s", "chunk_lane_fill_pct"} <= set(m)
    # a rehearsal has no device plane: no device number is made up
    assert not {"mtp_draft_ms", "mtp_draft_roofline", "latent_attn_ms",
                "latent_dense_roofline", "moe_ms.serve",
                "decode_roofline"} & set(m)


def test_a_step_that_accepts_a_wrong_draft_does_not_come_out_correct(
        tmp_path):
    """The same rehearsal with a step program whose accept rule lets every
    draft stand: wrong tokens are delivered, and the comparison with the
    reference catches them."""
    _, manifest = tiny_glm_lite.make_checkout(
        tmp_path, served_dtype="float32", limits=TIGHT,
        builder=tiny_glm_lite.ACCEPTS_ANYTHING)
    run = harness.Run(manifest, tiny_glm_lite.CELL, 2 ** 31 + 11, 1.0, 0,
                      True, time.time())
    try:
        result = serve.run(run)
    finally:
        tiny_glm_lite.restore_accept_rule()
    assert result["answered"] >= 6
    assert not result["correct"]
    rows = {r["name"]: r for r in result["compared"]}
    assert not rows["token_gap_mean"]["ok"]


def test_a_step_that_never_drafts_fails_the_cells_test():
    """The configuration states one prediction module and the builder
    refuses to leave it out: a cell whose step does not draft cannot be
    built from this configuration."""
    body = _config()
    builder = harness.load_module(os.path.join(ROOT, body["builder"]))
    args = {k: body[k] for k in body["builder_keys"]}
    import paddle_tpu as fluid

    for changed in (dict(num_nextn_predict_layers=0),
                    dict(nextn_layer=None)):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            with pytest.raises(ValueError, match="prediction module"):
                builder.step(dtype="bfloat16", **dict(args, **changed))
    # and the reader of the cell's own counter finds nothing to read where
    # a program counts no drafts: the metric would be missing from the line
    bare = ({"decode_steps": 1.0}, {"decode_steps": 4.0})
    assert _reader("mtp_accept_pct").read({"window_counters": bare}) is None


# -- the new readers on a recorded trace ----------------------------------------

def _recorded():
    p = serve_trace.PREFIX
    host = [(p + "decode.step", 0, 100), (p + "prefill.chunk", 110, 20),
            (p + "decode.step", 140, 460), (p + "decode.step", 800, 120)]
    device = [("fusion.1", 10, 40), ("fusion.2", 50, 10),
              ("fusion.3", 60, 20), ("fusion.4", 80, 4),
              ("fusion.5", 84, 1), ("fusion.6", 85, 1),       # step 1
              ("fusion.1", 150, 200), ("fusion.2", 350, 10),  # the chunk
              ("fusion.1", 400, 50), ("fusion.2", 450, 30),
              ("fusion.3", 480, 40), ("fusion.4", 520, 6),
              ("fusion.5", 526, 2), ("fusion.6", 528, 3),     # step 2
              ("fusion.1", 810, 60)]                          # step 3
    modules = [(10, 86), (150, 360), (400, 590), (810, 900)]

    def hlo(*scopes):
        return "\n".join(
            '%%fusion.%d = f32[] fusion(), metadata={op_name="jit(s)/%s"}'
            % (i + 1, scope) for i, scope in enumerate(scopes))

    text = {"step": hlo(
        "mul/dot_general",
        "latent_attention_dense/latent_attention/latent_attention.core/dot",
        "mtp.block/routed_experts/moe.router/dot_general",
        "mtp.block/latent_attention_dense/latent_attention/"
        "latent_attention.expand/dot_general",
        "mtp.head/mul/dot_general",
        "kv_cache_write_chunk/kv_cache_write/while/body/"
        "dynamic_update_slice"),
        "chunk": hlo("latent_attention_chunk/latent_attention.core/while",
                     "mtp.block/routed_experts/grouped_experts.fwd")}
    return serve_trace.ServeTrace([device], host, text, [modules])


class _Ticks:
    @staticmethod
    def live_positions(requests):
        # 32 rows of 800 positions all through the profiled window
        return np.array([0.0, 500.0, 1000.0]), np.full(3, 32 * 800.0)


def _ctx(**more):
    class Run:
        config = _config()

        @staticmethod
        def peaks():
            return {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    before = {"decode_steps": 100.0, "slot_live": 3200.0,
              "prefill_chunks": 20.0,
              "program_mtp_drafted": 10.0, "program_mtp_accepted": 1.0,
              "program_moe_experts_touched": 0.0}
    # four quanta, one of them a chunk run
    after = {"decode_steps": 104.0, "slot_live": 3328.0,
             "prefill_chunks": 21.0,
             # three steps of 32 rows: 96 drafts judged, 6 stood; 63 of 64
             # experts reached in each of the 7 expert layers
             "program_mtp_drafted": 106.0, "program_mtp_accepted": 7.0,
             "program_moe_experts_touched": 3 * 7 * 63.0}
    ctx = {"trace": _recorded(), "profile_counters": (before, after),
           "window_counters": (before, after), "run": Run,
           "ticks": _Ticks, "requests": [], "profiled": (0.0, 1000.0)}
    ctx.update(more)
    return ctx


def test_new_readers_on_a_recorded_trace_and_recorded_counters():
    ctx = _ctx()
    ms = 1e-6   # the recorded durations are nanoseconds
    cfg = _config()
    assert _reader("mtp_accept_pct").read(ctx) == pytest.approx(
        100.0 * 6 / 96)
    assert _reader("moe_experts_touched_pct").read(ctx) == pytest.approx(
        100.0 * 63 / 64)
    # the module's three scopes: its experts, its attention, its head
    draft_ms = (20 + 4 + 1 + 40 + 6 + 2) / 3 * ms
    assert _reader("mtp_draft_ms").read(ctx) == pytest.approx(draft_ms)
    # the accepted reader of the step form's scope finds the dense step,
    # the main model's layers and the module's
    attn_ms = (10 + 4 + 30 + 6) / 3 * ms
    assert _reader("latent_attn_ms").read(ctx) == pytest.approx(attn_ms)
    # and the accepted experts' reader the module's experts
    assert _reader("moe_ms.serve").read(ctx) == pytest.approx(
        (20 + 40) / 3 * ms)
    ops, nbytes = ops_count_glm_lite.draft_step(cfg, 32, 32 * 800.0)
    least_ms = max(ops / 197e12, nbytes / 819e9) * 1e3
    assert _reader("mtp_draft_roofline").read(ctx) == pytest.approx(
        100.0 * least_ms / draft_ms)
    ops, nbytes = ops_count_glm_lite.attention_step(cfg, 32,
                                                    32 * 800.0 + 32)
    least_ms = max(ops / 197e12, nbytes / 819e9) * 1e3
    assert _reader("latent_dense_roofline").read(ctx) == pytest.approx(
        100.0 * least_ms / attn_ms)
    # GLM-5.2's reader knows its own count and counter by name: not here
    assert _reader("latent_attn_roofline").read(ctx) is None


def test_the_accepted_step_readers_find_the_drafting_steps_writes():
    """The cell is on ``cache_write_ms``'s list: a drafting step writes its
    two lanes through the chunk write's op, under the step write's scope
    inside it, where the accepted reader looks."""
    ctx = _ctx()
    assert _reader("cache_write_ms").read(ctx) == pytest.approx(
        (1 + 3) / 3 * 1e-6)
    # and the chunk write's own scope alone is not a step's
    trace = ctx["trace"]
    assert trace.scope_ms_a_quantum("decode.step",
                                    ("kv_cache_write_chunk",)) is not None
    assert _reader("decode_matmul_ms").read(ctx) is not None


def test_new_readers_find_nothing_where_the_program_has_nothing():
    """The parent's program: no counters, no scopes. No reader raises."""
    ctx = _ctx()
    bare = ({"decode_steps": 1.0, "slot_live": 8.0},
            {"decode_steps": 4.0, "slot_live": 32.0})
    ctx.update(profile_counters=bare, window_counters=bare,
               trace=serve_trace.NoDeviceServeTrace([]))
    for name in NEW:
        assert _reader(name).read(ctx) is None, name

    # and on a configuration whose count has no such functions (GLM-5.2's
    # attention reads a selection): the dense share is not its share
    class Glm52:
        config = harness.load_json(_bench("configs", "glm-5.2.json"))
        peaks = _ctx()["run"].peaks

    ctx = _ctx(run=Glm52)
    assert _reader("latent_dense_roofline").read(ctx) is None
    assert _reader("mtp_draft_roofline").read(ctx) is None


# -- the operation counts --------------------------------------------------------

def test_operation_counts_against_hand_counts():
    cfg = _config()
    count = ops_count_glm_lite
    mla = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert count.attention_matrices(cfg) == mla == 21757952
    assert count.kv_b_matrix(cfg) == 512 * 8960
    assert count.expert_matrices(cfg) == 3 * 2048 * 1536 == 9437184
    assert count.latent_layers(cfg) == 8
    assert count.bytes_per_position(cfg) == 8 * 1152
    live, positions = 32, 32 * 800
    lanes = 2 * live
    # 64 lanes x 4 picks over 64 experts reach 63 of them in expectation,
    # not all 64 whatever the step
    reached = 64 * (1 - (1 - 4 / 64) ** lanes)
    assert 62.9 < reached < 63.0
    # the latent attention of all eight layers: a cached row once a
    # sequence, both lanes of a row sharing it
    ops, nbytes = count.attention_step(cfg, live, positions)
    assert ops == 2.0 * 8 * 20 * 2 * (live * 512 * 448 + positions * 1088)
    assert nbytes == 2 * 8 * (512 * 8960 + positions * 576
                              + lanes * 20 * 512)
    assert nbytes / 819e9 > ops / 197e12        # bytes bound it
    # the module: eh_proj, the head once more, one expert layer
    layer_matrices = mla - 512 * 8960 + 2048 * 64 + 9437184
    ops, nbytes = count.draft_step(cfg, live, positions)
    attn_ops = 2.0 * 20 * 2 * (live * 512 * 448 + (positions + lanes) * 1088)
    attn_bytes = 2 * (512 * 8960 + (positions + lanes) * 576
                      + lanes * 20 * 512)
    assert nbytes == pytest.approx(
        2 * (2 * 2048 * 2048 + 2048 * 154880)
        + 2 * (layer_matrices + reached * 9437184)
        + attn_bytes + lanes * 1152, rel=1e-12)
    assert ops == pytest.approx(
        2.0 * lanes * (2 * 2048 * 2048 + 2048 * 154880)
        + 2.0 * lanes * layer_matrices + 2.0 * lanes * 4 * 9437184
        + attn_ops, rel=1e-12)
    module_ops, module_bytes = ops, nbytes
    # the whole verifying step: seven layers, the head over both lanes,
    # the module
    ops, nbytes = count.decode_step(cfg, live, positions)
    dense = mla - 512 * 8960 + 3 * 2048 * 10240
    main_attn_bytes = 2 * 7 * (512 * 8960 + (positions + lanes) * 576
                               + lanes * 20 * 512)
    assert nbytes == pytest.approx(
        6 * 2 * (layer_matrices + reached * 9437184)
        + 2 * (dense + 2048 * 154880) + main_attn_bytes
        + lanes * 7 * 1152 + module_bytes, rel=1e-12)
    assert ops == pytest.approx(
        6 * (2.0 * lanes * layer_matrices + 2.0 * lanes * 4 * 9437184)
        + 2.0 * lanes * (dense + 2048 * 154880) + 7 * attn_ops
        + module_ops, rel=1e-12)
    # the weights bound a step: ~12.8 ms of bytes against 0.9 ms of
    # operations, so no share of the step's roofline can be over-counted
    # by counting all 64 experts (that would be 1.6% more)
    assert 12e-3 < nbytes / 819e9 < 13.5e-3
    assert ops / 197e12 < 0.1 * nbytes / 819e9
    # a draft is a sixth of a step here (one layer of 8, and a head of two)
    assert 0.15 < module_bytes / nbytes < 0.22
