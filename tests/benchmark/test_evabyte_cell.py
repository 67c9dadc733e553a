"""The EvaByte cell: the manifest's entries and lists held BY NAME on both
cases of ``appended.py``, the configuration file's keys and cut, the
parameter count and the cache shapes from the programs' own shapes (a window
cache of ``window_size`` positions and a summary cache of ``rung /
chunk_size`` entries a row, whatever the rung), the traffic file's fixed
trace, a whole rehearsal of a tiny twin on the CPU (float32 declared: the
numbers mean nothing, the control flow and the checks are the real ones)
that comes out correct while a summary cache that is zeroed or shifted by
one chunk does not, each new reader on a recorded trace and recorded
counters, and the operation counts against hand counts."""

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
import tiny_evabyte  # noqa: E402
from benchmark import harness, ops_count_evabyte, serve_trace  # noqa: E402
from benchmark.jobs import serve, serve_traffic  # noqa: E402

CELL = "evabyte.serve.bytes.sat"
NEW = ("eva_attn_ms", "eva_attn_roofline", "eva_summary_ms", "eva_read_pct")
JOINED = ("first_step_s", "trace_s", "lower_s", "backend_compile_s",
          "decode_step_ms", "predict_ms", "sample_deliver_ms",
          "prefill_ms_per_ktok", "batch_occupancy_pct", "cache_live_pct",
          "server_ttft_mean_ms", "server_tpot_mean_ms", "decode_device_ms",
          "decode_roofline", "cache_write_ms", "decode_matmul_ms",
          "warmup_s", "executables", "fetch_ms", "sample_ms",
          "admit_plan_ms", "chunk_wait_ms", "idle_host_ms",
          "idle_unspanned_pct", "chunk_lane_fill_pct", "cache_alias_pct")
# their readers know another configuration's ops by name and find nothing
# here
NOT_JOINED = ("cached_attn_ms", "indexer_ms", "latent_attn_ms",
              "latent_attn_roofline", "prefill_attn_ms_per_ktok",
              "moe_ms.serve", "full_attn_ms", "window_attn_ms",
              "window_read_pct", "mtp_accept_pct")
SOURCE = "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"


def _bench(*parts):
    return os.path.join(ROOT, "benchmark", *parts)


def _config():
    return harness.load_json(_bench("configs", "evabyte-6.5b.json"))


def _reader(name):
    return harness.load_module(_bench("layer_metrics", name + ".py"))


# -- the manifest and the configuration ----------------------------------------

@pytest.mark.parametrize("case", appended.CASES)
def test_manifest_holds_the_cell_its_configuration_and_its_metrics(
        case, tmp_path):
    """Every entry is found by name: nothing here says how many
    configurations, cells or metrics there are, nor where EvaByte's stand
    among them, so a later PR appends its own (``appended.py``)."""
    root = appended.root(case, tmp_path)
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    entry = {c["name"]: c for c in m["configs"]}["evabyte-6.5b"]
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/evabyte-6.5b.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_pred_heads"]
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("evabyte-6.5b", "serve.bytes.sat", 1)
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
    assert layers["eva_attn_roofline"]["unit"] == "%"
    assert layers["eva_attn_roofline"]["source"] == "device_trace"
    assert layers["eva_read_pct"]["source"] == "program_counter"
    # four-chip cells stay within a quarter of the cells, one at least
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)


def test_configuration_holds_the_sources_keys_and_the_cut():
    import json

    body = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # the catalog, where the machine has it
        with open(catalog) as f:
            row, = [json.loads(l) for l in f if '"name": "EvaByte"' in l]
        assert body["source"] == row["source_url"] == SOURCE
        for key, value in row["config"].items():
            if key not in body["reduced"]:
                assert body[key] == value, key
    assert body["reduced"] == ["num_hidden_layers", "num_pred_heads"]
    # no width is cut
    assert (body["hidden_size"], body["num_attention_heads"],
            body["num_key_value_heads"], body["intermediate_size"],
            body["window_size"], body["chunk_size"], body["vocab_size"],
            body["rope_theta"], body["rms_norm_eps"],
            body["max_position_embeddings"]) == (
        4096, 32, 32, 11008, 2048, 16, 320, 100000, 1e-5, 32768)
    assert (body["attention_class"], body["norm_add_unit_offset"],
            body["fp32_skip_add"], body["fp32_logits"]) == (
        "eva", True, True, True)
    assert (body["num_hidden_layers"], body["num_pred_heads"],
            body["layers_held"]) == (8, 1, [0, 8])
    assert body["published"] == {"num_hidden_layers": 32,
                                 "num_pred_heads": 8}
    assert body["served_dtype"] == "bfloat16"
    assert set(body["limits"]) == {"token_gap_max", "token_gap_mean"}
    assert set(body["builder_keys"]) <= set(body)
    for key in ("deployment", "precision", "reduced_why", "limits_why",
                "init_why"):
        assert len(body[key]) > 40, key
    assert {"pooling logits' scale", "pooling after rotary", "mu",
            "a window's own chunks", "head 0"} <= set(body["assumed"])


def test_parameters_and_cache_bytes_from_the_programs_own_shapes():
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
        feeds = {c["feed"]: (c["tail"], c.get("capacity"), c.get("stride"))
                 for c in spec["cache_feeds"]}
        # a layer's four caches: two of window_size positions whatever the
        # rung, two of one entry for every chunk_size positions of it
        expected = {}
        for l in range(8):
            for part in "kv":
                expected["win_%s_%d" % (part, l)] = ([4096], 2048, None)
                expected["sum_%s_%d" % (part, l)] = ([4096], None, 16)
        assert feeds == expected
        assert spec["pad_pos"] >= 1 << 20
    step = counts["step"]
    total = sum(int(np.prod(s)) for s in step.values())
    assert total == body["parameters"] == 1621757952
    assert ops_count_evabyte.parameter_count(body) == 1621757952
    # phi and mu are [H*D] vectors drawn N(0, 1): ``fan_in`` on a 1-D shape
    assert step["eva.l3.attn.phi"] == step["eva.l3.attn.mu"] == (4096,)
    assert seeded.init_kind("eva.l3.attn.phi", body["init"]) == "fan_in"
    # a norm's weight is an offset from 1
    assert seeded.init_kind("eva.l0.input_norm.w", body["init"]) == "bias"
    assert seeded.init_kind("eva.norm.w", body["init"]) == "bias"
    # the chunk program ingests: the step's leaves less the head's two
    assert set(step) - set(counts["chunk"]) == {"eva.norm.w", "eva.lm_head"}
    assert specs["chunk"].get("logits_fetch") is None
    assert step["eva.embed_tokens"] == (320, 4096)
    assert step["eva.lm_head"] == (4096, 320)
    assert step["eva.l7.attn.q"] == step["eva.l7.attn.o"] == (4096, 4096)
    assert step["eva.l7.mlp.gate"] == (4096, 11008)
    assert step["eva.l7.mlp.down"] == (11008, 4096)
    assert "eva.l8.attn.q" not in step
    assert specs["step"]["counters"] == [
        "eva_window_positions", "eva_summary_positions",
        "eva_context_positions"]
    # what the slot table reserves at 16 slots x 32768, from the spec's own
    # feeds: 4.29 GB of window caches and 4.29 GB of summaries, an eighth of
    # what 32 caches at the rung would take
    reserved = sum(2 * 16 * (c.get("capacity") or 32768 // c["stride"])
                   * c["tail"][0] for c in specs["step"]["cache_feeds"])
    assert reserved == 16 * body["cache_bytes_per_row"] == 8589934592
    assert ops_count_evabyte.cache_bytes(body, 16, 32768) == (
        4294967296, 4294967296)
    assert 2 * body["parameters"] + reserved < 0.75 * 16e9


def test_traffic_file_is_the_issues_fixed_trace():
    mix = harness.load_json(_bench("traffic", "serve.bytes.sat.json"))
    assert mix["job"] == "serve"
    arrivals = mix["arrivals"]
    assert arrivals == {"kind": "backlog", "requests": 192, "block": 16,
                        "open_after": 16}
    assert mix["lengths"]["answer"] == {"median": 512, "sigma": 0.5,
                                        "min": 128, "max": 2048}
    prompt = dict(mix["lengths"]["prompt"])
    # ISSUE 46's fall-back halves the median and nothing else
    assert prompt.pop("median") in (3072, 1536)
    assert prompt == {"sigma": 1.0, "min": 256, "max": 28672}
    engine = mix["engine"]
    assert engine["ladder"] == [16] and engine["seq_ladder"] == [32768]
    assert engine["prefill_ladder"] == [1024]
    assert mix["check"] == {"sample": 4}
    requests = serve_traffic.schedule(mix, 320, 2 ** 31 + 5, 10.0)
    assert len(requests) == 192
    block = requests[:16]
    # every block is the same requests; every one fits the context rung
    assert [len(r.prompt) for r in requests[16:32]] == [
        len(r.prompt) for r in block]
    assert max(r.positions for r in requests) <= 32768
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 320
               for r in block)
    # a block holds prompts inside the first window and contexts that pass
    # two window boundaries and more: the compared logits read summaries
    assert min(len(r.prompt) for r in block) < 2048
    assert sum(r.positions > 4096 for r in block) >= 5
    assert max(r.positions for r in block) > 8 * 2048


# -- a whole rehearsal of the tiny twin -----------------------------------------

TIGHT = {"token_gap_max": 1e-3, "token_gap_mean": 1e-4}


def test_traced_rehearsal_of_the_tiny_twin_is_correct(tmp_path):
    _, manifest = tiny_evabyte.make_checkout(
        tmp_path, served_dtype="float32", limits=TIGHT)
    run = harness.Run(manifest, tiny_evabyte.CELL, 2 ** 31 + 11, 1.0, 1,
                      True, time.time())
    result = serve.run(run)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["answered"] >= 6
    rows = {r["name"]: r for r in result["compared"]}
    assert set(rows) == {"token_gap_max", "token_gap_mean",
                         "tokens_miscounted"}
    assert rows["tokens_miscounted"]["value"] == 0
    m = result["metrics"]      # a traced run's line: the layers' metrics
    assert m["executables"] == 2     # one step and one chunk executable
    # the program's own counters: contexts of 34-116 bytes against a window
    # of 32 in chunks of 4: a layer reads well under the context it holds
    assert 10 < m["eva_read_pct"] < 90
    assert m["cache_alias_pct"] == 100.0     # all four kinds handed over
    assert {"decode_step_ms", "predict_ms", "batch_occupancy_pct",
            "cache_live_pct", "warmup_s", "chunk_lane_fill_pct"} <= set(m)
    # a rehearsal has no device plane: no device number is made up
    assert not {"eva_attn_ms", "eva_attn_roofline", "eva_summary_ms",
                "decode_roofline", "cache_write_ms"} & set(m)


@pytest.mark.parametrize("how", ["zeroed", "shifted"])
def test_a_broken_summary_cache_does_not_come_out_correct(how, tmp_path):
    """The same rehearsal with a step program that hands layer 0's summary
    keys back as zeros, or one entry late: the comparison catches it."""
    _, manifest = tiny_evabyte.make_checkout(
        tmp_path, served_dtype="float32", limits=TIGHT, broken=how)
    run = harness.Run(manifest, tiny_evabyte.CELL, 2 ** 31 + 11, 1.0, 0,
                      True, time.time())
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
                        "eva_attention/attn.eva/dot_general",
                        "eva_summary/eva.summary/reduce",
                        "eva_attention/attn.eva/exp"),
            "chunk": hlo("eva_attention_chunk/attn.eva/dot_general",
                         "eva_summary_chunk/eva.summary/gather")}
    return serve_trace.ServeTrace([device], host, text, [modules])


def _ctx(**more):
    class Run:
        config = _config()

        @staticmethod
        def peaks():
            return {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    before = {"decode_steps": 100.0, "slot_live": 1600.0,
              "program_eva_window_positions": 0.0,
              "program_eva_summary_positions": 0.0,
              "program_eva_context_positions": 0.0}
    after = {"decode_steps": 103.0, "slot_live": 1648.0,
             # three steps, 16 rows at position 4999 in eight layers: 904
             # window entries and 256 summaries of 5000 positions a row
             "program_eva_window_positions": 3 * 8 * 16 * 904.0,
             "program_eva_summary_positions": 3 * 8 * 16 * 256.0,
             "program_eva_context_positions": 3 * 8 * 16 * 5000.0}
    ctx = {"trace": _recorded(), "profile_counters": (before, after),
           "window_counters": (before, after), "run": Run}
    ctx.update(more)
    return ctx


def test_new_readers_on_a_recorded_trace_and_recorded_counters():
    ctx = _ctx()
    ms = 1e-6   # the recorded durations are nanoseconds
    attn = (10 + 4 + 30 + 6) / 3 * ms
    assert _reader("eva_attn_ms").read(ctx) == pytest.approx(attn)
    assert _reader("eva_summary_ms").read(ctx) == pytest.approx(
        (20 + 40) / 3 * ms)
    assert _reader("eva_read_pct").read(ctx) == pytest.approx(
        100.0 * (904 + 256) / 5000)
    ops, nbytes = ops_count_evabyte.eva_attention_step(
        _config(), 16, 16 * 904, 16 * 256)
    least_ms = max(ops / 197e12, nbytes / 819e9) * 1e3
    assert _reader("eva_attn_roofline").read(ctx) == pytest.approx(
        100.0 * least_ms / attn)


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
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert ops_count_evabyte.layer_matrices(cfg) == layer == 202375168
    assert ops_count_evabyte.held(cfg) == 8
    assert ops_count_evabyte.parameter_count(cfg) == (
        8 * (layer + 2 * 4096 + 2 * 32 * 128) + 2 * 320 * 4096 + 4096)
    # an entry: a key row and a value row of 4096 bfloat16 values
    assert ops_count_evabyte.bytes_per_position(cfg) == 16384
    # what the query at a position reads: its window up to itself, and 128
    # summaries for every window before
    assert ops_count_evabyte.entries_read(cfg, 0) == (1, 0)
    assert ops_count_evabyte.entries_read(cfg, 2047) == (2048, 0)
    assert ops_count_evabyte.entries_read(cfg, 2048) == (1, 128)
    assert ops_count_evabyte.entries_read(cfg, 4999) == (904, 256)
    assert ops_count_evabyte.entries_read(cfg, 32767) == (2048, 1920)
    # the attention of one step: 16 sequences at position 4999
    ops, nbytes = ops_count_evabyte.eva_attention_step(
        cfg, 16, 16 * 904, 16 * 256)
    assert ops == 8 * (4.0 * 4096 * 16 * 1160)
    assert nbytes == 8 * (16384 * 16 * 1160 + 2 * 2 * 16 * 4096)
    # bytes bound it on a v5e: an entry is read once for its 32 heads' one
    # query each
    assert nbytes / 819e9 > ops / 197e12
    # the whole step: every matrix once, the entries the rows hold, a key
    # and value row a layer written and a summary every sixteenth step
    live, positions = 16, 16 * 4999
    ops, nbytes = ops_count_evabyte.decode_step(cfg, live, positions)
    matrices = 8 * layer + 4096 * 320
    assert nbytes == pytest.approx(
        2 * (matrices + 8 * 4 * 4096 + 4096)
        + 8 * (16384 * 16 * 1160 + 2 * 2 * 16 * 4096)
        + 16 * 8 * 16384 * (1 + 1 / 16), rel=1e-12)
    assert ops == pytest.approx(
        2 * live * matrices + 8 * (4.0 * 4096 * 16 * 1160), rel=1e-12)
    # the weights and the caches both weigh: 3.24 GB and 2.4 GB of bytes
    assert 6e-3 < nbytes / 819e9 < 8e-3
    assert ops / 197e12 < 0.1 * nbytes / 819e9
