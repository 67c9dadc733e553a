"""bench.py contract guards: every BASELINE config _build()s with the
fields the bench math needs (flops for MFU configs, the row-latency
roofline key for deepfm), and metric names stay unique per config."""

import paddle_tpu as fluid


def _specs(monkeypatch):
    import bench

    monkeypatch.delenv("BENCH_SEQ", raising=False)
    out = {}
    for model in ("transformer", "bert", "resnet50", "deepfm"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            fluid.unique_name.switch()
            spec, batch, metric, unit, per_example, _seq = bench._build(
                model, on_tpu=False)
        out[model] = (spec, batch, metric, unit, per_example)
    return out


def test_build_contract(monkeypatch):
    specs = _specs(monkeypatch)
    metrics = [v[2] for v in specs.values()]
    assert len(set(metrics)) == len(metrics), metrics
    for model, (spec, batch, metric, unit, per_example) in specs.items():
        assert batch > 0 and per_example
        assert spec.flops_per_example and spec.flops_per_example > 0, model
    # deepfm's vs_baseline basis reads this key (bench.py _bench_static)
    assert "row_latency_s_per_example" in specs["deepfm"][0].extras
    assert specs["deepfm"][0].extras["row_latency_s_per_example"] > 0


def test_serving_bench_record(monkeypatch):
    """The serving SLO harness emits the ISSUE 14 record shape: open-loop
    Poisson arrival config, the rate sweep with shed/deadline counters,
    and the decode-tier fields (ttft_p99 / tpot_p50 / slot_occupancy +
    the continuous-vs-one-shot A/B)."""
    import bench

    monkeypatch.setenv("BENCH_SERVING_REQUESTS", "16")
    monkeypatch.setenv("BENCH_SERVING_RATES", "150,300")
    monkeypatch.setenv("BENCH_SERVING_REPLICAS", "1")
    monkeypatch.setenv("BENCH_DECODE_REQUESTS", "10")
    # router tier kept tiny for tier-1: two fleets (1 then 2 worker
    # processes), one rate, 8 requests each
    monkeypatch.setenv("BENCH_ROUTER_WORKERS", "1,2")
    monkeypatch.setenv("BENCH_ROUTER_REQUESTS", "8")
    monkeypatch.setenv("BENCH_ROUTER_RATES", "60")
    monkeypatch.setenv("BENCH_PREFIX_REQUESTS", "6")
    monkeypatch.setenv("BENCH_SPEC_REQUESTS", "4")
    rec = bench._bench_serving(on_tpu=False)
    assert rec["metric"] == "serving_requests_per_sec"
    assert rec["unit"] == "requests/sec"
    assert rec["value"] > 0
    assert rec["vs_baseline"] > 0
    # self-describing record (ROADMAP item 5): the knobs that shaped the
    # number ride in the line — arrival process included
    assert rec["config"]["arrival"] == "poisson-open-loop"
    assert rec["config"]["replicas"] == 1
    assert rec["config"]["p99_budget_s"] > 0
    assert rec["config"]["requests_per_rate"] == 16
    # the rate sweep: one row per rate with the overload counters
    assert [r["rate"] for r in rec["rate_sweep"]] == [150.0, 300.0]
    for row in rec["rate_sweep"]:
        assert {"rate", "completed_rps", "p99_s", "rejected", "expired",
                "met_slo"} <= set(row)
    # router tier (ISSUE 16): the multi-process front door's per-N
    # scaling rows with the door's reliability counters — the SLO
    # harness contract for the socket path
    router = rec["router"]
    assert router["mode"] == "multiprocess-router"
    assert router["worker_counts"] == [1, 2]
    assert router["p99_budget_s"] > 0
    assert "scaling_vs_1worker" in router and "scaling_claim" in router
    assert [r["workers"] for r in router["rows"]] == [1, 2]
    for row in router["rows"]:
        assert {"workers", "best_rps", "p99_s", "rate_sweep", "door_shed",
                "rerouted", "respawns", "deadline_refused"} <= set(row)
        assert [s["rate"] for s in row["rate_sweep"]] == [60.0]
        for s in row["rate_sweep"]:
            assert {"rate", "completed_rps", "p99_s", "rejected",
                    "expired", "errors", "met_slo"} <= set(s)
        # a healthy smoke run earns its numbers without degradation
        assert row["respawns"] == 0 and row["deadline_refused"] == 0
    # decode-tier gauges (continuous batcher)
    assert rec["ttft_p99"] is not None and rec["ttft_p99"] > 0
    assert rec["tpot_p50"] is not None and rec["tpot_p50"] > 0
    assert rec["slot_occupancy"] is not None
    assert 0 < rec["slot_occupancy"] <= 1.0
    dec = rec["decode"]
    assert dec["requests"] == 10
    assert dec["continuous_rps"] > 0 and dec["oneshot_rps"] > 0
    assert dec["speedup"] > 0 and dec["tokens_per_sec"] > 0
    # ISSUE 20: the shared-prefix TTFT A/B — the CPU smoke must MEASURE
    # a ratio > 1 (the TTFT-collapse acceptance), with the cache's own
    # evidence riding the record
    pab = rec["prefix_ab"]
    assert pab["requests"] == 6 and pab["shared_prefix_len"] > 0
    assert pab["prefix_hits"] > 0 and pab["prefix_tokens_reused"] > 0
    assert pab["ttft_p50_nocache_s"] > 0 and pab["ttft_p50_cache_s"] > 0
    assert pab["ttft_ratio"] is not None and pab["ttft_ratio"] > 1.0
    assert "claim" in pab
    # ISSUE 20: the speculative A/B — bitwise parity is enforced inside
    # the bench itself; the CPU speedup is recorded as the honest
    # negative result (the latency claim needs TPU dispatch costs)
    sab = rec["spec_ab"]
    assert sab["requests"] == 4 and sab["draft_k"] >= 2
    assert sab["bitwise_parity"] is True
    assert sab["plain_rps"] > 0 and sab["spec_rps"] > 0
    assert sab["speedup"] is not None
    assert sab["spec_accept_rate"] is None \
        or 0.0 <= sab["spec_accept_rate"] <= 1.0
    assert sab["decode_steps_spec"] < sab["decode_steps_plain"]
    assert "negative result" in sab["claim"]
    # reliability counters ride along and are all ZERO in a healthy run —
    # a nonzero means the number was earned under degradation
    rel = rec["reliability"]
    assert set(rel) == {"requests_shed", "requests_retried",
                        "replicas_evicted", "workers_respawned"}
    assert all(v == 0 for v in rel.values()), rel
    # ISSUE 17: every record carries its telemetry view; untraced runs
    # say so explicitly (no trace path, no spans)
    assert rec["obs"] == {"traced": False, "trace_path": None,
                          "span_count": 0}


def test_streaming_bench_record(monkeypatch):
    """The streaming train-to-serve harness emits the ISSUE 18 record
    shape: ingest rows/sec headline, publish period, live swap count,
    publish-to-swap staleness p50/p99, and the serving p99 over requests
    in flight during a swap — with the CPU run carrying its honest
    negative-result throughput claim."""
    import bench

    monkeypatch.setenv("BENCH_STREAMING_ROWS", "600")
    monkeypatch.setenv("BENCH_STREAMING_BATCH", "16")
    monkeypatch.setenv("BENCH_STREAMING_PUBLISH_EVERY", "10")
    monkeypatch.setenv("BENCH_STREAMING_REPLICAS", "2")
    rec = bench._bench_streaming(on_tpu=False)
    assert rec["metric"] == "streaming_ingest_rows_per_sec"
    assert rec["unit"] == "rows/sec"
    assert rec["value"] > 0
    cfg = rec["config"]
    assert cfg["rows"] == 600 and cfg["batch"] == 16
    assert cfg["publish_every_steps"] == 10 and cfg["replicas"] == 2
    assert cfg["steps"] > 0 and cfg["p99_budget_s"] > 0
    # the swap plane actually ran: publishes happened on a cadence and
    # at least one landed as a LIVE hot-swap with a staleness sample
    assert rec["publish_period_s_mean"] is not None
    assert rec["publish_period_s_mean"] > 0
    assert rec["swap_count"] >= 1
    assert rec["staleness_p50_s"] is not None
    assert rec["staleness_p50_s"] >= 0
    assert rec["staleness_p99_s"] >= rec["staleness_p50_s"]
    # serving stayed up throughout; during-swap p99 is the zero-drop
    # hot-swap claim in numbers (None only if no request overlapped a
    # swap window — then the overall p99 still pins liveness)
    assert rec["serving_p99_s"] is not None and rec["serving_p99_s"] > 0
    assert (rec["serving_p99_during_swap_s"] is None
            or rec["serving_p99_during_swap_s"] > 0)
    assert rec["during_swap_requests"] >= 0
    prox = rec["accuracy_proxy"]
    assert prox["eval_loss_first"] is not None
    assert prox["eval_loss_last"] is not None
    assert prox["improved"] in (True, False)
    # ISSUE 19 fleet block: takeover can't beat the lease TTL, a cold
    # 2-target fleet converges through prepare+commit with zero skew,
    # and a cursor resume replays a bounded, counted row tail
    fleet = rec["fleet"]
    assert set(fleet) == {"lease_ttl_s", "reassign_takeover_s",
                          "partitions_reassigned", "fleet_targets",
                          "fleet_version", "commit_convergence_s",
                          "fleet_version_skew", "resume_replayed_rows"}
    assert fleet["reassign_takeover_s"] >= fleet["lease_ttl_s"] > 0
    assert fleet["partitions_reassigned"] == 2
    assert fleet["fleet_targets"] == 2 and fleet["fleet_version"] is not None
    assert fleet["commit_convergence_s"] > 0
    assert fleet["fleet_version_skew"] == 0
    assert 0 <= fleet["resume_replayed_rows"] <= 64  # <= one chunk
    # healthy run: every reliability counter is zero
    rel = rec["reliability"]
    assert set(rel) == {"bad_publishes", "publish_failures",
                        "bad_chunks", "serving_errors"}
    assert all(v == 0 for v in rel.values()), rel
    # the CPU record says out loud that rows/sec is not a TPU claim
    assert rec["throughput_claim"].startswith("negative-result on CPU")
    assert rec["obs"] == {"traced": False, "trace_path": None,
                          "span_count": 0}


def test_seq_override_metric_suffix(monkeypatch):
    import bench

    monkeypatch.delenv("BENCH_SEQ", raising=False)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fluid.unique_name.switch()
        _, _, metric, _, _, seq = bench._build("transformer", on_tpu=False,
                                               seq_override=128)
    assert metric == "transformer_base_seq128_tokens_per_sec_per_chip"
    assert seq == 128


def _tiny_build(model, on_tpu, seq_override=None):
    """A seconds-fast stand-in for bench._build that preserves the
    record-assembly contract (metric/unit/flops/seq_len) so the
    floor-constant tests can exercise the REAL _bench_static plumbing
    without compiling the full configs."""
    main = fluid.default_main_program()
    x = fluid.layers.data("x", shape=[8])
    label = fluid.layers.data("label", shape=[1], dtype="int32")
    logits = fluid.layers.fc(x, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    from paddle_tpu.models.common import FeedSpec, ModelSpec

    spec = ModelSpec(loss,
                     feeds={"x": FeedSpec([8]),
                            "label": FeedSpec([1], "int32", 0, 4)},
                     flops_per_example=1e5, tokens_per_example=8)
    assert main is loss.block.program
    seq_len = seq_override if model == "transformer" else None
    name = {"resnet50": "resnet50_images_per_sec_per_chip",
            "transformer": "transformer_base_seq%s_tokens_per_sec_per_chip"
                           % seq_override}[model]
    per_example = 8 if model == "transformer" else 1
    return spec, 4, name, "x/sec", per_example, seq_len


def test_resnet50_record_carries_rederived_ceiling(monkeypatch):
    """ISSUE 12 floor pin: the resnet50 bench record must carry the HBM
    ceiling constant SOURCED from CHIP_CEILING.json's matrix-derived
    ``hbm_operative_gbs`` (never a hardcoded 552.2), plus the fusion
    state that produced the number."""
    import bench

    ceil = bench._chip_ceiling()
    assert ceil, "CHIP_CEILING.json missing"
    assert "hbm_matrix" in ceil and "rmw" in ceil["hbm_matrix"], \
        "ceiling record predates the copy/triad matrix re-derivation"
    measured = [v for v in ceil["hbm_matrix"].values() if v is not None]
    assert ceil["hbm_operative_gbs"] == max(measured), \
        "operative rate must be the max over measured matrix entries"

    monkeypatch.setattr(bench, "_build", _tiny_build)
    monkeypatch.setenv("BENCH_STEPS", "1")
    rec = bench._bench_static("resnet50", on_tpu=False)
    cfg = rec["config"]
    assert cfg["hbm_ceiling_source"] == "CHIP_CEILING.json"
    assert cfg["hbm_gbs"] == ceil["hbm_operative_gbs"]
    assert isinstance(cfg["fused_conv"], bool)
    # ISSUE 15: every static-graph bench line carries the cost engine's
    # re-derivable model of the measured program
    sm = cfg["static_model"]
    assert sm["flops_per_step"] > 0 and sm["hbm_bytes_per_step"] > 0
    assert sm["roofline_ms_per_step"] > 0
    assert sm["bound"] in ("compute", "hbm", "rows")
    assert sm["ceilings_source"] == "CHIP_CEILING.json"
    assert sm["row_floor_source"] in ("ROW_OP_FLOORS.json", "builtin-r5")
    # the sourcing is live, not a copied literal
    monkeypatch.setattr(bench, "_chip_ceiling",
                        lambda: {"hbm_operative_gbs": 777.0})
    rec2 = bench._bench_static("resnet50", on_tpu=False)
    assert rec2["config"]["hbm_gbs"] == 777.0


def test_bench_trace_obs_field(monkeypatch, tmp_path):
    """ISSUE 17: under BENCH_TRACE=1 the record's ``obs`` field points at
    a real trace capture: executor.run spans for the measured steps,
    each with its host phases as children."""
    import json

    import bench
    from paddle_tpu.obs import trace

    monkeypatch.setattr(bench, "_build", _tiny_build)
    monkeypatch.setenv("BENCH_STEPS", "1")
    monkeypatch.setenv("BENCH_TRACE", "1")
    monkeypatch.setenv("BENCH_TRACE_DIR", str(tmp_path))
    try:
        rec = bench._bench_static("resnet50", on_tpu=False)
    finally:
        trace.stop()
    obs = rec["obs"]
    assert obs["traced"] is True
    assert obs["span_count"] > 0
    assert obs["trace_path"].startswith(str(tmp_path))
    with open(obs["trace_path"], encoding="utf-8") as f:
        spans = [json.loads(line) for line in f if line.strip()]
    # warmup(2) + BENCH_STEPS(1) executor.run spans, plus startup
    assert sum(1 for s in spans if s["name"] == "executor.run") >= 3
    # untraced runs reset the gauge: a second record doesn't inherit the
    # first's MFU reading
    monkeypatch.setenv("BENCH_TRACE", "0")
    rec2 = bench._bench_static("resnet50", on_tpu=False)
    assert rec2["obs"] == {"traced": False, "trace_path": None,
                           "span_count": 0}


def test_seq2048_record_carries_stream_config(monkeypatch):
    """The long-context record is self-describing about the streaming
    path: flash block geometry + whether the packed copy-free path (vs
    the legacy head-split one) produced the number."""
    import bench

    monkeypatch.setattr(bench, "_build", _tiny_build)
    monkeypatch.setenv("BENCH_STEPS", "1")
    rec = bench._bench_static("transformer", on_tpu=False,
                              seq_override=1024)
    cfg = rec["config"]
    assert cfg["flash_block"] == 512
    assert cfg["packed_stream"] is True  # bf16 seq-1024 fits the gate
    # and so does seq-2048 since the kernels take one lane window of the
    # packed heads a program (ISSUE 29); seq-4096 is past the chip's VMEM
    rec = bench._bench_static("transformer", on_tpu=False,
                              seq_override=2048)
    assert rec["config"]["packed_stream"] is True
    rec = bench._bench_static("transformer", on_tpu=False,
                              seq_override=4096)
    assert rec["config"]["packed_stream"] is False


def test_batch_rounding_warns(monkeypatch):
    """The transformer token-budget batch auto-scale must WARN when it
    rounds (ROADMAP item 5 standing bug: it used to round silently,
    making vs_baseline numbers non-re-derivable across seq lengths)."""
    import warnings

    import bench

    monkeypatch.delenv("BENCH_SEQ", raising=False)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fluid.unique_name.switch()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            # 1000 does not divide the 32768-token budget -> rounds
            bench._build("transformer", on_tpu=True, seq_override=1000)
    msgs = [str(w.message) for w in caught
            if issubclass(w.category, RuntimeWarning)]
    assert any("ROUNDED DOWN" in m for m in msgs), msgs


def test_row_floor_constants_are_sourced(tmp_path):
    """ISSUE 13 floor pin: DeepFM's roofline constants come from
    ROW_OP_FLOORS.json (the CHIP_CEILING.json pattern), live — a
    re-measured file changes the spec, a missing one falls back to the
    round-5 builtins with the source saying so."""
    import json

    from paddle_tpu.models import deepfm as deepfm_mod

    # the committed record drives the default (and carries the pending
    # pallas A/B slots — the committed-negative-result form)
    g, s, src = deepfm_mod.row_op_floors()
    assert src == "ROW_OP_FLOORS.json"
    import os

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(deepfm_mod.__file__))))
    with open(os.path.join(repo_root, "ROW_OP_FLOORS.json")) as f:
        rec = json.load(f)
    assert (g, s) == (rec["gather_ns_per_row"], rec["scatter_ns_per_row"])
    assert "s_pallas" in rec["matrix_ns_per_row"]
    # live sourcing, not a copied literal
    alt = tmp_path / "ROW_OP_FLOORS.json"
    alt.write_text(json.dumps({"gather_ns_per_row": 1.5,
                               "scatter_ns_per_row": 4.0}))
    assert deepfm_mod.row_op_floors(str(alt)) == (1.5, 4.0,
                                                  "ROW_OP_FLOORS.json")
    # fallback: missing/corrupt file -> builtin constants, source honest
    g2, s2, src2 = deepfm_mod.row_op_floors(str(tmp_path / "missing.json"))
    assert (g2, s2) == (deepfm_mod._GATHER_NS_PER_ROW,
                        deepfm_mod._SCATTER_NS_PER_ROW)
    assert src2 == "builtin-r5"


def test_deepfm_spec_extras_carry_floor_provenance(monkeypatch):
    specs = _specs(monkeypatch)
    extras = specs["deepfm"][0].extras
    rf = extras["row_floors"]
    assert rf["source"] in ("ROW_OP_FLOORS.json", "builtin-r5")
    expected = 26 * (rf["gather_ns_per_row"]
                     + rf["scatter_ns_per_row"]) * 1e-9
    assert abs(extras["row_latency_s_per_example"] - expected) < 1e-12


def test_deepfm_record_is_self_describing(monkeypatch):
    """The deepfm bench JSON line carries the ISSUE 13 fields: lookup
    strategy (alltoall/psum), the analytic comm-bytes model for both
    formulations, the scatter-kernel choice, and the sourced floor
    constants."""
    import bench

    monkeypatch.setenv("BENCH_STEPS", "1")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fluid.unique_name.switch()
        rec = bench._bench_static("deepfm", on_tpu=False)
    cfg = rec["config"]
    assert cfg["emb_strategy"] == "alltoall"  # bench id count >> mp
    cm = cfg["emb_comm_model"]
    assert cm["mp"] == 8 and cm["n_ids"] == cfg["batch"] * 26
    # the headline claim in numbers: psum total volume is O(mp) worse
    assert cm["psum_total_bytes"] > 3 * cm["alltoall_total_bytes"]
    assert cfg["scatter_kernel"] in ("pallas_rowbin", "xla_at_add")
    assert cfg["row_floors"]["source"] in ("ROW_OP_FLOORS.json",
                                           "builtin-r5")
    # ISSUE 15 static model on the REAL deepfm program: row-bound, with
    # the engine's row counts matching the bench's id count
    sm = cfg["static_model"]
    assert sm["bound"] == "rows"
    assert sm["row_reads"] == cfg["batch"] * 26
    assert sm["row_writes"] == cfg["batch"] * 26
    assert sm["uncosted_ops"] == []
