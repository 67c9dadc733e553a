"""The serving job at tiny size on the CPU (rehearsals: the numbers mean
nothing, the control flow and the checks are the real ones): the schedule's
determinism, open-loop books, the reference against ``transformers``' OPT
and against the program through its cache, whole runs of both cells, the
fp8 control and a broken decode step coming out not correct, the trace
arithmetic, the operation counts and the manifest's entries."""

import json
import os
import subprocess
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
import tiny_serve  # noqa: E402
from benchmark import harness, ops_count_opt, serve_control  # noqa: E402
from benchmark import serve_trace  # noqa: E402
from benchmark.jobs import serve, serve_traffic  # noqa: E402

CHAT, SAT = "opt1p3b.serve.chat", "opt1p3b.serve.chat.sat"
SIZES = tiny_serve.SIZES


def _bench(*parts):
    return os.path.join(ROOT, "benchmark", *parts)


reference = harness.load_module(_bench("reference", "opt-1.3b.py"))
precision = harness.load_module(_bench("reference", "precision.py"))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_serve.make_checkout(tmp_path_factory.mktemp("serve"))


def in_process(manifest, workload, seed, trace=0, seconds=1.0):
    run = harness.Run(manifest, workload, seed, seconds, trace, True,
                      time.time())
    return run, serve.run(run)


def by_name(rows):
    return {r["name"]: r for r in rows}


# -- the schedule -------------------------------------------------------------

def _mix(name):
    return harness.load_json(_bench("traffic", name + ".json"))


def test_schedule_is_the_same_for_the_same_seed_and_large_seeds_work():
    a = serve_traffic.schedule(_mix("serve.chat"), 50272, 2 ** 31 + 7, 10)
    b = serve_traffic.schedule(_mix("serve.chat"), 50272, 2 ** 31 + 7, 10)
    assert [r.at for r in a] == [r.at for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50272 for r in a)


def test_the_window_holds_the_mixs_rate_and_lengths():
    mix = _mix("serve.chat")
    ramp, rate = mix["arrivals"]["ramp_s"], mix["arrivals"]["rate_per_s"]
    a = [r for r in serve_traffic.schedule(mix, 50272, 1, 10)
         if ramp <= r.at < ramp + 10]
    assert len(a) == round(rate * 10)
    assert [r.at for r in a] == sorted(r.at for r in a)
    # the quantiles of the clipped log-normals: the median is the mix's
    assert abs(np.median([len(r.prompt) for r in a])
               - mix["lengths"]["prompt"]["median"]) <= 12
    rule = mix["lengths"]
    for r in a:
        assert rule["prompt"]["min"] <= len(r.prompt) <= rule["prompt"]["max"]
        assert rule["answer"]["min"] <= r.max_new <= rule["answer"]["max"]
    # the longest request fits the top context rung
    assert max(r.positions for r in a) <= max(mix["engine"]["seq_ladder"])


def test_a_seed_draws_the_tokens_and_keeps_the_timetable():
    """A mix is one fixed trace: the same lengths at the same times for
    every seed, other token ids."""
    for name in ("serve.chat", "serve.chat.sat"):
        mix = _mix(name)
        a = serve_traffic.schedule(mix, 50272, 1, 10)
        b = serve_traffic.schedule(mix, 50272, 2 ** 31 + 5, 10)
        assert [(r.at, len(r.prompt), r.max_new) for r in a] == \
            [(r.at, len(r.prompt), r.max_new) for r in b]
        assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_a_backlog_is_due_at_once():
    rs = serve_traffic.schedule(_mix("serve.chat.sat"), 50272, 3, 10)
    assert len(rs) == _mix("serve.chat.sat")["arrivals"]["requests"]
    assert all(r.at == 0.0 for r in rs)


# -- the books ----------------------------------------------------------------

def _request(i, at, done, tokens=4, max_new=4, error=None, submitted=None):
    r = serve_traffic.Request(i, at, np.zeros(5, np.int64), max_new)
    r.done, r.error = done, error
    r.submitted = submitted if submitted is not None else at
    r.tokens = None if tokens is None else np.zeros(tokens, np.int64)
    return r


def test_open_loop_books_time_from_the_schedule_not_from_submit():
    t0, w0, w1 = 100.0, 101.0, 111.0
    rs = [_request(0, 0.5, 100.9),                    # the ramp's
          _request(1, 1.5, 103.5, submitted=103.0),   # submitted late
          _request(2, 2.0, 104.0),
          _request(3, 9.0, 150.0),                    # never in time
          _request(4, 9.5, 112.0, tokens=3),          # one token short
          _request(5, 10.5, None, tokens=None),       # never answered
          _request(6, 3.0, 105.0, tokens=None, error=RuntimeError("shed")),
          _request(7, 11.5, 112.5)]                   # the tail's
    counted, failed, answered = serve.account(rs, "poisson", t0, w0, w1,
                                              w1 + serve.DRAIN_S)
    assert [r.index for r in counted] == [1, 2, 3, 4, 5, 6]
    assert sorted(r.index for r in failed) == [3, 4, 5, 6]
    assert [r.index for r in answered] == [1, 2]
    m = serve.client_numbers(counted, failed, answered, t0, 10.0)
    # request 1 waited 2.0 s from its schedule, 0.5 s from its submit
    assert m["request_mean_ms"] == pytest.approx(2000.0)
    assert m["answers_tokens_per_s"] == pytest.approx(0.8)
    assert m["token_ms_mean"] == pytest.approx(500.0)
    assert m["request_p90_ms"] == float("inf")  # a failed one is the tail


def test_backlog_books_count_what_was_answered_inside_the_window():
    t0, w0, w1 = 0.0, 3.0, 13.0
    rs = [_request(0, 0.0, 2.0), _request(1, 0.0, 4.0),
          _request(2, 0.0, 12.9), _request(3, 0.0, 13.1),
          _request(4, 0.0, 5.0, tokens=None, error=RuntimeError("x")),
          _request(5, 0.0, None, tokens=None)]
    counted, failed, answered = serve.account(rs, "backlog", t0, w0, w1,
                                              w1 + serve.DRAIN_S)
    assert sorted(r.index for r in counted) == [1, 2, 4]
    assert [r.index for r in failed] == [4]
    assert serve.client_numbers(counted, failed, answered, t0, 10.0)[
        "answers_tokens_per_s"] == pytest.approx(0.8)


class _Engine:
    """Counters as ``ServingMetrics`` registers them, moved by hand."""

    def __init__(self):
        from paddle_tpu.obs.registry import Registry

        self.metrics_ = self
        self.registry = Registry()
        for name in serve.Ticks.NAMES:
            self.registry.counter("paddle_tpu_serving_" + name)

    def quantum(self, tokens=0, prompt=0, live=0):
        counts = (1, tokens, prompt, 1 if prompt else 0, live)
        for name, n in zip(serve.Ticks.NAMES, counts):
            self.registry.get("paddle_tpu_serving_" + name).inc(n)


def _ticks(rows):
    """``Ticks`` with the rows (clock, tokens sampled, prompt tokens
    ingested, live slots in that quantum) put in by hand, after an empty
    first row."""
    engine = _Engine()
    ticks = serve.Ticks(engine)
    ticks.rows[0] = (0.0,) + ticks.rows[0][1:]
    for at, *counts in rows:
        engine.quantum(*counts)
        ticks.read()
        ticks.rows[-1] = (at,) + ticks.rows[-1][1:]
    return ticks


def test_tokens_per_s_spreads_a_quantums_tokens_over_the_quantum():
    # steps of 4 tokens end at 1, 2, 3, 5 (a chunk of no token ran 3-4)
    ticks = _ticks([(1.0, 4, 0), (2.0, 4, 0), (3.0, 4, 0), (4.0, 0, 8),
                    (5.0, 4, 0)])
    assert ticks.tokens_per_s(1.0, 3.0) == pytest.approx(4.0)
    # half of the step that ends at 2, all of the next, none of the chunk
    assert ticks.tokens_per_s(1.5, 3.5) == pytest.approx(3.0)
    # an edge that moves a little moves the reading a little
    assert ticks.tokens_per_s(1.5, 3.01) == pytest.approx(6.0 / 1.51)
    assert ticks.tokens_per_s(1.5, 2.99) == pytest.approx(5.96 / 1.49)


def test_live_positions_are_what_was_ingested_less_what_answers_held():
    # two requests of 6 + 3: a step feeds each its first prompt token, a
    # chunk the next 4, and the sixth rides the step that samples the first
    # answer token
    ticks = _ticks([(1.0, 0, 0, 2), (1.5, 0, 8, 2), (2.0, 2, 0, 2),
                    (3.0, 2, 0, 2), (4.0, 2, 0, 2), (5.0, 0, 0, 0)])
    rs = [_request(0, 0.0, 3.9, tokens=3, max_new=3),
          _request(1, 0.0, 3.95, tokens=3, max_new=3)]
    for r in rs:
        r.prompt = np.zeros(6, np.int64)
    at, live = ticks.live_positions(rs)
    assert list(live) == [0, 2, 10, 12, 14, 0, 0]
    assert ticks.miscounted(rs, slots=4) == 0
    # a token the engine counted and no client holds, and the reverse
    rs[1].tokens = rs[1].tokens[:2]
    assert ticks.miscounted(rs, slots=4) == 1
    rs[1].tokens = np.zeros(5, np.int64)
    assert ticks.miscounted(rs, slots=4) == 2
    # a request still in flight may have had its whole answer sampled
    rs[1].tokens, rs[1].done = None, None
    assert ticks.miscounted(rs, slots=4) == 0


# -- the reference ------------------------------------------------------------

def _weights(seed=0, dtype=np.float32):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    d, f = SIZES["hidden_size"], SIZES["ffn_dim"]
    shapes = {"opt.embed_tokens": (SIZES["vocab_size"], d),
              "opt.embed_positions":
                  (SIZES["max_position_embeddings"] + 2, d),
              "opt.final_ln.w": (d,), "opt.final_ln.b": (d,)}
    for i in range(SIZES["num_hidden_layers"]):
        for leaf in reference.LAYER_LEAVES:
            part, kind = leaf.split(".")
            wide = {"fc1": (d, f), "fc2": (f, d)}.get(part, (d, d))
            shapes["opt.l%d.%s" % (i, leaf)] = wide if kind == "w" and \
                not part.endswith("_ln") else (wide[1],)
    out = {}
    for name, shape in shapes.items():
        w = 0.2 * rng.standard_normal(shape).astype(np.float32)
        if name.endswith("_ln.w"):
            w = 1 + w
        out[name] = jnp.asarray(w).astype(dtype)
    return out


def test_reference_is_transformers_opt():
    torch = pytest.importorskip("torch")
    from transformers import OPTConfig, OPTForCausalLM

    config = OPTConfig(
        do_layer_norm_before=True, activation_function="relu", dropout=0.0,
        word_embed_proj_dim=SIZES["hidden_size"], enable_bias=True,
        layer_norm_elementwise_affine=True, **SIZES)
    model = OPTForCausalLM(config).eval()
    weights = _weights(seed=5)
    t = lambda n: torch.tensor(np.asarray(weights[n]))  # noqa: E731
    state = {"model.decoder.embed_tokens.weight": t("opt.embed_tokens"),
             "model.decoder.embed_positions.weight":
                 t("opt.embed_positions"),
             "model.decoder.final_layer_norm.weight": t("opt.final_ln.w"),
             "model.decoder.final_layer_norm.bias": t("opt.final_ln.b"),
             "lm_head.weight": t("opt.embed_tokens")}
    theirs = {"attn_ln": "self_attn_layer_norm", "q": "self_attn.q_proj",
              "k": "self_attn.k_proj", "v": "self_attn.v_proj",
              "out": "self_attn.out_proj", "ffn_ln": "final_layer_norm",
              "fc1": "fc1", "fc2": "fc2"}
    for i in range(SIZES["num_hidden_layers"]):
        for leaf in reference.LAYER_LEAVES:
            part, kind = leaf.split(".")
            w = t("opt.l%d.%s" % (i, leaf))
            if kind == "w" and not part.endswith("_ln"):
                w = w.T  # torch keeps [out, in]
            state["model.decoder.layers.%d.%s.%s" % (
                i, theirs[part], "weight" if kind == "w" else "bias")] = w
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    tokens = np.random.default_rng(1).integers(0, SIZES["vocab_size"], 23)
    with torch.no_grad():
        want = model(torch.tensor(tokens)[None]).logits[0].numpy()
    got = np.asarray(reference.logits(weights, tokens, SIZES,
                                      precision.exact))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def _predictors(weights, dtype):
    import paddle_tpu as fluid
    from paddle_tpu.inference import ProgramPredictor

    builder = harness.load_module(_bench("builders", "opt.py"))
    scope, out = fluid.Scope(), {}
    for name, value in weights.items():
        scope.set(name, value)
    for kind in ("step", "chunk"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            fetch, spec = getattr(builder, kind)(dtype=dtype, **SIZES)
        feeds = [spec["token_feed"], spec["pos_feed"]] + [
            c["feed"] for c in spec["cache_feeds"]]
        out[kind] = (ProgramPredictor(main, feeds, fetch, scope=scope), spec)
    return out


def test_program_through_its_cache_gives_the_references_logits():
    """Prefill by a chunk, then decoding a token at a time through the
    cache, two rows at different fill levels: logits, not tokens."""
    weights = _weights(seed=9)
    preds = _predictors(weights, "float32")
    rng = np.random.default_rng(2)
    rows = [rng.integers(0, SIZES["vocab_size"], n) for n in (11, 6)]
    cap, d, k = 32, SIZES["hidden_size"], 8
    (chunk, cspec), (step, sspec) = preds["chunk"], preds["step"]
    caches = {c["feed"]: np.zeros((2, cap, d), np.float32)
              for c in sspec["cache_feeds"]}

    def carry(outs, spec, pred):
        names = list(pred.fetch_names)
        for c in spec["cache_feeds"]:
            caches[c["feed"]] = outs[names.index(c["fetch"])]
        return np.asarray(outs[names.index(spec["logits_fetch"])])

    tok = np.zeros((2, k), np.int64)
    pos = np.full((2, k), cap, np.int32)       # pad lanes write nowhere
    for i, row in enumerate(rows):
        n = min(k, len(row) - 1)
        tok[i, :n], pos[i, :n] = row[:n], np.arange(n)
    seen = [min(k, len(r) - 1) for r in rows]
    carry(chunk.run(dict(caches, tok_chunk=tok, chunk_pos=pos),
                    return_numpy=False), cspec, chunk)
    want = [np.asarray(reference.logits(weights, r, SIZES, precision.exact))
            for r in rows]
    compared = 0
    while any(s < len(r) for s, r in zip(seen, rows)):
        live = [s < len(r) for s, r in zip(seen, rows)]
        toks = np.array([r[min(s, len(r) - 1)] for s, r in zip(seen, rows)])
        at = np.array([s if a else cap for s, a in zip(seen, live)],
                      np.int32)
        logits = carry(step.run(dict(caches, tok_ids=toks, pos=at),
                                return_numpy=False), sspec, step)
        for i, alive in enumerate(live):
            if alive:
                np.testing.assert_allclose(logits[i], want[i][seen[i]],
                                           atol=2e-4, rtol=2e-4)
                seen[i] += 1
                compared += 1
    assert compared == (11 - 8) + (6 - 5)


# -- whole runs ---------------------------------------------------------------

@pytest.mark.parametrize("cell,seconds", [("tiny.serve.chat", 2.0),
                                          ("tiny.serve.chat.sat", 1.0)])
def test_rehearsal_of_the_tiny_cell_is_correct(checkout, cell, seconds):
    run, result = in_process(checkout[1], cell, seed=2 ** 31 + 11,
                             seconds=seconds)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    rows = by_name(result["compared"])
    assert set(rows) == {"token_gap_max", "token_gap_mean",
                         "tokens_miscounted"}
    assert rows["token_gap_max"]["value"] >= rows["token_gap_mean"]["value"]
    assert rows["tokens_miscounted"]["value"] == 0
    assert {"serve_tokens_per_s", "answers_tokens_per_s",
            "setup_s"} <= set(result["metrics"])
    assert result["metrics"]["serve_tokens_per_s"] > 0
    if cell.endswith("chat"):
        rate = run.traffic["arrivals"]["rate_per_s"]
        assert result["attempted"] == round(rate * seconds)
        # every answer is in: the books of cache positions come to rest
        # (to a few where the reader saw several quanta at once)
        assert abs(result["live_at_rest"]) <= 8


def test_traced_rehearsal_reads_the_spans_and_the_counters(checkout):
    _, result = in_process(checkout[1], "tiny.serve.chat.sat", seed=5,
                           trace=1, seconds=1.0)
    m = result["metrics"]
    assert {"decode_step_ms", "predict_ms", "sample_deliver_ms",
            "batch_occupancy_pct", "cache_live_pct", "first_step_s",
            "server_ttft_mean_ms", "server_tpot_mean_ms", "warmup_s",
            "executables", "import_s"} <= set(m)
    assert 0 < m["cache_live_pct"] < 100 and m["first_step_s"] <= m[
        "warmup_s"]
    assert m["predict_ms"] + m["sample_deliver_ms"] == pytest.approx(
        m["decode_step_ms"], rel=0.05)
    assert m["executables"] == 3 and 99.0 < m["batch_occupancy_pct"] <= 100
    # a rehearsal has no device plane: no device number is made up
    assert not {"decode_device_ms", "decode_roofline", "cache_write_ms",
                "cached_attn_ms", "decode_matmul_ms",
                "prefill_ms_per_ktok"} & set(m)
    assert result["correct"]


def test_whole_run_prints_the_contracts_keys_last(checkout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               BENCH_RUN="ignored")
    done = subprocess.run(
        [sys.executable, os.path.join(checkout[0], "benchmark", "run.py"),
         "--manifest", checkout[1], "--workload", "tiny.serve.chat",
         "--seed", "4242424242", "--seconds", "1.5", "--trace", "0",
         "--rehearse"], capture_output=True, text=True, timeout=600,
        env=env, cwd=checkout[0])
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    assert set(line["metrics"]) == {"setup_s"}  # no device metric
    # each number compared, beside its limit, ends standard error and is
    # the last key of the result line
    names = ["token_gap_max", "token_gap_mean", "tokens_miscounted"]
    last = done.stderr.strip().splitlines()[-3:]
    assert [l.split()[1] for l in last] == names
    assert all(l.startswith("compared ") and " limit " in l for l in last)
    assert list(line)[-1] == "compared" and list(line["compared"]) == names
    assert all(set(v) == {"value", "limit"}
               for v in line["compared"].values())


def test_fp8_control_comes_out_not_correct_and_the_program_correct(checkout):
    control, program = serve_control.control(
        checkout[1], "tiny.serve.chat", seed=7, seconds=1.5, rehearse=True)
    assert all(r["ok"] for r in program), program
    assert not all(r["ok"] for r in control), control
    assert by_name(control)["token_gap_mean"]["value"] > \
        3 * by_name(program)["token_gap_mean"]["value"]


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_decode_step_comes_out_not_correct(checkout, fault,
                                                  monkeypatch):
    """The rest of a run, with the timed path broken underneath: a token
    altered where it is produced (the step's logits favour a wrong token
    every third step), and a step that hands back its caches unchanged
    (a sequence then reads the same whatever it shares a batch with: gaps
    of 0.855 and 0.130, fourteen and twenty-two times the tiny limits)."""
    from paddle_tpu.inference import ProgramPredictor

    real_run, calls = ProgramPredictor.run, [0]

    def broken(self, inputs, return_numpy=True):
        outs = list(real_run(self, inputs, return_numpy=return_numpy))
        if "tok_ids" not in inputs:
            return outs
        calls[0] += 1
        if fault == "token_altered" and calls[0] % 3 == 0:
            logits = np.array(outs[0])
            logits[:, 1] += 50.0
            outs[0] = logits
        if fault == "state_unchanged":
            names = list(self.fetch_names)
            for feed in inputs:
                if feed.startswith("cache_k_"):
                    i = int(feed.rsplit("_", 1)[1])
                    outs[1 + 2 * i] = inputs[feed]
                    outs[2 + 2 * i] = inputs["cache_v_%d" % i]
            assert len(names) == len(outs)
        return outs

    monkeypatch.setattr(ProgramPredictor, "run", broken)
    _, result = in_process(checkout[1], "tiny.serve.chat", seed=7,
                           seconds=1.5)
    assert result["correct"] is False
    rows = by_name(result["compared"])
    assert not rows["token_gap_mean"]["ok"]
    assert rows["tokens_miscounted"]["ok"] and result["failed"] == 0


# -- the trace arithmetic -----------------------------------------------------

def _recorded():
    p = serve_trace.PREFIX
    # a step, a chunk (its span ends at the dispatch; its run lies inside
    # the NEXT step's span, before that step's own run), two more steps
    host = [(p + "decode.step", 0, 100), (p + "executor.run", 5, 30),
            (p + "prefill.chunk", 110, 20), (p + "executor.run", 112, 15),
            (p + "decode.step", 140, 460), (p + "executor.run", 145, 20),
            (p + "decode.step", 800, 120), (p + "executor.run", 805, 40)]
    device = [("fusion.1", 10, 40), ("fusion.2", 60, 20),     # step 1
              ("fusion.1", 150, 200),                          # the chunk
              ("fusion.1", 400, 50), ("fusion.2", 500, 30),   # step 2
              ("fusion.1", 810, 60), ("copy.9", 980, 10)]     # step 3, none
    modules = [(10, 85), (150, 360), (400, 590), (810, 900)]
    hlo = {"step": '%fusion.1 = f32[] fusion(), metadata={op_name="jit(s)'
                   '/mul/dot_general"}\n%fusion.2 = f32[] fusion(), '
                   'metadata={op_name="jit(s)/cached_attention/reduce"}',
           "chunk": '%fusion.1 = f32[] fusion(), metadata={op_name="jit(c)'
                    '/cached_attention_chunk/dot_general"}'}
    return p, host, device, modules, hlo


def test_a_run_of_an_executable_finds_its_quantum():
    p, host, device, modules, hlo = _recorded()
    steps = serve_trace.spans_named(host, "decode.step")
    chunks = serve_trace.spans_named(host, "prefill.chunk")
    runs = serve_trace.runs_by_kind(modules, steps, chunks)
    assert runs["decode.step"] == [(10, 85), (400, 590), (810, 900)]
    assert runs["prefill.chunk"] == [(150, 360)]
    # a run that began before the first recorded quantum is nobody's
    runs = serve_trace.runs_by_kind([(-50, -5)] + modules,
                                    [(s + 0, e) for s, e in steps], chunks)
    assert runs["prefill.chunk"] == [(150, 360)]


def test_device_events_find_their_run_and_their_scope():
    p, host, device, modules, hlo = _recorded()
    trace = serve_trace.ServeTrace([device], host, hlo, [modules])
    assert trace.count("decode.step") == 3
    assert trace.median_span("decode.step") == (800, 920)
    assert trace.child_ms("decode.step", "executor.run") == \
        pytest.approx(40e-6)
    assert trace.device_ms_a_quantum("decode.step") == pytest.approx(
        (60 + 80 + 60) / 3 * 1e-6)
    assert trace.device_ms("prefill.chunk") == pytest.approx(200e-6)
    # fusion.1 is a matmul in the step and attention in the chunk
    assert trace.scope_ms_a_quantum("decode.step", ("mul", "matmul")) == \
        pytest.approx((40 + 50 + 60) / 3 * 1e-6)
    assert trace.scope_ms_a_quantum("decode.step", ("cached_attention",)) \
        == pytest.approx((20 + 30) / 3 * 1e-6)
    assert trace.scope_ms_a_quantum("prefill.chunk",
                                    ("cached_attention_chunk",)) == \
        pytest.approx(200e-6)
    assert trace.scope_ms_a_quantum("decode.step", ("kv_cache_write",)) \
        is None
    gaps = trace.breakdown()["idle_gaps"]
    assert all(name.startswith(p) or name == "host_other"
               for name, _ in gaps)
    assert any(name.startswith(p) for name, _ in gaps)
    # without a modules line an event belongs to the span that holds it
    plain = serve_trace.ServeTrace([device], host, hlo)
    assert plain.quanta["decode.step"] == serve_trace.spans_named(
        host, "decode.step")


def test_operation_counts_against_a_hand_count():
    cfg = harness.load_json(_bench("configs", "opt-1.3b.json"))
    assert ops_count_opt.parameter_count(cfg) == cfg["parameters"] \
        == 1315758080
    assert ops_count_opt.bytes_per_position(cfg) == 24 * 2 * 2048 * 2
    ops, nbytes = ops_count_opt.decode_step(cfg, live=16, positions=4800)
    matrices = 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) + 2048 * 50272
    assert ops == 2 * (16 * matrices + 24 * 2 * 4800 * 2048)
    assert nbytes == 2 * (1315758080 - 2050 * 2048) + (4800 + 16) * 196608
    # bytes bound a decode step on a v5e: 3.6 ms against 0.2 ms
    assert nbytes / 819e9 > 10 * ops / 197e12


# -- the manifest -------------------------------------------------------------

@pytest.mark.parametrize("case", appended.CASES)
def test_manifest_holds_the_serving_cells(case, tmp_path):
    root = appended.root(case, tmp_path)
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    config = {c["name"]: c for c in m["configs"]}["opt-1.3b"]
    assert config["reduced"] == [] and "facebook/opt-1.3b" in config["source"]
    body = harness.load_json(os.path.join(root, config["file"]))
    assert (body["num_hidden_layers"], body["hidden_size"], body["ffn_dim"],
            body["num_attention_heads"], body["vocab_size"],
            body["max_position_embeddings"]) == (24, 2048, 8192, 32, 50272,
                                                 2048)
    assert body["do_layer_norm_before"] and body["reduced"] == []
    assert set(body["limits"]) == {"token_gap_max", "token_gap_mean"}
    cells = {w["name"]: w for w in m["workloads"]}
    for cell, traffic in ((CHAT, "serve.chat"), (SAT, "serve.chat.sat")):
        assert cells[cell]["chips"] == 1
        assert cells[cell]["config"] == "opt-1.3b"
        assert cells[cell]["traffic"] == traffic
        assert _mix(traffic)["job"] == "serve"
    # both mixes offer the same requests to the same engine
    chat, sat = _mix("serve.chat"), _mix("serve.chat.sat")
    assert chat["lengths"] == sat["lengths"]
    assert chat["engine"] == sat["engine"]
    assert chat["shape_seed"] == sat["shape_seed"]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert SAT in e2e["serve_tokens_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    for cell in (CHAT, SAT):  # besides setup_s, one more; and a layer's
        assert any(cell in x.get("workloads", ()) for x in e2e.values())
        assert any(cell in x.get("workloads", ()) for x in m["per_layer"])
    for x in m["per_layer"]:
        for cell in set(x.get("workloads", ())) & {CHAT, SAT}:
            moved = e2e[x["moves"]]
            assert "workloads" not in moved or cell in moved["workloads"]
