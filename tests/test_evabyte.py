"""EvaByte's serving path at a small size on the CPU, float32 declared,
seeded weights, a window of 32 positions in chunks of 4 and prompts of 33 to
70 bytes (up to two window boundaries): chunks then steps through a real
``DecodeBatcher`` against the benchmark's plain reference, with chunk rungs
shorter than the window and as long as it, partial chunks with pad lanes,
chunk runs that cross a window boundary and sub-batched chunk runs; steps
alone at every position; each departure from the equations alone failing the
tolerance; what a slot table must get right about a cache that follows the
rung at a stride (its shapes, re-bucketing, a recycled row, the lanes a
sub-batched run writes back); and what cannot hold beside these caches
refused at construction."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import ProgramPredictor
from paddle_tpu.models import evabyte
from paddle_tpu.ops import eva_attention
from paddle_tpu.serving import decode_batcher
from paddle_tpu.serving.decode_batcher import DecodeBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

REFERENCE = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "evabyte-6.5b.py"))
EXACT = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "precision.py")).exact

sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
import tiny_evabyte  # noqa: E402

# the benchmark's tiny twin: two layers, 4 heads of 8, a window of 32
# positions in chunks of 4, a rung of 128
_TWIN = tiny_evabyte.tiny_config("float32")
TINY = {k: _TWIN[k] for k in _TWIN["builder_keys"]}
VOCAB, W, C = TINY["vocab_size"], TINY["window_size"], TINY["chunk_size"]
TOL = dict(rtol=2e-4, atol=2e-5)
# two window boundaries and no multiple of C; one past the first boundary;
# a multiple of C and of the chunk rung plus the byte the steps take
PROMPTS = (70, 33, 49)
NEW = 9


def _draw(rng, name, shape):
    if name.endswith("norm.w"):
        return 0.1 * rng.standard_normal(shape)     # an offset from 1
    if name.endswith((".phi", ".mu")):
        return rng.standard_normal(shape)           # the scale of a key
    fan_in = shape[-1] if "embed" in name else shape[0]
    return rng.standard_normal(shape) / np.sqrt(fan_in)


class Recorded:
    """A predictor that keeps the positions fed and the first fetch of
    every run."""

    def __init__(self, predictor, pos_feed):
        self._predictor = predictor
        self._pos_feed = pos_feed
        self.fetch_names = predictor.fetch_names
        self.runs = []

    def run(self, feed, return_numpy=False, **kw):
        outs = self._predictor.run(feed, return_numpy=return_numpy, **kw)
        self.runs.append((np.array(feed[self._pos_feed]),
                          np.asarray(outs[0])))
        return outs

    def stage(self, *args, **kw):
        return self._predictor.stage(*args, **kw)


def _programs(dtype="float32", sizes=TINY):
    scope = fluid.Scope()
    predictors, specs = {}, {}
    for kind in ("step", "chunk"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            fetch, spec = getattr(evabyte, "evabyte_" + kind)(
                dtype=dtype, **sizes)
        feeds = [spec["token_feed"], spec["pos_feed"]] + [
            c["feed"] for c in spec["cache_feeds"]]
        predictors[kind] = ProgramPredictor(main, feeds, fetch, scope=scope)
        specs[kind] = spec
        if kind == "step":
            leaves = [(p.name, tuple(p.shape))
                      for p in main.global_block().all_parameters()]
    rng = np.random.default_rng(0)
    weights = {name: jnp.asarray(_draw(rng, name, shape), jnp.float32)
               for name, shape in leaves}
    for name, value in weights.items():
        scope.set(name, value.astype(dtype))
    return predictors, specs, weights


def _serve(ladder, prompts=PROMPTS, budget=None, monkeypatch=None,
           dtype="float32", slots=4, ctx_ladder=(128,), new=NEW, sizes=TINY):
    """Requests in a bucket of ``slots`` rows, prompts by chunks of
    ``ladder`` (None: by forced steps alone) and answers by steps. Returns
    (weights, [(prompt, served tokens, {position: the step program's logits
    where the request's row was fed it})], the batcher, the specs, the
    chunk runs' fed positions)."""
    if budget is not None:
        monkeypatch.setattr(decode_batcher, "CHUNK_TOKEN_BUDGET", budget)
    predictors, specs, weights = _programs(dtype, sizes)
    step = Recorded(predictors["step"], specs["step"]["pos_feed"])
    chunk = Recorded(predictors["chunk"], specs["chunk"]["pos_feed"])
    prefill = None if ladder is None else {
        "predictor": chunk, "spec": specs["chunk"], "ladder": ladder}
    batcher = DecodeBatcher(step, specs["step"], ladder=(slots,),
                            ctx_ladder=ctx_ladder, start=False,
                            prefill=prefill)
    prompts = [np.random.default_rng(10 + i).integers(0, VOCAB, size=n)
               for i, n in enumerate(prompts)]
    futures = [batcher.submit(p, max_new_tokens=new) for p in prompts]
    batcher.drive()
    batcher.step_ops = predictors["step"]._program.global_block().ops
    served = []
    for row, (prompt, future) in enumerate(zip(prompts, futures)):
        tokens = np.asarray(future.result())
        logits = {}
        if len(prompts) <= slots:
            # a fresh table takes admissions in order: request i rides row i
            # (the first run that fed a position: a retired row is fed 0)
            for pos, rows in step.runs:
                logits.setdefault(int(pos[row]), rows[row])
        served.append((prompt, tokens, logits))
    return weights, served, batcher, specs, [pos for pos, _ in chunk.runs]


def _reference(weights, prompt, tokens, **changed):
    return np.asarray(REFERENCE.logits(
        weights, np.concatenate([prompt, tokens]), dict(TINY, **changed),
        EXACT))


def _sampled(prompt, tokens, logits):
    """The step program's logits at the request's sampling positions."""
    return np.stack([logits[p] for p in range(
        len(prompt) - 1, len(prompt) - 1 + len(tokens))])


def _hold(weights, served):
    for prompt, tokens, logits in served:
        full = _reference(weights, prompt, tokens)
        at = slice(len(prompt) - 1, len(prompt) - 1 + len(tokens))
        np.testing.assert_allclose(_sampled(prompt, tokens, logits),
                                   full[at], **TOL)
        assert (np.argmax(full[at], -1) == tokens).all()


# -- the programs against the reference ------------------------------------------

# (chunk ladder, the lanes a chunk run may compute): rungs shorter than a
# chunk's worth of the window and as long as the window, both with partial
# chunks; a budget under 4 rows x rung makes a chunk run a gathered sub-batch
CASES = {"rung_of_a_chunk": ((4,), None), "rung_16": ((16,), None),
         "rung_of_the_window": ((32,), None), "two_rungs": ((4, 16), None),
         "sub_batched_16": ((16,), 32), "sub_batched_4": ((4,), 8)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunks_then_steps_give_the_references_logits(case, monkeypatch):
    ladder, budget = CASES[case]
    if case == "two_rungs":
        # a chunk run walks its caches in blocks: several a cache here
        monkeypatch.setattr(eva_attention, "CHUNK_BLOCK", 8)
    weights, served, batcher, specs, _ = _serve(
        ladder, budget=budget, monkeypatch=monkeypatch)
    if budget is not None:      # the chunk ran over gathered sub-rows
        assert decode_batcher.chunk_rows(ladder[-1], 4) < 4
        assert batcher._rows_staged
    _hold(weights, served)
    assert specs["chunk"].get("logits_fetch") is None   # it only ingests


# the twin with caches the step kernel takes: rows of 128 (8 heads of 16), a
# window of two blocks of 128 slots, and as many summary entries a rung
WIDE = dict(TINY, hidden_size=128, num_attention_heads=8,
            num_key_value_heads=8, window_size=256, chunk_size=16,
            max_position_embeddings=4096)


@pytest.mark.parametrize("path", ["eva_step", "rung_xla"])
def test_steps_give_the_references_logits_by_the_kernel_and_by_the_jnp_form(
        path, monkeypatch):
    """Requests whose steps read a window's second block and the summaries
    of one and of two earlier windows: through the step kernel where its
    gate admits the site (the interpreter stands in for the TPU here) and
    through the ``jnp`` form where it does not (the CPU), the same rows of
    the reference."""
    monkeypatch.setattr(eva_attention, "_INTERPRET", path == "eva_step")
    jax.clear_caches()
    weights, served, batcher, _, _ = _serve(
        (256,), prompts=(300, 650), new=4, slots=2, ctx_ladder=(4096,),
        sizes=WIDE)
    jax.clear_caches()
    took = [op.attrs["_kernel_choice"]["kernel"] for op in batcher.step_ops
            if op.type == "eva_attention"]
    assert took == [path] * TINY["num_hidden_layers"]
    for prompt, tokens, logits in served:
        full = _reference(weights, prompt, tokens, **WIDE)
        at = slice(len(prompt) - 1, len(prompt) - 1 + len(tokens))
        np.testing.assert_allclose(_sampled(prompt, tokens, logits),
                                   full[at], **TOL)
        assert (np.argmax(full[at], -1) == tokens).all()


@pytest.fixture(scope="module")
def run_16():
    return _serve((16,))


def test_steps_alone_are_right_at_every_position():
    """No chunk program: every prompt byte is a forced step, so the step
    program is held at every position it is fed, those that end a chunk (p %
    4 == 3) and those that open a window (p % 32 == 0) among them, two rows
    at different positions in every run."""
    weights, served, _, _, _ = _serve(None, prompts=(70, 41), new=5)
    for prompt, tokens, logits in served:
        full = _reference(weights, prompt, tokens)
        fed = len(prompt) + len(tokens) - 1
        assert sorted(logits) == list(range(fed))
        np.testing.assert_allclose(
            np.stack([logits[p] for p in range(fed)]), full[:fed], **TOL)


def test_chunk_runs_cross_window_boundaries_and_carry_pad_lanes():
    """A row that generates from the start makes every chunk tick alternate
    with a step, which forces a byte on the rows that still ingest: a run
    starts wherever its row stands (0, 17, 34, 51, ..), so runs hold a
    multiple of the window INSIDE their live lanes and start inside a chunk
    of 4; the last run of a prompt is partial and pads with ``PAD_POS``.
    The logits are the reference's all the same."""
    weights, served, _, specs, chunk_runs = _serve(
        (16,), prompts=(6, 70, 61), new=40)
    _hold(weights, served)
    assert specs["chunk"]["pad_pos"] == evabyte.PAD_POS
    crossed = padded = off_chunk = 0
    for pos in chunk_runs:
        for row in pos:
            live = row[row < evabyte.PAD_POS]
            if live.size == 0:
                continue
            assert (np.diff(live) == 1).all()
            crossed += live[0] // W != live[-1] // W
            padded += live.size < row.size
            off_chunk += live[0] % C != 0
    assert crossed >= 3 and padded >= 2 and off_chunk >= 4


def test_a_chunk_run_longer_than_the_window_is_refused():
    predictors, specs, _ = _programs()
    batcher = DecodeBatcher(
        predictors["step"], specs["step"], ladder=(4,), ctx_ladder=(128,),
        start=False, prefill={"predictor": predictors["chunk"],
                              "spec": specs["chunk"], "ladder": (64,)})
    batcher.submit(np.arange(70) % VOCAB, max_new_tokens=2)
    with pytest.raises(ValueError, match="crosses more than one multiple"):
        batcher.drive()


# -- each departure from the equations, alone ------------------------------------

def _zeroed(suffix):
    def change(weights):
        return {k: jnp.zeros_like(v) if k.endswith(suffix) else v
                for k, v in weights.items()}
    return change


DEPARTURES = {
    "no_mu": (_zeroed(".attn.mu"), {}),
    "uniform_pooling": (_zeroed(".attn.phi"), {}),
    "window_one_chunk_short": (None, {"window_size": W - C}),
    "window_one_chunk_long": (None, {"window_size": W + C}),
    "chunks_of_half": (None, {"chunk_size": C // 2}),
    "chunks_of_twice": (None, {"chunk_size": 2 * C}),
    "plain_norm_weight": (None, {"norm_add_unit_offset": False}),
}


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_each_departure_from_the_equations_alone_fails(name, run_16):
    """The reference with one equation changed no longer gives the served
    logits: what each part of the layer is worth to the comparison. ``mu``
    and ``phi`` each move them."""
    change, sizes = DEPARTURES[name]
    weights, served, _, _, _ = run_16
    prompt, tokens, logits = served[0]      # 70 bytes: two earlier windows
    other = _reference(change(weights) if change else weights, prompt,
                       tokens, **sizes)
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    gap = np.abs(_sampled(prompt, tokens, logits) - other[at]).max()
    assert gap > 1e-2, gap


def test_summaries_are_never_read_inside_their_own_window():
    """The query at p reads the entries ``c < (p // W) * (W / C)`` and no
    other: garbage at and past that entry (a recycled row's, or its own
    window's summaries) changes nothing, garbage below it does."""
    rng = np.random.default_rng(3)
    b, heads, d, entries = 3, 4, 8, 128 // C
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    q, win_k, win_v = f(b, heads * d), f(b, W, heads * d), f(b, W, heads * d)
    sum_k, sum_v = f(b, entries, heads * d), f(b, entries, heads * d)
    pos = jnp.asarray([5, 40, 75], jnp.int32)       # windows 0, 1 and 2

    def out(sum_k, sum_v):
        return np.asarray(eva_attention.attend_step(
            q, win_k, win_v, sum_k, sum_v, pos, heads, W, C)[0])

    base = out(sum_k, sum_v)
    first = np.array([0, 8, 16])        # (p // W) * (W / C) of each row
    mask = np.arange(entries)[None, :, None] >= first[:, None, None]
    noise = f(b, entries, heads * d) * 10.0
    same = out(jnp.where(mask, noise, sum_k), jnp.where(mask, noise, sum_v))
    np.testing.assert_array_equal(base, same)
    moved = out(jnp.where(mask, sum_k, noise), sum_v)
    assert np.abs(moved[0] - base[0]).max() == 0        # window 0: none
    assert np.abs(moved[1:] - base[1:]).max() > 1e-3
    count = np.asarray(eva_attention.attend_step(
        q, win_k, win_v, sum_k, sum_v, pos, heads, W, C)[1])
    assert count.tolist() == [6 + 9 + 12, 0 + 8 + 16, 6 + 41 + 76]


def test_bfloat16_stays_within_a_band_of_the_float32_reference():
    """Declared bfloat16 (weights, activations, caches, summaries), the
    stream, the statistics and the logits float32: the served token's logit
    lies within 0.1 of the reference's best over 27 sampled positions of
    logits some 3 wide, the mean gap under 0.02."""
    weights, served, _, _, _ = _serve((16,), dtype="bfloat16")
    rounded = {k: v.astype(jnp.bfloat16) for k, v in weights.items()}
    gaps = []
    for prompt, tokens, logits in served:
        full = _reference(rounded, prompt, tokens)
        rows = full[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
        got = _sampled(prompt, tokens, logits)
        assert got.dtype == np.float32
        assert np.abs(got - rows).max() < 0.15
        gaps.append(rows.max(-1) - rows[np.arange(len(tokens)), tokens])
    gaps = np.concatenate(gaps)
    assert gaps.max() < 0.1 and gaps.mean() < 0.02


# -- the slot table beside caches that are no rung long ---------------------------

def test_the_spec_states_a_capacity_or_a_stride_for_every_cache(run_16):
    _, _, batcher, specs, _ = run_16
    for kind in ("step", "chunk"):
        feeds = {c["feed"]: (c["tail"], c.get("capacity"), c.get("stride"))
                 for c in specs[kind]["cache_feeds"]}
        assert feeds == {
            "%s_%s_%d" % (cache, part, l): (
                [32], W if cache == "win" else None,
                C if cache == "sum" else None)
            for cache in ("win", "sum") for part in "kv" for l in (0, 1)}
    shapes = {n: s.shape for n, s in batcher._cache_shapes(4, 128).items()}
    assert shapes["win_k_0"] == shapes["win_v_1"] == (4, W, 32)
    assert shapes["sum_k_0"] == shapes["sum_v_1"] == (4, 128 // C, 32)
    synth = batcher._synth_caches(2, 64)
    assert synth["win_k_1"].shape == (2, W, 32)
    assert synth["sum_v_0"].shape == (2, 64 // C, 32)


def test_the_step_program_counts_what_its_layers_read_and_hold(run_16):
    _, _, batcher, _, _ = run_16
    counters = {line.split()[0].rsplit("program_", 1)[1]: float(
        line.split()[1]) for line in
        batcher.metrics_.prometheus_text().splitlines()
        if "_program_" in line and not line.startswith("#")}
    window, summary, context = (counters["eva_" + n + "_positions"]
                                for n in ("window", "summary", "context"))
    assert window > 0 and summary > 0
    # a layer reads under the context it holds once a row is past a window,
    # and never more than a window and the summaries of the rung
    assert window + summary < context
    steps = batcher.metrics()["decode_steps"]
    assert window <= steps * 4 * 2 * W      # rows x layers x a window


def test_a_recycled_slot_row_and_a_raised_rung_keep_the_logits():
    """Five requests through two slot rows, the rung raised from 64 to 128
    when the long one is admitted beside a live short one: a recycled row
    starts on whatever the row held (stale summaries and window slots are
    masked, not cleared), and re-bucketing copies a summary cache at its
    own length."""
    weights, served, batcher, _, _ = _serve(
        (16,), prompts=(33, 41, 70, 37, 50), slots=2, ctx_ladder=(64, 128),
        new=6)
    assert {(2, 64), (2, 128)} <= batcher.seen_signatures
    for prompt, tokens, _ in served:
        full = _reference(weights, prompt, tokens)
        at = slice(len(prompt) - 1, len(prompt) - 1 + len(tokens))
        assert (np.argmax(full[at], -1) == tokens).all()
        best = full[at].max(-1)
        assert np.allclose(best, full[at][np.arange(len(tokens)), tokens])


# -- what cannot hold beside these caches -----------------------------------------

def _batcher(**options):
    predictors, specs, _ = _programs()
    return DecodeBatcher(
        predictors["step"], specs["step"], ladder=(4,), ctx_ladder=(128,),
        start=False, prefill={"predictor": predictors["chunk"],
                              "spec": specs["chunk"], "ladder": (16,)},
        **options)


class _Draft:
    def propose(self, histories, n):
        return [[0] * n for _ in histories]


@pytest.mark.parametrize("option", ["prefix_cache", "speculative"])
def test_a_prefix_cache_and_speculation_are_refused(option):
    value = True if option == "prefix_cache" else {"draft": _Draft(), "k": 4}
    with pytest.raises(ValueError, match="cannot be used with this decode "
                                         "spec: cache 'win_k_0' is a ring"):
        _batcher(**{option: value})


class _Fake:
    fetch_names = ["logits", "kept"]

    def run(self, feed, return_numpy=False):
        raise AssertionError("never run")


def _strided_spec(**feed):
    return {"token_feed": "tok", "pos_feed": "pos", "logits_fetch": "logits",
            "cache_feeds": [dict({"feed": "summary", "fetch": "kept",
                                  "tail": [8]}, **feed)]}


@pytest.mark.parametrize("option", ["prefix_cache", "speculative"])
def test_a_derived_cache_alone_refuses_them_and_says_why(option):
    """No ring in the spec: the cache that holds one entry for every 16
    positions is reason enough, and the message says so."""
    value = True if option == "prefix_cache" else {"draft": _Draft(), "k": 4}
    with pytest.raises(ValueError, match="holds one entry for every 16 "
                                         "positions, derived"):
        DecodeBatcher(_Fake(), _strided_spec(stride=16), ladder=(4,),
                      ctx_ladder=(128,), start=False, **{option: value})


def test_a_stride_must_divide_every_rung_and_excludes_a_capacity():
    with pytest.raises(ValueError, match="rung 72 is no multiple"):
        DecodeBatcher(_Fake(), _strided_spec(stride=16), ladder=(4,),
                      ctx_ladder=(72, 128), start=False)
    with pytest.raises(ValueError, match="a capacity and a stride"):
        DecodeBatcher(_Fake(), _strided_spec(stride=16, capacity=32),
                      ladder=(4,), ctx_ladder=(128,), start=False)
    with pytest.raises(ValueError, match="a stride of 0"):
        DecodeBatcher(_Fake(), _strided_spec(stride=0), ladder=(4,),
                      ctx_ladder=(128,), start=False)
    table = DecodeBatcher(_Fake(), _strided_spec(stride=16), ladder=(4,),
                          ctx_ladder=(128,), start=False)
    assert table._synth_caches(4, 128)["summary"].shape == (4, 8, 8)


def test_the_model_refuses_grouped_heads_and_drafting_heads():
    for changed, message in (
            ({"num_key_value_heads": 2}, "as many key/value heads"),
            ({"num_pred_heads": 8}, "head 0 alone is served"),
            ({"layers_held": [1, 2]}, "layers_held"),
            ({"fp32_skip_add": False}, "as EvaByte is published"),
            ({"chunk_size": 5}, "chunks of 5")):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            with pytest.raises(ValueError, match=message):
                evabyte.evabyte_step(dtype="float32",
                                     **dict(TINY, **changed))
