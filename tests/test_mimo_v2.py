"""MiMo-V2's serving path at a small size on the CPU, float32 declared,
seeded weights, a window and a ring of 8 positions and prompts of 20 to 43
tokens (up to five wraps of a ring): chunks then steps through a real
``DecodeBatcher`` against the benchmark's plain reference, with chunk rungs
shorter and longer than the ring, partial chunks with pad lanes and
sub-batched chunk runs; the ring against the same program with every layer
cached to the context rung; each departure from the equations alone failing
the tolerance; the three hazards of a ring in a slot table each held by a
test of its own; what cannot hold with a ring refused at construction; the
sixteen shares of one expert layer adding up to the uncut layer; and OPT's
two programs lowering to the StableHLO they had before the ops took grouped
heads, a window, a sink and rings."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.inference import ProgramPredictor
from paddle_tpu.models import mimo_v2
from paddle_tpu.ops import cache_attention
from paddle_tpu.parallel import moe
from paddle_tpu.serving import decode_batcher
from paddle_tpu.serving.decode_batcher import DecodeBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

REFERENCE = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "mimo-v2-flash.py"))
EXACT = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "precision.py")).exact

sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
import tiny_mimo  # noqa: E402

# the benchmark's tiny twin: layer 0 (full, dense) and layers 6-11 (window
# x5, then full), 4 of 8 experts, a window and a ring of 8
_TWIN = tiny_mimo.tiny_config("float32")
TINY = {k: _TWIN[k] for k in _TWIN["builder_keys"]}
VOCAB, RING = TINY["vocab_size"], TINY["sliding_window"]
TOL = dict(rtol=2e-4, atol=2e-5)
PROMPTS = (43, 21, 30)      # five, two and three wraps of the ring
NEW = 7


def _draw(rng, name, shape):
    if name.endswith("norm.w"):
        return 1.0 + 0.1 * rng.standard_normal(shape)
    if name.endswith("router_bias"):
        # a selection bias of the scores' own size, so that it moves picks
        return 0.3 * rng.standard_normal(shape)
    if name.endswith("sink"):
        return rng.standard_normal(shape)       # N(0, 1): it weighs
    fan_in = shape[2] if len(shape) == 3 else (
        shape[-1] if "embed" in name else shape[0])
    return rng.standard_normal(shape) / np.sqrt(fan_in)


class Recorded:
    """A step predictor that keeps the positions fed and the logits of
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


def _programs(window_cache="ring", sizes=TINY):
    scope = fluid.Scope()
    predictors, specs = {}, {}
    for kind in ("step", "chunk"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            fetch, spec = getattr(mimo_v2, "mimo_v2_" + kind)(
                dtype="float32", window_cache=window_cache, **sizes)
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
        scope.set(name, value)
    return predictors, specs, weights


def _serve(ladder, window_cache="ring", budget=None, monkeypatch=None):
    """Three requests in a bucket of four slot rows, prompts by chunks of
    ``ladder`` and answers by steps. Returns (weights, [(prompt, served
    tokens, the step program's logits at the request's sampling steps)],
    the batcher, the specs)."""
    if budget is not None:
        monkeypatch.setattr(decode_batcher, "CHUNK_TOKEN_BUDGET", budget)
    predictors, specs, weights = _programs(window_cache)
    step = Recorded(predictors["step"], specs["step"]["pos_feed"])
    batcher = DecodeBatcher(
        step, specs["step"], ladder=(4,), ctx_ladder=(64,), start=False,
        prefill={"predictor": predictors["chunk"], "spec": specs["chunk"],
                 "ladder": ladder})
    prompts = [np.random.default_rng(10 + i).integers(0, VOCAB, size=n)
               for i, n in enumerate(PROMPTS)]
    futures = [batcher.submit(p, max_new_tokens=NEW) for p in prompts]
    batcher.drive()
    served = []
    for row, (prompt, future) in enumerate(zip(prompts, futures)):
        tokens = np.asarray(future.result())
        # a fresh table takes admissions in order: request i rides row i,
        # and samples at the steps that feed positions L-1 .. L+NEW-2
        at = range(len(prompt) - 1, len(prompt) - 1 + NEW)
        logits = [rows[row] for pos, rows in step.runs if pos[row] in at]
        assert len(logits) == NEW
        served.append((prompt, tokens, np.stack(logits)))
    return weights, served, batcher, specs


def _reference_rows(weights, prompt, tokens, **changed):
    full = REFERENCE.logits(weights, np.concatenate([prompt, tokens]),
                            dict(TINY, **changed), EXACT)
    return np.asarray(full[len(prompt) - 1:len(prompt) - 1 + len(tokens)])


# -- the programs against the reference ------------------------------------------

# (chunk ladder, the lanes a chunk run may compute): rungs shorter than the
# ring, longer than it (two and four rings), both with partial chunks; a
# budget under 4 rows x rung makes a chunk run a gathered sub-batch
CASES = {"shorter": ((4,), None), "longer": ((16,), None),
         "four_rings": ((32,), None), "both": ((4, 16), None),
         "sub_batched_longer": ((16,), 32),
         "sub_batched_shorter": ((4,), 8)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunks_then_steps_give_the_references_logits(case, monkeypatch):
    ladder, budget = CASES[case]
    weights, served, batcher, specs = _serve(ladder, "ring", budget,
                                             monkeypatch)
    if budget is not None:      # the chunk ran over gathered sub-rows
        assert decode_batcher.chunk_rows(ladder[-1], 4) < 4
        assert batcher._rows_staged
    for prompt, tokens, logits in served:
        np.testing.assert_allclose(
            logits, _reference_rows(weights, prompt, tokens), **TOL)
        assert (np.argmax(logits, -1) == tokens).all()
    assert specs["chunk"].get("logits_fetch") is None   # it only ingests


@pytest.fixture(scope="module")
def ring_run():
    return _serve((4, 16))


def test_a_ring_gives_the_logits_of_every_layer_cached_to_the_rung(ring_run):
    _, ring, _, ring_specs = ring_run
    _, rung, _, rung_specs = _serve((4, 16), "context")
    for (_, tokens, logits), (_, tokens2, logits2) in zip(ring, rung):
        assert (tokens == tokens2).all()
        np.testing.assert_allclose(logits, logits2, rtol=1e-5, atol=1e-5)
    caps = {c["feed"]: c.get("capacity")
            for c in ring_specs["step"]["cache_feeds"]}
    # five window layers hold a ring of sliding_window positions, the two
    # full layers the rung; keys are wider than values, heads of two counts
    assert [caps["cache_k_%d" % l] for l in TINY["layers_held"]] == [
        None, RING, RING, RING, RING, RING, None]
    assert all(c.get("capacity") is None
               for c in rung_specs["step"]["cache_feeds"])
    tails = {c["feed"]: c["tail"] for c in ring_specs["chunk"]["cache_feeds"]}
    assert (tails["cache_k_0"], tails["cache_v_0"]) == ([24], [16])
    assert (tails["cache_k_6"], tails["cache_v_6"]) == ([48], [32])
    assert ring_specs["chunk"]["pad_pos"] == mimo_v2.PAD_POS
    assert "pad_pos" not in rung_specs["chunk"]


def test_the_slot_table_holds_each_cache_at_its_own_capacity(ring_run):
    _, _, batcher, _ = ring_run
    shapes = {n: s.shape for n, s in batcher._cache_shapes(4, 64).items()}
    assert shapes["cache_k_0"] == (4, 64, 24)
    assert shapes["cache_v_11"] == (4, 64, 16)
    assert shapes["cache_k_6"] == (4, RING, 48)
    assert shapes["cache_v_10"] == (4, RING, 32)
    synth = batcher._synth_caches(2, 32)
    assert synth["cache_k_7"].shape == (2, RING, 48)
    assert synth["cache_k_11"].shape == (2, 32, 24)


def test_the_step_program_counts_the_positions_each_kind_of_layer_reads(
        ring_run):
    _, _, batcher, _ = ring_run
    counters = {line.split()[0].rsplit("program_", 1)[1]: float(
        line.split()[1]) for line in
        batcher.metrics_.prometheus_text().splitlines()
        if "_program_" in line and not line.startswith("#")}
    steps = int(batcher.metrics()["decode_steps"]) \
        if "decode_steps" in batcher.metrics() else None
    full, window = (counters["attn_full_positions"],
                    counters["attn_window_positions"])
    # two full layers read every position held, five window layers at most
    # the ring: every prompt is past the ring when its steps begin
    assert full > 0 and window > 0
    assert window / 5 < full / 2
    assert 0 < counters["moe_rows_held"] <= counters["moe_rows_run"]
    assert steps is None or steps > 0


# -- each departure from the equations, alone ------------------------------------

def _ungrouped(weights):
    """The weights with every layer's query heads (and their sinks and
    rows of ``W_o``) reordered so that the reference, which gives head ``j``
    key/value head ``j // (H / n_kv)``, computes the model whose head ``i``
    reads key/value head ``i % n_kv``: heads grouped by the remainder and
    not by the quotient, everything else the same."""
    out = dict(weights)
    for l in TINY["layers_held"]:
        pre = "swa_" if TINY["hybrid_layer_pattern"][l] else ""
        h, g = TINY[pre + "num_attention_heads"], \
            TINY[pre + "num_key_value_heads"]
        dk, dv = TINY[pre + "head_dim"], TINY[pre + "v_head_dim"]
        r = h // g
        perm = np.array([(j % r) * g + j // r for j in range(h)])
        nm = "mimo.l%d.attn." % l
        q = np.asarray(weights[nm + "q"]).reshape(-1, h, dk)
        o = np.asarray(weights[nm + "o"]).reshape(h, dv, -1)
        out[nm + "q"] = jnp.asarray(q[:, perm].reshape(q.shape[0], -1))
        out[nm + "o"] = jnp.asarray(o[perm].reshape(-1, o.shape[-1]))
        if nm + "sink" in weights:
            out[nm + "sink"] = weights[nm + "sink"][perm]
    return out


def _without_sinks(weights):
    return {k: v for k, v in weights.items() if not k.endswith("sink")}


DEPARTURES = {
    "no_sink": (_without_sinks, {}),
    "window_one_short": (None, {"sliding_window": RING - 1}),
    "window_one_long": (None, {"sliding_window": RING + 1}),
    "value_scale_dropped": (None, {"attention_value_scale": 1.0}),
    "full_theta_on_window_layers": (
        None, {"swa_rope_theta": TINY["rope_theta"]}),
    "ungrouped_heads": (_ungrouped, {}),
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_each_departure_alone_fails_the_tolerance(ring_run, departure):
    weights, served, _, _ = ring_run
    change, args = DEPARTURES[departure]
    given = weights if change is None else change(weights)
    prompt, tokens, logits = served[0]
    rows = _reference_rows(given, prompt, tokens, **args)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(logits, rows, **TOL)


def test_the_reference_reads_permuted_heads_as_the_same_model(ring_run):
    """The permutation ``_ungrouped`` applies changes nothing but which
    key/value head a query head reads: with ONE key/value head (nothing to
    group) the permuted weights give the reference's own logits."""
    rng = np.random.default_rng(3)
    sizes = dict(TINY, num_key_value_heads=1, swa_num_key_value_heads=1)
    _, _, weights = _programs(sizes=sizes)
    tokens = rng.integers(0, VOCAB, size=20)
    same = dict(weights)
    for l in TINY["layers_held"]:
        nm = "mimo.l%d.attn." % l
        h, dk, dv = 8, TINY["head_dim"], TINY["v_head_dim"]
        perm = rng.permutation(h)
        q = np.asarray(weights[nm + "q"]).reshape(-1, h, dk)
        o = np.asarray(weights[nm + "o"]).reshape(h, dv, -1)
        same[nm + "q"] = jnp.asarray(q[:, perm].reshape(q.shape[0], -1))
        same[nm + "o"] = jnp.asarray(o[perm].reshape(-1, o.shape[-1]))
        if nm + "sink" in weights:
            same[nm + "sink"] = weights[nm + "sink"][perm]
    np.testing.assert_allclose(
        REFERENCE.logits(same, tokens, sizes, EXACT),
        REFERENCE.logits(weights, tokens, sizes, EXACT),
        rtol=1e-5, atol=1e-5)


# -- the three hazards of a ring in a slot table ----------------------------------

def test_a_chunk_longer_than_the_ring_lands_only_its_last_lanes():
    """(a) ``kv_cache_write_chunk`` scatters ``Out[b, Pos[b, j]]``; a chunk
    longer than the ring names a slot more than once. Only the lanes that
    no later live lane overwrites carry a slot; (b) a pad lane's position,
    taken modulo the ring, would land on a live slot: it is told from a
    live one by ``pad_pos`` and drops."""
    pad = mimo_v2.PAD_POS
    pos = np.full((3, 20), pad, np.int32)
    pos[0, :20] = np.arange(5, 25)          # a full chunk: 20 live lanes
    pos[1, :11] = np.arange(40, 51)         # 11 live lanes, 9 pad lanes
    slots = np.asarray(cache_attention.ring_slots(jnp.asarray(pos), 8, pad))
    assert (slots[2] == 8).all()            # a row of pad lanes: all drop
    for row, live in ((0, 20), (1, 11)):
        landed = slots[row] < 8
        assert landed.sum() == 8            # one lane a slot, the last 8
        assert landed[live - 8:live].all()
        assert sorted(slots[row][landed]) == list(range(8))
        assert (slots[row][landed] == pos[row][landed] % 8).all()
    # a chunk shorter than the ring: every live lane lands, no pad lane
    short = np.asarray(cache_attention.ring_slots(
        jnp.asarray(pos[:2, :4]), 8, pad))
    assert (short == pos[:2, :4] % 8).all()
    # the op: the ring holds the last 8 of the chunk's rows, each at its
    # position modulo 8, whatever order the scatter takes
    x = np.arange(3 * 20 * 2, dtype=np.float32).reshape(3, 20, 2)
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        cache = layers.data("cache", shape=[8, 2], dtype="float32")
        new = layers.data("new", shape=[20, 2], dtype="float32")
        at = layers.data("at", shape=[20], dtype="int32")
        out = layers.kv_cache_write_chunk(cache, new, at, ring=True,
                                          pad_pos=pad)
    exe = fluid.Executor(fluid.CPUPlace())
    before = -np.ones((3, 8, 2), np.float32)
    ring, = exe.run(main, feed={"cache": before, "new": x, "at": pos},
                    fetch_list=[out])
    for p in range(17, 25):
        assert (ring[0, p % 8] == x[0, p - 5]).all()
    for p in range(43, 51):
        assert (ring[1, p % 8] == x[1, p - 40]).all()
    assert (ring[2] == -1).all()            # the pad row wrote nothing


def test_a_ring_write_without_pad_pos_is_refused():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        cache = layers.data("cache", shape=[8, 2], dtype="float32")
        new = layers.data("new", shape=[4, 2], dtype="float32")
        at = layers.data("at", shape=[4], dtype="int32")
        with pytest.raises(ValueError, match="pad_pos"):
            layers.kv_cache_write_chunk(cache, new, at, ring=True)


def test_a_sub_batched_chunk_writes_a_ring_row_back_whole():
    """(c) ``serve_rows_scatter`` writes lanes ``[start, start + lanes)``
    clipped to the capacity; a ring's lanes land modulo the ring, so its
    whole row goes back, while a context cache beside it takes its lanes."""
    gather, scatter = decode_batcher._rows_helpers(4, frozenset({"ring"}))
    table = {"ring": jnp.zeros((4, 8, 2)), "ctx": jnp.zeros((4, 32, 2))}
    idx = jnp.asarray([2, 4], jnp.int32)        # sub-row 1 is a pad sub-row
    sub = gather(table, idx, jnp.int32(1))
    assert sub["ring"].shape == (2, 8, 2) and sub["ctx"].shape == (2, 32, 2)
    sub = {"ring": sub["ring"] + 5.0, "ctx": sub["ctx"] + 7.0}
    start = jnp.asarray([13, 0], jnp.int32)     # the chunk began at 13
    out = scatter(table, sub, idx, start, jnp.int32(1))
    assert (np.asarray(out["ring"][2]) == 5.0).all()    # the whole ring row
    ctx = np.asarray(out["ctx"][2])
    assert (ctx[13:17] == 7.0).all() and (ctx[:13] == 0).all() \
        and (ctx[17:] == 0).all()
    for name in ("ring", "ctx"):                # no other table row moved
        assert (np.asarray(out[name])[[0, 1, 3]] == 0).all()


def test_a_chunks_lanes_see_the_ring_as_it_was_before_the_chunk():
    """A lane early in a chunk longer than the ring reads positions that
    the chunk's own later lanes overwrite: the chunk form reads the ring
    before the writes and the chunk's own rows beside it, and agrees with
    the plain causal window over the whole sequence."""
    rng = np.random.default_rng(2)
    heads, kv_heads, dk, dv, window, t = 4, 2, 6, 4, 8, 40
    q = jnp.asarray(rng.standard_normal((1, t, heads * dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, t, kv_heads * dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, t, kv_heads * dv)), jnp.float32)
    sink = jnp.asarray(rng.standard_normal(heads), jnp.float32)
    plain = REFERENCE._attend(
        q[0].reshape(t, kv_heads, heads // kv_heads, dk),
        k[0].reshape(t, kv_heads, dk), v[0].reshape(t, kv_heads, dv),
        sink.reshape(kv_heads, -1), window, EXACT)
    ring_k = jnp.zeros((1, window, kv_heads * dk))
    ring_v = jnp.zeros((1, window, kv_heads * dv))
    outs, at = [], 0
    for lanes in (4, 16, 3, 16, 1):     # the last of 16 is cut by pad lanes
        live = min(lanes, t - at)
        pos = np.full((1, lanes), mimo_v2.PAD_POS, np.int32)
        pos[0, :live] = np.arange(at, at + live)

        def padded(x):
            return jnp.pad(x[:, at:at + live],
                           ((0, 0), (0, lanes - live), (0, 0)))

        out = cache_attention.attend_chunk_ring(
            padded(q), ring_k, ring_v, padded(k), padded(v),
            jnp.asarray(pos), heads, kv_heads, window, sink)
        outs.append(out[0, :live])
        slots = cache_attention.ring_slots(jnp.asarray(pos), window,
                                           mimo_v2.PAD_POS)
        ring_k = ring_k.at[0, slots[0]].set(padded(k)[0], mode="drop")
        ring_v = ring_v.at[0, slots[0]].set(padded(v)[0], mode="drop")
        at += live
    assert at == t
    np.testing.assert_allclose(jnp.concatenate(outs), plain, rtol=1e-5,
                               atol=1e-5)


# -- what cannot hold with a ring is refused --------------------------------------

@pytest.mark.parametrize("option,value", [
    ("prefix_cache", True),
    ("speculative", {"draft": object(), "k": 2})])
def test_what_cannot_hold_with_a_ring_is_refused_by_name(option, value):
    predictors, specs, _ = _programs()
    with pytest.raises(ValueError, match="cache_k_6.*ring of 8"):
        DecodeBatcher(predictors["step"], specs["step"], ladder=(2,),
                      ctx_ladder=(64,), start=False,
                      prefill={"predictor": predictors["chunk"],
                               "spec": specs["chunk"], "ladder": (8,)},
                      **{option: value})


def test_a_ring_spec_needs_the_chunk_programs_pad_pos():
    predictors, specs, _ = _programs()
    bare = {k: v for k, v in specs["chunk"].items() if k != "pad_pos"}
    with pytest.raises(ValueError, match="pad_pos"):
        DecodeBatcher(predictors["step"], specs["step"], ladder=(2,),
                      ctx_ladder=(64,), start=False,
                      prefill={"predictor": predictors["chunk"],
                               "spec": bare, "ladder": (8,)})
    with pytest.raises(ValueError, match="inside the context rung"):
        DecodeBatcher(predictors["step"], specs["step"], ladder=(2,),
                      ctx_ladder=(64,), start=False,
                      prefill={"predictor": predictors["chunk"],
                               "spec": dict(specs["chunk"], pad_pos=32),
                               "ladder": (8,)})


def test_the_programs_cached_to_the_rung_take_a_prefix_cache():
    """Without a ring nothing is refused: the same model cached to the
    context rung serves under a prefix cache."""
    predictors, specs, _ = _programs("context")
    batcher = DecodeBatcher(
        predictors["step"], specs["step"], ladder=(2,), ctx_ladder=(64,),
        start=False, prefix_cache=True,
        prefill={"predictor": predictors["chunk"], "spec": specs["chunk"],
                 "ladder": (8,)})
    prompt = np.arange(1, 20)
    first = batcher.submit(prompt, max_new_tokens=3)
    batcher.drive()
    again = batcher.submit(prompt, max_new_tokens=3)
    batcher.drive()
    assert (np.asarray(first.result()) == np.asarray(again.result())).all()


# -- the ops: shapes, costs, the plain form's lowering ----------------------------

def test_shape_rules_hold_grouped_heads_widths_sinks_and_rings():
    from paddle_tpu.analysis.passes import analyze_program

    def shape_errors(chunk=False, **kw):
        main = fluid.Program()
        lane = [5] if chunk else []
        with fluid.program_guard(main, fluid.Program()):
            q = layers.data("q", shape=lane + [kw.get("qd", 48)],
                            dtype="float32")
            ck = layers.data("ck", shape=[kw.get("cap", -1), 12],
                             dtype="float32")
            cv = layers.data("cv", shape=[kw.get("cap", -1), 8],
                             dtype="float32")
            pos = layers.data("pos", shape=lane, dtype="int32")
            more = {}
            if kw.get("ring"):
                more = dict(ring=True, window=kw["window"],
                            new_k=layers.data("nk", shape=[5, 12],
                                              dtype="float32"),
                            new_v=layers.data("nv", shape=[5, 8],
                                              dtype="float32"))
            out = layers.cached_attention(q, ck, cv, pos, 8,
                                          kw.get("kv", 2), **more)
        found = analyze_program(main, checks=("shape",)).errors
        return tuple(out.shape), " ".join(str(d) for d in found)

    shape, errors = shape_errors()
    assert shape[-1] == 8 * 4 and not errors    # 8 heads of Dv = 8 / 2
    assert "feature dim" in shape_errors(qd=40)[1]
    assert "not a multiple" in shape_errors(kv=3)[1]
    shape, errors = shape_errors(chunk=True, ring=True, window=8, cap=8)
    assert shape[-2:] == (5, 32) and not errors
    assert "serves a window of 1 to 8" in shape_errors(
        chunk=True, ring=True, window=9, cap=8)[1]


def test_cost_rules_count_a_window_and_the_caches_own_widths():
    from paddle_tpu.analysis.cost import estimate_program

    def cost(window):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            q = layers.data("q", shape=[48], dtype="float32")
            ck = layers.data("ck", shape=[64, 12], dtype="float32")
            cv = layers.data("cv", shape=[64, 8], dtype="float32")
            pos = layers.data("pos", shape=[], dtype="int32")
            layers.cached_attention(q, ck, cv, pos, 8, 2, window=window)
        report = estimate_program(main, batch=4)
        return [r for r in report.records
                if r.op.type == "cached_attention"][0]

    full, windowed = cost(0), cost(16)
    # 8 heads score 6-wide keys and mix 4-wide values over what is read
    assert full.flops == 2.0 * 4 * 64 * (48 + 32)
    assert windowed.flops == 2.0 * 4 * 16 * (48 + 32)
    assert full.hbm_bytes == (4 * 64 * (12 + 8) + 4 * (48 + 32)) * 4


# OPT's two programs (``benchmark/builders/opt.py`` at a small size) as
# they lowered at the parent of the PR that gave ``cached_attention*`` and
# ``kv_cache_write*`` their new attributes (commit d09dd59; JAX 0.9.0):
# sha256 of ``Executor.lowered_hlo_text(optimized=False)``. The plain forms
# must lower as they did, byte for byte, so that the two OPT cells cannot
# move. A PR that changes the plain forms on purpose records new digests
# from ITS parent's checkout with this same builder.
OPT_STABLEHLO = {
    "step": "c2a749e62f502129376ae3581b651666ca89cd877d5aa94990e363045234d0fa",
    "chunk": "a9b7891bfc0b87fbad1460ca2035cdb8383792e3399d50171f6547a593baf1dc",
}


@pytest.mark.parametrize("kind,lanes", [("step", ()), ("chunk", (8,))])
def test_opts_programs_lower_to_the_parents_stablehlo(kind, lanes):
    builder = harness.load_module(os.path.join(
        ROOT, "benchmark", "builders", "opt.py"))
    sizes = dict(vocab_size=96, hidden_size=32, ffn_dim=64,
                 num_attention_heads=4, num_hidden_layers=2,
                 max_position_embeddings=64)
    scope = fluid.Scope()
    built = {}
    for k in ("step", "chunk"):       # both, in the order the digests saw
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 1
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            fetch, spec = getattr(builder, k)(dtype="bfloat16", **sizes)
        if k == "step":
            with fluid.scope_guard(scope):
                fluid.Executor(fluid.CPUPlace()).run(startup)
        built[k] = (main, fetch, spec)
    main, fetch, spec = built[kind]
    feeds = [spec["token_feed"], spec["pos_feed"]] + [
        c["feed"] for c in spec["cache_feeds"]]
    predictor = ProgramPredictor(main, feeds, fetch, scope=scope)
    feed = {spec["token_feed"]: np.zeros((2,) + lanes, np.int64),
            spec["pos_feed"]: np.zeros((2,) + lanes, np.int32)}
    for c in spec["cache_feeds"]:
        feed[c["feed"]] = jnp.zeros((2, 16) + tuple(c["tail"]),
                                    jnp.bfloat16)
    predictor.run(feed, return_numpy=False)
    text = predictor._exe.lowered_hlo_text(optimized=False)
    assert hashlib.sha256(text.encode()).hexdigest() == OPT_STABLEHLO[kind]


# -- the share of the experts ------------------------------------------------------

def test_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """MiMo-V2-Flash's routing (sigmoid scores, a selection bias that
    chooses and does not weigh, top-k renormalised, scale 1, NO shared
    expert) at 32 experts in 16 shares of 2: the routed parts the shares
    compute add up to the uncut layer as the reference gives it, and the
    reference given one share is that share."""
    rng = np.random.default_rng(8)
    d, f, e, k, t = 16, 12, 32, 4, 37
    y = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    p = {"moe.router": rng.standard_normal((d, e)),
         "moe.router_bias": 0.5 * rng.standard_normal(e),
         "moe.experts.gate": rng.standard_normal((e, f, d)) / 4,
         "moe.experts.up": rng.standard_normal((e, f, d)) / 4,
         "moe.experts.down": rng.standard_normal((e, d, f)) / 4}
    p = {name: jnp.asarray(v, jnp.float32) for name, v in p.items()}
    a = {"top_k": k, "norm_topk_prob": True, "scale": 1.0,
         "first_expert": 0}
    whole = REFERENCE._experts(y, p, a, EXACT)
    total, load = 0.0, 0
    for lo in range(0, e, 2):
        part, counts = moe.routed_experts(
            y, p["moe.router"], p["moe.experts.gate"][lo:lo + 2],
            p["moe.experts.up"][lo:lo + 2], p["moe.experts.down"][lo:lo + 2],
            k, lo, score="sigmoid", bias=p["moe.router_bias"], scale=1.0)
        total = total + part
        load += int(counts.sum())
        held = dict(p, **{n: p[n][lo:lo + 2] for n in (
            "moe.experts.gate", "moe.experts.up", "moe.experts.down")})
        np.testing.assert_allclose(
            part, REFERENCE._experts(y, held, dict(a, first_expert=lo),
                                     EXACT), rtol=1e-4, atol=1e-5)
    assert load == t * k                     # every pick is some share's
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    # the bias moved picks: the routing is not the plain top-k of the scores
    scores = jax.nn.sigmoid(y @ p["moe.router"])
    assert (jax.lax.top_k(scores, k)[1]
            != jax.lax.top_k(scores + p["moe.router_bias"], k)[1]).any()
