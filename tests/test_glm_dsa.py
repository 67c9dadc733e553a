"""GLM-5.2's serving path at a small size on the CPU, float32 declared,
seeded weights, ``index_topk`` 8 and contexts of 40 and more: chunks then
steps through a real ``DecodeBatcher`` against the benchmark's plain
reference (and the reference without the selection, or without IndexShare,
failing the same tolerance), the indexer and the latent attention against
plain forms, ``rotary`` with fed positions against a closed form, and the
sixteen shares of one expert layer adding up to the uncut layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.inference import ProgramPredictor
from paddle_tpu.models import glm_dsa
from paddle_tpu.ops import sparse_latent
from paddle_tpu.parallel import moe
from paddle_tpu.serving.decode_batcher import DecodeBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

REFERENCE = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "glm-5.2.py"))
EXACT = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "precision.py")).exact

sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
import tiny_glm  # noqa: E402

# the benchmark's tiny twin: layers 2-6 of a 7-layer pattern (dense + full,
# three shared, sparse + full), 4 of 8 experts, index_topk 8
_TWIN = tiny_glm.tiny_config("float32")
TINY = {k: _TWIN[k] for k in _TWIN["builder_keys"]}
VOCAB = TINY["vocab_size"]
TOL = dict(rtol=2e-4, atol=2e-5)


def _draw(rng, name, shape):
    if name.endswith("norm.w"):
        return 1.0 + 0.1 * rng.standard_normal(shape)
    if name.endswith(".b") or name.endswith("router_bias"):
        # a selection bias of the scores' own size, so that it moves picks
        return (0.3 if name.endswith("router_bias") else 0.02) \
            * rng.standard_normal(shape)
    fan_in = shape[2] if len(shape) == 3 else (
        shape[-1] if "embed" in name else shape[0])
    return rng.standard_normal(shape) / np.sqrt(fan_in)


class Recorded:
    """A predictor that keeps the logits of every run."""

    def __init__(self, predictor):
        self._predictor = predictor
        self.fetch_names = predictor.fetch_names
        self.logits = []

    def run(self, feed, return_numpy=False):
        outs = self._predictor.run(feed, return_numpy=return_numpy)
        self.logits.append(np.asarray(outs[0]))
        return outs


@pytest.fixture(scope="module")
def served():
    """(weights, prompt, served tokens, the step program's logits of row 0
    a step, the engine's program counters) of one request whose prompt goes
    in by chunks of 8 and 16 and whose answer comes out by steps."""
    scope = fluid.Scope()
    predictors, specs = {}, {}
    for kind in ("step", "chunk"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            fetch, spec = getattr(glm_dsa, "glm_dsa_" + kind)(
                dtype="float32", **TINY)
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
    step = Recorded(predictors["step"])
    batcher = DecodeBatcher(
        step, specs["step"], ladder=(2,), ctx_ladder=(64,), start=False,
        prefill={"predictor": predictors["chunk"], "spec": specs["chunk"],
                 "ladder": (8, 16)})
    prompt = np.random.default_rng(1).integers(0, VOCAB, size=43)
    future = batcher.submit(prompt, max_new_tokens=7)
    batcher.drive()
    tokens = np.asarray(future.result())
    counters = {line.split()[0].rsplit("program_", 1)[1]: float(
        line.split()[1]) for line in
        batcher.metrics_.prometheus_text().splitlines()
        if "_program_" in line and not line.startswith("#")}
    assert specs["chunk"].get("logits_fetch") is None   # it only ingests
    return (weights, prompt, tokens,
            np.stack([rows[0] for rows in step.logits]), counters,
            (predictors, specs))


def _reference_rows(weights, prompt, tokens, **changed):
    full = REFERENCE.logits(weights, np.concatenate([prompt, tokens]),
                            dict(TINY, **changed), EXACT)
    return np.asarray(full[len(prompt) - 1:len(prompt) - 1 + len(tokens)])


def test_chunks_then_steps_give_the_references_logits(served):
    weights, prompt, tokens, logits, counters, _ = served
    assert len(tokens) == 7 and len(logits) == 7
    np.testing.assert_allclose(
        logits, _reference_rows(weights, prompt, tokens), **TOL)
    # the served token is the reference's best at every position
    assert (np.argmax(logits, -1) == tokens).all()
    # seven steps at positions 42..48, two full layers: 8 of 43..49 cached
    # (and the one position of the free slot row beside it)
    assert counters["index_selected"] == 2 * (7 * 8 + 7)
    assert counters["index_cached"] == 2 * (sum(range(43, 50)) + 7)
    assert 0 < counters["moe_rows_held"] <= counters["moe_rows_run"]


def test_reference_without_the_selection_fails_the_tolerance(served):
    weights, prompt, tokens, logits, _, _ = served
    dense = _reference_rows(weights, prompt, tokens, index_topk=4096)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(logits, dense, **TOL)


def test_reference_without_index_share_fails_the_tolerance(served):
    """The shared layers given indexers of their own (fresh weights, every
    layer ``full``): the reference no longer follows the program."""
    weights, prompt, tokens, logits, _, _ = served
    rng = np.random.default_rng(5)
    own = dict(weights)
    for l in (3, 4, 5):
        for leaf in ("wq_b", "wk", "k_norm.w", "k_norm.b", "weights_proj"):
            like = weights["glm.l2.indexer." + leaf]
            name = "glm.l%d.indexer.%s" % (l, leaf)
            own[name] = jnp.asarray(_draw(rng, name, like.shape),
                                    jnp.float32)
    rows = _reference_rows(own, prompt, tokens,
                           indexer_types=["full"] * 7)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(logits, rows, **TOL)


def test_speculation_refuses_a_chunk_program_that_builds_no_head(served):
    _, _, _, _, _, (predictors, specs) = served
    with pytest.raises(ValueError, match="logits"):
        DecodeBatcher(predictors["step"], specs["step"], ladder=(2,),
                      ctx_ladder=(64,), start=False,
                      prefill={"predictor": predictors["chunk"],
                               "spec": specs["chunk"], "ladder": (8,)},
                      speculative={"draft": object(), "k": 2})


def test_counters_an_op_leaves_in_the_scope_stage_no_second_executable(
        served):
    """``routed_experts`` writes ``<name>.load`` and ``<name>.rows`` into a
    scope that never ran a startup program: written and never read, they are
    no inputs of the non-donating step, so their first appearance does not
    change the variant's key."""
    _, _, _, _, _, (predictors, _) = served
    for kind in ("step", "chunk"):
        exe = predictors[kind]._exe
        assert exe.runs > 2 and exe.variant_misses <= 2, kind   # two rungs
    assert predictors["step"]._exe.variant_misses == 1
    scope = predictors["step"]._scope
    assert "glm.l3.moe.rows" in scope and "glm.l3.moe.load" in scope


# -- the indexer ---------------------------------------------------------------

def _index_inputs(rng, b, kq, c, heads=2, d=8):
    q = jnp.asarray(rng.standard_normal((b, kq, heads * d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, kq, heads)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((b, c, d)), jnp.float32)
    return q, w, keys


def _plain_scores(q, w, keys, heads):
    b, kq, _ = q.shape
    s = jnp.einsum("bkhd,bcd->bkhc", q.reshape(b, kq, heads, -1), keys)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=2)


def test_fewer_cached_positions_than_top_k_selects_them_all():
    rng = np.random.default_rng(2)
    q, w, keys = _index_inputs(rng, 3, 1, 16)
    pos = jnp.asarray([0, 4, 15], jnp.int32)
    index, count = sparse_latent.sparse_index(q[:, 0], w[:, 0], keys, pos,
                                              2, 8)
    index = np.asarray(index)
    assert sorted(index[0]) == [0] + [16] * 7
    assert sorted(index[1]) == [0, 1, 2, 3, 4, 16, 16, 16]
    assert (index[2] < 16).all() and len(set(index[2])) == 8
    assert list(np.asarray(count)) == [1 + 5 + 8, 1 + 5 + 16]
    # a chunk's lanes: lane j of row 0 sits at position j, row 1 is padding
    q, w, keys = _index_inputs(rng, 2, 6, 16)
    pos = jnp.stack([jnp.arange(6), jnp.full((6,), 16)]).astype(jnp.int32)
    mask, count = sparse_latent.sparse_index_chunk(q, w, keys, pos, 2, 8)
    causal = np.arange(16)[None, :] <= np.arange(6)[:, None]
    assert (np.asarray(mask[0]) == causal).all()
    assert not np.asarray(mask[1]).any()
    assert list(np.asarray(count)) == [21, 21]


@pytest.mark.parametrize("top_k", [3, 8])
def test_step_and_chunk_select_the_exact_top_k(top_k):
    rng = np.random.default_rng(3)
    b, kq, c = 2, 5, 32
    q, w, keys = _index_inputs(rng, b, kq, c)
    pos = jnp.asarray([[20, 21, 22, 23, 24], [3, 4, 5, 32, 32]], jnp.int32)
    scores = np.asarray(_plain_scores(q, w, keys, 2))
    mask, _ = sparse_latent.sparse_index_chunk(q, w, keys, pos, 2, top_k)
    for bi in range(b):
        for j in range(kq):
            p = int(pos[bi, j])
            got = set(np.flatnonzero(np.asarray(mask[bi, j])))
            if p >= c:
                assert not got
                continue
            best = np.argsort(-scores[bi, j, :p + 1],
                              kind="stable")[:top_k]
            assert got == set(best)
            index, _ = sparse_latent.sparse_index(
                q[bi:bi + 1, j], w[bi:bi + 1, j], keys[bi:bi + 1],
                pos[bi:bi + 1, j], 2, top_k)
            assert set(np.asarray(index[0])) - {c} == got


def test_select_top_breaks_ties_to_the_lower_position():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 3.0, -jnp.inf, 0.5],
                          [-2.0, -2.0, -2.0, -jnp.inf, -jnp.inf, -2.0]])
    got = np.asarray(sparse_latent.select_top(scores, 2))
    assert got.tolist() == [[False, True, True, False, False, False],
                            [True, True, False, False, False, False]]


# -- the index path in float32 ---------------------------------------------------

def test_fc_reads_a_weight_kept_in_another_type_exactly():
    """``fc(param_dtype=, precision=)``: a float32 input against the
    bfloat16 weight that a bfloat16 ``fc`` of the same name made: one
    parameter, a float32 product of the weight's own values."""
    x = np.random.default_rng(9).standard_normal((3, 24)).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = layers.data("x", shape=[24])
        low = layers.fc(layers.cast(xv, "bfloat16"), 5, bias_attr=False,
                        param_attr=fluid.ParamAttr(name="w"))
        high = layers.fc(xv, 5, bias_attr=False, name="w.exact",
                         param_attr=fluid.ParamAttr(name="w"),
                         param_dtype="bfloat16", precision="highest")
        with pytest.raises(ValueError, match="dtype"):
            layers.fc(xv, 5, bias_attr=False,
                      param_attr=fluid.ParamAttr(name="w"))
    params = main.global_block().all_parameters()
    assert [(q.name, str(q.dtype)) for q in params] == [("w", "bfloat16")]
    assert str(low.dtype) == "bfloat16" and str(high.dtype) == "float32"
    w = jnp.asarray(np.random.default_rng(10).standard_normal((24, 5)),
                    jnp.bfloat16)
    want = x.astype(np.float64) @ np.asarray(w, np.float64)
    scope = fluid.Scope()
    scope.set("w", w)
    a, b = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x}, fetch_list=[low, high], scope=scope)
    np.testing.assert_allclose(b, want, rtol=1e-6, atol=1e-6)
    assert np.abs(np.asarray(a, np.float64) - want).max() > 1e-4


@pytest.fixture(scope="module")
def served_bfloat16():
    """The two programs declared bfloat16 over one scope of bfloat16
    weights, one row, with the first ``full`` layer's picks fetched."""
    scope = fluid.Scope()
    kinds = {}
    for kind in ("step", "chunk"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            fetch, spec = getattr(glm_dsa, "glm_dsa_" + kind)(
                dtype="bfloat16", **TINY)
        ops = main.global_block().ops
        index = [op for op in ops if op.type.startswith("sparse_index")]
        picked = index[0].output("Index" if kind == "step" else "Mask")
        feeds = [spec["token_feed"], spec["pos_feed"]] + [
            c["feed"] for c in spec["cache_feeds"]]
        predictor = ProgramPredictor(main, feeds, list(fetch) + [picked],
                                     scope=scope)
        kinds[kind] = (main, spec, predictor, index)
    rng = np.random.default_rng(11)
    weights = {q.name: jnp.asarray(_draw(rng, q.name, tuple(q.shape)),
                                   jnp.bfloat16)
               for q in kinds["step"][0].global_block().all_parameters()}
    for name, value in weights.items():
        scope.set(name, value)
    return kinds, weights


def test_the_index_path_is_float32_in_a_bfloat16_program(served_bfloat16):
    kinds, weights = served_bfloat16
    for kind, (main, spec, _, index) in kinds.items():
        params = main.global_block().all_parameters()
        assert {str(q.dtype) for q in params
                if not q.name.endswith("router_bias")} == {"bfloat16"}
        assert {q.name for q in params} <= set(weights)
        kept = {c["feed"]: c["dtype"] for c in spec["cache_feeds"]}
        assert kept == {
            "cache_latent_2": "bfloat16", "cache_index_2": "float32",
            "cache_latent_3": "bfloat16", "cache_latent_4": "bfloat16",
            "cache_latent_5": "bfloat16", "cache_latent_6": "bfloat16",
            "cache_index_6": "float32"}
        for op in index:
            assert str(op.input("CacheK").dtype) == "float32"
            # a step scores float32 queries exactly; a chunk its lanes'
            # bfloat16 ones in one pass
            assert str(op.input("Q").dtype) == (
                "float32" if kind == "step" else "bfloat16")
            assert str(op.input("W").dtype) == str(op.input("Q").dtype)


def test_a_bfloat16_step_picks_the_references_sets(served_bfloat16):
    """Layer 2 reads the embedding, which both sides hold alike: its index
    keys (cached by chunks and by steps), a step's index queries and the
    scores are float32 in the program as in the reference, so a step's set
    is the reference's, place for place, though everything else is
    bfloat16."""
    kinds, weights = served_bfloat16
    ctx, rung, top_k = 64, 8, TINY["index_topk"]
    tokens = np.random.default_rng(12).integers(0, VOCAB, size=60)
    wanted = np.asarray(REFERENCE.selections(
        weights, np.concatenate([tokens, np.zeros(ctx - 60, np.int64)]),
        TINY, EXACT)[2])
    caches = {c["feed"]: jnp.zeros((1, ctx) + tuple(c["tail"]), c["dtype"])
              for c in kinds["step"][1]["cache_feeds"]}

    def run(kind, tok, pos):
        _, spec, predictor, _ = kinds[kind]
        feed = dict(caches)
        feed[spec["token_feed"]], feed[spec["pos_feed"]] = tok, pos
        outs = predictor.run(feed, return_numpy=False)
        names = list(predictor.fetch_names)
        for c in spec["cache_feeds"]:
            caches[c["feed"]] = outs[names.index(c["fetch"])]
        return np.asarray(outs[-1])

    for at in range(0, 40, rung):
        run("chunk", tokens[None, at:at + rung].astype(np.int64),
            np.arange(at, at + rung, dtype=np.int32)[None])
    for t in range(40, 60):
        index = run("step", tokens[t:t + 1].astype(np.int64),
                    np.asarray([t], np.int32))[0]
        assert len(index) == top_k and t + 1 > top_k
        assert set(index) == set(np.flatnonzero(wanted[t])), t


# -- the latent attention ------------------------------------------------------

def _decompressed(q, kv_b, cache, member, heads, nope, v_dim, scale):
    """Per head: keys and values taken out of the latent, a softmax over
    the member positions. q [K, H*(N+P)], cache [C, R+P], member [K, C]."""
    r = kv_b.shape[0]
    both = kv_b.reshape(r, heads, nope + v_dim)
    qh = q.reshape(q.shape[0], heads, -1)
    k_nope = jnp.einsum("cr,rhn->chn", cache[:, :r], both[..., :nope])
    v = jnp.einsum("cr,rhv->chv", cache[:, :r], both[..., nope:])
    s = (jnp.einsum("khn,chn->khc", qh[..., :nope], k_nope)
         + jnp.einsum("khp,cp->khc", qh[..., nope:], cache[:, r:])) * scale
    s = jnp.where(member[:, None, :], s, -jnp.inf)
    return jnp.einsum("khc,chv->khv", jax.nn.softmax(s, -1), v).reshape(
        q.shape[0], heads * v_dim)


def test_absorbed_attention_is_the_decompressed_one():
    rng = np.random.default_rng(4)
    heads, nope, rope, v_dim, r, c, kq = 4, 6, 4, 8, 8, 1536, 3
    scale = (nope + rope) ** -0.5
    kv_b = jnp.asarray(rng.standard_normal((r, heads * (nope + v_dim)))
                       / np.sqrt(r), jnp.float32)
    cache = jnp.asarray(rng.standard_normal((2, c, r + rope)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, kq, heads * (nope + rope))),
                    jnp.float32)
    # row 0 reaches past two blocks of 512, row 1 is padding but one lane
    pos = jnp.asarray([[1100, 1101, 1102], [c, 7, c]], jnp.int32)
    member = rng.random((2, kq, c)) < 0.3
    member &= np.arange(c)[None, None, :] <= np.asarray(pos)[:, :, None]
    member &= (np.asarray(pos) < c)[:, :, None]    # a pad lane selects none
    member[0, :, 1100] = member[1, 1, 7] = True
    member = jnp.asarray(member)
    out = sparse_latent.latent_attention_chunk(
        q, kv_b, cache, member, pos, heads, nope, v_dim, scale)
    for bi, j in ((0, 0), (0, 1), (0, 2), (1, 1)):
        want = _decompressed(q[bi, j:j + 1], kv_b, cache[bi],
                             member[bi, j:j + 1], heads, nope, v_dim, scale)
        np.testing.assert_allclose(out[bi, j:j + 1], want, rtol=1e-4,
                                   atol=1e-5)
        # the step form over the same set, named by its positions
        at = np.flatnonzero(np.asarray(member[bi, j]))
        index = jnp.asarray(np.concatenate(
            [at, np.full(c - len(at), c)])[None, :640], jnp.int32)
        assert len(at) <= 640
        step = sparse_latent.latent_attention(
            q[bi:bi + 1, j], kv_b, cache[bi:bi + 1], index, heads, nope,
            v_dim, scale)
        np.testing.assert_allclose(step, want, rtol=1e-4, atol=1e-5)
    assert not np.asarray(out[1, 0]).any() and not np.asarray(
        out[1, 2]).any()                       # a pad lane gives 0


# -- rotary --------------------------------------------------------------------

@pytest.mark.parametrize("interleaved", [False, True])
@pytest.mark.parametrize("step", [False, True], ids=["chunk", "step"])
def test_rotary_with_fed_positions_is_the_closed_form(interleaved, step):
    heads, d, rot, off, theta = 3, 10, 4, 6, 100.0
    rng = np.random.default_rng(6)
    lead = (2,) if step else (2, 5)
    x = rng.standard_normal(lead + (heads * d,)).astype(np.float32)
    pos = rng.integers(0, 500, size=lead).astype(np.int32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = layers.data("x", shape=list(lead[1:]) + [heads * d])
        pv = layers.data("p", shape=list(lead[1:]), dtype="int32")
        out = layers.rotary(xv, heads, rot, theta, pos=pv,
                            interleaved=interleaved, offset=off)
    got, = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x, "p": pos}, fetch_list=[out],
        scope=fluid.Scope())
    want = x.reshape(lead + (heads, d)).copy()
    for i in range(rot // 2):
        a, b = (off + 2 * i, off + 2 * i + 1) if interleaved else (
            off + i, off + i + rot // 2)
        angle = pos[..., None] * theta ** (-2.0 * i / rot)
        xa, xb = want[..., a].copy(), want[..., b].copy()
        want[..., a] = xa * np.cos(angle) - xb * np.sin(angle)
        want[..., b] = xb * np.cos(angle) + xa * np.sin(angle)
    np.testing.assert_allclose(got, want.reshape(x.shape), rtol=1e-4,
                               atol=1e-5)


def test_rotary_without_fed_positions_keeps_the_index_along_t():
    x = np.random.default_rng(7).standard_normal((2, 6, 8)).astype(
        np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = layers.data("x", shape=[6, 8])
        pv = layers.data("p", shape=[6], dtype="int32")
        plain = layers.rotary(xv, 2, 4, 50.0)
        fed = layers.rotary(xv, 2, 4, 50.0, pos=pv)
    a, b = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x, "p": np.tile(np.arange(6, dtype=np.int32),
                                         (2, 1))},
        fetch_list=[plain, fed], scope=fluid.Scope())
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


# -- the share of the experts ---------------------------------------------------

def test_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """GLM-5.2's routing (sigmoid scores, a selection bias that chooses and
    does not weigh, top-k renormalised times 2.5) at 32 experts in 16
    shares of 2: the routed parts the shares compute, with the shared
    expert, which every chip computes alike, counted once, are the uncut
    layer as the reference gives it; the reference given one share is that
    share plus the shared part."""
    rng = np.random.default_rng(8)
    d, f, e, k, t = 16, 12, 32, 4, 37
    y = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    p = {"moe.router": rng.standard_normal((d, e)),
         "moe.router_bias": 0.5 * rng.standard_normal(e),
         "moe.experts.gate": rng.standard_normal((e, f, d)) / 4,
         "moe.experts.up": rng.standard_normal((e, f, d)) / 4,
         "moe.experts.down": rng.standard_normal((e, d, f)) / 4,
         "moe.shared.gate_proj": rng.standard_normal((d, f)) / 4,
         "moe.shared.up_proj": rng.standard_normal((d, f)) / 4,
         "moe.shared.down_proj": rng.standard_normal((f, d)) / 4}
    p = {name: jnp.asarray(v, jnp.float32) for name, v in p.items()}
    a = {"top_k": k, "norm_topk_prob": True, "scale": 2.5,
         "first_expert": 0}
    whole = REFERENCE._experts(y, p, a, EXACT)
    shared = REFERENCE._swiglu(y, p["moe.shared.gate_proj"],
                               p["moe.shared.up_proj"],
                               p["moe.shared.down_proj"], EXACT)
    total, load = shared, 0
    for lo in range(0, e, 2):
        part, counts = moe.routed_experts(
            y, p["moe.router"], p["moe.experts.gate"][lo:lo + 2],
            p["moe.experts.up"][lo:lo + 2], p["moe.experts.down"][lo:lo + 2],
            k, lo, score="sigmoid", bias=p["moe.router_bias"], scale=2.5)
        total = total + part
        load += int(counts.sum())
        held = dict(p, **{n: p[n][lo:lo + 2] for n in (
            "moe.experts.gate", "moe.experts.up", "moe.experts.down")})
        np.testing.assert_allclose(
            part + shared,
            REFERENCE._experts(y, held, dict(a, first_expert=lo), EXACT),
            rtol=1e-4, atol=1e-5)
    assert load == t * k                     # every pick is some share's
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    # the bias moved picks: the routing is not the plain top-k of the scores
    scores = jax.nn.sigmoid(y @ p["moe.router"])
    assert (jax.lax.top_k(scores, k)[1]
            != jax.lax.top_k(scores + p["moe.router_bias"], k)[1]).any()
