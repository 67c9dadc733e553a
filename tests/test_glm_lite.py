"""GLM-4.7-Flash's serving path at a small size on the CPU, float32 declared,
seeded weights: chunks then verifying steps through a real ``DecodeBatcher``
against the benchmark's plain reference (logits, not tokens), the prediction
module's drafts against the reference's ``draft_logits``, each departure
alone failing the same tolerance, and the dense step of latent attention
against the chunk form under a causal mask and against the decompressed
form."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import ProgramPredictor
from paddle_tpu.models import glm_lite
from paddle_tpu.ops import sparse_latent
from paddle_tpu.serving.decode_batcher import DecodeBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

REFERENCE_PATH = os.path.join(ROOT, "benchmark", "reference",
                              "glm-4.7-flash.py")
REFERENCE = harness.load_module(REFERENCE_PATH)
EXACT = harness.load_module(os.path.join(
    ROOT, "benchmark", "reference", "precision.py")).exact

sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
import tiny_glm_lite  # noqa: E402

# the benchmark's tiny twin: a dense layer, two expert layers of all 8
# experts, the module as layer 5
_TWIN = tiny_glm_lite.tiny_config("float32")
TINY = {k: _TWIN[k] for k in _TWIN["builder_keys"]}
VOCAB = TINY["vocab_size"]
MODULE = "cache_latent_%d" % TINY["nextn_layer"]
TOL = dict(rtol=2e-4, atol=2e-5)
PROMPT, ANSWER = 43, 12


def _draw(rng, name, shape):
    if name.endswith("norm.w"):
        return 1.0 + 0.1 * rng.standard_normal(shape)
    if name.endswith("router_bias"):
        # a selection bias of the scores' own size, so that it moves picks
        return 0.3 * rng.standard_normal(shape)
    fan_in = shape[2] if len(shape) == 3 else (
        shape[-1] if "embed" in name else shape[0])
    return rng.standard_normal(shape) / np.sqrt(fan_in)


class Recorded:
    """A step predictor that keeps what every run was fed and gave: (tokens
    [b, 2], positions [b, 2], yield [b, 4], the model's logits [b, 2, V],
    the module's [b, 2, V])."""

    def __init__(self, predictor, spec):
        self._predictor = predictor
        self.fetch_names = predictor.fetch_names
        names = list(predictor.fetch_names)
        self._at = [names.index(n) for n in
                    [spec["self_draft"]["yield_fetch"]]
                    + spec["self_draft"]["logits"]]
        self._feeds = (spec["token_feed"], spec["pos_feed"])
        self.runs = []

    def run(self, feed, return_numpy=False):
        outs = self._predictor.run(feed, return_numpy=return_numpy)
        self.runs.append(tuple(np.asarray(feed[n]) for n in self._feeds)
                         + tuple(np.asarray(outs[i]) for i in self._at))
        return outs


def _build(sizes=TINY):
    """(predictors, specs, [(leaf, shape)]) of the two programs over one
    scope; the step predictor also fetches both heads' logits."""
    scope = fluid.Scope()
    predictors, specs = {}, {}
    for kind in ("step", "chunk"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            fetch, spec = getattr(glm_lite, "glm_lite_" + kind)(
                dtype="float32", **sizes)
        feeds = [spec["token_feed"], spec["pos_feed"]] + [
            c["feed"] for c in spec["cache_feeds"]]
        if kind == "step":
            block = main.global_block()
            fetch = fetch + [block.var(n)
                             for n in spec["self_draft"]["logits"]]
            leaves = [(p.name, tuple(p.shape))
                      for p in block.all_parameters()]
        predictors[kind] = ProgramPredictor(main, feeds, fetch, scope=scope)
        specs[kind] = spec
    return predictors, specs, leaves, scope


def _weights(leaves, scope):
    rng = np.random.default_rng(0)
    weights = {name: jnp.asarray(_draw(rng, name, shape), jnp.float32)
               for name, shape in leaves}
    for name, value in weights.items():
        scope.set(name, value)
    return weights


def _batcher(predictors, specs, step=None, context=64):
    return DecodeBatcher(
        step or predictors["step"], specs["step"], ladder=(2,),
        ctx_ladder=(context,), start=False,
        prefill={"predictor": predictors["chunk"], "spec": specs["chunk"],
                 "ladder": (8, 16)})


@pytest.fixture(scope="module")
def served():
    """(weights, prompt, served tokens, the recorded steps of row 0, the
    engine's program counters, the batcher's metrics) of one request whose
    prompt goes in by chunks of 8 and 16 and whose answer comes out by
    verifying steps."""
    predictors, specs, leaves, scope = _build()
    weights = _weights(leaves, scope)
    step = Recorded(predictors["step"], specs["step"])
    batcher = _batcher(predictors, specs, step)
    prompt = np.random.default_rng(1).integers(0, VOCAB, size=PROMPT)
    future = batcher.submit(prompt, max_new_tokens=ANSWER)
    batcher.drive()
    tokens = np.asarray(future.result())
    counters = {line.split()[0].rsplit("program_", 1)[1]: float(
        line.split()[1]) for line in
        batcher.metrics_.prometheus_text().splitlines()
        if "_program_" in line and not line.startswith("#")}
    return (weights, prompt, tokens, [tuple(a[0] for a in run)
                                      for run in step.runs], counters,
            batcher.metrics(), (predictors, specs))


def _walk(prompt, tokens, steps):
    """(position of lane 0, tokens emitted so far, the step's row) a step,
    following the answer the steps gave; checks that the steps' yields ARE
    the answer."""
    p, done, out = len(prompt) - 1, 0, []
    for tok, pos, gave, logits, drafts in steps:
        assert pos[0] == p and tok[0] == (
            prompt[-1] if done == 0 else tokens[done - 1])
        out.append((p, done, tok, pos, gave, logits, drafts))
        fed = pos[1] == p + 1
        stood = bool(fed and tok[1] == gave[1])
        assert gave[0] == 1 + stood
        count = min(1 + stood, len(tokens) - done)
        assert list(gave[1:1 + count]) == list(tokens[done:done + count])
        p, done = p + count, done + count
    assert done == len(tokens)
    return out


def test_chunks_then_steps_give_the_references_logits(served):
    weights, prompt, tokens, steps, counters, metrics, _ = served
    assert len(tokens) == ANSWER
    full = np.concatenate([prompt, tokens])
    rows = np.asarray(REFERENCE.logits(weights, full, TINY, EXACT))
    for p, done, tok, pos, gave, logits, _ in _walk(prompt, tokens, steps):
        np.testing.assert_allclose(logits[0], rows[p], **TOL)
        assert gave[1] == np.argmax(rows[p])
        if pos[1] == p + 1 and tok[1] == full[p + 1]:
            # a draft that was the sequence's own token: lane 1 is the
            # model's row of the next position, whether it stood or not
            np.testing.assert_allclose(logits[1], rows[p + 1], **TOL)
    # every step but the last had a draft to judge (the chunk's, then the
    # steps' own); the engine's books and the program's agree
    assert counters["mtp_drafted"] == metrics["spec_drafted"] == len(steps) - (
        1 if steps[-1][1][1] != steps[-1][1][0] + 1 else 0)
    assert counters["mtp_accepted"] == metrics["spec_accepted"]
    assert metrics["spec_steps"] == len(steps)
    # all but the first were fed by the step before, on the device, before
    # that one was read; ``_walk`` has held each one's tokens and positions
    # to the answer
    assert metrics["decode_steps_ahead_total"] == len(steps) - 1
    assert metrics["spec_accepted"] == ANSWER - len(steps)
    # two lanes of one live row: at most 2 x 2 picks an expert layer, and
    # the dead row's lanes beside them
    layers_ = 3
    assert 0 < counters["moe_experts_touched"] <= len(steps) * layers_ * 8
    assert 0 < counters["moe_rows_held"] <= counters["moe_rows_run"]


# the twin with a latent row the step kernel takes (a latent of 128 under a
# rotary tail of 64, as the published 512 + 64) over a rung of two blocks
WIDE = dict(TINY, kv_lora_rank=128, qk_rope_head_dim=64,
            max_position_embeddings=256)


@pytest.mark.parametrize("path", ["latent_step", "rung_xla"])
def test_steps_give_the_references_logits_by_the_kernel_and_by_the_rung_form(
        path, monkeypatch):
    """A request whose verifying steps cross the border of a block of 128:
    through the step kernel where its gate admits the site (the interpreter
    stands in for the TPU here) and through the ``jnp`` form where it does
    not (the CPU), the same rows of the reference."""
    from paddle_tpu.ops import cache_attention

    monkeypatch.setattr(cache_attention, "_INTERPRET", path == "latent_step")
    jax.clear_caches()
    predictors, specs, leaves, scope = _build(WIDE)
    weights = _weights(leaves, scope)
    step = Recorded(predictors["step"], specs["step"])
    batcher = _batcher(predictors, specs, step, context=256)
    prompt = np.random.default_rng(2).integers(0, VOCAB, size=121)
    future = batcher.submit(prompt, max_new_tokens=ANSWER)
    batcher.drive()
    tokens = np.asarray(future.result())
    jax.clear_caches()
    for kind, sites in (("step", 4), ("chunk", 0)):
        took = [op.attrs["_kernel_choice"]["kernel"]
                for op in predictors[kind]._program.global_block().ops
                if op.type == "latent_attention_dense"]
        assert took == [path] * sites
    full = np.concatenate([prompt, tokens])
    rows = np.asarray(REFERENCE.logits(weights, full, WIDE, EXACT))
    walked = _walk(prompt, tokens, [tuple(a[0] for a in run)
                                    for run in step.runs])
    assert walked[0][0] < 127 < walked[-1][0] + 1     # over the border
    for p, _, _, _, gave, logits, _ in walked:
        np.testing.assert_allclose(logits[0], rows[p], **TOL)
        assert gave[1] == np.argmax(rows[p])


def test_the_modules_drafts_are_the_references(served):
    weights, prompt, tokens, steps, _, _, _ = served
    full = np.concatenate([prompt, tokens])
    rows = np.asarray(REFERENCE.draft_logits(weights, full, TINY, EXACT))
    walked = _walk(prompt, tokens, steps)
    # the draft the first step was fed is the chunk program's: the module's
    # best token after the prompt's last lane but one
    assert walked[0][2][1] == np.argmax(rows[len(prompt) - 2])
    for p, done, tok, pos, gave, _, drafts in walked:
        if done + 1 <= len(tokens) - 1:
            # lane 0: h_p and the token the model put at p + 1
            np.testing.assert_allclose(drafts[0], rows[p], **TOL)
        if gave[0] == 2 and done + 2 <= len(tokens) - 1:
            np.testing.assert_allclose(drafts[1], rows[p + 1], **TOL)
            assert gave[3] == np.argmax(rows[p + 1])
        elif gave[0] == 1:
            assert gave[3] == np.argmax(drafts[0])


def _departed(patch):
    """A fresh copy of the reference (a module of its own, with its own jit
    caches) with ``patch`` applied before its first trace."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "departed_reference_%s" % patch.__qualname__.replace(".", "_"),
        REFERENCE_PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    patch(module)
    return module


def _main_rows_differ(served, module, args=TINY):
    weights, prompt, tokens, steps, _, _, _ = served
    rows = np.asarray(module.logits(
        weights, np.concatenate([prompt, tokens]), args, EXACT))
    walked = _walk(prompt, tokens, steps)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(
            np.stack([w[5][0] for w in walked]),
            np.stack([rows[w[0]] for w in walked]), **TOL)


def test_attention_over_a_selection_fails_the_tolerance(served):
    def patch(module):
        def selected(q, c, k_pe, p, a, ops):
            # the 16 latest positions of each query, and not all of them
            t = c.shape[0]
            near = jnp.arange(t)[None, :] > jnp.arange(t)[:, None] - 16
            heads, nope, v_dim = a["heads"], a["nope_dim"], a["v_dim"]
            rank, rot = c.shape[-1], a["rope_dim"]
            kv_b = p["attn.kv_b"].reshape(rank, heads, nope + v_dim)
            k_nope = jnp.einsum("tr,rhn->thn", c, kv_b[..., :nope])
            v = jnp.einsum("tr,rhv->thv", c, kv_b[..., nope:])
            s = (jnp.einsum("qhn,khn->hqk", q[..., :nope], k_nope)
                 + jnp.einsum("qhp,kp->hqk", q[..., nope:], k_pe)) \
                * (nope + rot) ** -0.5
            causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
            s = jnp.where((causal & near)[None], s, -jnp.inf)
            return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, -1),
                              v).reshape(t, heads * v_dim)

        module._attend = selected

    _main_rows_differ(served, _departed(patch))


def test_a_bias_that_weighs_fails_the_tolerance(served):
    def patch(module):
        def weighed(y, p, a, ops):
            # the selection bias inside the weights too
            scores = jax.nn.sigmoid(ops.dot(y, p["moe.router"]))
            biased = scores + p["moe.router_bias"]
            weights, picks = jax.lax.top_k(biased, a["top_k"])
            weights = weights / jnp.sum(weights, -1, keepdims=True) \
                * a["scale"]
            dense = jnp.zeros_like(scores).at[
                jnp.arange(y.shape[0])[:, None], picks].set(weights)
            h = jax.nn.silu(jnp.einsum("td,efd->etf", y,
                                       p["moe.experts.gate"])) \
                * jnp.einsum("td,efd->etf", y, p["moe.experts.up"])
            routed = jnp.einsum("etf,edf,te->td", h, p["moe.experts.down"],
                                dense)
            return routed + module._swiglu(
                y, p["moe.shared.gate_proj"], p["moe.shared.up_proj"],
                p["moe.shared.down_proj"], ops)

        module._experts = weighed

    _main_rows_differ(served, _departed(patch))


def test_the_scale_left_out_fails_the_tolerance(served):
    _main_rows_differ(served, REFERENCE,
                      dict(TINY, routed_scaling_factor=1.0))


def test_a_module_fed_the_current_token_fails_the_tolerance(served):
    weights, prompt, tokens, steps, _, _, _ = served
    full = np.concatenate([prompt, tokens])
    # the module given Emb(t_i) beside h_i: the sequence shifted by one
    # under the embedding alone
    m, l, eps = REFERENCE, TINY["nextn_layer"], TINY["rms_norm_eps"]
    x = m._join(m._embed(weights, full), m._stream(weights, full, TINY,
                                                   EXACT),
                weights["glm.l%d.enorm.w" % l],
                weights["glm.l%d.hnorm.w" % l], weights["glm.norm.w"],
                weights["glm.l%d.eh_proj" % l], eps=eps, ops=EXACT)
    rows = np.asarray(m._head(
        m._run_layer(weights, x, l, False, TINY, EXACT),
        weights["glm.l%d.shared_head.norm.w" % l], weights["glm.lm_head"],
        eps=eps, ops=EXACT))
    walked = _walk(prompt, tokens, steps)[:-1]
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(
            np.stack([w[6][0] for w in walked]),
            np.stack([rows[w[0]] for w in walked]), **TOL)


def test_a_module_cache_without_the_prompt_fails_the_tolerance(served):
    """The same request with the module's cache wiped once the prompt is in:
    the served tokens stay the model's own (the drafts only decide how far a
    step gets), the drafts leave the reference's."""
    weights, prompt, tokens, _, _, _, (predictors, specs) = served
    step = Recorded(predictors["step"], specs["step"])
    batcher = _batcher(predictors, specs, step)
    future = batcher.submit(prompt, max_new_tokens=ANSWER)
    while not step.runs:
        if not any(s is not None and s.forcing for s in batcher._slots) \
                and batcher._slots:
            batcher._caches[MODULE] = jnp.zeros_like(batcher._caches[MODULE])
        batcher.drive(max_steps=1)
    batcher.drive()
    assert (np.asarray(future.result()) == tokens).all()
    steps = [tuple(a[0] for a in run) for run in step.runs]
    rows = np.asarray(REFERENCE.draft_logits(
        weights, np.concatenate([prompt, tokens]), TINY, EXACT))
    walked = _walk(prompt, tokens, steps)[1:-1]
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(
            np.stack([w[6][0] for w in walked]),
            np.stack([rows[w[0]] for w in walked]), **TOL)


def test_both_caches_hold_the_sequence_whatever_stood(served):
    """The caches after the run are those of the sequence itself: ONE chunk
    run over the whole sequence writes the same rows (a lane that did not
    stand was overwritten by the step after it)."""
    weights, prompt, tokens, _, _, _, (predictors, specs) = served
    batcher = _batcher(predictors, specs)
    future = batcher.submit(prompt, max_new_tokens=ANSWER)
    kept = {}
    while batcher._slots == [] or any(s is not None for s in batcher._slots):
        batcher.drive(max_steps=1)
        if batcher._caches:
            kept = {k: np.asarray(v) for k, v in batcher._caches.items()}
    assert (np.asarray(future.result()) == tokens).all()
    full = np.concatenate([prompt, tokens])
    n = len(full) - 1                  # the last token is never fed back
    feed = {specs["chunk"]["token_feed"]: full[None, :n + 1].astype(np.int64),
            specs["chunk"]["pos_feed"]: np.arange(n, dtype=np.int32)[None]}
    feed.update({c["feed"]: np.zeros((1, 64, c["tail"][0]), np.float32)
                 for c in specs["chunk"]["cache_feeds"]})
    outs = predictors["chunk"].run(feed, return_numpy=True)
    names = list(predictors["chunk"].fetch_names)
    for c in specs["chunk"]["cache_feeds"]:
        whole = outs[names.index(c["fetch"])][0]
        # the module's last row takes the token AFTER the last one fed back
        rows = n - 1 if c["feed"] == MODULE else n
        np.testing.assert_allclose(kept[c["feed"]][0, :rows], whole[:rows],
                                   **TOL)


def test_a_ring_refuses_the_self_draft(served):
    _, _, _, _, _, _, (predictors, specs) = served
    ringed = dict(specs["step"], cache_feeds=[
        dict(c, capacity=8) if i == 0 else c
        for i, c in enumerate(specs["step"]["cache_feeds"])])
    chunk = dict(specs["chunk"], pad_pos=1 << 20)
    with pytest.raises(ValueError, match="ring"):
        DecodeBatcher(predictors["step"], ringed, ladder=(2,),
                      ctx_ladder=(64,), start=False,
                      prefill={"predictor": predictors["chunk"],
                               "spec": chunk, "ladder": (8,)})


def test_the_self_draft_needs_the_chunk_program_and_no_second_proposer(
        served):
    _, _, _, _, _, _, (predictors, specs) = served
    with pytest.raises(ValueError, match="chunk program"):
        DecodeBatcher(predictors["step"], specs["step"], ladder=(2,),
                      ctx_ladder=(64,), start=False)
    with pytest.raises(ValueError, match="one proposer"):
        DecodeBatcher(predictors["step"], specs["step"], ladder=(2,),
                      ctx_ladder=(64,), start=False,
                      prefill={"predictor": predictors["chunk"],
                               "spec": specs["chunk"], "ladder": (8,)},
                      speculative={"draft": object(), "k": 2})


def test_the_module_is_part_of_the_configuration():
    for changed in (dict(num_nextn_predict_layers=0),
                    dict(nextn_layer=None),
                    dict(num_nextn_predict_layers=2)):
        with pytest.raises(ValueError, match="prediction module"):
            glm_lite.glm_lite_step(dtype="float32", **dict(TINY, **changed))


# -- the dense step of latent attention -----------------------------------------

def _decompressed(q, kv_b, cache, member, heads, nope, v_dim, scale):
    """q [K, H*(N+P)] over one row's cache under ``member`` [K, C]."""
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


@pytest.mark.parametrize("lanes", [1, 2])
def test_the_dense_step_is_the_chunk_form_and_the_decompressed_one(lanes):
    rng = np.random.default_rng(4)
    heads, nope, rope, v_dim, r, c = 4, 6, 4, 8, 8, 1536
    scale = (nope + rope) ** -0.5
    kv_b = jnp.asarray(rng.standard_normal((r, heads * (nope + v_dim)))
                       / np.sqrt(r), jnp.float32)
    cache = jnp.asarray(rng.standard_normal((3, c, r + rope)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, lanes, heads * (nope + rope))),
                    jnp.float32)
    # row 0 reaches past two blocks of 512, row 1 has one position, row 2
    # is a dead row: every lane a pad lane
    pos = np.array([[1100, 1101], [0, c], [c, c]], np.int32)[:, :lanes]
    dense = sparse_latent.latent_attention_dense(
        q, kv_b, cache, jnp.asarray(pos), heads, nope, v_dim, scale)
    chunk = sparse_latent.latent_attention_chunk(
        q, kv_b, cache, None, jnp.asarray(pos), heads, nope, v_dim, scale)
    causal = jnp.asarray((np.arange(c)[None, None, :] <= pos[:, :, None])
                         & (pos < c)[:, :, None])
    masked = sparse_latent.latent_attention_chunk(
        q, kv_b, cache, causal, jnp.asarray(pos), heads, nope, v_dim, scale)
    np.testing.assert_allclose(dense, chunk, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dense, masked, rtol=1e-4, atol=1e-5)
    for bi in (0, 1):
        live = [j for j in range(lanes) if pos[bi, j] < c]
        want = _decompressed(q[bi, live], kv_b, cache[bi],
                             causal[bi, live], heads, nope, v_dim, scale)
        np.testing.assert_allclose(dense[bi, live], want, rtol=1e-4,
                                   atol=1e-5)
    assert not np.asarray(dense[2]).any()          # a pad lane gives 0
    if lanes == 2:
        assert not np.asarray(dense[1, 1]).any()


def test_the_dense_step_reads_no_selection():
    """Against the step form over an index: the same numbers where the index
    names every live position, other numbers where it names some."""
    rng = np.random.default_rng(5)
    heads, nope, rope, v_dim, r, c = 4, 6, 4, 8, 8, 64
    scale = (nope + rope) ** -0.5
    kv_b = jnp.asarray(rng.standard_normal((r, heads * (nope + v_dim)))
                       / np.sqrt(r), jnp.float32)
    cache = jnp.asarray(rng.standard_normal((2, c, r + rope)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, 1, heads * (nope + rope))),
                    jnp.float32)
    pos = jnp.asarray([[40], [9]], jnp.int32)
    dense = sparse_latent.latent_attention_dense(
        q, kv_b, cache, pos, heads, nope, v_dim, scale)[:, 0]
    every = jnp.where(jnp.arange(c)[None, :] <= pos, jnp.arange(c)[None, :],
                      c).astype(jnp.int32)
    step = sparse_latent.latent_attention(
        q[:, 0], kv_b, cache, every, heads, nope, v_dim, scale)
    np.testing.assert_allclose(dense, step, rtol=1e-4, atol=1e-5)
    some = jnp.where(every % 2 == 0, every, c)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(dense, sparse_latent.latent_attention(
            q[:, 0], kv_b, cache, some, heads, nope, v_dim, scale),
            rtol=1e-4, atol=1e-5)
