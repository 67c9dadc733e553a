"""The step kernel behind ``cached_attention`` (``ops/cache_attention.py``:
``cache_step.fwd``, interpret mode here) against the ``jnp`` form that
reads the whole rung and against a float32 softmax, and the gate that
decides which of the two a site takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.executor import build_step_fn
from paddle_tpu.ops import cache_attention as ca
from paddle_tpu.ops import gates
from paddle_tpu.ops.kernel_names import collect_traces, tally_traces

F32, BF16 = jnp.float32, jnp.bfloat16
C = 512


@pytest.fixture(autouse=True)
def interpreted():
    """Interpret mode, and JAX's trace caches emptied of what another
    mode traced."""
    ca._INTERPRET = True
    jax.clear_caches()
    yield
    ca._INTERPRET = False
    jax.clear_caches()


# (query heads, key/value heads, Dk, Dv, sink): OPT's plain heads, and
# MiMo-V2-Flash's grouped heads of two widths (64 on 4 there) cut down
LAYOUTS = {
    "plain_32x64": (32, 32, 64, 64, False),
    "grouped_16on2_192_128": (16, 2, 192, 128, False),
    "grouped_16on2_192_128_sink": (16, 2, 192, 128, True),
}


def _block(layout, dtype=BF16):
    _, g, dk, dv, _ = LAYOUTS[layout]
    return ca.step_block(C, g * (dk + dv) * jnp.dtype(dtype).itemsize)


def _arrays(layout, rows, dtype=BF16, seed=0):
    heads, g, dk, dv, sink = LAYOUTS[layout]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (rows, heads * dk)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (rows, C, g * dk)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (rows, C, g * dv)), dtype)
    s = jnp.asarray(rng.normal(0, 1, (heads,)), dtype) if sink else None
    return q, k, v, s


def _kernel(layout, q, k, v, s, pos):
    heads, g = LAYOUTS[layout][:2]
    out, count = ca.step_blocks(q, k, v, jnp.asarray(pos, jnp.int32), heads,
                                g, s)
    return np.asarray(out.astype(F32)), int(count[0])


def _dense(layout, q, k, v, s, pos):
    """A float32 softmax a head over the positions ``<= pos``, the sink in
    its denominator."""
    heads, g, dk, dv, _ = LAYOUTS[layout]
    rows = q.shape[0]
    qh = np.asarray(q.astype(F32)).reshape(rows, g, heads // g, dk)
    kh = np.asarray(k.astype(F32)).reshape(rows, C, g, dk)
    vh = np.asarray(v.astype(F32)).reshape(rows, C, g, dv)
    x = np.einsum("bgrd,bcgd->bgrc", qh, kh) / np.sqrt(dk)
    x = np.where(np.arange(C) <= np.asarray(pos)[:, None, None, None], x,
                 -np.inf)
    top = x.max(-1, keepdims=True)
    sink = None
    if s is not None:
        sink = np.asarray(s.astype(F32)).reshape(1, g, heads // g, 1)
        top = np.maximum(top, sink)
    e = np.exp(x - top)
    total = e.sum(-1, keepdims=True)
    if sink is not None:
        total = total + np.exp(sink - top)
    return np.einsum("bgrc,bcgd->bgrd", e / total, vh).reshape(rows, -1)


def _positions(where, block):
    return {"first": [0, 0], "mid_block": [block // 2, block + 7],
            "last_of_a_block": [block - 1, 2 * block - 1],
            "first_of_the_next": [block, 2 * block], "last": [C - 1, C - 1],
            "mixed": [0, block - 1, block, C // 2 + 3, C - 1, 1]}[where]


@pytest.mark.parametrize("where", ["first", "mid_block", "last_of_a_block",
                                   "first_of_the_next", "last", "mixed"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_equals_the_rung_form_and_a_float32_softmax(layout, where):
    heads, g = LAYOUTS[layout][:2]
    pos = _positions(where, _block(layout))
    q, k, v, s = _arrays(layout, len(pos))
    out, count = _kernel(layout, q, k, v, s, pos)
    rung, rung_count = ca.attend_step(q, k, v, jnp.asarray(pos, jnp.int32),
                                      heads, g, 0, s)
    # bfloat16 outputs of sums in two orders: a few units in the last place
    np.testing.assert_allclose(out, np.asarray(rung.astype(F32)), atol=0.03)
    np.testing.assert_allclose(out, _dense(layout, q, k, v, s, pos),
                               atol=0.03)
    assert count == int(rung_count[0]) == sum(p + 1 for p in pos)


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_float32_caches_agree_with_the_softmax_closely(layout):
    pos = _positions("mixed", _block(layout, F32))
    q, k, v, s = _arrays(layout, len(pos), F32)
    out, count = _kernel(layout, q, k, v, s, pos)
    np.testing.assert_allclose(out, _dense(layout, q, k, v, s, pos),
                               atol=2e-5, rtol=2e-5)
    assert count == sum(p + 1 for p in pos)


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_garbage_past_a_rows_position_changes_no_output_bit(layout):
    """Large finite values where a row holds nothing yet, in the blocks the
    kernel fetches and in those it does not."""
    pos = _positions("mixed", _block(layout))
    q, k, v, s = _arrays(layout, len(pos))
    past = (np.arange(C) > np.asarray(pos)[:, None])[:, :, None]
    clean = _kernel(layout, q, jnp.where(past, 0, k), jnp.where(past, 0, v),
                    s, pos)
    dirty = _kernel(layout, q, jnp.where(past, 1e30, k).astype(BF16),
                    jnp.where(past, -3e37, v).astype(BF16), s, pos)
    assert np.array_equal(clean[0], dirty[0]) and clean[1] == dirty[1]


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_a_row_alone_and_among_fifteen_others_agree_bit_for_bit(layout):
    block = _block(layout)
    pos = [int(p) for p in np.random.default_rng(5).integers(0, C, 16)]
    pos[3] = block + 5
    q, k, v, s = _arrays(layout, 16, seed=3)
    batched, _ = _kernel(layout, q, k, v, s, pos)
    alone, count = _kernel(layout, q[3:4], k[3:4], v[3:4], s, pos[3:4])
    assert np.array_equal(batched[3], alone[0]) and count == block + 6


def test_a_position_past_the_cache_or_below_it_reads_inside_it():
    """What a retired slot may be fed: the copies stay inside the cache, and
    the results are the rung form's (everything, and nothing)."""
    layout = "plain_32x64"
    heads, g = LAYOUTS[layout][:2]
    q, k, v, s = _arrays(layout, 2)
    pos = [C + 40, -1]
    out, count = _kernel(layout, q, k, v, s, pos)
    rung, rung_count = ca.attend_step(q, k, v, jnp.asarray(pos, jnp.int32),
                                      heads, g, 0, s)
    np.testing.assert_allclose(out, np.asarray(rung.astype(F32)), atol=0.03)
    assert not out[1].any() and count == int(rung_count[0]) == C


# ---------------------------------------------------------------------------
# which sites take it
# ---------------------------------------------------------------------------

# (rows, capacity, heads, key/value heads, Dk, Dv) of the cells' step ops
OPT = (16, 1280, 32, 32, 64, 64)
MIMO_FULL = (16, 16384, 64, 4, 192, 128)
MIMO_WINDOW = (16, 128, 64, 8, 192, 128)


def _sites(shape, n=1, dtype="bfloat16", **more):
    """A program of ``n`` ``cached_attention`` ops of one signature, and
    the abstract feeds it is traced with."""
    b, c, heads, g, dk, dv = shape
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        q = layers.data("q", shape=[heads * dk], dtype=dtype)
        ck = layers.data("ck", shape=[c, g * dk], dtype=dtype)
        cv = layers.data("cv", shape=[c, g * dv], dtype=dtype)
        pos = layers.data("pos", shape=[], dtype="int32")
        outs = [layers.cached_attention(q, ck, cv, pos, heads, g, **more)
                for _ in range(n)]
    real = jnp.dtype(dtype)
    feed = {"q": jax.ShapeDtypeStruct((b, heads * dk), real),
            "ck": jax.ShapeDtypeStruct((b, c, g * dk), real),
            "cv": jax.ShapeDtypeStruct((b, c, g * dv), real),
            "pos": jax.ShapeDtypeStruct((b,), jnp.int32)}
    return main, [o[0].name if isinstance(o, tuple) else o.name
                  for o in outs], feed


def _trace(main, fetch, feed, placement):
    """Trace the program's step as an Executor placed so would (nothing
    lowered, nothing run): the ops' recorded choices, the gate tally and
    the kernel bodies traced."""
    persist = sorted(v.name for v in main.list_vars() if v.persistable)
    state = {n: jax.ShapeDtypeStruct(
        tuple(main.global_block().var(n).shape), BF16) for n in persist}
    step = build_step_fn(main, fetch, persist, infer_only=True)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    with gates.placed(*placement), gates.collect() as met, \
            collect_traces() as bodies:
        jax.jit(step).trace(state, feed, rng)
    choices = [op.attrs["_kernel_choice"]
               for op in main.global_block().ops
               if op.type == "cached_attention"]
    return choices, gates.tally(met), tally_traces(bodies)


@pytest.fixture
def compiled_mode():
    """The gate as a served step meets it: no interpret mode, so only the
    placement admits a kernel."""
    ca._INTERPRET = False
    yield
    ca._INTERPRET = True


@pytest.mark.parametrize("shape,more", [
    (OPT, {}), (MIMO_FULL, {"count": True}),
    (MIMO_FULL, {"count": True, "sink_attr": fluid.ParamAttr(name="sink")})],
    ids=["opt", "mimo_full", "mimo_full_sink"])
def test_a_step_on_one_tpu_takes_the_kernel_and_sites_share_its_body(
        compiled_mode, shape, more):
    main, fetch, feed = _sites(shape, n=3, **more)
    choices, tally, bodies = _trace(main, fetch, feed, ("tpu",))
    assert len(choices) == 3
    for choice in choices:
        assert choice["admitted"] and choice["kernel"] == "cache_step"
    assert tally == {"cached_attention": {"kernel cache_step": 3}}
    assert bodies == {"cache_step.fwd": {"traced": 1, "reused": 2}}


@pytest.mark.parametrize("shape,more,placement,check,says", [
    (MIMO_WINDOW, {"window": 128, "ring": True, "count": True}, ("tpu",),
     "shape", "a ring of 128 positions is read whole"),
    (MIMO_FULL, {"window": 128, "count": True}, ("tpu",), "shape",
     "a window of 128 positions is read whole"),
    (OPT, {}, ("cpu",), "platform", "placed on 'cpu', not a TPU"),
    (OPT, {}, ("tpu", True), "platform", "partitioned over a mesh"),
    ((16, 128, 32, 32, 64, 64), {}, ("tpu",), "geometry",
     "no longer than one block"),
    ((16, 1280, 12, 12, 64, 64), {}, ("tpu",), "geometry",
     "12 heads no multiple of 16 sublanes"),
], ids=["ring", "window", "cpu", "mesh", "one_block", "narrow"])
def test_the_rest_keep_the_rung_form_and_say_why(
        compiled_mode, shape, more, placement, check, says):
    main, fetch, feed = _sites(shape, **more)
    (choice,), tally, bodies = _trace(main, fetch, feed, placement)
    assert not choice["admitted"] and choice["kernel"] == "rung_xla"
    assert choice["fallback"] == "cache_step"
    assert [r["check"] for r in choice["reasons"]] == [check]
    (line, times), = tally["cached_attention"].items()
    assert line.startswith("fell back to rung_xla (wanted cache_step): "
                           + check) and says in line and times == 1
    assert not bodies


def test_the_block_follows_the_rung_and_the_rows_widths():
    """OPT's rows of 8 KB take the shortest block, MiMo-V2-Flash's rows of
    2.5 KB over a rung of 16384 a longer one; a dtype the kernel does not
    take and a working set past the budget are refused by name."""
    assert ca.step_block(1280, 8192) == 128
    assert ca.step_block(16384, 2560) == 512
    assert ca.step_block(1280 + 64, 8192) is None
    plan = ca.step_plan(*OPT[:4], 2048, 2048, 2)
    assert plan and "blocks of 128 of 1280 positions" in plan.reasons[0].detail
    assert ca.step_plan(*OPT[:4], 2048, 2048, 1).blocked_only_by("dtype")
    wide = ca.step_plan(4096, 1280, 32, 32, 2048, 2048, 2)
    assert wide.blocked_only_by("vmem") and "32 MB" in wide.describe()
    with gates.placed("tpu"):
        mixed = ca.plan_for(jnp.zeros((2, 2048), F32),
                            jnp.zeros((2, 1280, 2048), BF16),
                            jnp.zeros((2, 1280, 2048), BF16), 32, 32)
    assert mixed.blocked_only_by("dtype")


# What ``step_blocks`` traces to, the kernel's body with it, as equations
# by primitive (sub-programs included): (equations in all, then the ones a
# step kernel is made of). PR 43's tree traces to the same counts: the loop
# and the streaming softmax are shared with ``latent_step.fwd`` since PR 44
# (``_walk_blocks``, ``_stream``), and ``cached_attention``'s
# instantiations trace to what they traced to. A PR that changes this
# kernel on purpose records new counts.
_STEP_PARTS = {"pallas_call": 1, "scan": 1, "while": 1, "cond": 3,
               "dma_start": 4, "dma_wait": 2, "dot_general": 2, "exp": 2,
               "reduce_max": 1, "swap": 7}
STEP_JAXPRS = {
    "opt": (OPT, False, 158, dict(_STEP_PARTS, get=12, reduce_sum=3)),
    "mimo_full": (MIMO_FULL, False, 163,
                  dict(_STEP_PARTS, get=12, reduce_sum=2, slice=4)),
    "mimo_full_sink": (MIMO_FULL, True, 165,
                       dict(_STEP_PARTS, get=13, reduce_sum=2, slice=4)),
}


def _primitives(jaxpr, counts):
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, counts)
    return counts


@pytest.mark.parametrize("case", list(STEP_JAXPRS))
def test_the_shared_loop_traces_to_the_kernel_as_it_was(compiled_mode, case):
    (b, c, heads, g, dk, dv), sink, equations, parts = STEP_JAXPRS[case]
    args = (jax.ShapeDtypeStruct((b, heads * dk), BF16),
            jax.ShapeDtypeStruct((b, c, g * dk), BF16),
            jax.ShapeDtypeStruct((b, c, g * dv), BF16),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((heads,), BF16) if sink else None)
    counts = _primitives(jax.make_jaxpr(lambda q, k, v, pos, s: ca.step_blocks(
        q, k, v, pos, heads, g, s))(*args).jaxpr, {})
    assert {name: counts.get(name, 0) for name in parts} == parts
    assert sum(counts.values()) == equations
