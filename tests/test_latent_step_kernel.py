"""The step kernel behind ``latent_attention_dense``
(``ops/cache_attention.py``: ``latent_step.fwd``, interpret mode here)
against the ``jnp`` form that scores the whole rung under a mask and against
a float32 softmax, and the gate that decides which of the two a site
takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.executor import build_step_fn
from paddle_tpu.ops import cache_attention as ca
from paddle_tpu.ops import gates
from paddle_tpu.ops import sparse_latent as sl
from paddle_tpu.ops.kernel_names import collect_traces, tally_traces

F32, BF16 = jnp.float32, jnp.bfloat16
C = 512
PAD = C          # a pad lane's position: past the cache

# (heads, R, P, N, V): GLM-4.7-Flash's ratios (20 heads, a latent of a
# multiple of 128 under a 64-wide rotary tail, 512 + 64 there) cut down, and
# a latent of two tiles
WIDTHS = {
    "20x128+64": (20, 128, 64, 24, 32),
    "4x256+64": (4, 256, 64, 16, 16),
}


@pytest.fixture(autouse=True)
def interpreted():
    """Interpret mode, and JAX's trace caches emptied of what another
    mode traced."""
    ca._INTERPRET = True
    jax.clear_caches()
    yield
    ca._INTERPRET = False
    jax.clear_caches()


def _block(widths, dtype=BF16):
    _, r, p, _, _ = WIDTHS[widths]
    return ca.step_block(C, (r + p) * jnp.dtype(dtype).itemsize)


def _arrays(widths, rows, lanes, dtype=BF16, seed=0):
    heads, r, p, n, v = WIDTHS[widths]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (rows, lanes, heads * (n + p))), dtype)
    kv_b = jnp.asarray(rng.normal(0, r ** -0.5, (r, heads * (n + v))), dtype)
    cache = jnp.asarray(rng.normal(0, 1, (rows, C, r + p)), dtype)
    return q, kv_b, cache


def _attend(widths, q, kv_b, cache, pos, kernel):
    heads, _, p, n, v = WIDTHS[widths]
    plan = ca.latent_plan_for(q, cache, kv_b.shape[0], heads)
    assert plan.kernel == "latent_step", plan
    out = sl.latent_attention_dense(
        q, kv_b, cache, jnp.asarray(pos, jnp.int32), heads, n, v,
        (n + p) ** -0.5, plan=plan if kernel else None)
    return np.asarray(out.astype(F32))


def _dense(widths, q, kv_b, cache, pos):
    """A float32 softmax a head of a lane over the positions ``<= pos``, in
    the form that is NOT absorbed: keys and values taken out of the latent
    a position."""
    heads, r, p, n, v = WIDTHS[widths]
    rows, lanes = q.shape[:2]
    qh = np.asarray(q.astype(F32)).reshape(rows, lanes, heads, n + p)
    both = np.asarray(kv_b.astype(F32)).reshape(r, heads, n + v)
    rows_c = np.asarray(cache.astype(F32))
    k_nope = np.einsum("bcr,rhn->bchn", rows_c[..., :r], both[..., :n])
    vals = np.einsum("bcr,rhv->bchv", rows_c[..., :r], both[..., n:])
    x = np.einsum("bkhn,bchn->bkhc", qh[..., :n], k_nope) \
        + np.einsum("bkhp,bcp->bkhc", qh[..., n:], rows_c[..., r:])
    x = x * (n + p) ** -0.5
    pos = np.asarray(pos)
    reach = np.where(pos < C, pos, -1)
    member = np.arange(C) <= reach[:, :, None, None]
    x = np.where(member, x, -1e30)
    e = np.where(member, np.exp(x - x.max(-1, keepdims=True)), 0.0)
    probs = e / np.maximum(e.sum(-1, keepdims=True), 1e-30)
    return np.einsum("bkhc,bchv->bkhv", probs, vals).reshape(rows, lanes, -1)


def _positions(where, block, lanes):
    """A row's first lane's position; lane k holds the k-th after it."""
    first = {"first": [0, 0], "mid_block": [block // 2, block + 7],
             "straddle_a_border": [block - 1, 2 * block - 1],
             "first_of_the_next": [block, 2 * block],
             "last": [C - lanes, C - lanes],
             "mixed": [0, block - 1, block, C // 2 + 3, C - lanes, 1]}[where]
    return [[p + k for k in range(lanes)] for p in first]


@pytest.mark.parametrize("where", ["first", "mid_block", "straddle_a_border",
                                   "first_of_the_next", "last", "mixed"])
@pytest.mark.parametrize("lanes", [1, 2])
@pytest.mark.parametrize("widths", list(WIDTHS))
def test_kernel_equals_the_rung_form_and_a_float32_softmax(widths, lanes,
                                                           where):
    pos = _positions(where, _block(widths), lanes)
    q, kv_b, cache = _arrays(widths, len(pos), lanes)
    out = _attend(widths, q, kv_b, cache, pos, kernel=True)
    rung = _attend(widths, q, kv_b, cache, pos, kernel=False)
    # bfloat16 outputs of sums in two orders: a few units in the last place
    np.testing.assert_allclose(out, rung, atol=0.03, rtol=0.01)
    np.testing.assert_allclose(out, _dense(widths, q, kv_b, cache, pos),
                               atol=0.05, rtol=0.02)


@pytest.mark.parametrize("lanes", [1, 2])
@pytest.mark.parametrize("widths", list(WIDTHS))
def test_float32_caches_agree_with_the_softmax_closely(widths, lanes):
    pos = _positions("mixed", _block(widths, F32), lanes)
    q, kv_b, cache = _arrays(widths, len(pos), lanes, F32)
    out = _attend(widths, q, kv_b, cache, pos, kernel=True)
    np.testing.assert_allclose(out, _dense(widths, q, kv_b, cache, pos),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("pos,dead", [
    ([[7, PAD], [300, 301]], [(0, 1)]),                 # beside a live lane
    ([[PAD, PAD], [300, 301], [PAD, PAD + 9]], [(0, 0), (0, 1), (2, 0),
                                                (2, 1)]),   # rows of them
    ([[PAD, 5], [C - 1, PAD]], [(0, 0), (1, 1)]),       # in either lane
], ids=["beside_a_live_lane", "rows_of_pad_lanes", "either_lane"])
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bfloat16", "float32"])
def test_a_pad_lane_comes_out_zero_and_the_live_ones_as_the_rung_forms(
        dtype, pos, dead):
    widths = "20x128+64"
    q, kv_b, cache = _arrays(widths, len(pos), 2, dtype)
    out = _attend(widths, q, kv_b, cache, pos, kernel=True)
    rung = _attend(widths, q, kv_b, cache, pos, kernel=False)
    for b, k in dead:
        assert not out[b, k].any() and not rung[b, k].any()
    np.testing.assert_allclose(out, rung, rtol=0.01,
                               atol=0.03 if dtype == BF16 else 2e-4)


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_garbage_past_a_rows_position_changes_no_output_bit(widths):
    """Large finite values where a row holds nothing yet, in the blocks the
    kernel fetches and in those it does not; a lane reads no position of
    the lane after it."""
    pos = _positions("mixed", _block(widths), 2)
    q, kv_b, cache = _arrays(widths, len(pos), 2)
    past = (np.arange(C) > np.asarray(pos)[:, 1:2])[:, :, None]
    clean = _attend(widths, q, kv_b, jnp.where(past, 0, cache), pos, True)
    dirty = _attend(widths, q, kv_b,
                    jnp.where(past, 3e37, cache).astype(BF16), pos, True)
    assert np.array_equal(clean, dirty)
    own = (np.arange(C) == np.asarray(pos)[:, 1:2])[:, :, None]
    other = _attend(widths, q, kv_b,
                    jnp.where(own, -3e37, cache).astype(BF16), pos, True)
    assert np.array_equal(clean[:, 0], other[:, 0])


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_a_row_alone_and_among_fifteen_others_agree_bit_for_bit(widths):
    block = _block(widths)
    first = [int(p) for p in np.random.default_rng(5).integers(0, C - 1, 16)]
    first[3], first[4] = block - 1, PAD
    pos = [[p, p + 1] for p in first]
    q, kv_b, cache = _arrays(widths, 16, 2, seed=3)
    batched = _attend(widths, q, kv_b, cache, pos, True)
    alone = _attend(widths, q[3:4], kv_b, cache[3:4], pos[3:4], True)
    assert np.array_equal(batched[3], alone[0])


# ---------------------------------------------------------------------------
# which sites take it
# ---------------------------------------------------------------------------

# (rows, capacity, lanes, heads, R, P, N, V) of the cells' dense latent step
GLM47 = (32, 4096, 2, 20, 512, 64, 192, 256)


def _sites(shape, n=1, dtype="bfloat16"):
    """A program of ``n`` ``latent_attention_dense`` ops of one signature,
    and the abstract feeds it is traced with."""
    b, c, lanes, heads, r, p, nope, v = shape
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        q = layers.data("q", shape=[lanes, heads * (nope + p)], dtype=dtype)
        cache = layers.data("cache", shape=[c, r + p], dtype=dtype)
        pos = layers.data("pos", shape=[lanes], dtype="int32")
        outs = [layers.latent_attention(
            q, cache, None, pos, heads, r, nope, v, (nope + p) ** -0.5,
            param_attr=fluid.ParamAttr(name="kv_b%d" % i), dense=True)
            for i in range(n)]
    real = jnp.dtype(dtype)
    feed = {"q": jax.ShapeDtypeStruct((b, lanes, heads * (nope + p)), real),
            "cache": jax.ShapeDtypeStruct((b, c, r + p), real),
            "pos": jax.ShapeDtypeStruct((b, lanes), jnp.int32)}
    return main, [o.name for o in outs], feed


def _trace(main, fetch, feed, placement, dtype=BF16):
    """Trace the program's step as an Executor placed so would (nothing
    lowered, nothing run): the ops' recorded choices, the gate tally and
    the kernel bodies traced."""
    persist = sorted(v.name for v in main.list_vars() if v.persistable)
    state = {n: jax.ShapeDtypeStruct(
        tuple(main.global_block().var(n).shape), dtype) for n in persist}
    step = build_step_fn(main, fetch, persist, infer_only=True)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    with gates.placed(*placement), gates.collect() as met, \
            collect_traces() as bodies:
        jax.jit(step).trace(state, feed, rng)
    choices = [op.attrs["_kernel_choice"]
               for op in main.global_block().ops
               if op.type == "latent_attention_dense"]
    return choices, gates.tally(met), tally_traces(bodies)


@pytest.fixture
def compiled_mode():
    """The gate as a served step meets it: no interpret mode, so only the
    placement admits a kernel."""
    ca._INTERPRET = False
    yield
    ca._INTERPRET = True


@pytest.mark.parametrize("shape", [GLM47, GLM47[:2] + (1,) + GLM47[3:]],
                         ids=["two_lanes", "one_lane"])
def test_a_step_on_one_tpu_takes_the_kernel_and_sites_share_its_body(
        compiled_mode, shape):
    main, fetch, feed = _sites(shape, n=3)
    choices, tally, bodies = _trace(main, fetch, feed, ("tpu",))
    assert len(choices) == 3
    for choice in choices:
        assert choice["admitted"] and choice["kernel"] == "latent_step"
        assert "blocks of 512 of 4096 positions" in \
            choice["reasons"][0]["detail"]
    assert tally == {"latent_attention_dense": {"kernel latent_step": 3}}
    assert bodies == {"latent_step.fwd": {"traced": 1, "reused": 2}}


def _with(shape, **changed):
    names = ("b", "c", "lanes", "heads", "r", "p", "nope", "v")
    return tuple(changed.get(n, x) for n, x in zip(names, shape))


@pytest.mark.parametrize("shape,dtype,placement,check,says", [
    (GLM47, "bfloat16", ("cpu",), "platform", "placed on 'cpu', not a TPU"),
    (GLM47, "bfloat16", ("tpu", True), "platform",
     "partitioned over a mesh"),
    (_with(GLM47, c=128), "bfloat16", ("tpu",), "geometry",
     "no longer than one block"),
    (_with(GLM47, c=4096 + 64), "bfloat16", ("tpu",), "geometry",
     "no longer than one block"),
    (_with(GLM47, r=448), "bfloat16", ("tpu",), "geometry",
     "a latent part of 448 columns of a row of 512"),
    (_with(GLM47, p=128), "bfloat16", ("tpu",), "geometry",
     "a cache of rows of 640 columns lies row-major on the device"),
    (GLM47, "float16", ("tpu",), None, None),
], ids=["cpu", "mesh", "one_block", "no_divisor", "narrow_latent",
        "row_major_rows", "float16"])
def test_the_rest_keep_the_rung_form_and_say_why(
        compiled_mode, shape, dtype, placement, check, says):
    main, fetch, feed = _sites(shape, dtype=dtype)
    (choice,), tally, bodies = _trace(main, fetch, feed, placement,
                                      jnp.dtype(dtype))
    if check is None:       # another 2-byte floating type is taken as well
        assert choice["admitted"] and choice["kernel"] == "latent_step"
        return
    assert not choice["admitted"] and choice["kernel"] == "rung_xla"
    assert choice["fallback"] == "latent_step"
    assert [r["check"] for r in choice["reasons"]] == [check]
    (line, times), = tally["latent_attention_dense"].items()
    assert line.startswith("fell back to rung_xla (wanted latent_step): "
                           + check) and says in line and times == 1
    assert not bodies


def test_the_gate_counts_the_block_the_queries_and_the_types():
    """The cell's rows of 1,152 B take blocks of 512 of the 4,096; two
    lanes of 20 heads are 48 query rows in bfloat16 and 40 in float32; a
    type the kernel does not take, queries of another type than the cache
    and a working set past the budget are refused by name."""
    assert ca.step_block(4096, 1152) == 512
    assert ca._query_rows(2, 20, 2) == 48 and ca._query_rows(2, 20, 4) == 40
    b, c, lanes, heads, r, p = GLM47[:6]
    plan = ca.latent_plan(b, c, lanes, heads, r + p, r, 2)
    assert plan and "blocks of 512 of 4096" in plan.reasons[0].detail
    assert ca.latent_plan(b, c, lanes, heads, r + p, r, 4)
    assert ca.latent_plan(b, c, lanes, heads, r + p, r,
                          1).blocked_only_by("dtype")
    wide = ca.latent_plan(4096, c, lanes, heads, r + p, r, 2)
    assert wide.blocked_only_by("vmem") and "32 MB" in wide.describe()
    with gates.placed("tpu"):
        mixed = ca.latent_plan_for(jnp.zeros((2, 2, 20 * 256), F32),
                                   jnp.zeros((2, c, r + p), BF16), r, heads)
    assert mixed.blocked_only_by("dtype")
